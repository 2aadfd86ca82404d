"""The program's own flight recorder, read for the per-layer metrics.

`tendermint_tpu.libs.trace.RECORDER` holds the spans the program records
where the work happens (block-sync stages, apply, the light client, the
commit funnel, the hub, the device dispatch). This module clips them to a
window, gives totals, attribute sums and self time, and — in a traced run —
loads the run's `.xplane.pb` once more for the two device readers: device
time by `jax.named_scope` phase, and the device's idle time that no `tm.*`
span of the program covers.

A reader built on this returns None, with one earlier line saying why,
where it finds nothing to read: a program without these spans (the parent
of the PR that added them), a recorder that is switched off, a ring that
dropped rows of the window, clocks that do not agree. Never a truncated
number.
"""

from __future__ import annotations

import glob
import os
import time

from benchmark import harness
from benchmark.harness import say
from benchmark.trace_reduce import union_ns

#: the recorder stamps time.monotonic, the harness time.perf_counter: on
#: Linux both read CLOCK_MONOTONIC. Checked at read time, refused beyond
MAX_CLOCK_OFFSET_S = 1e-3

_said: set = set()


def _once(msg: str) -> None:
    if msg not in _said:
        _said.add(msg)
        say(f"program_spans: {msg}")


def clock_offset_s() -> float:
    a = time.perf_counter()
    m = time.monotonic()
    b = time.perf_counter()
    return abs(m - (a + b) / 2)


_rows_cache: dict = {}


def window_rows(t0: float, t1: float) -> list[dict] | None:
    """The recorder's rows that overlap [t0, t1] (harness clock), each
    with `start`/`end` in seconds beside the recorder's own keys; None
    where they cannot be trusted to be all of the window's."""
    key = (round(t0, 6), round(t1, 6))
    if key in _rows_cache:
        return _rows_cache[key]
    _rows_cache[key] = out = _window_rows(t0, t1)
    return out


def _window_rows(t0: float, t1: float) -> list[dict] | None:
    try:
        from tendermint_tpu.libs import trace
    except ImportError:
        _once("no tendermint_tpu.libs.trace")
        return None
    rec = trace.RECORDER
    if not rec.enabled:
        _once("the recorder is disabled (TMTPU_TRACE=0): nothing to read")
        return None
    off = clock_offset_s()
    if off >= MAX_CLOCK_OFFSET_S:
        _once(f"recorder and harness clocks are {off:.6f}s apart: refused")
        return None
    rows = rec.dump()
    for d in rows:
        d["start"] = d["start_s"]
        d["end"] = d["start_s"] + d["duration_ms"] / 1e3
    # rows land in the order spans END, so every dropped row ended before
    # the oldest kept one did: the window is whole unless that one ended
    # inside it
    if rec.dropped and rows and rows[0]["end"] > t0:
        _once(f"the ring wrapped inside the window ({rec.dropped} rows dropped): refused")
        return None
    return [d for d in rows if d["end"] > t0 and d["start"] < t1]


def _key(d: dict) -> str:
    return f"{d['subsystem']}.{d['name']}"


def select(rows: list[dict], *keys: str) -> list[dict]:
    return [d for d in rows if _key(d) in keys]


def _clip(d: dict, t0: float, t1: float) -> float:
    return max(0.0, min(d["end"], t1) - max(d["start"], t0))


def total_s(rows: list[dict] | None, t0: float, t1: float, *keys: str):
    """Seconds inside spans called any of `keys`, clipped to [t0, t1];
    None where there is no such span."""
    if rows is None:
        return None
    mine = select(rows, *keys)
    if not mine:
        return None
    return sum(_clip(d, t0, t1) for d in mine)


def attr_sum(rows: list[dict], attr: str, t0: float, t1: float, *keys: str) -> float:
    """Sum of `attr` over spans called `keys`, each weighted by the share
    of the span that lies inside [t0, t1]."""
    out = 0.0
    for d in select(rows, *keys):
        length = d["end"] - d["start"]
        share = _clip(d, t0, t1) / length if length > 0 else 1.0
        out += share * float((d.get("attrs") or {}).get(attr, 0))
    return out


def per_unit_ms(rows, t0: float, t1: float, units: float, *keys: str):
    """ms of spans `keys` per unit of progress (block, header)."""
    s = total_s(rows, t0, t1, *keys)
    return None if s is None or not units else 1e3 * s / units


def per_ksig_ms(rows, t0: float, t1: float, attr: str, *keys: str):
    """ms of spans `keys` per thousand of what their `attr` counts."""
    s = total_s(rows, t0, t1, *keys)
    if s is None:
        return None
    n = attr_sum(rows, attr, t0, t1, *keys)
    return 1e3 * s / (n / 1e3) if n else None


def per_span_ms(rows, t0: float, t1: float, *keys: str):
    s = total_s(rows, t0, t1, *keys)
    if s is None:
        return None
    n = sum(1 for d in select(rows, *keys) if _clip(d, t0, t1) > 0)
    return 1e3 * s / n if n else None


def descendants(rows: list[dict], root: dict) -> list[dict]:
    """Rows under `root`: by parent_id, and — where a thread that
    inherits no context recorded them (the hub's) — by trace id and
    containment."""
    by_parent: dict = {}
    for d in rows:
        by_parent.setdefault(d.get("parent_id", 0), []).append(d)
    out, seen, stack = [], {root.get("span_id")}, [root.get("span_id")]
    while stack:
        for c in by_parent.get(stack.pop(), ()):
            if c.get("span_id") not in seen:
                seen.add(c.get("span_id"))
                out.append(c)
                stack.append(c.get("span_id"))
    if root.get("trace_id"):
        for d in rows:
            if (d.get("trace_id") == root["trace_id"] and d.get("span_id") not in seen
                    and d["start"] >= root["start"] and d["end"] <= root["end"]):
                seen.add(d.get("span_id"))
                out.append(d)
    return out


def self_s(rows: list[dict] | None, t0: float, t1: float, key: str, *cover: str):
    """Seconds of spans called `key` that none of their descendants
    called `cover` accounts for (all descendants, where `cover` is
    empty): the span minus the union of what they cover."""
    if rows is None:
        return None
    mine = select(rows, key)
    if not mine:
        return None
    out = 0.0
    for root in mine:
        lo, hi = max(root["start"], t0), min(root["end"], t1)
        if hi <= lo:
            continue
        kids = [d for d in descendants(rows, root) if not cover or _key(d) in cover]
        covered, _ = union_ns([(max(d["start"], lo), min(d["end"], hi))
                               for d in kids if d["end"] > lo and d["start"] < hi])
        out += (hi - lo) - covered
    return out


# -- what the metric files call, with a window's Readings ---------------------


def ms_per_unit(r, *keys: str):
    """ms inside spans `keys` per block applied / header verified."""
    return per_unit_ms(window_rows(r.t0, r.t1), r.t0, r.t1, r.units, *keys)


def ms_per_unit_less(r, key: str, less: str):
    """ms inside spans `key` per unit, WITHOUT the time of the spans `less`
    that ran inside them (none recorded: nothing to take out); None where
    there is no `key` span."""
    rows = window_rows(r.t0, r.t1)
    whole = total_s(rows, r.t0, r.t1, key)
    if whole is None or not r.units:
        return None
    return 1e3 * (whole - (total_s(rows, r.t0, r.t1, less) or 0.0)) / r.units


def ms_per_ksig(r, attr: str, *keys: str):
    return per_ksig_ms(window_rows(r.t0, r.t1), r.t0, r.t1, attr, *keys)


def ms_per_span(r, *keys: str):
    return per_span_ms(window_rows(r.t0, r.t1), r.t0, r.t1, *keys)


def self_ms_per_unit(r, key: str, *cover: str):
    s = self_s(window_rows(r.t0, r.t1), r.t0, r.t1, key, *cover)
    return None if s is None or not r.units else 1e3 * s / r.units


def counter_ratio(r, num: str, den: str, scale: float = 1.0):
    """Δnum / Δden of the program's counters over the window; None where
    the program has no such counter or nothing was counted."""
    if num not in r.counters or not r.counters.get(den):
        return None
    return scale * r.counters[num] / r.counters[den]


def setup_span_s(key: str, attr: str | None = None):
    """Seconds of the process's one start-up span `key` (its attribute
    `attr` where given): recorded before any window, so read from the
    whole ring; None where it is not (or no longer) there."""
    rows = window_rows(float("-inf"), float("inf"))
    mine = select(rows, key) if rows else []
    if not mine:
        return None
    if attr is None:
        return mine[0]["end"] - mine[0]["start"]
    value = (mine[0].get("attrs") or {}).get(attr)
    return None if value is None else float(value)


# -- the run's own device trace, read with the operations' metadata ----------

#: xplane.proto (tsl/profiler/protobuf), the fields read here
_XPLANE_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("id", 1, "int64", False), ("name", 2, "string", False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, "int64", False), ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False), ("value", 2, "XStatMetadata", False)],
    "XLine": [("id", 1, "int64", False), ("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False), ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False), ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XEventMetadata": [("id", 1, "int64", False), ("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStat": [("metadata_id", 1, "int64", False), ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
    "XStatMetadata": [("id", 1, "int64", False), ("name", 2, "string", False)],
}

_xspace_class = None


def _xspace():
    """A protobuf class for XSpace built from the schema above (the
    container ships google.protobuf, and no compiled xplane_pb2 short of
    importing all of tensorflow)."""
    global _xspace_class
    if _xspace_class is None:
        from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

        fd = descriptor_pb2.FileDescriptorProto(
            name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
        scalar = {"int64": 3, "uint64": 4, "string": 9}
        for msg, fields in _XPLANE_SCHEMA.items():
            m = fd.message_type.add(name=msg)
            for name, number, kind, repeated in fields:
                f = m.field.add(name=name, number=number, label=3 if repeated else 1)
                if kind in scalar:
                    f.type = scalar[kind]
                else:
                    f.type, f.type_name = 11, f".bench_xplane.{kind}"
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fd)
        _xspace_class = message_factory.GetMessageClass(
            pool.FindMessageTypeByName("bench_xplane.XSpace"))
    return _xspace_class


#: the event-metadata stat that carries an operation's jax name stack
#: (`jit(_kernel_eq)/decompress/...`), as found on a TPU v5e
OP_NAME_STATS = ("tf_op", "hlo_op_name", "op_name")
SPAN_PREFIX = "tm."
KERNEL_EQ_MODULE = "jit__kernel_eq"
KERNEL_EQ_SCOPE = "jit(_kernel_eq)"


def load_xplane(path: str) -> dict | None:
    """{"ops": [(start_ns, end_ns, op_name)], "modules": [(start_ns,
    end_ns, name)], "host": [(start_ns, end_ns, name)]}: the first
    device plane's operations with their name stacks, its program runs,
    and the program's `tm.*` spans from the host planes."""
    try:
        space = _xspace()()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
    except Exception as e:  # noqa: BLE001 — a reader returns nothing, never raises
        _once(f"cannot parse {path}: {e!r}")
        return None
    out: dict = {"ops": [], "modules": [], "host": []}
    device = next((p for p in sorted(space.planes, key=lambda p: p.name)
                   if p.name.startswith("/device:") and any(
                       ln.name == "XLA Ops" and ln.events for ln in p.lines)), None)
    for plane in space.planes:
        if plane.name.startswith("/device:") and plane is not device:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            op_name = ""
            for st in e.value.stats:
                if stat_names.get(st.metadata_id) in OP_NAME_STATS:
                    op_name = st.str_value or stat_names.get(st.ref_value, "")
                    break
            meta[e.key] = (e.value.name, op_name)
        for line in plane.lines:
            base = line.timestamp_ns
            if plane is device:
                dest = {"XLA Ops": out["ops"], "XLA Modules": out["modules"]}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    name, op_name = meta.get(ev.metadata_id, ("", ""))
                    s = base + ev.offset_ps / 1e3
                    dest.append((s, s + ev.duration_ps / 1e3,
                                 op_name if dest is out["ops"] else name))
            else:
                for ev in line.events:
                    name = meta.get(ev.metadata_id, ("", ""))[0]
                    if name.startswith(SPAN_PREFIX):
                        s = base + ev.offset_ps / 1e3
                        out["host"].append((s, s + ev.duration_ps / 1e3, name))
    return out


_xplane_cache: dict = {}


def run_xplane(r) -> dict | None:
    """This run's device trace (the newest `.xplane.pb` under
    `.bench_trace/`, written after this process started), parsed once."""
    if not getattr(r, "trace", None):
        return None
    found = glob.glob(os.path.join(harness.ROOT, ".bench_trace", "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    started = time.time() - (time.monotonic() - harness.T0)
    found = [p for p in found if os.path.getmtime(p) >= started - 1.0]
    if not found:
        _once("no device trace of this run under .bench_trace/")
        return None
    path = max(found, key=os.path.getmtime)
    if path not in _xplane_cache:
        _xplane_cache[path] = load_xplane(path)
        x = _xplane_cache[path]
        if x is not None:
            say(f"program_spans: {path}: {len(x['ops'])} device operations, "
                f"{sum(1 for o in x['ops'] if o[2])} with a name stack, "
                f"{len(x['modules'])} program runs, {len(x['host'])} tm.* spans")
    return _xplane_cache[path]


def _scoped(op_name: str, scope: str) -> bool:
    """`jit(_kernel_eq)/msm_sigs/buckets/while:While` is under `buckets`."""
    return scope in op_name.split(":")[0].split("/")


def kernel_phase_share(x: dict | None, *scopes: str):
    """% of `jit__kernel_eq`'s device time spent in operations whose name
    stack carries one of `scopes`. On this chip an operation inside a
    `while` is an event of its own under the `while`'s, so a phase is the
    UNION of its operations' intervals, not their sum."""
    if not x:
        return None
    kernel_ns = sum(e - s for s, e, name in x["modules"]
                    if name.split("(")[0].strip() == KERNEL_EQ_MODULE)
    mine = [(s, e) for s, e, op in x["ops"]
            if op.startswith(KERNEL_EQ_SCOPE) and any(_scoped(op, sc) for sc in scopes)]
    if kernel_ns <= 0 or not mine:
        return None
    return 100.0 * union_ns(mine)[0] / kernel_ns


def idle_unattributed_share(x: dict | None):
    """% of the device's idle time (the gaps between its operations, over
    the traced stretch) in which the host was inside NO `tm.*` span."""
    if not x or not x["ops"] or not x["host"]:
        return None
    _, busy = union_ns([(s, e) for s, e, _ in x["ops"]])
    gaps = [(b0, a1) for (_a0, b0), (a1, _b1) in zip(busy, busy[1:]) if a1 > b0]
    idle = sum(b - a for a, b in gaps)
    if idle <= 0:
        return None
    _, inside = union_ns([(s, e) for s, e, _ in x["host"]])
    covered = 0.0
    i = 0
    for a, b in gaps:
        while i < len(inside) and inside[i][1] <= a:
            i += 1
        j = i
        while j < len(inside) and inside[j][0] < b:
            covered += min(b, inside[j][1]) - max(a, inside[j][0])
            j += 1
    return 100.0 * (idle - covered) / idle
