"""Reduction of a jax.profiler trace (`.xplane.pb`) to the numbers the
per-layer metrics read: seconds the device was busy, seconds per compiled
program, the device operations that took most time, and the longest idle
gaps laid to what the host was doing in them.

Only jax reads the file (`jax.profiler.ProfileData`). A device plane is one
whose name starts with `/device:`; on it the line "XLA Ops" holds one event
per executed operation and "XLA Modules" one per run of a compiled program
(`jit__kernel_eq(...)`). The harness's own spans arrive on the host planes
as events called `bench.<name>` (`jax.profiler.TraceAnnotation`), on the
same clock.
"""

from __future__ import annotations

import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
TOP = 10
MIN_GAP_NS = 20_000.0


def load(path: str) -> list[dict]:
    """[{plane, line, events: [(name, start_ns, dur_ns)]}] for every line
    that has events."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
            if events:
                rows.append({"plane": plane.name, "line": line.name, "events": events})
    return rows


def union_ns(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Total length and the merged intervals of a set of [start, end)."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def _program(name: str) -> str:
    """`jit__kernel_eq(8273645)` -> `jit__kernel_eq`."""
    return re.sub(r"\(.*$", "", name).strip()


def _op_family(name: str) -> str:
    """`%fusion.1234 = f32[...] fusion(...)` -> `fusion`: the operation's
    own name without its number; the family is what repeats."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head or name


def _device_lines(rows: list[dict], line_name: str) -> dict[str, list]:
    out: dict[str, list] = {}
    for r in rows:
        if r["plane"].startswith("/device:") and r["line"] == line_name:
            out.setdefault(r["plane"], []).extend(r["events"])
    return out


def reduce(rows: list[dict], window_s: float) -> dict:
    """The reduction. `window_s` is the traced stretch by the host's clock.
    busy_s is the union of the intervals in which an operation ran on a
    device, averaged over the devices that ran any."""
    ops = _device_lines(rows, OPS_LINE)
    modules = _device_lines(rows, MODULES_LINE)
    if not ops:
        # a backend that names its lines otherwise: the busiest device line
        busiest: dict[str, list] = {}
        for r in rows:
            if r["plane"].startswith("/device:") and len(r["events"]) > len(
                    busiest.get(r["plane"], [])):
                busiest[r["plane"]] = r["events"]
        ops = busiest
    busy, merged_all = [], []
    for events in ops.values():
        total, merged = union_ns([(s, s + d) for _n, s, d in events])
        busy.append(total)
        merged_all.append(merged)
    busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0

    programs: dict[str, float] = defaultdict(float)
    for events in modules.values():
        for name, _s, d in events:
            programs[_program(name)] += d / 1e9
    n_dev = max(1, len(modules))
    programs = {k: v / n_dev for k, v in programs.items()}

    families: dict[str, float] = defaultdict(float)
    for events in ops.values():
        for name, _s, d in events:
            families[_op_family(name)] += d / 1e9
    device_ops = sorted(([k, v / max(1, len(ops))] for k, v in families.items()),
                        key=lambda kv: -kv[1])[:TOP]

    spans = [(n[len(SPAN_PREFIX):], s, s + d)
             for r in rows if not r["plane"].startswith("/device:")
             for n, s, d in r["events"] if n.startswith(SPAN_PREFIX)]
    gaps: dict[str, float] = defaultdict(float)
    if merged_all:
        merged = merged_all[0]
        for (_a0, b0), (a1, _b1) in zip(merged, merged[1:]):
            # the breath between two operations of one program is not the
            # host's doing: only longer gaps are laid to a span
            if a1 - b0 < MIN_GAP_NS:
                gaps["between_ops"] += (a1 - b0) / 1e9
                continue
            for name, ns in _host_doing(spans, b0, a1).items():
                gaps[name] += ns / 1e9
    idle_gaps = sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "programs": programs,
        "device_ops": device_ops,
        "idle_gaps": idle_gaps,
        "device_planes": sorted(ops),
        "device_events": sum(len(e) for e in ops.values()),
    }


def _host_doing(spans: list[tuple[str, float, float]], a: float, b: float) -> dict[str, float]:
    """The idle gap [a, b) shared out, instant by instant, to the innermost
    harness span the host was inside (the shortest one covering that
    instant); `other` where it was inside none (fetching, the event loop,
    the scheduler)."""
    over = [(n, max(s, a), min(e, b), e - s) for n, s, e in spans if s < b and e > a]
    cuts = sorted({a, b, *(x for _n, s, e, _l in over for x in (s, e))})
    out: dict[str, float] = defaultdict(float)
    for lo, hi in zip(cuts, cuts[1:]):
        inside = [(length, n) for n, s, e, length in over if s <= lo and e >= hi]
        out[min(inside)[1] if inside else "other"] += hi - lo
    return out


KERNEL_PROGRAM = "jit__kernel"


def kernel_seconds(programs: dict[str, float]) -> float:
    """Device seconds of the verification kernels' programs:
    `jit__kernel_eq` (the batch equation) and `jit__kernel` (the
    per-signature attribution)."""
    return sum(v for k, v in programs.items() if k.startswith(KERNEL_PROGRAM))


def describe(rows: list[dict]) -> str:
    """One line per plane and line, for an earlier line of a traced run."""
    return "; ".join(f"{r['plane']}|{r['line']}:{len(r['events'])}" for r in rows[:40])
