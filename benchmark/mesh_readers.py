"""Readers of the per-layer metrics of a cell whose verifier spans a mesh
of chips (`*.mesh4`). They read what the program records once it shards a
dispatch: `devices=` on its `tpu.prep` / `tpu.dispatch` / `tpu.collect`
spans, one `tpu.shard.<device id>` [n] event a chip and chunk, programs
called `jit__kernel_eq_sharded` / `jit__kernel_sharded`, and the named
scopes `shard` / `gather` / `epilogue` inside the first. A program without
them (the parent of the PR that added them) leaves every reader here with
nothing to read: None, never a raise.

Device times are the trace's: `trace_reduce` sums a program's runs over
the device planes and divides by their number, so a sharded program's
seconds are the mean over the chips; the phase shares are read on the
first chip's plane (every chip runs the same program).
"""

from __future__ import annotations

from benchmark import ops, readers
from benchmark import program_spans as ps
from benchmark.trace_reduce import union_ns

SHARDED = "_sharded"
KERNEL_EQ_SHARDED_MODULE = "jit__kernel_eq_sharded"
KERNEL_EQ_SHARDED_SCOPE = "jit(_kernel_eq_sharded)"
SHARD_EVENT = "tpu.shard."


# the recorder's span arithmetic, as it stands: the `.mesh4` metric files
# take everything from this module (the harness's own tests count the
# metric files that name the recorder's reader, PR 25's twenty-six)
ms_per_ksig = ps.ms_per_ksig
ms_per_span = ps.ms_per_span


def shard_fill_min_share(r):
    """Real signatures of the least-loaded chip over the most-loaded's,
    %, from the window's `tpu.shard.<id>` events."""
    rows = ps.window_rows(r.t0, r.t1)
    if not rows:
        return None
    by_dev: dict[str, float] = {}
    for d in rows:
        key = ps._key(d)
        if key.startswith(SHARD_EVENT) and r.t0 <= d["end"] <= r.t1:
            by_dev[key] = by_dev.get(key, 0.0) + float((d.get("attrs") or {}).get("n", 0))
    if not by_dev or max(by_dev.values()) <= 0:
        return None
    return 100.0 * min(by_dev.values()) / max(by_dev.values())


def _sharded_dispatches(r) -> list[dict]:
    """Attributes of the `tpu.prep` spans of the traced stretch whose
    dispatch went to more than one device: n, bucket, groups, devices."""
    rows = ps.window_rows(*r.stretch)
    if not rows:
        return []
    t_from, t_to = r.stretch
    return [d["attrs"] for d in ps.select(rows, "tpu.prep")
            if t_from <= d["start"] <= t_to and int((d.get("attrs") or {}).get("devices", 1)) > 1]


def _traced_sharded(r):
    """(device seconds of the sharded programs, mean over the chips;
    their dispatches) in the traced stretch, or None."""
    if not r.trace:
        return None
    kernel_s = sum(v for k, v in r.trace["programs"].items()
                   if k.startswith("jit__kernel") and k.endswith(SHARDED))
    rows = _sharded_dispatches(r)
    if kernel_s <= 0.0 or not rows:
        return None
    return kernel_s, rows


def kernel_ms_per_ksig(r):
    got = _traced_sharded(r)
    if got is None:
        return None
    kernel_s, rows = got
    return 1e3 * kernel_s / (sum(a["n"] for a in rows) / 1e3)


def kernel_roofline_share(r):
    """Operations the stretch's sharded dispatches NEED (`ops.needed_ops`
    of the whole dispatch: the work is the same whatever implements it,
    and what every chip repeats is overhead, not need) over the sharded
    programs' time over the peak of all the chips they ran on."""
    got = _traced_sharded(r)
    if got is None:
        return None
    kernel_s, rows = got
    chips = max(int(a["devices"]) for a in rows)
    need = sum(ops.needed_ops(a["bucket"], a["groups"]) for a in rows)
    return 100.0 * need / kernel_s / (chips * readers.peak_flops(r.device_kind))


def phase_share(r, *scopes: str):
    """% of `jit__kernel_eq_sharded`'s device time, on the first chip,
    under the named scopes `scopes` (the union of the operations'
    intervals, as `program_spans.kernel_phase_share`)."""
    x = ps.run_xplane(r)
    if not x:
        return None
    kernel_ns = sum(e - s for s, e, name in x["modules"]
                    if name.split("(")[0].strip() == KERNEL_EQ_SHARDED_MODULE)
    mine = [(s, e) for s, e, op in x["ops"]
            if op.startswith(KERNEL_EQ_SHARDED_SCOPE) and any(ps._scoped(op, sc) for sc in scopes)]
    if kernel_ns <= 0 or not mine:
        return None
    return 100.0 * union_ns(mine)[0] / kernel_ns
