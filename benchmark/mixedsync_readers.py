"""Readers of the per-layer metrics of a block-sync cell whose committee mixes
key types (`*.mixedsync`). THE MECHANISM's read what the program records once
a hub dispatch holds rows of a key type with no device kernel and hands them
to the verifier's host lane: `edwards=` / `host=` on `validation.collect`, one
`batch.host_lane` [n, scheme, workers] row a dispatch (from the lane's start
to the end of its join) and under it `batch.host_lane_wait` [n] (the part of
the join the hub's runner spent blocked, after the Edwards partition had
answered), all under `hub.dispatch`; and the hub's counter `scheme_host_sigs`.
A program without them (the parent of the PR that put the hub on the
verifier's lane: its hub verified such rows in a loop of its own, with no
span and no counter) leaves each of those readers with nothing to read: None,
never a raise.

The twins of the `.blocksync` metrics take the same arithmetic as they do —
the recorder's spans, the harness's counters, the run's device trace, the
spans' on-CPU readings — on the new traffic: they say the rest is alive
beside the lane. The `.mixedsync` metric files take everything from this
module (the harness's own tests count the metric files that name the
recorder's reader or the on-CPU one by name).
"""

from __future__ import annotations

from benchmark import cpu_readers as _cpu
from benchmark import mixed_readers as _mixed
from benchmark import program_spans as ps

# the recorder's span arithmetic and the counters', as they stand
ms_per_unit = ps.ms_per_unit
ms_per_ksig = ps.ms_per_ksig
ms_per_span = ps.ms_per_span
self_ms_per_unit = ps.self_ms_per_unit
counter_ratio = ps.counter_ratio
# `validation.collect` [sigs, edwards]: the same span whoever called the funnel
edwards_row_share = _mixed.edwards_row_share
# the spans' on-CPU readings (the work inside a wall reading)
on_cpu_ms_per_ksig = _cpu.cpu_ms_per_ksig
off_cpu_share = _cpu.off_cpu_share
cores_busy = _cpu.cores_busy

HOST_LANE_WAIT = "batch.host_lane_wait"
VERIFY = "blocksync.verify"


def host_lane_share(r):
    """% of the seconds inside `blocksync.verify` (the reactor waiting for a
    run's verification) in which the hub's runner was blocked on the lane's
    join: only the lane was running. None where no lane span was recorded."""
    rows = ps.window_rows(r.t0, r.t1)
    waited = ps.total_s(rows, r.t0, r.t1, HOST_LANE_WAIT)
    verify = ps.total_s(rows, r.t0, r.t1, VERIFY)
    if waited is None or not verify:
        return None
    return 100.0 * waited / verify
