"""Readers of the per-layer metrics of a cell whose committee mixes key
types (`*.mixed`). They read what the program records once a batch holds
rows of a key type with no device kernel: `edwards=` / `host=` on
`validation.collect`, one `batch.host_lane` [n, scheme, workers] row a
verify (from the lane's start to the end of its join) and under it
`batch.host_lane_wait` [n] (the part of the join the caller spent
blocked). A program without them (the parent of the PR that added them)
leaves every reader here with nothing to read: None, never a raise.

The twins of the `.light` metrics take the same arithmetic as they do
(`readers`, `program_spans`): the device path is the same code in another
shape.
"""

from __future__ import annotations

from benchmark import program_spans as ps

# the recorder's span arithmetic, as it stands: the `.mixed` metric files
# take everything from this module (the harness's own tests count the
# metric files that name the recorder's reader, PR 25's twenty-six)
ms_per_unit = ps.ms_per_unit
ms_per_ksig = ps.ms_per_ksig
ms_per_span = ps.ms_per_span

HOST_LANE_WAIT = "batch.host_lane_wait"
COLLECT = "validation.collect"
VERIFY = "light.verify"


def host_lane_share(r):
    """% of the seconds inside `light.verify` that the caller spent blocked
    on the lane's join: only the lane was running."""
    rows = ps.window_rows(r.t0, r.t1)
    waited = ps.total_s(rows, r.t0, r.t1, HOST_LANE_WAIT)
    verify = ps.total_s(rows, r.t0, r.t1, VERIFY)
    if waited is None or not verify:
        return None
    return 100.0 * waited / verify


def edwards_row_share(r):
    """% of the rows `validation.collect` collected whose key rides the
    Edwards batch; None where the span carries no such count."""
    rows = ps.window_rows(r.t0, r.t1)
    if not rows:
        return None
    mine = [d for d in ps.select(rows, COLLECT) if "edwards" in (d.get("attrs") or {})]
    sigs = ps.attr_sum(mine, "sigs", r.t0, r.t1, COLLECT)
    if not sigs:
        return None
    return 100.0 * ps.attr_sum(mine, "edwards", r.t0, r.t1, COLLECT) / sigs
