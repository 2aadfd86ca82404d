"""Readers of the per-layer metrics of a block-sync cell whose committee
changes (`*.churn`). The new ones read what the program records once the
reactor plans a run before it verifies it: one `blocksync.plan` [run,
planned, sets, cut] a verify call under `blocksync.range`, `blocksync.verify`
[sigs, sets], `blocksync.sequential` [n, applied] around the one-commit-at-a-
time fallback, and `state.valset_update` [changes, size] around the
executor's application of a non-empty update. A program without them (the
parent of the PR that added them) leaves each of those readers with nothing
to read: None, never a raise.

The twins of the `.blocksync` metrics take the same arithmetic as they do
(`readers`, `program_spans`): the same code on another traffic.
"""

from __future__ import annotations

from benchmark import program_spans as ps

# the recorder's span arithmetic, as it stands: the `.churn` metric files take
# everything from this module (the harness's own tests count the metric files
# that name the recorder's reader, PR 25's twenty-six)
ms_per_unit = ps.ms_per_unit
ms_per_ksig = ps.ms_per_ksig
ms_per_span = ps.ms_per_span
self_ms_per_unit = ps.self_ms_per_unit

PLAN = "blocksync.plan"
RANGE = "blocksync.range"
SEQUENTIAL = "blocksync.sequential"


def _plans(r):
    """The window's `blocksync.plan` rows that went on to a verify call
    (planned > 0), or None where the program records none."""
    rows = ps.window_rows(r.t0, r.t1)
    if not rows:
        return None
    mine = [d for d in ps.select(rows, PLAN) if (d.get("attrs") or {}).get("planned")]
    return mine or None


def plan_commits_per_verify(r):
    mine = _plans(r)
    if mine is None:
        return None
    return sum(d["attrs"]["planned"] for d in mine) / len(mine)


def plan_sets_per_verify(r):
    """The MOST distinct validator sets one verify call carried (the planner
    holds at most the two the state knows)."""
    mine = _plans(r)
    if mine is None:
        return None
    return float(max(d["attrs"].get("sets", 0) for d in mine))


def cuts_per_range(r):
    """Plans that ended at a header naming a third set, over ranges."""
    mine = _plans(r)
    rows = ps.window_rows(r.t0, r.t1)
    ranges = len(ps.select(rows, RANGE)) if rows else 0
    if mine is None or not ranges:
        return None
    return sum(1 for d in mine if d["attrs"].get("cut") == "third_set") / ranges


def sequential_block_share(r):
    """% of the blocks applied that went through the one-commit-at-a-time
    fallback. 0 on honest traffic; None where the program has no planner
    (its fallback is then its answer to every change, and unrecorded)."""
    if _plans(r) is None or not r.units:
        return None
    rows = ps.window_rows(r.t0, r.t1)
    return 100.0 * ps.attr_sum(rows, "applied", r.t0, r.t1, SEQUENTIAL) / r.units
