"""The changing committee as a deployment (cell `churn150.blocksync`), at a
small size: the benchmark's own `blocksync_churn` driver drives a seeded
chain of 7 validators whose set changes every 4 heights (three power changes
to one swap) through the real `BlockSyncReactor`, hub, executor and stores on
the host route, and every number compared equals the plain reference's
(`benchmark/reference_churn.py`: the set of every height derived from the
chain's own `val:` transactions). Then the same with the control in the
program's place (one height of grace for the previous set), and with the
planner broken underneath: `correct` has to come out false each time, by the
check that is there for it.
"""

import pytest

from benchmark import control, control_churn, run
from benchmark.tests import tiny_churn

#: what the host route cannot show: no device
HOST_ROUTE_CHECKS = {"probe_errors", "tpu_route_sigs"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_churn.make_root(str(tmp_path_factory.mktemp("churn")))


def _failed(res):
    return {k for k, c in res["checks"].items() if not c["ok"]}


@pytest.mark.parametrize("seed", [3000003511, 3000003512])
def test_sound_run_holds_every_check_but_the_device_s(root, seed):
    res = run.execute(root, tiny_churn.CELL, seed, 0.3, False, device=tiny_churn.CPU_DEVICE)
    assert _failed(res) == HOST_ROUTE_CHECKS and res["correct"] is False
    checks = {k: c["value"] for k, c in res["checks"].items()}
    for name in ("verdict_mismatches", "apply_order_faults", "stored_mismatches",
                 "app_hash_mismatch", "sigs_asked_minus_needed", "valset_hash_mismatches",
                 "sequential_blocks", "plans_minus_expected",
                 "warmup_refusal_height_delta.bitflip", "warmup_refusal_height_delta.stale_set",
                 "warmup_other_faults"):
        assert checks[name] == 0, name
    assert res["metrics"]["blocksync_blocks_per_s"]["value"] > 0
    assert res["attempted"] >= checks["blocks_applied"] > 0 and res["failed"] == 0


def test_traced_run_reports_the_planner_s_layers(root):
    res = run.execute(root, tiny_churn.CELL, 3000003513, 0.3, True, device=tiny_churn.CPU_DEVICE)
    assert _failed(res) == HOST_ROUTE_CHECKS
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # what a CPU run can read: spans and counters (no device plane: the
    # trace's shares are left out, never 0)
    assert {"plan_commits_per_verify.churn", "plan_sets_per_verify.churn",
            "cuts_per_range.churn", "sequential_block_share.churn",
            "valset_update_ms_per_block.churn", "collect_ms_per_ksig.churn",
            "hub_sigs_per_dispatch.churn", "hub_submit_ms_per_ksig.churn",
            "verify_self_ms_per_block.churn", "store_ms_per_block.churn",
            "exec_ms_per_block.churn", "device_route_share.churn",
            "inline_compiles.churn",
            # the recorder's CPU readings (PR 37)
            "host_off_cpu_share.churn", "host_cores_busy.churn"} == set(m)
    assert 0 <= m["host_off_cpu_share.churn"] <= 100 and m["host_cores_busy.churn"] > 0
    assert 2 <= m["plan_commits_per_verify.churn"] <= 5  # a change every 4 heights
    assert m["plan_sets_per_verify.churn"] in (1.0, 2.0)
    assert m["cuts_per_range.churn"] >= 3 and m["sequential_block_share.churn"] == 0.0
    assert m["valset_update_ms_per_block.churn"] > 0


def test_control_stale_set_is_not_correct_by_its_own_check_alone(root):
    assert control.CONTROLS["stale_set"] is control_churn.stale_set
    with control_churn.stale_set():
        res = run.execute(root, tiny_churn.CELL, 3000003514, 0.3, False,
                          device=tiny_churn.CPU_DEVICE)
    assert _failed(res) == HOST_ROUTE_CHECKS | {"warmup_refusal_height_delta.stale_set"}
    assert res["checks"]["verdict_mismatches"]["ok"]  # honest traffic reads the same
    assert res["checks"]["sigs_asked_minus_needed"]["ok"]


def test_a_reactor_that_holds_a_run_to_one_set_is_caught(root, monkeypatch):
    """The parent's planner underneath: every entry of a run against today's
    set. The batch fails at the first change and the run goes one commit at a
    time: `sequential_blocks`, `plans_minus_expected` and the signature count
    tell, whatever the verdicts."""
    from tendermint_tpu.blocksync import reactor

    async def one_set(self, run, range_span=None):
        from tendermint_tpu.types.block import BlockID

        vals = self.state.validators
        parts = [b.make_part_set() for b, _p in run[:-1]]
        ids = [BlockID(b.hash(), p.header) for (b, _p), p in zip(run[:-1], parts)]
        entries = [(vals, ids[i], run[i][0].header.height, run[i + 1][0].last_commit)
                   for i in range(len(ids))]
        try:
            reactor.verify_commit_range(self.state.chain_id, entries, lane="backfill")
            for e in entries:
                self._commit_proofs[e[2]] = vals.hash()
        except reactor.InvalidCommitError:
            pass
        await self._apply_sequential(run, parts, ids, 0, len(ids))

    monkeypatch.setattr(reactor.BlockSyncReactor, "_verify_and_apply", one_set)
    # a window that outlasts the first run's failed batch (64 commits on the
    # host route, ≈ 0.8 s here): at 0.3 s it closed before or after the first
    # block was applied, as the machine's load had it
    res = run.execute(root, tiny_churn.CELL, 3000003515, 2.0, False,
                      device=tiny_churn.CPU_DEVICE)
    assert {"sequential_blocks", "plans_minus_expected",
            "sigs_asked_minus_needed"} <= _failed(res)
    assert res["checks"]["app_hash_mismatch"]["ok"]  # it still applies the right chain
