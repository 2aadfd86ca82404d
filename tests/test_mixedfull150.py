"""The mixed committee BEHIND THE HUB as a deployment (cell
`mixedfull150.blocksync`), at a small size: the benchmark's own
`blocksync_mixed` driver drives a seeded chain of 7 validators (4 ed25519 +
3 secp256k1; the quorum 2 + 3) through the real `BlockSyncReactor`, hub,
executor and stores, and every number compared equals the plain reference's
(`benchmark/reference_mixedfull.py`: every signature under its own key's
scheme). A range enters the hub as one group of both key types and has to
leave it for ONE verifier, whose host lane takes the secp256k1 rows: the same
run with the lane answering True (the control), and with the hub put back on
a per-row loop of its own, has to come out not `correct`, each by the one
check that is there for it. Then the cell on the device route of the suite's
CPU devices, as `tests/test_mixed150.py` drives its light twin.
"""

import pytest

from benchmark import control, control_mixed, fixtures, run
from benchmark import reference_mixedfull as refmf
from benchmark.tests import tiny_mixedfull

#: what the host route cannot show: no device, so no Edwards row on it
HOST_ROUTE_CHECKS = {"probe_errors", "tpu_route_sigs",
                     "edwards_sigs_on_device_minus_range_needed"}
EXACT = ("verdict_mismatches", "apply_order_faults", "stored_mismatches", "app_hash_mismatch",
         "sigs_asked_minus_needed", "ecdsa_sigs_routed_minus_needed",
         "warmup_refusal_height_delta.edwards", "warmup_refusal_height_delta.ecdsa",
         "warmup_other_faults")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_mixedfull.make_root(str(tmp_path_factory.mktemp("mixedfull")))


def _failed(res):
    return {k for k, c in res["checks"].items() if not c["ok"]}


@pytest.fixture
def benchmark_ring():
    """The flight recorder's ring as a benchmark process has it (the
    default 32,768 rows). The recorder is the process's: a test that booted
    a node earlier on this worker left it at the node configuration's 4,096,
    which the ~450 blocks of a traced 1.5 s window overrun since ISSUE 42
    made apply faster — and a ring that wrapped inside the window is
    refused by every span reader."""
    from tendermint_tpu.libs import trace

    old = trace.RECORDER.ring_size
    trace.configure(ring_size=trace.DEFAULT_RING)
    yield
    trace.configure(ring_size=old)


@pytest.mark.parametrize("seed", [3000004111, 3000004112])
def test_sound_run_holds_every_check_but_the_device_s(root, seed):
    res = run.execute(root, tiny_mixedfull.CELL, seed, 1.5, False,
                      device=tiny_mixedfull.CPU_DEVICE)
    assert _failed(res) == HOST_ROUTE_CHECKS and res["correct"] is False
    checks = {k: c["value"] for k, c in res["checks"].items()}
    for name in EXACT:
        assert checks[name] == 0, name
    assert res["metrics"]["blocksync_blocks_per_s"]["value"] > 0
    assert res["attempted"] >= checks["blocks_applied"] > 0 and res["failed"] == 0
    assert res["chain_left_blocks"] > 64


def test_traced_run_reports_the_lane_s_layers_behind_the_hub(root, benchmark_ring):
    res = run.execute(root, tiny_mixedfull.CELL, 3000004113, 1.5, True,
                      device=tiny_mixedfull.CPU_DEVICE)
    assert _failed(res) == HOST_ROUTE_CHECKS
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # what a CPU run on the host route can read: spans and counters (no device
    # plane and no tpu.* span: those readers are left out, never 0)
    assert {"host_lane_ms_per_ksig.mixedsync", "host_lane_wait_ms_per_block.mixedsync",
            "host_lane_share.mixedsync", "edwards_row_share.mixedsync",
            "hub_host_rows_per_dispatch.mixedsync", "verify_ms_per_block.mixedsync",
            "verify_self_ms_per_block.mixedsync", "collect_ms_per_ksig.mixedsync",
            "collect_cpu_ms_per_ksig.mixedsync", "hub_sigs_per_dispatch.mixedsync",
            "hub_submit_ms_per_ksig.mixedsync", "hub_queue_wait_ms.mixedsync",
            "exec_ms_per_block.mixedsync", "store_ms_per_block.mixedsync",
            "inline_compiles.mixedsync", "device_route_share.mixedsync",
            "host_cores_busy.mixedsync", "host_off_cpu_share.mixedsync"} == set(m)
    assert m["edwards_row_share.mixedsync"] == 40.0  # 2 of the quorum's 5
    # a range is one group and one dispatch: 64 commits x 5 rows, 3 of them ECDSA
    assert 5 * 32 <= m["hub_sigs_per_dispatch.mixedsync"] <= 5 * 64
    assert m["hub_host_rows_per_dispatch.mixedsync"] == pytest.approx(
        0.6 * m["hub_sigs_per_dispatch.mixedsync"])
    assert 0 < m["host_lane_share.mixedsync"] <= 100 and m["host_lane_ms_per_ksig.mixedsync"] > 0
    assert 0 < m["host_lane_wait_ms_per_block.mixedsync"] < m["verify_ms_per_block.mixedsync"]
    assert m["verify_self_ms_per_block.mixedsync"] <= m["verify_ms_per_block.mixedsync"]
    assert 0 < m["collect_cpu_ms_per_ksig.mixedsync"] <= m["collect_ms_per_ksig.mixedsync"] + 1e-6
    assert m["device_route_share.mixedsync"] == 0.0 and m["inline_compiles.mixedsync"] == 0.0
    assert 0 <= m["host_off_cpu_share.mixedsync"] <= 100 and m["host_cores_busy.mixedsync"] > 0


def test_control_lane_answers_true_is_not_correct_by_its_own_check_alone(root):
    """`control_mixed.py` covers this cell as it is: with the hub on the
    verifier's lane, the lane is where the guarantee is kept."""
    assert control.CONTROLS["lane_answers_true"] is control_mixed.lane_answers_true
    with control_mixed.lane_answers_true():
        res = run.execute(root, tiny_mixedfull.CELL, 3000004114, 1.5, False,
                          device=tiny_mixedfull.CPU_DEVICE)
    assert _failed(res) == HOST_ROUTE_CHECKS | {"warmup_refusal_height_delta.ecdsa"}
    for name in ("verdict_mismatches", "sigs_asked_minus_needed",
                 "ecdsa_sigs_routed_minus_needed", "warmup_refusal_height_delta.edwards"):
        assert res["checks"][name]["ok"], name  # honest traffic and every count read the same


def _per_row_loop(self, batch):
    """The hub's local path before it went to one verifier: a row whose key
    has no batch kernel verified there and then, one after another, on the
    runner — off every counted route, with no span."""
    from tendermint_tpu.crypto.batch import AdaptiveBatchVerifier, supports_batch_verifier

    results = [False] * len(batch)
    batchable = []
    for i, p in enumerate(batch):
        if supports_batch_verifier(p.pub_key):
            batchable.append(i)
        else:
            results[i] = p.pub_key.verify_signature(p.msg, p.sig)
    self._route_local.route, self._route_local.dispatch = "cpu", None
    if batchable:
        bv = AdaptiveBatchVerifier()
        bv.add_many([(batch[i].pub_key, batch[i].msg, batch[i].sig) for i in batchable])
        for i, good in zip(batchable, bv.verify()[1]):
            results[i] = bool(good)
    return results


def test_a_hub_with_a_per_row_loop_of_its_own_is_caught_by_the_ecdsa_count_alone(
        root, monkeypatch):
    from tendermint_tpu.crypto.verify_hub import VerifyHub

    monkeypatch.setattr(VerifyHub, "_verify_batch", _per_row_loop)
    res = run.execute(root, tiny_mixedfull.CELL, 3000004115, 1.5, False,
                      device=tiny_mixedfull.CPU_DEVICE)
    assert _failed(res) == HOST_ROUTE_CHECKS | {"ecdsa_sigs_routed_minus_needed"}
    # every secp256k1 row the reference needs is missing from the counted routes
    needed_ecdsa = 3 * res["attempted"]
    assert res["checks"]["ecdsa_sigs_routed_minus_needed"]["value"] == needed_ecdsa
    assert res["checks"]["verdict_mismatches"]["ok"]  # the verdicts are right all the same


@pytest.fixture
def device_route(monkeypatch):
    """`tests/test_mixed150.py`'s: the device route, on the suite's CPU
    devices, with the cut-off at 1 and pristine telemetry."""
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.libs.retry import CircuitBreaker

    monkeypatch.setattr(B, "_tpu_available", True)
    monkeypatch.setattr(B, "MIN_TPU_BATCH", 1)
    monkeypatch.setattr(B, "_tpu_breaker",
                        CircuitBreaker(failure_threshold=1, reset_timeout=30, name="t"))
    bt.reset()
    bt.set_active("tpu")
    yield B
    bt.reset()


def test_tiny_cell_on_the_device_route(device_route, tmp_path):
    """33-block chains: a range is at most 32 commits, so its 64 Edwards rows
    run the 64-row programs `tests/test_mixed150.py` compiles (the equation's
    and, for the warm-up's flipped Edwards row, the per-signature one), beside
    96 ECDSA rows on the host lane. The chain ends inside the window: an
    untraced run closes there."""
    root = tiny_mixedfull.make_root(str(tmp_path), blocks=33, warmup_blocks=33)
    res = run.execute(root, tiny_mixedfull.CELL, 3000004116, 20.0, False,
                      device=tiny_mixedfull.CPU_DEVICE)
    assert not _failed(res) and res["correct"] is True
    checks = {k: c["value"] for k, c in res["checks"].items()}
    assert checks["blocks_applied"] == 32 and res["chain_left_blocks"] == 1
    assert checks["tpu_route_sigs"] == 2 * res["attempted"]  # every Edwards row, on the device
    assert checks["edwards_sigs_on_device_minus_range_needed"] == 0
    assert checks["ecdsa_sigs_routed_minus_needed"] == 0


def test_the_reference_and_the_program_agree_commit_by_commit():
    """A seeded 7-validator chain with one bad row of each scheme: the plain
    reference's verdicts, needs by scheme and first refusal are the
    program's own funnel's, commit by commit."""
    import asyncio

    from benchmark import fixtures_mixedfull
    from tendermint_tpu.types.block import BlockID
    from tendermint_tpu.types.validation import InvalidCommitError, verify_commit_range

    chain = asyncio.run(fixtures_mixedfull.kvstore_chain(
        41, "mfref", 12, 7, 10, 2, ("ed25519", "secp256k1")))
    types = [v.pub_key.TYPE for v in chain.vals.validators]
    bad = {4: types.index("ed25519"), 9: types.index("secp256k1")}  # height -> row, in the quorum
    assert all(i < 5 for i in bad.values())

    def commit(h):
        c = chain.commit(h)
        return fixtures.corrupt_commit(c, bad[h]) if h in bad else c

    data = [fixtures.commit_data(chain.chain_id, commit(h), chain.vals) for h in range(1, 12)]
    for h, d in enumerate(data, start=1):
        ok, checked, at, by = refmf.commit_verdict(d)
        block = chain.block(h)
        entry = (chain.vals, BlockID(block.hash(), block.make_part_set().header), h, commit(h))
        if h in bad:
            assert (ok, at, checked) == (False, bad[h], bad[h] + 1)
            with pytest.raises(InvalidCommitError, match=f"index {bad[h]}"):
                verify_commit_range(chain.chain_id, [entry])
        else:
            assert (ok, at, checked) == (True, -1, 5)
            assert by == {"ed25519": types[:5].count("ed25519"),
                          "secp256k1": types[:5].count("secp256k1")}
            verify_commit_range(chain.chain_id, [entry])
    assert refmf.first_refused(data) == 3 and refmf.first_refused(data[4:]) == 4
    assert refmf.first_refused(data[9:]) == -1
    # a range the program refused at the reference's index, and one it accepted
    rr = refmf.read_ranges(lambda h: data[h - 1], [(1, 6, 3), (10, 2, None)])
    assert (rr.attempted, rr.failed, rr.mismatches) == (8, 1, 0)
    assert rr.by_call[1] == {"ed25519": 2 * types[:5].count("ed25519"),
                             "secp256k1": 2 * types[:5].count("secp256k1")}
    # ... and a verifier that let the bad ECDSA row through, or refused a good one
    assert refmf.read_ranges(lambda h: data[h - 1], [(8, 3, None)]).mismatches == 1
    assert refmf.read_ranges(lambda h: data[h - 1], [(10, 2, 1)]).mismatches == 1
    assert refmf.apply_order_faults([1, 2, 4, 3], 4) == 2
    assert refmf.apply_order_faults([1, 2, 3], 4) == 1
    assert refmf.stored_mismatches({1: chain.block_hash_at[1], 2: b"x"}, chain.block_hash_at, 3) == 2
    txs = {h: chain.txs_at[h] for h in range(1, 6)}
    assert refmf.app_hash_mismatch(chain.app_hash_at[5], txs, 5, chain.app_hash_at[5]) == 0
    assert refmf.app_hash_mismatch(chain.app_hash_at[4], txs, 5, chain.app_hash_at[5]) == 1
