"""The mixed chain's builder signs a commit's rows on worker processes
(`fixtures_mixedfull.SignerPool`, `sign_worker.py`): the commit it lays out is
`testing.make_commit`'s, field for field; the plain reference's commit rule
accepts it; a tiny chain built so syncs `correct`; and no worker outlives the
process that started it, whether that process ends or is killed."""

import time

import psutil
import pytest

from benchmark import fixtures, fixtures_mixedfull, harness, run
from benchmark import reference_mixedfull as refmf
from benchmark.tests import tiny_mixedfull
from benchmark.tests.test_mixedfull import HOST_ROUTE_CHECKS

SEED = 3000004451
KEY_TYPES = ("ed25519", "secp256k1")


def _block_id(tag: bytes):
    from tendermint_tpu import testing as tt

    return tt.make_block_id(tag)


# one worker, uneven shares, more workers than the set has keys (7)
@pytest.mark.parametrize("workers", [1, 3, 9])
def test_the_pooled_commit_is_laid_out_as_make_commit_s(workers):
    from tendermint_tpu import testing as tt

    chain_id = "bench-pool-1"
    vals, by_addr = fixtures_mixedfull.pinned_set(SEED, "pool", 7, 10, KEY_TYPES)
    signer = fixtures_mixedfull.CommitSigner(chain_id, vals, by_addr, workers)
    try:
        assert len(signer.pool.procs) == min(workers, 7)
        for height, ts in ((5, 1_700_000_000_000_000_123), (6, 1_700_000_001_000_000_000)):
            bid = _block_id(b"h%d" % height)
            signer.start(height, bid, ts)
            if height == 5:
                with pytest.raises(RuntimeError, match="never collected"):
                    signer.pool.start([b"x"] * 7)
            mine = signer.finish()
            theirs = tt.make_commit(chain_id, height, 0, bid, vals, by_addr, timestamp_ns=ts)
            assert (mine.height, mine.round, mine.block_id) == (height, 0, bid) == (
                theirs.height, theirs.round, theirs.block_id)
            assert len(mine.signatures) == len(theirs.signatures) == 7
            for i, (a, b, v) in enumerate(zip(mine.signatures, theirs.signatures,
                                              vals.validators)):
                assert (a.flag, a.validator_address, a.timestamp_ns) == (
                    b.flag, b.validator_address, b.timestamp_ns) == (
                    b.flag, v.address, ts + i)
                assert a.is_commit() and len(a.signature) == 64
                sign_bytes = mine.vote_sign_bytes(chain_id, i)
                assert sign_bytes == theirs.vote_sign_bytes(chain_id, i)
                assert v.pub_key.verify_signature(sign_bytes, a.signature)
                # ed25519 signs deterministically; OpenSSL draws a nonce an ECDSA signature
                assert (a.signature == b.signature) is (v.pub_key.TYPE == "ed25519")
            assert refmf.commit_verdict(fixtures.commit_data(chain_id, mine, vals)) == (
                True, 5, -1, {"ed25519": 2, "secp256k1": 3})
    finally:
        signer.close()
    assert all(p.returncode == 0 for p in signer.pool.procs)


def test_a_worker_that_died_is_an_error_not_a_hang():
    vals, by_addr = fixtures_mixedfull.pinned_set(SEED, "pool", 7, 10, KEY_TYPES)
    signer = fixtures_mixedfull.CommitSigner("bench-pool-2", vals, by_addr, 2)
    try:
        signer.pool.procs[1].kill()
        signer.pool.procs[1].wait(10)
        with pytest.raises((RuntimeError, BrokenPipeError)):
            signer.start(3, _block_id(b"x"), 1_700_000_000_000_000_000)
            signer.finish()
    finally:
        signer.close()


def test_a_tiny_chain_built_with_the_pool_syncs_correct(tmp_path):
    res = run.execute(tiny_mixedfull.make_root(str(tmp_path)), tiny_mixedfull.CELL, SEED,
                      1.5, False, device=tiny_mixedfull.CPU_DEVICE)
    assert {k for k, c in res["checks"].items() if not c["ok"]} <= HOST_ROUTE_CHECKS
    for check in ("verdict_mismatches", "stored_mismatches", "app_hash_mismatch",
                  "sigs_asked_minus_needed", "ecdsa_sigs_routed_minus_needed",
                  "warmup_refusal_height_delta.ecdsa", "warmup_refusal_height_delta.edwards"):
        assert res["checks"][check]["value"] == 0, check
    assert res["chain_left_blocks"] > 0


def _sign_workers(pid: int, wait_s: float = 20.0) -> list:
    """The `sign_worker.py` processes among `pid`'s children, once there are any."""
    end = time.monotonic() + wait_s
    while time.monotonic() < end:
        try:
            kids = [k for k in psutil.Process(pid).children()
                    if any("sign_worker.py" in a for a in k.cmdline())]
        except psutil.NoSuchProcess:
            return []
        if kids:
            return kids
        time.sleep(0.02)
    return []


@pytest.mark.parametrize("how", ["finished", "killed"])
def test_no_worker_outlives_the_fixture_child(tmp_path, how):
    """`finished`: the builder closes its pool before the child leaves through
    `os._exit`. `killed` (`FixtureChild.close()` on a run that failed): nobody
    is left to close anything — the workers end at the end of their stdin."""
    root = tiny_mixedfull.make_root(str(tmp_path), blocks=2400)
    child = harness.FixtureChild(root, tiny_mixedfull.CELL, SEED)
    try:
        fx = child.take()  # the warm-up chain is over: the window's chain is being signed now
        workers = _sign_workers(child.proc.pid)
        assert 1 <= len(workers) <= fixtures_mixedfull.SIGN_WORKERS
        if how == "finished":
            child.finish(fx)
            assert fx.chain.n_blocks == 2400 and child.proc.returncode == 0
    finally:
        child.close()
    _gone, alive = psutil.wait_procs(workers, timeout=20)
    assert not alive, [p.pid for p in alive]
