"""VerifyHub tests: micro-batch window semantics, per-item result
routing, dedup-cache + in-flight coalescing, TPU-breaker CPU-fallback
identity, clean shutdown with in-flight requests, adoption (votes,
proposals, commits route through the hub), the callsite lint, and the
4-node live-consensus cache-hit acceptance check."""

import os
import subprocess
import sys
import time

import pytest

from tendermint_tpu.crypto import verify_hub as vh
from tendermint_tpu.crypto.batch import CPUBatchVerifier
from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
from tendermint_tpu.crypto.verify_hub import VerifyHub

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _items(n, tag=b"vh", priv=None):
    priv = priv or Ed25519PrivKey(b"\x11" * 32)
    pub = priv.pub_key()
    out = []
    for i in range(n):
        msg = tag + b"-%d" % i
        out.append((pub, msg, priv.sign(msg)))
    return out


@pytest.fixture
def hub():
    """Standalone hub (not the process default) for scheduler tests."""
    h = VerifyHub(max_batch=8, window_ms=100.0, cache_size=256, adaptive=False)
    h.start()
    yield h
    h.stop()


@pytest.fixture
def process_hub():
    """The process-wide hub — what verify_one / Vote.verify / the
    validation shim discover via running_hub()."""
    h = vh.acquire_hub(max_batch=8, window_ms=100.0, cache_size=256, adaptive=False)
    yield h
    vh.release_hub()


class TestScheduling:
    def test_sync_facade_verdicts(self, hub):
        (pub, msg, sig), = _items(1)
        assert hub.verify_sync(pub, msg, sig) is True
        assert hub.verify_sync(pub, msg, b"\x00" * 64) is False

    def test_window_coalesces_concurrent_submissions(self, hub):
        """Non-urgent requests submitted inside the window land in ONE
        dispatch (batch occupancy = number of requests)."""
        futs = [hub.submit_nowait(pk, m, s) for pk, m, s in _items(4)]
        assert all(f.result(10.0) is True for f in futs)
        s = hub.stats()
        assert s["dispatches"] == 1, s
        assert s["dispatched_sigs"] == 4
        assert s["mean_occupancy"] == 4.0

    def test_full_batch_dispatches_before_window(self):
        """max_batch queued requests dispatch immediately — the window
        is a deadline, not a delay."""
        h = VerifyHub(max_batch=8, window_ms=3000.0, cache_size=64, adaptive=False)
        h.start()
        try:
            t0 = time.monotonic()
            futs = [h.submit_nowait(pk, m, s) for pk, m, s in _items(8, b"full")]
            assert all(f.result(10.0) is True for f in futs)
            # well under the 3s window: the full batch fired on size
            assert time.monotonic() - t0 < 2.0
            assert h.stats()["dispatches"] == 1
        finally:
            h.stop()

    def test_per_item_result_routing(self, hub):
        """One bad signature fails only its own future."""
        items = _items(6, b"route")
        pub, msg, _ = items[2]
        items[2] = (pub, msg, items[3][2])  # sig for a different msg
        res = hub.verify_many(items)
        assert res == [True, True, False, True, True, True]

    def test_dedup_cache_hit(self, hub):
        (pub, msg, sig), = _items(1, b"dup")
        assert hub.verify_sync(pub, msg, sig) is True
        assert hub.verify_sync(pub, msg, sig) is True
        s = hub.stats()
        assert s["cache_hits"] == 1
        assert s["dispatched_sigs"] == 1  # the duplicate never dispatched
        # negative verdicts are cached too (deterministic)
        assert hub.verify_sync(pub, msg, b"\x01" * 64) is False
        assert hub.verify_sync(pub, msg, b"\x01" * 64) is False
        assert hub.stats()["cache_hits"] == 2

    def test_inflight_duplicate_coalesces(self, hub):
        """An identical triple submitted while the first is still queued
        attaches to the SAME pending verify — the device sees it once."""
        (pub, msg, sig), = _items(1, b"join")
        f1 = hub.submit_nowait(pub, msg, sig)
        f2 = hub.submit_nowait(pub, msg, sig)
        assert f1.result(10.0) is True and f2.result(10.0) is True
        s = hub.stats()
        assert s["coalesced"] == 1
        assert s["dispatched_sigs"] == 1

    def test_async_api(self, hub):
        import asyncio

        items = _items(5, b"async")

        async def go():
            return await asyncio.gather(
                *(hub.verify(pk, m, s) for pk, m, s in items)
            )

        assert asyncio.run(go()) == [True] * 5

    def test_clean_shutdown_resolves_inflight(self):
        """stop() drains: every future submitted before shutdown still
        resolves with a correct verdict."""
        h = VerifyHub(max_batch=16, window_ms=500.0, cache_size=64, adaptive=False)
        h.start()
        items = _items(40, b"drain")
        futs = [h.submit_nowait(pk, m, s) for pk, m, s in items]
        h.stop()  # long window: most of the queue is still undispatched
        assert all(f.result(10.0) is True for f in futs)
        # post-shutdown submissions verify inline, never hang
        (pub, msg, sig), = _items(1, b"late")
        assert h.submit_nowait(pub, msg, sig).result(1.0) is True

    def test_verifier_exception_fails_batch_futures(self, hub, monkeypatch):
        def boom(_pk):
            raise RuntimeError("verifier construction exploded")

        monkeypatch.setattr(vh, "create_batch_verifier", boom)
        futs = [hub.submit_nowait(pk, m, s) for pk, m, s in _items(3, b"err")]
        hub.flush()
        for f in futs:
            with pytest.raises(RuntimeError):
                f.result(10.0)
        assert hub.stats()["verify_errors"] == 1


class TestFallbackIdentity:
    def test_tpu_crash_degrades_to_identical_cpu_results(self, hub, monkeypatch):
        """A TPU failure mid-hub-batch trips the breaker and the batch
        transparently re-verifies on the CPU — hub verdicts identical to
        the pure-CPU path (same contract as AdaptiveBatchVerifier)."""
        from tendermint_tpu.crypto import batch as batch_mod
        from tendermint_tpu.libs.metrics import RESILIENCE
        from tendermint_tpu.libs.retry import CircuitBreaker

        breaker = CircuitBreaker(failure_threshold=1, reset_timeout=30.0, name="t")
        monkeypatch.setattr(batch_mod, "_tpu_breaker", breaker)
        monkeypatch.setattr(batch_mod, "tpu_verifier_available", lambda: True)
        monkeypatch.setattr(batch_mod, "MIN_TPU_BATCH", 1)

        class CrashingTPU(CPUBatchVerifier):
            def verify(self):
                raise RuntimeError("simulated TPU backend crash mid-batch")

        monkeypatch.setattr(
            batch_mod.AdaptiveBatchVerifier,
            "_make_tpu_verifier",
            lambda self: CrashingTPU(),
        )

        items = _items(6, b"fb")
        pub, msg, _ = items[4]
        items[4] = (pub, msg, b"\x02" * 64)  # one bad sig survives fallback too

        expect = CPUBatchVerifier()
        for pk, m, s in items:
            expect.add(pk, m, s)
        _, want = expect.verify()

        fallback_before = RESILIENCE["tpu_fallback_batches"]
        got = hub.verify_many(items)
        assert got == want
        assert breaker.state == "open"
        assert RESILIENCE["tpu_fallback_batches"] == fallback_before + 1


class TestAdoption:
    def test_vote_verify_routes_through_hub(self, process_hub):
        hub = process_hub
        from tendermint_tpu import testing as tt
        from tendermint_tpu.types.keys import SignedMsgType

        vals, keys = tt.make_validator_set(4)
        val = vals.validators[0]
        vote = tt.make_vote(
            "hub-chain", keys[val.address], 0, 1, 0,
            SignedMsgType.PREVOTE, tt.make_block_id(),
        )
        before = hub.stats()["dispatched_sigs"]
        assert vote.verify("hub-chain", val.pub_key) is True
        assert hub.stats()["dispatched_sigs"] == before + 1
        # gossip duplicate: second verification is a cache hit
        hits = hub.stats()["cache_hits"]
        assert vote.verify("hub-chain", val.pub_key) is True
        assert hub.stats()["cache_hits"] == hits + 1

    def test_commit_verification_routes_through_hub(self, process_hub):
        hub = process_hub
        from tendermint_tpu import testing as tt
        from tendermint_tpu.types import validation

        vals, keys = tt.make_validator_set(4)
        bid = tt.make_block_id(b"commit-hub")
        commit = tt.make_commit("hub-chain", 1, 0, bid, vals, keys)
        before = hub.stats()["dispatched_sigs"]
        validation.verify_commit("hub-chain", vals, bid, 1, commit)
        assert hub.stats()["dispatched_sigs"] > before

    def test_fallbacks_without_hub(self):
        """No hub running -> verify_one and the validation shim hit the
        host directly (library/unit-test mode, bypass by design)."""
        assert vh.running_hub() is None
        (pub, msg, sig), = _items(1, b"nohub")
        assert vh.verify_one(pub, msg, sig) is True
        assert vh.verify_one(pub, msg, b"\x03" * 64) is False

    def test_metrics_render_folds_hub_series(self):
        from tendermint_tpu.libs.metrics import NodeMetrics

        hub = vh.acquire_hub(max_batch=8, window_ms=1.0)
        try:
            (pub, msg, sig), = _items(1, b"metrics")
            hub.verify_sync(pub, msg, sig)
            hub.verify_sync(pub, msg, sig)
            out = NodeMetrics().render()
            assert "tendermint_tpu_verifyhub_dispatches 1" in out
            assert "tendermint_tpu_verifyhub_cache_hits 1" in out
            assert "tendermint_tpu_verifyhub_batch_occupancy" in out
            assert "tendermint_tpu_verifyhub_queue_latency_seconds_count 1" in out
        finally:
            vh.release_hub()


def test_callsite_lint_clean():
    """tmtlint's verify rules are the tier-1 guard against new direct
    verify_signature call sites bypassing the hub."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "tmtlint"),
         "--rule", "verify-chokepoint", "--rule", "transitive-verify", "tendermint_tpu"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


class TestLiveConsensusCacheHits:
    @pytest.mark.asyncio
    async def test_four_node_gossip_duplicates_served_from_cache(self):
        """Acceptance: in a 4-validator live-consensus net every vote is
        signed once but verified by all four nodes — the shared hub
        answers the three duplicate verifications from its cache, so the
        cache-hit metric must be > 0 (and far fewer sigs reach the
        device than verifications requested)."""
        from tests.test_node import NodeNet

        net = NodeNet(4)
        await net.start()
        try:
            await net.wait_for_height(2, timeout=60)
            hub = vh.running_hub()
            assert hub is not None, "nodes did not acquire the verify hub"
            s = hub.stats()
            assert s["cache_hits"] > 0, s
            assert s["dispatched_sigs"] > 0, s
            # duplicates (cache + in-flight joins) never reached a verifier
            requests = s["submitted"] + s["cache_hits"] + s["coalesced"]
            assert requests > s["dispatched_sigs"]
        finally:
            await net.stop()
        assert vh.running_hub() is None  # last node released the hub
