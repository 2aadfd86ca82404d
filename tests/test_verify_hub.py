"""VerifyHub tests: micro-batch window semantics, per-item result
routing, dedup-cache + in-flight coalescing, TPU-breaker CPU-fallback
identity, clean shutdown with in-flight requests, adoption (votes,
proposals, commits route through the hub), the callsite lint, and the
4-node live-consensus cache-hit acceptance check."""

import os
import subprocess
import sys
import threading
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
        def boom():
            raise RuntimeError("verifier construction exploded")

        monkeypatch.setattr(vh, "AdaptiveBatchVerifier", boom)
        futs = [hub.submit_nowait(pk, m, s) for pk, m, s in _items(3, b"err")]
        hub.flush()
        for f in futs:
            with pytest.raises(RuntimeError):
                f.result(10.0)
        assert hub.stats()["verify_errors"] == 1


def _held(hub):
    """Both in-flight slots taken: the dispatcher parks at its
    pack-at-the-last-moment acquire, so whatever is submitted meanwhile
    is queued TOGETHER when `release()` lets it pack."""
    hub._slots.acquire()
    hub._slots.acquire()

    def release():
        hub._slots.release()
        hub._slots.release()

    return release


def _in_thread(fn):
    """Run `fn` on a thread; returns (thread, out) — out[0] is its result
    or the exception it raised."""
    out = []

    def run():
        try:
            out.append(fn())
        except Exception as e:  # noqa: BLE001 — handed to the test
            out.append(e)

    t = threading.Thread(target=run)
    t.start()
    return t, out


def _until(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition not reached"
        time.sleep(0.002)


def _recording(hub):
    """Every dispatch's lanes, row by row, as `_verify_batch` is handed them."""
    batches = []
    orig = hub._verify_batch

    def record(batch):
        batches.append([p.lane for p in batch])
        return orig(batch)

    hub._verify_batch = record
    return batches


class TestGroups:
    """`verify_many`: the group is ONE unit from submit to settle."""

    def test_a_thousand_rows_are_one_dispatch(self, hub):
        items = _items(1000, b"grp")
        pub, msg, _ = items[417]
        items[417] = (pub, msg, items[418][2])  # forged: another row's signature
        s0 = hub.stats()
        res = hub.verify_many(items, lane="backfill")
        s1 = hub.stats()
        assert res == [i != 417 for i in range(1000)]
        assert hub.max_batch == 8  # never cut there
        assert s1["dispatches"] - s0["dispatches"] == 1
        assert s1["dispatched_sigs"] - s0["dispatched_sigs"] == 1000
        assert s1["bulk_groups"] - s0["bulk_groups"] == 1
        assert s1["bulk_group_sigs"] - s0["bulk_group_sigs"] == 1000
        assert s1["submitted"] == s1["lane_backfill_submitted"] == 1000
        assert s1["lane_backfill_dispatched"] == 1000 and s1["queued"] == 0
        # the queue-wait counter and the histogram weigh the group by its rows
        _counts, _sum, count = hub.latency_snapshot()
        assert count == 1000 and s1["queue_wait_s"] == pytest.approx(_sum)

    def test_the_same_group_again_is_answered_from_the_lru(self):
        h = VerifyHub(max_batch=8, window_ms=100.0, cache_size=2048, adaptive=False)
        h.start()
        try:
            items = _items(1000, b"again")
            items[3] = (*items[3][:2], b"\x05" * 64)
            first = h.verify_many(items)
            s1 = h.stats()
            assert h.verify_many(items) == first and first.count(False) == 1
            s2 = h.stats()
        finally:
            h.stop()
        assert s2["cache_hits"] - s1["cache_hits"] == 1000
        assert s2["dispatches"] == s1["dispatches"] == 1
        assert s2["bulk_groups"] == s1["bulk_groups"] == 1 and s2["submitted"] == 1000

    def test_rows_shared_with_a_group_in_flight_coalesce(self, hub):
        gate = threading.Event()
        orig = hub._verify_batch
        hub._verify_batch = lambda batch: (gate.wait(10.0), orig(batch))[1]
        a = _items(300, b"share")
        a[7] = (*a[7][:2], b"\x06" * 64)
        b = a[200:] + _items(50, b"share-own") + [a[7], a[250]]
        ta, out_a = _in_thread(lambda: hub.verify_many(a, lane="backfill"))
        _until(lambda: hub.stats()["dispatches"] == 1)  # a is in flight
        tb, out_b = _in_thread(lambda: hub.verify_many(b, lane="backfill"))
        _until(lambda: hub.stats()["coalesced"] == 102)
        # a lone request for a row of the group in flight joins it too
        lone = hub.submit_nowait(*a[7])
        gate.set()
        ta.join(10.0)
        tb.join(10.0)
        assert out_a[0] == [i != 7 for i in range(300)]
        assert out_b[0] == [True] * 150 + [False, True]
        assert lone.result(10.0) is False
        s = hub.stats()
        assert s["coalesced"] == 103 and s["submitted"] == 350
        assert s["dispatched_sigs"] == 350 and s["bulk_groups"] == 2

    def test_a_group_s_own_duplicates_and_a_queued_lone_request_coalesce(self, hub):
        release = _held(hub)
        lone = _items(1, b"dup-lone")[0]
        f = hub.submit_nowait(*lone, lane="backfill")
        items = _items(20, b"dup")
        group = items + [items[4], lone, items[4]]
        t, out = _in_thread(lambda: hub.verify_many(group, lane="backfill"))
        _until(lambda: hub.stats()["coalesced"] == 3)
        assert hub.stats()["lane_backfill_queued"] == 21
        release()
        t.join(10.0)
        assert out[0] == [True] * 23 and f.result(10.0) is True
        s = hub.stats()
        assert s["submitted"] == 21 == s["dispatched_sigs"] and s["bulk_group_sigs"] == 20

    def test_rows_asked_are_counted_once_each(self, hub):
        """`submitted + cache_hits + coalesced` = rows asked, however they
        came: the benchmark's `sigs_asked_minus_needed` rests on it."""
        asked = 0
        a, b, c = _items(40, b"cnt-a"), _items(40, b"cnt-b"), _items(5, b"cnt-c")
        for group in (a, a[:10] + b, b + b[:3], []):
            assert all(hub.verify_many(group, lane="backfill"))
            asked += len(group)
        for pk, m, sig in c + c[:2]:
            assert hub.verify_sync(pk, m, sig) is True
            asked += 1
        assert all(hub.verify_many(c + a[30:] + _items(9, b"cnt-d")))
        asked += 5 + 10 + 9
        s = hub.stats()
        assert s["submitted"] + s["cache_hits"] + s["coalesced"] == asked
        assert s["submitted"] == 40 + 40 + 5 + 9 == s["dispatched_sigs"]
        assert s["lane_live_submitted"] == 5 + 9 and s["lane_backfill_submitted"] == 80

    def test_a_live_request_is_packed_ahead_of_a_waiting_group(self):
        h = VerifyHub(max_batch=4, window_ms=5_000.0, cache_size=64, adaptive=False)
        batches = _recording(h)
        h.start()
        try:
            release = _held(h)
            first, second = _items(30, b"bulk-1"), _items(30, b"bulk-2")
            t1, out1 = _in_thread(lambda: h.verify_many(first, lane="backfill"))
            _until(lambda: h.stats()["lane_backfill_queued"] == 30)
            t2, out2 = _in_thread(lambda: h.verify_many(second, lane="backfill"))
            _until(lambda: h.stats()["lane_backfill_queued"] == 60)
            (pk, m, sig), = _items(1, b"vote")
            vote = h.submit_nowait(pk, m, sig, urgent=True)
            release()
            assert vote.result(10.0) is True
            t1.join(10.0)
            t2.join(10.0)
            assert out1[0] == [True] * 30 == out2[0]
        finally:
            h.stop()
        # the vote leads the very next dispatch; the first group rides it
        # whole (never cut at max_batch 4), the second waits for its own
        assert batches == [["live"] + ["backfill"] * 30, ["backfill"] * 30], batches
        s = h.stats()
        assert s["dispatches"] == 2 and s["lane_live_dispatched"] == 1

    def test_small_groups_share_a_dispatch_up_to_max_batch(self):
        h = VerifyHub(max_batch=16, window_ms=5_000.0, cache_size=64, adaptive=False)
        batches = _recording(h)
        h.start()
        try:
            release = _held(h)
            threads = []
            for k in range(4):
                items = _items(6, b"small-%d" % k)
                threads.append(_in_thread(lambda items=items: h.verify_many(items)))
                _until(lambda: h.stats()["lane_live_queued"] == 6 * (k + 1))
            release()
            for t, out in threads:
                t.join(10.0)
                assert out[0] == [True] * 6
        finally:
            h.stop()
        assert [len(b) for b in batches] == [12, 12], batches

    def test_a_live_row_promotes_the_queued_backfill_group(self):
        h = VerifyHub(max_batch=4, window_ms=5_000.0, cache_size=64, adaptive=False)
        batches = _recording(h)
        h.start()
        try:
            release = _held(h)
            ahead, behind = _items(10, b"ahead"), _items(10, b"behind")
            ta, out_a = _in_thread(lambda: h.verify_many(ahead, lane="backfill"))
            _until(lambda: h.stats()["lane_backfill_queued"] == 10)
            tb, out_b = _in_thread(lambda: h.verify_many(behind, lane="backfill"))
            _until(lambda: h.stats()["lane_backfill_queued"] == 20)
            f = h.submit_nowait(*behind[3], lane="live")
            st = h.stats()
            assert st["lane_promotions"] == 1 and st["coalesced"] == 1
            assert st["lane_live_queued"] == 10 == st["lane_backfill_queued"]
            release()
            assert f.result(10.0) is True
            ta.join(10.0)
            tb.join(10.0)
            assert out_a[0] == [True] * 10 == out_b[0]
        finally:
            h.stop()
        assert batches == [["live"] * 10, ["backfill"] * 10], batches

    def test_stop_drains_a_queued_group(self):
        h = VerifyHub(max_batch=4, window_ms=5_000.0, cache_size=64, adaptive=False)
        h.start()
        release = _held(h)
        items = _items(50, b"drain-group")
        t, out = _in_thread(lambda: h.verify_many(items, lane="backfill"))
        _until(lambda: h.stats()["lane_backfill_queued"] == 50)
        stopper, _ = _in_thread(h.stop)
        _until(lambda: not h.is_running)
        release()
        stopper.join(10.0)
        t.join(10.0)
        assert out[0] == [True] * 50 and h.stats()["dispatches"] == 1
        # after shutdown a group verifies inline, never hangs, counts no submission
        late = _items(3, b"late-group")
        late[1] = (*late[1][:2], b"\x07" * 64)
        assert h.verify_many(late, timeout=1.0) == [True, False, True]
        assert h.stats()["submitted"] == 50

    def test_a_raising_verifier_fails_the_group_and_the_funnel_falls_back(
        self, process_hub, monkeypatch
    ):
        from tendermint_tpu.types import validation

        def boom():
            raise RuntimeError("verifier construction exploded")

        monkeypatch.setattr(vh, "AdaptiveBatchVerifier", boom)
        items = _items(12, b"grp-err")
        with pytest.raises(RuntimeError, match="exploded"):
            process_hub.verify_many(items, lane="backfill")
        s = process_hub.stats()
        assert s["verify_errors"] == 1 and s["queued"] == 0 and not process_hub._group_rows
        # the commit funnel's shim: the hub's failure costs latency, not the verdict
        items[5] = (*items[5][:2], b"\x08" * 64)
        bv = validation._CommitVerifier(lane="backfill")
        for it in items:
            bv.add(*it)
        ok, bitmap = bv.verify()
        assert not ok and bitmap == [i != 5 for i in range(12)] and bv.via == "local"
        assert process_hub.stats()["verify_errors"] == 2

    def test_unknown_lane_rejected(self, hub):
        with pytest.raises(ValueError, match="unknown verify lane"):
            hub.verify_many(_items(2, b"lane"), lane="backfil")

    @pytest.mark.parametrize(
        "rows, bucket, groups, ecdsa",
        [(70, 128, 63, 0), (100, 128, 63, 0), (101, 512, 255, 0), (150, 512, 255, 0),
         (400, 512, 255, 0), (1100, 512, 255, 0),
         # the DISPATCH is over max_batch, whatever its key types: the Edwards
         # rows of a mixed group go out at the chunk shape beside the host lane
         (70, 512, 255, 40), (60, 64, 63, 30)],
    )
    def test_a_dispatch_past_max_batch_goes_out_at_the_chunk_shape(
        self, monkeypatch, rows, bucket, groups, ecdsa
    ):
        """≤ max_batch rows: the ladder rung of the row count and the
        keys' own group bucket, as lone requests always went. More: ONE
        shape, the program start-up warms — the verifier's chunk (512
        rows here; a group beyond it is cut there by the verifier) at the
        group bucket of `verify._CHUNK_GROUPS` keys."""
        from tendermint_tpu.crypto import backend_telemetry as bt
        from tests import stub_dispatch

        log, shapes = [], []
        eq, _sig = stub_dispatch.install_kernels(monkeypatch, log, max_bucket=512)
        V = stub_dispatch.V
        monkeypatch.setattr(
            V, "_get_kernel_eq",
            lambda: lambda *a: (shapes.append((a[1].shape[0], a[0].shape[0])), eq(*a))[1],
        )
        stub_dispatch.install_device_route(monkeypatch)
        h = VerifyHub(max_batch=100, window_ms=1.0, cache_size=0)
        h.start()
        try:
            items = _items(rows, b"shape-%d" % rows) + _mixed_rows(
                "s" * ecdsa, b"shape-%d" % rows)
            assert all(h.verify_many(items, lane="backfill"))
            assert h.stats()["dispatches"] == 1
            assert h.stats()["scheme_host_sigs"] == ecdsa
            routed = {k: v[1] for k, v in bt.ROUTES.items() if v[1]}
        finally:
            h.stop()
            bt.reset()
        assert shapes == [(bucket, groups)] * -(-rows // 512), shapes
        assert routed == {"tpu": rows, **({"host-ecdsa": ecdsa} if ecdsa else {})}


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


# -- dispatches that mix key types: ONE verifier a dispatch, its own partition ----


def _mixed_rows(spec, tag):
    """Rows from a spec string: `e` an ed25519 row, `s` a secp256k1 row, an
    upper-case letter the same with one signature bit flipped, a digit a
    repeat of the row at that index (the same triple again)."""
    from tendermint_tpu.crypto.secp256k1 import Secp256k1PrivKey

    keys = {"e": Ed25519PrivKey(b"\x21" * 32), "s": Secp256k1PrivKey(b"\x22" * 32)}
    rows = []
    for i, c in enumerate(spec):
        if c.isdigit():
            rows.append(rows[int(c)])
            continue
        priv = keys[c.lower()]
        msg = b"%s-%d" % (tag, i)
        sig = priv.sign(msg)
        if c.isupper():
            sig = sig[:7] + bytes([sig[7] ^ 0x10]) + sig[8:]
        rows.append((priv.pub_key(), msg, sig))
    return rows


MIXED_SHAPES = {
    # name: (rows, how they are submitted, ECDSA rows the verifier's lane takes)
    "one ecdsa row alone": ("s", "group", 0),  # a dispatch of ONE row verifies directly
    "one edwards and one ecdsa": ("es", "group", 1),
    "lone requests under max_batch": ("esesse", "lone", 3),
    "a group over max_batch": ("es" * 10, "group", 10),
    "all ecdsa": ("s" * 12, "group", 12),
    "a corrupted edwards row": ("esEsse", "group", 3),
    "a corrupted ecdsa row": ("eseSse", "group", 3),
    "a duplicate triple of each scheme": ("esse01es", "group", 3),
}


class TestMixedDispatch:
    @pytest.mark.parametrize("shape", sorted(MIXED_SHAPES))
    def test_a_mixed_dispatch_goes_to_one_verifier_with_its_host_lane(self, shape, monkeypatch):
        from tendermint_tpu.crypto import backend_telemetry as bt
        from tendermint_tpu.crypto.secp256k1 import Secp256k1PubKey
        from tendermint_tpu.libs import trace

        spec, how, lane_rows = MIXED_SHAPES[shape]
        items = _mixed_rows(spec, shape.replace(" ", "_").encode())
        want = [pk.verify_signature(m, s) for pk, m, s in items]
        assert want == [not c.isupper() for c in spec.replace("0", "e").replace("1", "s")]
        cold_ecdsa = len({it for it in items if it[0].TYPE == "secp256k1"})
        verifies = []
        real = Secp256k1PubKey.verify_signature
        monkeypatch.setattr(
            Secp256k1PubKey, "verify_signature",
            lambda self, m, s: (verifies.append(1), real(self, m, s))[1])
        old = trace.RECORDER.enabled
        trace.RECORDER.enabled = True
        trace.RECORDER.clear()
        bt.reset()
        h = VerifyHub(max_batch=8, window_ms=100.0, cache_size=256, adaptive=False)
        h.start()
        try:
            if how == "lone":
                got = [f.result(10.0) for f in [h.submit_nowait(*it) for it in items]]
            else:
                got = h.verify_many(items, lane="backfill")
            s = h.stats()
            spans = trace.RECORDER.dump()
            # the verdict LRU answers the same rows again: no second verify
            assert h.verify_many(items) == want and len(verifies) == cold_ecdsa
            assert h.stats()["dispatches"] == s["dispatches"] == 1
            lane_routed = bt.ROUTES.get("host-ecdsa", [0, 0])[1]
        finally:
            h.stop()
            trace.RECORDER.enabled = old
            bt.reset()
        assert got == want  # row by row, in the caller's order
        assert len(verifies) == cold_ecdsa  # a repeated triple coalesced onto its first
        assert s["scheme_host_sigs"] == cold_ecdsa and s["scheme_bls_sigs"] == 0
        assert s["scheme_edwards_sigs"] == s["dispatched_sigs"] - cold_ecdsa
        assert s["dispatched_sigs"] == len(set(items)) == s["lane_backfill_dispatched"] + (
            s["lane_live_dispatched"])
        assert lane_routed == lane_rows
        by = {}
        for x in spans:
            by.setdefault(f"{x['subsystem']}.{x['name']}", []).append(x)
        (dispatch,) = by["hub.dispatch"]
        assert dispatch["attrs"].get("host_rows", 0) == cold_ecdsa
        if not lane_rows:
            assert "batch.host_lane" not in by
            return
        (lane,), (wait,) = by["batch.host_lane"], by["batch.host_lane_wait"]
        assert lane["attrs"]["n"] == wait["attrs"]["n"] == lane_rows
        assert lane["parent_id"] == wait["parent_id"] == dispatch["span_id"]
        end = lambda x: x["start_s"] + x["duration_ms"] / 1e3  # noqa: E731
        if len(set(items)) > lane_rows:
            # started before the Edwards partition is routed, joined after it
            (route,) = by["batch.route"]
            assert route["parent_id"] == dispatch["span_id"]
            assert lane["start_s"] <= route["start_s"] and end(lane) >= end(route)
            assert wait["start_s"] >= end(route) - 1e-6
            assert dispatch["attrs"]["route"] == "mixed"
        else:
            assert "batch.route" not in by and dispatch["attrs"]["route"] == "host-ecdsa"

    @pytest.mark.parametrize("how", ["stopped", "re-entrant"])
    def test_a_stopped_hub_and_a_re_entrant_call_verify_inline(self, how):
        items = _mixed_rows("esSe", how.encode())
        h = VerifyHub(max_batch=8, window_ms=1.0, cache_size=0)
        if how == "re-entrant":
            h.start()
            h._worker_ids.add(threading.get_ident())  # as if called from the runner
        try:
            assert h.verify_many(items, timeout=5.0) == [True, True, False, True]
            assert h.stats()["dispatches"] == 0
        finally:
            h.stop()
