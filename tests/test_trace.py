"""Flight-recorder tracing (libs/trace.py) — the ISSUE 6 acceptance
suite: span propagation across the live verify funnel, ring-buffer
eviction, dump-on-wedge, disabled-mode zero overhead, and the guard
that matters most — tracing must not perturb same-seed chaos
bit-reproducibility."""

import asyncio
import contextvars
import importlib.util
import json
import os
import threading
import time

import pytest

from tendermint_tpu.consensus.harness import LocalNetwork, fast_config
from tendermint_tpu.crypto import verify_hub as vh
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.chaos import ChaosConfig, ChaosNetwork
from tendermint_tpu.libs.clock import Clock, ManualClock
from tendermint_tpu.libs.trace import NOP_SPAN, FlightRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000


def _load_tracectl():
    spec = importlib.util.spec_from_file_location(
        "tracectl", os.path.join(REPO, "scripts", "tracectl.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _StubClock(Clock):
    """Deterministic monotonic source: each read advances 1s."""

    def __init__(self):
        self.t = 0.0

    def now_ns(self) -> int:
        return 0

    def monotonic(self) -> float:
        self.t += 1.0
        return self.t


# ---------------------------------------------------------------------------
# recorder unit semantics


class TestRecorder:
    def test_ring_eviction_drop_on_full(self):
        rec = FlightRecorder(enabled=True, ring_size=8)
        for i in range(20):
            rec.emit("t", f"s{i}")
        assert len(rec) == 8
        assert rec.recorded == 20
        assert rec.dropped == 12
        names = [s["name"] for s in rec.dump()]
        assert names == [f"s{i}" for i in range(12, 20)]  # newest kept

    def test_disabled_mode_records_nothing_and_allocates_one_span(self):
        rec = FlightRecorder(enabled=False, ring_size=8)
        assert rec.start() is None
        assert rec.span("a", "b") is NOP_SPAN  # shared singleton
        with rec.span("a", "b") as sp:
            sp.set(x=1)  # no-op, no crash
        rec.emit("a", "b", duration_s=1.0)
        rec.record(None, "a", "b", 0.0, 1.0)
        rec.finish(None, "a", "b")
        assert len(rec) == 0 and rec.recorded == 0

    def test_span_context_manager_and_explicit_boundaries(self):
        rec = FlightRecorder(enabled=True, ring_size=64)
        clk = _StubClock()
        with rec.span("hub", "dispatch", clock=clk, lane="live") as sp:
            sp.set(batch=4)
        ctx = rec.start(clk)
        rec.record(ctx, "consensus", "ingest.wait", 10.0, 10.5, peer="p0")
        dump = rec.dump()
        assert dump[0]["subsystem"] == "hub"
        assert dump[0]["duration_ms"] == pytest.approx(1000.0)
        assert dump[0]["attrs"] == {"lane": "live", "batch": 4}
        assert dump[1]["trace_id"] == ctx.trace_id
        assert dump[1]["duration_ms"] == pytest.approx(500.0)
        # filters
        assert rec.dump(subsystem="hub") == dump[:1]
        assert rec.dump(trace_id=ctx.trace_id) == dump[1:]

    def test_span_records_error_attr_and_reraises(self):
        rec = FlightRecorder(enabled=True, ring_size=8)
        with pytest.raises(ValueError):
            with rec.span("t", "boom"):
                raise ValueError("x")
        (s,) = rec.dump()
        assert "ValueError" in s["attrs"]["error"]

    def test_auto_dump_writes_file(self, tmp_path):
        rec = FlightRecorder(enabled=True, ring_size=8, out_dir=str(tmp_path))
        rec.emit("t", "s1", duration_s=0.1)
        path = rec.auto_dump("breaker-trip")
        assert path is not None and os.path.exists(path)
        data = json.loads(open(path).read())
        assert data["reason"] == "breaker-trip"
        assert data["spans"][0]["name"] == "s1"
        assert rec.stats()["auto_dumps"][0]["path"] == path

    def test_auto_dump_sanitizes_reason_and_reports_failure(self, tmp_path):
        # reasons reach auto_dump from operator input
        # (/debug/flight?dump=<reason>): path characters must not escape
        # the dump dir, and a failed write must not report a path
        rec = FlightRecorder(enabled=True, ring_size=8, out_dir=str(tmp_path))
        rec.emit("t", "s1")
        path = rec.auto_dump("manual-a/b")
        assert path is not None and os.path.dirname(path) == str(tmp_path)
        assert os.path.exists(path)
        # out_dir pointing at a FILE: the write fails, the caller (and
        # /debug/flight) must see "no dump", not a phantom path
        blocker = tmp_path / "not-a-dir"
        blocker.write_text("x")
        rec.out_dir = str(blocker)
        assert rec.auto_dump("wedge") is None
        assert "path" not in rec.stats()["auto_dumps"][-1]

    def test_manual_clock_spans_still_have_duration(self):
        # ManualClock freezes the wall-clock domain only: its monotonic
        # domain advances, so spans recorded under a frozen chaos clock
        # still measure real elapsed time
        rec = FlightRecorder(enabled=True, ring_size=8)
        clk = ManualClock(0)
        with rec.span("t", "s", clock=clk):
            time.sleep(0.01)
        (s,) = rec.dump()
        assert s["duration_ms"] >= 5.0


# ---------------------------------------------------------------------------
# which thread ran a span, and how long that thread was on a core (ISSUE 37).
# Every case compares a span with itself (its CPU reading against its own
# duration, or against what the test itself burnt), never with the wall.


def _burn(cpu_s: float) -> None:
    """Hold a core until THIS thread's CPU clock has advanced `cpu_s`."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < cpu_s:
        sum(range(2000))


OLD_KEYS = {"trace_id", "span_id", "parent_id", "subsystem", "name", "start_s",
            "duration_ms", "attrs"}


class TestThreadAndCpu:
    def test_a_busy_span_reads_its_cpu(self):
        rec = FlightRecorder(enabled=True, ring_size=8)
        with rec.span("t", "busy"):
            _burn(0.020)
        (s,) = rec.dump()
        assert 20.0 <= s["cpu_ms"] <= s["duration_ms"] + 1.0

    def test_a_sleeping_span_reads_almost_none(self):
        rec = FlightRecorder(enabled=True, ring_size=8)
        with rec.span("t", "asleep"):
            time.sleep(0.05)
        (s,) = rec.dump()
        assert s["duration_ms"] >= 45.0 and s["cpu_ms"] < 10.0

    def test_two_named_threads_dump_their_names(self):
        rec = FlightRecorder(enabled=True, ring_size=8)
        gate = threading.Barrier(3, timeout=30)

        def work(name):
            with rec.span("t", name):
                pass
            gate.wait()  # both stay alive until the dump is taken
            gate.wait()

        threads = [threading.Thread(target=work, args=(n,), name=f"tm-test-{n}")
                   for n in ("a", "b")]
        for t in threads:
            t.start()
        gate.wait()
        dumped = rec.dump()
        gate.wait()
        for t in threads:
            t.join(30)
            assert not t.is_alive()
        assert {s["name"]: s["thread"] for s in dumped} == {
            "a": "tm-test-a", "b": "tm-test-b"}
        # once its thread is gone a row falls back to the thread's id
        assert all(isinstance(s["thread"], int) for s in rec.dump())

    def test_a_span_closed_on_another_thread_has_no_cpu_reading(self):
        rec = FlightRecorder(enabled=True, ring_size=8)
        sp = rec.span("t", "handed_over")
        # entered in a copy of the context: the span that never exits here
        # must not stay this thread's current span for the tests that follow
        contextvars.copy_context().run(sp.__enter__)
        assert trace.current() is None
        t = threading.Thread(target=sp.__exit__, args=(None, None, None),
                             name="tm-test-closer")
        t.start()
        t.join(30)
        assert not t.is_alive()
        (s,) = rec.dump()
        assert "cpu_ms" not in s and s["duration_ms"] >= 0.0
        # the row names the thread that ENTERED it
        assert s["thread"] == threading.current_thread().name

    def test_a_root_span_also_reads_the_process(self):
        rec = FlightRecorder(enabled=True, ring_size=8)
        with rec.span("t", "root", root=True):
            _burn(0.005)
            with rec.span("t", "child"):
                _burn(0.005)
        child, root = rec.dump()
        assert root["proc_cpu_ms"] >= root["cpu_ms"] - 1.0 and root["cpu_ms"] >= 10.0
        assert "proc_cpu_ms" not in child and child["cpu_ms"] >= 5.0

    def test_boundary_rows_carry_the_thread_alone(self):
        rec = FlightRecorder(enabled=True, ring_size=8)
        ctx = rec.start()
        rec.record(ctx, "t", "stage", ctx.t0, ctx.t0 + 0.5)
        rec.emit("t", "event", duration_s=0.1)
        rec.finish(ctx, "t", "done")
        rows = rec.dump()
        assert [s["name"] for s in rows] == ["stage", "event", "done"]
        me = threading.current_thread().name
        for s in rows:
            assert s["thread"] == me
            assert "cpu_ms" not in s and "proc_cpu_ms" not in s

    @pytest.mark.parametrize("clock", ["manual", "stub"])
    def test_wall_follows_the_injected_clock_and_cpu_stays_real(self, clock):
        rec = FlightRecorder(enabled=True, ring_size=8)
        clk = ManualClock(0) if clock == "manual" else _StubClock()
        with rec.span("t", "s", clock=clk):
            _burn(0.005)
        (s,) = rec.dump()
        if clock == "stub":  # one read at enter, one at exit: exactly 1 s
            assert s["duration_ms"] == pytest.approx(1000.0)
            assert 5.0 <= s["cpu_ms"] < 1000.0
        else:  # ManualClock's monotonic domain is the real one
            assert 5.0 <= s["cpu_ms"] <= s["duration_ms"] + 1.0

    def test_dump_keeps_every_key_it_had(self):
        rec = FlightRecorder(enabled=True, ring_size=8)
        with rec.span("t", "s", root=True, k=1):
            pass
        (s,) = rec.dump()
        assert OLD_KEYS <= set(s)
        assert set(s) - OLD_KEYS == {"thread", "cpu_ms", "proc_cpu_ms"}
        json.dumps(s)  # /debug/traces serves it as JSON

    def test_disabled_reads_no_clock_and_no_thread(self, monkeypatch):
        def boom():
            raise AssertionError("read while the recorder is disabled")

        for name in ("thread_time", "process_time", "get_ident"):
            monkeypatch.setattr(trace, name, boom)
        rec = FlightRecorder(enabled=False, ring_size=8)
        assert rec.span("t", "s", root=True) is NOP_SPAN
        with rec.span("t", "s", root=True):
            pass
        rec.emit("t", "e")
        rec.record(None, "t", "r", 0.0, 1.0)
        rec.finish(None, "t", "f")
        assert len(rec) == 0 and rec.recorded == 0

    def test_tracectl_shows_cpu_and_threads_where_the_rows_carry_them(self):
        tracectl = _load_tracectl()
        rec = FlightRecorder(enabled=True, ring_size=16)
        with rec.span("t", "root", root=True) as root:
            with rec.span("t", "busy"):
                _burn(0.005)
            with rec.span("t", "asleep"):
                time.sleep(0.02)
        spans = rec.dump()
        table = tracectl.summarize(spans)
        assert "cpums" in table.splitlines()[0]
        me = threading.current_thread().name
        (mine,) = [ln for ln in table.splitlines() if ln.startswith(me)]
        # one thread: 3 rows; its outermost span's wall and CPU, not the sum of all three
        rows, wall, cpu = int(mine.split()[-4]), float(mine.split()[-3]), float(mine.split()[-2])
        by_name = {s["name"]: s for s in spans}
        assert rows == 3
        assert wall == pytest.approx(by_name["root"]["duration_ms"], abs=0.02)
        assert cpu == pytest.approx(by_name["root"]["cpu_ms"], abs=0.02)
        tree = tracectl.render_trace(spans, root.trace_id)
        assert "cpu " in tree and f"[{me}]" in tree
        assert tracectl.thread_totals(spans)[me][0] == 3

    def test_tracectl_renders_an_old_dump_as_before(self):
        tracectl = _load_tracectl()
        rec = FlightRecorder(enabled=True, ring_size=16)
        with rec.span("t", "root", root=True, k=1) as root:
            with rec.span("t", "child"):
                pass
        new = rec.dump()
        old = [{k: v for k, v in s.items() if k in OLD_KEYS} for s in new]
        table = tracectl.summarize(old)
        assert "cpums" not in table and "thread" not in table
        assert table.splitlines()[0].split() == [
            "stage", "count", "p50ms", "p90ms", "p99ms", "maxms", "totalms", "selfms"]
        # the same rows, the same table above the new column and summary
        assert [ln[: len(table.splitlines()[0])] for ln in
                tracectl.summarize(new).splitlines()[: len(table.splitlines())]] == (
            table.splitlines())
        tree = tracectl.render_trace(old, root.trace_id)
        assert "cpu " not in tree and "[" not in tree
        assert "no row carries a thread" in tracectl.per_thread(old)


class TestWedgeDump:
    @pytest.mark.asyncio
    async def test_loop_wedge_triggers_flight_dump(self, tmp_path):
        """The LoopWatchdog wedge path must dump the span ring — the
        spans leading up to a stall are half the diagnosis."""
        from tendermint_tpu.libs.watchdog import LoopWatchdog

        old_dir, old_enabled = trace.RECORDER.out_dir, trace.RECORDER.enabled
        trace.RECORDER.out_dir = str(tmp_path)
        trace.RECORDER.enabled = True
        wd = LoopWatchdog(str(tmp_path), threshold_s=0.2, interval_s=0.1)
        wd.start()
        try:
            trace.emit("test", "pre-wedge")
            time.sleep(0.7)  # deliberately block the loop past threshold
            await asyncio.sleep(0.1)  # let the heartbeat recover
        finally:
            wd.stop()
            trace.RECORDER.out_dir = old_dir
            trace.RECORDER.enabled = old_enabled
        assert wd.reports, "watchdog never saw the wedge"
        flights = [f for f in os.listdir(tmp_path) if f.startswith("flight-loop-wedged")]
        assert flights, "wedge did not dump the flight recorder"
        spans = json.loads(open(os.path.join(tmp_path, flights[0])).read())["spans"]
        assert any(s["name"] == "pre-wedge" for s in spans)


class TestBackendInitWatchdog:
    """Bounded-retry watchdogged backend init (the attach path crypto/
    batch._probe_tpu runs behind) — no more one-shot 180 s cliff."""

    def setup_method(self):
        from tendermint_tpu.crypto import backend_telemetry as bt

        bt.reset()

    def test_success_first_attempt(self):
        from tendermint_tpu.crypto import backend_telemetry as bt
        from tendermint_tpu.libs.watchdog import BackendInitWatchdog

        wd = BackendInitWatchdog(attempts=3, timeout_s=5.0, backoff_s=0.0)
        assert wd.run(lambda: "backend") == "backend"
        assert wd.log == [{"latency_s": wd.log[0]["latency_s"], "outcome": "ok"}]
        assert bt.BACKEND["attach_attempts"] == 1
        assert bt.BACKEND["attach_failures"] == 0

    def test_bounded_attempts_on_error(self):
        from tendermint_tpu.crypto import backend_telemetry as bt
        from tendermint_tpu.libs.watchdog import BackendInitWatchdog

        calls = []

        def boom():
            calls.append(1)
            raise RuntimeError("device down")

        wd = BackendInitWatchdog(attempts=3, timeout_s=5.0, backoff_s=0.0)
        assert wd.run(boom) is None
        assert len(calls) == 3
        assert [e["outcome"] for e in wd.log] == ["error"] * 3
        assert bt.BACKEND["attach_attempts"] == 3
        assert bt.BACKEND["attach_failures"] == 3

    def test_falsy_result_is_a_failed_attempt_not_an_attach(self):
        # backend_ready() returning False (no device came up)
        # must not be telemetered as a successful attach — the exact
        # lost-TPU signal this subsystem exists to expose
        from tendermint_tpu.crypto import backend_telemetry as bt
        from tendermint_tpu.libs.watchdog import BackendInitWatchdog

        calls = []

        def unavailable():
            calls.append(1)
            return False

        wd = BackendInitWatchdog(attempts=3, timeout_s=5.0, backoff_s=0.0)
        assert wd.run(unavailable) is None
        assert len(calls) == 3
        assert [e["outcome"] for e in wd.log] == ["unavailable"] * 3
        assert bt.BACKEND["attach_attempts"] == 3
        assert bt.BACKEND["attach_failures"] == 3

    def test_hung_attempt_adopted_when_it_finishes_late(self):
        # attempt 1 outlives its per-attempt timeout; while attempt 2
        # waits, attempt 1 completes and its result is adopted — a
        # device that comes up at t=70s is not thrown away by a 60s
        # timeout (the probe thread can't be killed, only outwaited)
        from tendermint_tpu.libs.watchdog import BackendInitWatchdog

        started = []

        def slow():
            started.append(time.monotonic())
            time.sleep(0.6)
            return "late"

        wd = BackendInitWatchdog(
            attempts=3, timeout_s=0.25, backoff_s=0.0, poll_s=0.05
        )
        assert wd.run(slow) == "late"
        assert wd.log[0]["outcome"] == "hung"
        assert wd.log[-1]["outcome"] == "ok"


class TestFallbackDumpGating:
    def test_flight_dump_only_on_active_kind_transition(self, tmp_path):
        """A flapping device re-probes via the half-open breaker; every
        failed probe records a fallback, but only an actual TPU->CPU
        TRANSITION dumps the flight ring (one file per transition, not
        one per failed batch)."""
        from tendermint_tpu.crypto import backend_telemetry as bt

        bt.reset()
        old_dir, old_enabled = trace.RECORDER.out_dir, trace.RECORDER.enabled
        trace.RECORDER.out_dir = str(tmp_path)
        trace.RECORDER.enabled = True
        try:
            bt.set_active("tpu")
            for _ in range(5):  # first trips the transition, rest flap
                bt.record_fallback("tpu", "cpu", "device error")
            assert bt.BACKEND["fallbacks"] == 5
            dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight-")]
            assert len(dumps) == 1
            bt.set_active("tpu")  # breaker closed again
            bt.record_fallback("tpu", "cpu", "device error")
            dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight-")]
            assert len(dumps) == 2
        finally:
            bt.reset()
            trace.RECORDER.out_dir = old_dir
            trace.RECORDER.enabled = old_enabled


# ---------------------------------------------------------------------------
# live propagation: gossip -> ingest -> hub -> apply


STAGES = ("ingest.wait", "ingest.verify", "ingest.reorder", "apply")


def _by_trace(spans):
    out: dict[int, dict[str, dict]] = {}
    for s in spans:
        if s["trace_id"]:
            out.setdefault(s["trace_id"], {})[f"{s['subsystem']}.{s['name']}"] = s
    return out


class TestLivePropagation:
    @pytest.mark.asyncio
    async def test_end_to_end_spans_answer_where_time_went(self, tmp_path):
        """Acceptance: a live 4-node LocalNetwork run produces
        end-to-end traces whose stage durations tile the observed
        ingest latency exactly, /debug/traces serves them, and
        tracectl renders the per-stage table from the dump."""
        old_enabled = trace.RECORDER.enabled
        trace.RECORDER.enabled = True
        trace.RECORDER.clear()
        # cache OFF: the in-process harness shares one hub across all 4
        # nodes, so a vote's signer (sync own-vote check) would otherwise
        # pre-cache every triple and peers' stage-1 submissions would all
        # short-circuit as cache hits — real per-process nodes dispatch
        # cold, which is the path this test pins
        hub = vh.acquire_hub(max_batch=64, window_ms=1.0, cache_size=0)
        net = LocalNetwork(4, config=fast_config())
        try:
            await net.start()
            await net.wait_for_height(2, timeout=60)
        finally:
            await net.stop()
            vh.release_hub()
            trace.RECORDER.enabled = old_enabled
        spans = trace.RECORDER.dump()
        assert spans, "tracing enabled but the live run recorded nothing"

        # every funnel stage appears somewhere in the run
        seen = {f"{s['subsystem']}.{s['name']}" for s in spans}
        for stage in (
            "consensus.ingest.wait", "consensus.ingest.verify",
            "consensus.ingest.reorder", "consensus.apply", "consensus.msg",
            "hub.queue", "hub.execute", "consensus.height",
        ):
            assert stage in seen, f"missing {stage} (saw {sorted(seen)})"

        # the tiling invariant: wait + verify + reorder + apply == msg
        complete = [
            t for t in _by_trace(spans).values()
            if all(f"consensus.{st}" in t for st in STAGES) and "consensus.msg" in t
        ]
        assert complete, "no trace carried the full stage set"
        for t in complete:
            total = sum(t[f"consensus.{st}"]["duration_ms"] for st in STAGES)
            assert total == pytest.approx(
                t["consensus.msg"]["duration_ms"], abs=0.01
            ), f"stages do not tile the end-to-end span: {t}"
        # hub spans join the same trace as the ingest stages
        assert any("hub.queue" in t and "hub.execute" in t for t in complete)

        # ... and the node edge serves it: /debug/traces + tracectl
        from tendermint_tpu.rpc.core import Environment
        from tendermint_tpu.rpc.server import RPCServer

        import aiohttp

        server = RPCServer(Environment(chain_id="trace-test"))
        await server.start("127.0.0.1", 0)
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(
                    f"http://127.0.0.1:{server.port}/debug/traces"
                ) as resp:
                    assert resp.status == 200
                    body = await resp.json()
                async with s.get(
                    f"http://127.0.0.1:{server.port}/debug/flight"
                ) as resp:
                    assert (await resp.json())["stats"]["ring_size"] > 0
                # the /metrics 404 fix: an env with NO metrics object
                # still serves an (empty) registry render with 200
                async with s.get(
                    f"http://127.0.0.1:{server.port}/metrics"
                ) as resp:
                    assert resp.status == 200
        finally:
            await server.stop()
        assert body["stats"]["recorded"] >= len(spans)
        fetched = body["spans"]
        assert {s["subsystem"] for s in fetched} >= {"consensus", "hub"}

        tracectl = _load_tracectl()
        table = tracectl.summarize(fetched)
        assert "consensus.msg" in table and "p50ms" in table and "p99ms" in table
        # single-trace rendering: a message's life, top to bottom
        tid = fetched[-1]["trace_id"] or next(
            s["trace_id"] for s in fetched if s["trace_id"]
        )
        assert f"trace {tid}" in tracectl.render_trace(fetched, tid)
        # round-trips through a dump file too (the auto-dump shape)
        dump_file = tmp_path / "dump.json"
        dump_file.write_text(json.dumps({"spans": fetched}))
        assert tracectl.load_spans(str(dump_file)) == fetched


# ---------------------------------------------------------------------------
# the determinism guard: tracing ON vs OFF, same seed, identical output


TARGET = 2


async def _chaos_run(seed: int):
    """Trimmed test_chaos_live run: 4 validators, asymmetric partition +
    clock skew on frozen ManualClocks. Returns (header times, own
    non-nil precommit timestamps)."""
    from tendermint_tpu.consensus import messages as m
    from tendermint_tpu.types.keys import SignedMsgType

    chaos = ChaosNetwork(ChaosConfig(seed=seed, clock_skew_ms=80.0))
    genesis_ns = 1_700_000_000_000_000_000
    net = LocalNetwork(
        4,
        config=fast_config(),
        chaos=chaos,
        base_clock=ManualClock(genesis_ns - 500 * MS),
    )
    chaos.partition_oneway("node0", "node1")
    precommit_ts: dict[tuple[int, int], int] = {}
    await net.start()
    try:
        for i, node in enumerate(net.nodes):
            orig = node.cs.broadcast_hook

            def hook(msg, _i=i, _orig=orig):
                if (
                    isinstance(msg, m.VoteMessage)
                    and msg.vote.type == SignedMsgType.PRECOMMIT
                    and not msg.vote.block_id.is_nil()
                ):
                    precommit_ts.setdefault(
                        (msg.vote.height, _i), msg.vote.timestamp_ns
                    )
                _orig(msg)

            node.cs.broadcast_hook = hook
        await asyncio.gather(
            *(n.cs.wait_for_height(TARGET, 60) for n in net.nodes)
        )
        header_times = {
            h: net.nodes[0].block_store.load_block(h).header.time_ns
            for h in range(1, TARGET + 1)
        }
    finally:
        await net.stop()
    return header_times, dict(precommit_ts)


class TestBitReproducibility:
    @pytest.mark.asyncio
    async def test_same_seed_identical_with_tracing_on_vs_off(self):
        """Tracing must never read wall clock in seeded paths or alter
        scheduling: a same-seed chaos run with the recorder ON produces
        the exact block/vote timestamps of a run with it OFF."""
        old = trace.RECORDER.enabled
        try:
            trace.RECORDER.enabled = True
            t_on, v_on = await _chaos_run(seed=424)
            trace.RECORDER.enabled = False
            t_off, v_off = await _chaos_run(seed=424)
        finally:
            trace.RECORDER.enabled = old
        genesis_ns = 1_700_000_000_000_000_000
        # the deterministic closed form still holds with tracing on
        assert t_on == {h: genesis_ns + (h - 1) * MS for h in t_on}
        assert t_on == t_off, "block timestamps diverged with tracing on"
        common = v_on.keys() & v_off.keys()
        assert common
        assert {k: v_on[k] for k in common} == {k: v_off[k] for k in common}
