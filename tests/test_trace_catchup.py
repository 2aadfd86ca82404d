"""The flight recorder on the catch-up path (ISSUE 25): cause and identity
(span/parent ids through `await` and `asyncio.to_thread`), the annotator
hook, the span trees a block-sync range and a light-client window leave,
the hub's one-row-per-(dispatch, trace) rule, and its two wait counters.

The chains come from the benchmark's seeded fixtures at a tiny size; the
route is the host's (conftest sets TMTPU_DISABLE_TPU), so the `tpu.*`
spans under `batch.route` are exercised by test_tpu_crypto's device path
and by the benchmark's own tests."""

import asyncio
import importlib.util
import os
import sys
import threading

import pytest

from benchmark import fixtures, harness
from benchmark.drivers import blocksync as bs_driver
from benchmark.drivers import light_sequential as light_driver
from tendermint_tpu.crypto import verify_hub as vh
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.trace import NOP_SPAN, FlightRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3000002507


@pytest.fixture
def recorder():
    """The process recorder, on and empty; restored afterwards."""
    old_enabled, old_annotator = trace.RECORDER.enabled, trace._annotator
    trace.RECORDER.enabled = True
    trace.RECORDER.clear()
    yield trace.RECORDER
    trace.RECORDER.enabled = old_enabled
    trace.set_annotator(old_annotator)
    trace.RECORDER.clear()


def _key(s):
    return f"{s['subsystem']}.{s['name']}"


def _by_id(spans):
    return {s["span_id"]: s for s in spans}


def _inside(child, parent, slack_ms=0.05):
    c0, p0 = child["start_s"] * 1e3, parent["start_s"] * 1e3
    return (c0 >= p0 - slack_ms
            and c0 + child["duration_ms"] <= p0 + parent["duration_ms"] + slack_ms)


# -- cause and identity ----------------------------------------------------------


class TestInheritance:
    def test_child_inherits_trace_and_parent(self, recorder):
        with trace.span("a", "root", root=True) as root:
            with trace.span("a", "child") as child:
                with trace.span("a", "grandchild") as grand:
                    pass
            trace.emit("a", "event")
        with trace.span("a", "alone") as alone:
            pass
        assert root.trace_id > 0 and root.parent_id == 0
        assert (child.trace_id, child.parent_id) == (root.trace_id, root.span_id)
        assert (grand.trace_id, grand.parent_id) == (root.trace_id, child.span_id)
        assert (alone.trace_id, alone.parent_id) == (0, 0)
        rows = {_key(s): s for s in recorder.dump()}
        assert rows["a.event"]["trace_id"] == root.trace_id
        assert rows["a.event"]["parent_id"] == root.span_id
        ids = [s["span_id"] for s in recorder.dump()]
        assert len(set(ids)) == len(ids) and all(ids)
        # dump keeps every key it had
        assert {"trace_id", "subsystem", "name", "start_s", "duration_ms"} <= set(rows["a.root"])
        assert trace.current() is None

    def test_root_ignores_the_current_span(self, recorder):
        with trace.span("a", "outer", root=True) as outer:
            with trace.span("a", "inner", root=True) as inner:
                pass
            assert trace.current() is outer
        assert inner.trace_id != outer.trace_id and inner.parent_id == 0

    @pytest.mark.asyncio
    async def test_through_await_and_to_thread_but_not_a_plain_thread(self, recorder):
        seen = {}

        def in_worker():
            with trace.span("t", "worker") as sp:
                seen["worker"] = (sp.trace_id, sp.parent_id, threading.get_ident())

        def in_plain_thread():
            seen["plain"] = trace.current()

        async def awaited():
            await asyncio.sleep(0)
            with trace.span("t", "awaited") as sp:
                seen["awaited"] = (sp.trace_id, sp.parent_id)

        with trace.span("t", "root", root=True) as root:
            await awaited()
            await asyncio.to_thread(in_worker)
            t = threading.Thread(target=in_plain_thread)
            t.start()
            t.join()
            # a task started inside the span copies its context too
            await asyncio.get_running_loop().create_task(awaited())
        assert seen["awaited"] == (root.trace_id, root.span_id)
        assert seen["worker"][:2] == (root.trace_id, root.span_id)
        assert seen["worker"][2] != threading.get_ident()
        assert seen["plain"] is None  # the hub's threads inherit nothing

    def test_explicit_ctx_from_another_context(self, recorder):
        """What VerifyHub.verify_many does: the caller's current span is
        handed to a thread that inherits no context."""
        got = {}
        with trace.span("v", "verify", root=True) as parent:
            ctx = trace.current()

            def runner():
                with trace.span("hub", "dispatch", ctx=ctx) as sp:
                    got["ids"] = (sp.trace_id, sp.parent_id)
                trace.record(ctx, "hub", "execute", 1.0, 2.0)

            t = threading.Thread(target=runner)
            t.start()
            t.join()
        assert got["ids"] == (parent.trace_id, parent.span_id)
        execute = next(s for s in recorder.dump() if s["name"] == "execute")
        assert (execute["trace_id"], execute["parent_id"]) == got["ids"]

    def test_tracectx_record_and_finish_are_parent_and_root(self, recorder):
        ctx = trace.start()
        trace.record(ctx, "c", "stage", 1.0, 2.0)
        trace.finish(ctx, "c", "msg")
        stage, msg = recorder.dump()
        assert msg["span_id"] == ctx.span_id and msg["parent_id"] == 0
        assert stage["parent_id"] == ctx.span_id and stage["trace_id"] == ctx.trace_id


# -- the annotator: one clock with the profiler -----------------------------------


class _Annotation:
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


class TestAnnotator:
    def test_entered_span_enters_a_tm_annotation(self, recorder):
        _Annotation.log = []
        trace.set_annotator(_Annotation)
        with trace.span("blocksync", "range", root=True):
            with trace.span("blocksync", "build"):
                pass
        trace.emit("backend", "compile")  # closed by construction: no annotation
        assert _Annotation.log == [
            ("enter", "tm.blocksync.range"), ("enter", "tm.blocksync.build"),
            ("exit", "tm.blocksync.build"), ("exit", "tm.blocksync.range")]

    def test_disabled_recorder_no_row_no_annotation_shared_nop(self, recorder):
        _Annotation.log = []
        trace.set_annotator(_Annotation)
        recorder.enabled = False
        with trace.span("blocksync", "range", root=True) as outer:
            assert outer is NOP_SPAN
            with trace.span("blocksync", "build") as inner:
                assert inner is NOP_SPAN and trace.current() is None
        trace.emit("a", "b")
        assert len(recorder) == 0 and recorder.recorded == 0 and _Annotation.log == []

    def test_installed_only_once_jax_is_loaded(self):
        """libs/trace never imports jax; importing the device module does
        not install the annotator; the first step that loads jax does."""
        code = (
            "import sys\n"
            "from tendermint_tpu.libs import trace\n"
            "assert 'jax' not in sys.modules and not trace.annotator_installed()\n"
            "from tendermint_tpu.crypto.tpu import verify as tpuv\n"
            "assert 'jax' not in sys.modules and not trace.annotator_installed()\n"
            "assert tpuv.backend_ready()\n"
            "import jax\n"
            "assert trace._annotator is jax.profiler.TraceAnnotation\n"
            "with trace.span('x', 'y'):\n"
            "    pass\n"
            "print('ok')\n"
        )
        import subprocess

        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                           timeout=120, env=env, cwd=REPO)
        assert p.returncode == 0 and p.stdout.strip().endswith("ok"), p.stderr[-2000:]


class TestRing:
    def test_default_ring_holds_a_window(self):
        assert trace.DEFAULT_RING == 32768
        assert FlightRecorder().ring_size == 32768


# -- the trees the two catch-up paths leave ----------------------------------------

N_VALS, POWER = 10, 10

RANGE_TREE = {
    "blocksync.build": "blocksync.range",
    "blocksync.plan": "blocksync.range",
    "blocksync.verify": "blocksync.range",
    "validation.collect": "blocksync.verify",
    "validation.verify": "blocksync.verify",
    "hub.submit": "validation.verify",
    "hub.wait": "validation.verify",
    "hub.queue": "validation.verify",
    "hub.execute": "validation.verify",
    "hub.dispatch": "validation.verify",
    "batch.route": "hub.dispatch",
    "blocksync.apply": "blocksync.range",
    "blocksync.save_block": "blocksync.apply",
    "state.validate": "blocksync.apply",
    "state.exec": "blocksync.apply",
    "state.save_responses": "blocksync.apply",
    "state.commit": "blocksync.apply",
    "state.save": "blocksync.apply",
}


async def _tiny_sync(n_blocks=70):
    chain = await fixtures.kvstore_chain(SEED, "trsync", n_blocks, N_VALS, POWER, 2)
    cell = {"traffic": {"peers": 4, "window": 64, "trace_seconds": 0.1}}
    trace.RECORDER.clear()  # building the chain applied blocks too
    return chain, await bs_driver._sync(chain, cell, 60.0, harness.Spans())


class TestBlockSyncTree:
    @pytest.mark.asyncio
    async def test_a_range_leaves_exactly_the_tree(self, recorder):
        hub = vh.acquire_hub(max_batch=512, window_ms=2.0, cache_size=8192)
        try:
            before = hub.stats()
            chain, s = await _tiny_sync()
            after = hub.stats()
        finally:
            vh.release_hub()
        assert s.final_height >= 64 and not s.refused
        spans = recorder.dump()
        assert recorder.dropped == 0
        ranges = [x for x in spans if _key(x) == "blocksync.range"]
        assert ranges and ranges[0]["attrs"]["first"] == 1 and ranges[0]["attrs"]["n"] >= 63
        root = ranges[0]
        mine = [x for x in spans if x["trace_id"] == root["trace_id"]]
        ids = _by_id(mine)
        # exactly the names of table B (host route: nothing under batch.route)
        assert {_key(x) for x in mine} == set(RANGE_TREE) | {"blocksync.range"}
        for x in mine:
            if x is root:
                assert x["parent_id"] == 0
                continue
            parent = ids[x["parent_id"]]
            assert _key(parent) == RANGE_TREE[_key(x)], (_key(x), _key(parent))
            assert _inside(x, parent), (_key(x), _key(parent))
        n = root["attrs"]["n"]
        per_block = [k for k, v in RANGE_TREE.items() if v == "blocksync.apply"]
        for k in ["blocksync.apply"] + per_block:
            assert sum(1 for x in mine if _key(x) == k) == n, k
        for k in ("blocksync.build", "blocksync.plan", "blocksync.verify",
                  "validation.collect", "validation.verify", "hub.submit", "hub.wait"):
            assert sum(1 for x in mine if _key(x) == k) == 1, k
        # a static set: ONE plan of the whole run, one set, ended by the run
        plan = next(x for x in mine if _key(x) == "blocksync.plan")
        assert plan["attrs"] == {"run": n, "planned": n, "sets": 1, "cut": "run_end"}
        assert next(x for x in mine if _key(x) == "blocksync.verify")["attrs"]["sets"] == 1
        assert not any(x["subsystem"] == "state" and x["name"] == "valset_update" for x in spans)
        collect = next(x for x in mine if _key(x) == "validation.collect")
        needed = N_VALS * 2 // 3 + 1  # equal powers: the quorum's early cut-off
        # one sign-bytes template a commit, applied once per signature
        assert collect["attrs"] == {
            "commits": n, "sigs": n * needed, "templates": n,
            "edwards": n * needed, "host": 0,  # rows by lane: an ed25519 chain has one
        }
        assert root["attrs"]["sigs"] == n * N_VALS
        assert next(x for x in mine if _key(x) == "validation.verify")["attrs"]["via"] == "hub"
        # range = build + verify + sum(apply) to within its own self time
        direct = [x for x in mine if x["parent_id"] == root["span_id"]]
        assert {_key(x) for x in direct} == {"blocksync.build", "blocksync.plan",
                                             "blocksync.verify", "blocksync.apply"}
        self_ms = root["duration_ms"] - sum(x["duration_ms"] for x in direct)
        assert 0 <= self_ms <= 0.1 * root["duration_ms"] + 5.0
        # no row per signature: the hub's rows go by dispatch, and the
        # whole window stays under 12 rows a block
        dispatches = after["dispatches"] - before["dispatches"]
        hub_rows = sum(1 for x in spans if _key(x) in ("hub.queue", "hub.execute"))
        assert 1 <= dispatches and hub_rows <= 2 * dispatches
        assert len(spans) <= 12 * s.final_height
        assert {_key(x) for x in spans} - set(RANGE_TREE) <= {
            "blocksync.range", "blocksync.idle", "hash.batch"}
        assert after["queue_wait_s"] > before["queue_wait_s"]
        assert after["slot_wait_s"] >= before["slot_wait_s"]

    @pytest.mark.asyncio
    async def test_a_mixed_range_puts_the_host_lane_under_its_dispatch(self, recorder):
        """A 4 ed25519 + 3 secp256k1 committee behind the hub: the range's
        ONE dispatch holds both key types, goes to one verifier and reads
        `mixed`; the lane's two rows sit under `hub.dispatch`, around
        `batch.route`, and `tracectl` prints them there."""
        from benchmark import fixtures_mixedfull

        chain = await fixtures_mixedfull.kvstore_chain(
            SEED, "trmixed", 70, 7, POWER, 2, ("ed25519", "secp256k1"))
        cell = {"traffic": {"peers": 4, "window": 64, "trace_seconds": 0.1}}
        trace.RECORDER.clear()
        hub = vh.acquire_hub(max_batch=512, window_ms=2.0, cache_size=8192)
        try:
            s = await bs_driver._sync(chain, cell, 60.0, harness.Spans())
            stats = hub.stats()
        finally:
            vh.release_hub()
        assert s.final_height >= 64 and not s.refused
        spans = recorder.dump()
        root = next(x for x in spans if _key(x) == "blocksync.range")
        mine = [x for x in spans if x["trace_id"] == root["trace_id"]]
        ids = _by_id(mine)
        tree = dict(RANGE_TREE, **{"batch.host_lane": "hub.dispatch",
                                   "batch.host_lane_wait": "hub.dispatch"})
        assert {_key(x) for x in mine} == set(tree) | {"blocksync.range"}
        for x in mine:
            if x is not root:
                assert _key(ids[x["parent_id"]]) == tree[_key(x)], _key(x)
        n = root["attrs"]["n"]
        one = {k: next(x for x in mine if _key(x) == k) for k in (
            "validation.collect", "hub.dispatch", "hub.execute", "batch.route",
            "batch.host_lane", "batch.host_lane_wait")}
        assert one["validation.collect"]["attrs"]["edwards"] == 2 * n  # the quorum: 2 + 3
        assert one["validation.collect"]["attrs"]["host"] == 3 * n
        assert one["hub.dispatch"]["attrs"]["sigs"] == 5 * n
        assert one["hub.dispatch"]["attrs"]["host_rows"] == 3 * n
        assert one["hub.dispatch"]["attrs"]["route"] == one["hub.execute"]["attrs"]["route"] == "mixed"
        assert one["batch.route"]["attrs"]["n"] == 2 * n
        assert one["batch.route"]["attrs"]["partitions"] == 2
        assert one["batch.host_lane"]["attrs"] == {
            "n": 3 * n, "scheme": "secp256k1", "workers": one["batch.host_lane"]["attrs"]["workers"]}
        assert one["batch.host_lane"]["start_s"] <= one["batch.route"]["start_s"]
        assert _inside(one["batch.host_lane_wait"], one["batch.host_lane"])
        # a group's rows are counted once, under the lane they were submitted on
        assert stats["lane_backfill_dispatched"] == stats["dispatched_sigs"] == stats["submitted"]
        assert stats["scheme_host_sigs"] * 5 == stats["dispatched_sigs"] * 3
        out = _tracectl().render_trace(spans, root["trace_id"]).splitlines()
        at = {k: next(i for i, ln in enumerate(out) if k in ln) for k in (
            "hub.dispatch", "batch.route", "batch.host_lane ", "batch.host_lane_wait")}
        col = {k: out[i].index(k) for k, i in at.items()}  # a child is indented under its parent
        assert all(at[k] > at["hub.dispatch"] and col[k] > col["hub.dispatch"]
                   for k in at if k != "hub.dispatch")

    @pytest.mark.asyncio
    async def test_same_seed_sync_identical_with_tracing_on_vs_off(self, recorder):
        _chain, on = await _tiny_sync(40)
        recorder.enabled = False
        _chain, off = await _tiny_sync(40)
        assert len(recorder) == 0
        assert (on.final_height, on.app_hash, on.applied, on.stored_hashes) == (
            off.final_height, off.app_hash, off.applied, off.stored_hashes)


WINDOW_TREE = {
    "light.fetch": "light.window",
    "light.link": "light.window",
    "light.verify": "light.window",
    "validation.collect": "light.verify",
    "validation.verify": "light.verify",
    "batch.route": "validation.verify",
}


class TestLightTree:
    @pytest.mark.asyncio
    async def test_a_window_leaves_exactly_the_tree(self, recorder):
        chain = fixtures.light_chain(SEED, "trlight", 30, N_VALS, POWER)
        assert vh.running_hub() is None
        recorder.clear()
        client = light_driver._client(chain, chain.blocks)
        await client.verify_light_block_at_height(30, chain.now_ns)
        spans = recorder.dump()
        (root,) = [x for x in spans if _key(x) == "light.window"]
        assert root["attrs"] == {"first": 2, "n": 29} and root["parent_id"] == 0
        mine = [x for x in spans if x["trace_id"] == root["trace_id"]]
        ids = _by_id(mine)
        assert {_key(x) for x in mine} == set(WINDOW_TREE) | {"light.window"}
        for x in mine:
            if x is not root:
                parent = ids[x["parent_id"]]
                assert _key(parent) == WINDOW_TREE[_key(x)] and _inside(x, parent)
            assert sum(1 for y in mine if _key(y) == _key(x)) == 1
        assert next(x for x in mine if _key(x) == "validation.verify")["attrs"] == {
            "sigs": 29 * 7, "via": "local"}
        route = next(x for x in mine if _key(x) == "batch.route")["attrs"]
        assert route["route"] == "cpu" and route["why"] == "no-device" and route["n"] == 29 * 7
        # outside the window, once per call: the witness check, the saves
        outside = {_key(x): x for x in spans if x["trace_id"] != root["trace_id"]
                   and x["subsystem"] == "light"}
        assert set(outside) == {"light.detect_divergence", "light.store"}
        # host route: no dispatch went out, so nothing was encoded ahead
        assert outside["light.store"]["attrs"] == {"n": 30, "ahead": 0}
        direct = sum(x["duration_ms"] for x in mine if x["parent_id"] == root["span_id"])
        assert 0 <= root["duration_ms"] - direct <= 0.1 * root["duration_ms"] + 2.0


# -- the hub: once per (dispatch, trace), and its wait counters ------------------------


class TestHubBulk:
    def test_500_signatures_leave_rows_per_dispatch_not_per_signature(self, recorder):
        from tendermint_tpu.testing import det_priv_keys

        keys = det_priv_keys(20)
        items = []
        for i in range(500):
            k = keys[i % 20]
            msg = b"bulk-%d" % i
            items.append((k.pub_key(), msg, k.sign(msg)))
        hub = vh.acquire_hub(max_batch=128, window_ms=1.0, cache_size=1024)
        try:
            s0 = hub.stats()
            with trace.span("validation", "verify", root=True) as caller:
                assert all(hub.verify_many(items, lane="backfill"))
            s1 = hub.stats()
            # the same group again: answered from the verdict cache, and
            # not one hub.cache_hit row each
            with trace.span("validation", "verify", root=True):
                assert all(hub.verify_many(items, lane="backfill"))
            s2 = hub.stats()
        finally:
            vh.release_hub()
        spans = recorder.dump()
        dispatches = int(s1["dispatches"] - s0["dispatches"])
        # the group is ONE unit: dispatched whole, never cut at max_batch (128 here)
        assert dispatches == 1 and s1["dispatched_sigs"] - s0["dispatched_sigs"] == 500
        assert s1["bulk_groups"] - s0["bulk_groups"] == 1 == s2["bulk_groups"] - s0["bulk_groups"]
        by = {}
        for x in spans:
            by.setdefault(_key(x), []).append(x)
        assert len(by["hub.queue"]) == len(by["hub.execute"]) == dispatches
        assert len(by["hub.dispatch"]) == dispatches
        assert sum(x["attrs"]["n"] for x in by["hub.queue"]) == 500
        assert sum(x["attrs"]["n"] for x in by["hub.execute"]) == 500
        for x in by["hub.queue"] + by["hub.execute"] + by["hub.dispatch"]:
            assert (x["trace_id"], x["parent_id"]) == (caller.trace_id, caller.span_id)
        assert all(x["attrs"]["traces"] == [caller.trace_id] for x in by["hub.dispatch"])
        assert "hub.cache_hit" not in by
        assert [x["attrs"] for x in by["hub.submit"]] == [
            {"n": 500, "answered": 0}, {"n": 500, "answered": 500}]
        assert len(spans) < 60 + 8 * dispatches
        # the wait counters exist and only grow
        for k in ("queue_wait_s", "slot_wait_s"):
            assert 0.0 <= s0[k] <= s1[k] <= s2[k]
        assert s1["queue_wait_s"] > s0["queue_wait_s"]
        assert s2["dispatches"] == s1["dispatches"] and s2["cache_hits"] - s1["cache_hits"] == 500

    def test_two_traces_in_one_dispatch_stand_alone_and_are_listed(self, recorder):
        from tendermint_tpu.testing import det_priv_keys

        k = det_priv_keys(1)[0]
        hub = vh.VerifyHub(max_batch=64, window_ms=50.0, cache_size=0, adaptive=False)
        hub.start()
        try:
            ctxs = [trace.start(), trace.start()]
            futs = [hub.submit_nowait(k.pub_key(), b"m%d" % i, k.sign(b"m%d" % i),
                                      trace_ctx=ctxs[i % 2]) for i in range(6)]
            hub.flush()
            assert all(f.result(30) for f in futs)
        finally:
            hub.stop()
        spans = recorder.dump()
        dispatch = [x for x in spans if _key(x) == "hub.dispatch"]
        assert len(dispatch) == 1 and dispatch[0]["trace_id"] == 0
        assert sorted(dispatch[0]["attrs"]["traces"]) == sorted(c.trace_id for c in ctxs)
        for name in ("hub.queue", "hub.execute"):
            rows = [x for x in spans if _key(x) == name]
            assert sorted(x["trace_id"] for x in rows) == sorted(c.trace_id for c in ctxs)
            assert [x["attrs"]["n"] for x in rows] == [3, 3]


# -- tracectl ------------------------------------------------------------------------


def _tracectl():
    spec = importlib.util.spec_from_file_location(
        "tracectl", os.path.join(REPO, "scripts", "tracectl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestTracectl:
    SPANS = [
        {"trace_id": 7, "span_id": 1, "parent_id": 0, "subsystem": "blocksync",
         "name": "range", "start_s": 10.0, "duration_ms": 100.0, "attrs": {"n": 2}},
        {"trace_id": 7, "span_id": 2, "parent_id": 1, "subsystem": "blocksync",
         "name": "build", "start_s": 10.0, "duration_ms": 10.0},
        {"trace_id": 7, "span_id": 3, "parent_id": 1, "subsystem": "blocksync",
         "name": "verify", "start_s": 10.010, "duration_ms": 60.0},
        # two overlapping children: self time takes their UNION
        {"trace_id": 7, "span_id": 4, "parent_id": 3, "subsystem": "hub",
         "name": "dispatch", "start_s": 10.020, "duration_ms": 30.0},
        {"trace_id": 7, "span_id": 5, "parent_id": 3, "subsystem": "hub",
         "name": "execute", "start_s": 10.020, "duration_ms": 30.0},
        {"trace_id": 8, "span_id": 6, "parent_id": 0, "subsystem": "light",
         "name": "window", "start_s": 11.0, "duration_ms": 5.0},
    ]

    def test_self_times(self):
        own = _tracectl().self_times(self.SPANS)
        assert own[1] == pytest.approx(30.0) and own[3] == pytest.approx(30.0)
        assert own[2] == pytest.approx(10.0) and own[6] == pytest.approx(5.0)

    def test_tree_by_parent_id(self):
        out = _tracectl().render_trace(self.SPANS, 7).splitlines()
        assert out[0] == "trace 7 (5 spans):"
        labels = [ln.split("ms ", 1)[1].split()[0] for ln in out[1:]]
        assert labels == ["blocksync.range", "blocksync.build", "blocksync.verify",
                          "hub.dispatch", "hub.execute"]
        depth = [len(ln.split("ms ", 1)[1]) - len(ln.split("ms ", 1)[1].lstrip()) for ln in out[1:]]
        assert depth == [0, 2, 2, 4, 4]
        assert "self    30.000ms" in out[1] and "n=2" in out[1]

    def test_table_has_self_time_and_old_dumps_still_render(self):
        t = _tracectl()
        table = t.summarize(self.SPANS)
        assert "selfms" in table.splitlines()[0]
        row = next(ln for ln in table.splitlines() if ln.startswith("blocksync.verify"))
        assert row.split()[-2:] == ["60.00", "30.00"]
        old = [{k: v for k, v in s.items() if k not in ("span_id", "parent_id")}
               for s in self.SPANS]
        assert "blocksync.range" in t.summarize(old)
        assert len(t.render_trace(old, 7).splitlines()) == 6
