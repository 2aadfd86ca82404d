"""The readers of the program's own flight recorder: arithmetic on
hand-made rows, the refusals (wrapped ring, recorder off, a program
without the spans), the device readers on hand-made intervals and on a
small trace with scoped operations recorded on a TPU v5e, and every new
metric file driven by the tiny cells on the CPU."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import harness, run
from benchmark import program_spans as ps
from benchmark.tests import tiny

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SCOPED = os.path.join(os.path.dirname(__file__), "data", "tiny_scoped.xplane.pb")
OLD = os.path.join(os.path.dirname(__file__), "data", "tiny_tpu.xplane.pb")


def _row(key, start, dur, span_id, parent_id=0, trace_id=1, **attrs):
    sub, name = key.split(".", 1)
    d = {"subsystem": sub, "name": name, "start": start, "end": start + dur,
         "span_id": span_id, "parent_id": parent_id, "trace_id": trace_id}
    if attrs:
        d["attrs"] = attrs
    return d


ROWS = [
    _row("blocksync.range", 10.0, 1.0, 1, first=1, n=64),
    _row("blocksync.build", 10.0, 0.2, 2, 1, n=64),
    _row("blocksync.verify", 10.2, 0.5, 3, 1, sigs=6464),
    _row("validation.collect", 10.21, 0.09, 4, 3, commits=64, sigs=6464),
    _row("validation.verify", 10.3, 0.39, 5, 3, sigs=6464),
    _row("hub.submit", 10.3, 0.04, 6, 5, n=6464),
    # recorded by a thread that inherits no context: same trace, parent 5
    _row("hub.dispatch", 10.32, 0.1, 7, 5, sigs=512),
    _row("hub.dispatch", 10.40, 0.1, 8, 5, sigs=512),  # overlaps the first
    _row("tpu.prep", 10.33, 0.01, 9, 7, n=512, bucket=512),
    _row("tpu.collect", 10.35, 0.016, 10, 7, bucket=512, eq_ok=True),
    _row("tpu.collect", 10.45, 0.014, 11, 8, bucket=512, eq_ok=True),
    _row("blocksync.apply", 10.7, 0.3, 12, 1, height=1),
    _row("unrelated.other", 10.25, 0.2, 13, 0, trace_id=0),
]


def test_totals_clip_to_the_window():
    assert ps.total_s(ROWS, 0, 99, "blocksync.build") == pytest.approx(0.2)
    assert ps.total_s(ROWS, 10.1, 99, "blocksync.build") == pytest.approx(0.1)
    assert ps.total_s(ROWS, 0, 99, "tpu.collect") == pytest.approx(0.030)
    assert ps.total_s(ROWS, 0, 99, "no.such") is None and ps.total_s(None, 0, 9, "x.y") is None
    assert ps.per_unit_ms(ROWS, 0, 99, 64, "blocksync.build") == pytest.approx(3.125)
    assert ps.per_unit_ms(ROWS, 0, 99, 0, "blocksync.build") is None
    assert ps.per_span_ms(ROWS, 0, 99, "tpu.collect") == pytest.approx(15.0)


def test_per_ksig_weights_a_clipped_span_by_its_share():
    assert ps.per_ksig_ms(ROWS, 0, 99, "sigs", "validation.collect") == pytest.approx(
        1e3 * 0.09 / 6.464)
    # half the span inside the window: half its time over half its signatures
    assert ps.per_ksig_ms(ROWS, 10.255, 99, "sigs", "validation.collect") == pytest.approx(
        1e3 * 0.09 / 6.464)
    assert ps.per_ksig_ms(ROWS, 0, 99, "absent", "validation.collect") is None


def test_self_time_is_the_span_minus_the_union_of_what_covers_it():
    # verify 0.5 s; collect [10.21, 10.30], submit [10.30, 10.34], dispatches
    # [10.32, 10.50] overlapping: the union is [10.21, 10.50] = 0.29
    got = ps.self_s(ROWS, 0, 99, "blocksync.verify", "validation.collect", "hub.submit",
                    "hub.dispatch")
    assert got == pytest.approx(0.5 - 0.29)
    # every descendant: validation.verify reaches to 10.69
    assert ps.self_s(ROWS, 0, 99, "blocksync.verify") == pytest.approx(0.5 - 0.48)
    assert ps.self_s(ROWS, 0, 99, "blocksync.range") == pytest.approx(0.0)
    assert ps.self_s(ROWS, 0, 99, "no.such") is None


def test_descendants_by_trace_and_containment_where_the_parent_is_not_known():
    rows = [dict(r) for r in ROWS]
    for r in rows:  # the hub's rows as a program without ctx hand-over leaves them
        if r["subsystem"] == "hub" and r["name"] == "dispatch":
            r["parent_id"] = 0
    root = next(r for r in rows if r["name"] == "verify" and r["subsystem"] == "blocksync")
    names = sorted({f"{d['subsystem']}.{d['name']}" for d in ps.descendants(rows, root)})
    assert "hub.dispatch" in names and "tpu.collect" in names
    assert "unrelated.other" not in names and "blocksync.apply" not in names


# -- refusals --------------------------------------------------------------------


@pytest.fixture
def recorder():
    from tendermint_tpu.libs import trace

    old = trace.RECORDER
    ps._rows_cache.clear()
    yield trace
    trace.RECORDER = old
    ps._rows_cache.clear()


def _readings(t0, t1, **kw):
    return SimpleNamespace(t0=t0, t1=t1, units=kw.pop("units", 10), counters={},
                           trace=None, **kw)


def test_a_wrapped_ring_makes_a_reader_return_none(recorder):
    import time

    recorder.RECORDER = recorder.FlightRecorder(enabled=True, ring_size=8)
    t0 = time.perf_counter()
    for _ in range(6):
        with recorder.RECORDER.span("light", "fetch", n=1):
            pass
    r = _readings(t0, time.perf_counter() + 1)
    assert ps.ms_per_unit(r, "light.fetch") is not None
    ps._rows_cache.clear()
    for _ in range(6):  # 12 rows through a ring of 8: the window's first rows are gone
        with recorder.RECORDER.span("light", "fetch", n=1):
            pass
    assert recorder.RECORDER.dropped == 4
    assert ps.ms_per_unit(r, "light.fetch") is None
    assert ps.setup_span_s("light.fetch") is None
    # a window that opened after the oldest kept row ended is whole
    ps._rows_cache.clear()
    later = _readings(time.perf_counter(), time.perf_counter() + 1)
    with recorder.RECORDER.span("light", "fetch", n=1):
        pass
    assert ps.ms_per_unit(later, "light.fetch") is not None


def test_recorder_off_and_unknown_spans_read_nothing(recorder):
    import time

    recorder.RECORDER = recorder.FlightRecorder(enabled=True, ring_size=64)
    t0 = time.perf_counter()
    with recorder.RECORDER.span("hub", "dispatch"):  # what the parent program records
        pass
    r = _readings(t0, time.perf_counter() + 1)
    assert ps.ms_per_unit(r, "blocksync.build") is None
    assert ps.ms_per_ksig(r, "n", "tpu.prep") is None
    assert ps.self_ms_per_unit(r, "blocksync.verify", "hub.dispatch") is None
    assert ps.setup_span_s("backend.probe", "available_s") is None
    ps._rows_cache.clear()
    recorder.RECORDER.enabled = False
    assert ps.ms_per_unit(r, "hub.dispatch") is None


def test_setup_span_and_counter_ratio(recorder):
    recorder.RECORDER = recorder.FlightRecorder(enabled=True, ring_size=64)
    with recorder.RECORDER.span("backend", "probe", root=True) as sp:
        with recorder.RECORDER.span("backend", "pallas_ab"):
            pass
        sp.set(available_s=61.5, ok=True)
    assert ps.setup_span_s("backend.probe", "available_s") == 61.5
    assert 0 <= ps.setup_span_s("backend.pallas_ab") < 1
    assert ps.setup_span_s("backend.probe", "absent") is None
    r = SimpleNamespace(counters={"hub.queue_wait_s": 3.0, "hub.dispatched_sigs": 6000.0})
    assert ps.counter_ratio(r, "hub.queue_wait_s", "hub.dispatched_sigs", 1e3) == 0.5
    assert ps.counter_ratio(SimpleNamespace(counters={"hub.dispatched_sigs": 5.0}),
                            "hub.queue_wait_s", "hub.dispatched_sigs") is None


def test_clocks_agree():
    assert ps.clock_offset_s() < ps.MAX_CLOCK_OFFSET_S


# -- the device readers ------------------------------------------------------------


def _xplane():
    ms = 1e6
    k = "jit(_kernel_eq)/jit(main)/"
    return {
        "modules": [(0, 10 * ms, "jit__kernel_eq(123)"), (20 * ms, 30 * ms, "jit__kernel_eq(123)"),
                    (40 * ms, 45 * ms, "jit__kernel(7)")],
        "ops": [
            (0, 2 * ms, k + "decompress/mul:Mul"),
            (2 * ms, 9 * ms, k + "msm_sigs/buckets/while:While"),
            # the while's body operations are events of their own under it
            (3 * ms, 4 * ms, k + "msm_sigs/buckets/while/body/add:Add"),
            (5 * ms, 6 * ms, k + "msm_sigs/buckets/while/body/add:Add"),
            (9 * ms, 10 * ms, k + "finish/eq:Eq"),
            (20 * ms, 22 * ms, k + "decompress/mul:Mul"),
            (22 * ms, 25 * ms, k + "msm_keys/fold/while:While"),
            (25 * ms, 29 * ms, k + "msm_sigs/fold/while:While"),
            (29 * ms, 30 * ms, ""),
            (40 * ms, 45 * ms, "jit(_kernel)/jit(main)/decompress/mul:Mul"),
        ],
        "host": [(1 * ms, 12 * ms, "tm.light.window"), (11 * ms, 18 * ms, "tm.tpu.prep"),
                 (31 * ms, 35 * ms, "tm.light.store")],
    }


def test_kernel_phases_are_unions_over_kernel_eq_time():
    x = _xplane()
    assert ps.kernel_phase_share(x, "decompress") == pytest.approx(100 * 4 / 20)
    # a sum would count the while's body twice: 7 + 1 + 1 + 3 + 4
    assert ps.kernel_phase_share(x, "msm_keys", "msm_sigs") == pytest.approx(100 * 14 / 20)
    assert ps.kernel_phase_share(x, "buckets") == pytest.approx(100 * 7 / 20)
    assert ps.kernel_phase_share(x, "decompress", "msm_keys", "msm_sigs", "finish") == (
        pytest.approx(100 * 19 / 20))
    assert ps.kernel_phase_share(x, "no_such_scope") is None
    assert ps.kernel_phase_share(None, "decompress") is None
    assert ps.kernel_phase_share(dict(x, modules=[]), "decompress") is None


def test_idle_unattributed_is_idle_time_under_no_tm_span():
    x = _xplane()
    # busy [0,10] [20,30] [40,45]: gaps [10,20] and [30,40]; the program's
    # spans cover [10,18] and [31,35] of them
    assert ps.idle_unattributed_share(x) == pytest.approx(100 * (2 + 6) / 20)
    assert ps.idle_unattributed_share(dict(x, host=[])) is None
    assert ps.idle_unattributed_share(dict(x, ops=[])) is None


def test_an_old_trace_without_scopes_reads_nothing():
    x = ps.load_xplane(OLD)
    assert len(x["ops"]) == 12 and len(x["modules"]) == 3
    assert any(op.startswith("jit(_kernel_eq)/") for _s, _e, op in x["ops"])
    assert ps.kernel_phase_share(x, "decompress") is None
    assert ps.idle_unattributed_share(x) is None  # bench.* spans only: no tm.*
    assert ps.load_xplane(__file__) is None or ps.load_xplane(__file__)["ops"] == []


@pytest.mark.skipif(not os.path.exists(SCOPED), reason="recorded trace not kept")
def test_recorded_trace_with_scoped_operations():
    """Three runs of a small jitted `_kernel_eq` whose phases carry the
    kernel's scope names (a fori_loop under `msm_keys` and one under
    `msm_sigs/buckets`), under `tm.light.window` / `tm.tpu.prep` spans;
    `_scratch/explore25.py` of PR 25 recorded it on a TPU v5e."""
    x = ps.load_xplane(SCOPED)
    assert len(x["modules"]) == 3 and x["ops"] and len(x["host"]) == 6
    assert {h[2] for h in x["host"]} == {"tm.light.window", "tm.tpu.prep"}
    dec = ps.kernel_phase_share(x, "decompress")
    msm = ps.kernel_phase_share(x, "msm_keys", "msm_sigs")
    every = ps.kernel_phase_share(x, "decompress", "msm_keys", "msm_sigs", "finish")
    assert 0 < dec < msm < 100 and dec + msm <= every + 1e-6 <= 100.0 + 1e-6
    assert ps.kernel_phase_share(x, "buckets") < msm
    idle = ps.idle_unattributed_share(x)
    assert 0 <= idle < 100


# -- every new metric file, driven by the tiny cells ------------------------------------

NEW = [m for m in BENCH["per_layer"]
       if os.path.exists(os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".py"))
       and "program_spans" in open(os.path.join(ROOT, "benchmark", "metrics",
                                                m["name"] + ".py")).read()]
#: what a CPU run on the host route can read: no device, no probe, no tpu.* span
CPU_READS = {
    "tinyfull.blocksync": {
        "build_ms_per_block.blocksync", "sync_idle_ms_per_block.blocksync",
        "verify_self_ms_per_block.blocksync", "store_ms_per_block.blocksync",
        "exec_ms_per_block.blocksync", "collect_ms_per_ksig.blocksync",
        "hub_submit_ms_per_ksig.blocksync", "hub_queue_wait_ms.blocksync"},
    "tinylight.sequential": {
        "collect_ms_per_ksig.light", "fetch_ms_per_header.light", "link_ms_per_header.light",
        "store_ms_per_header.light", "verify_ms_per_header.light"},
}


def test_every_new_entry_lists_its_cells():
    assert len(NEW) == 31  # PR 25's twenty-six; PR 38: verify_ (three re-pointed), fill_ (two)
    for m in NEW:
        assert m["workloads"] and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}


@pytest.mark.parametrize("cell", sorted(CPU_READS))
def test_tiny_cells_drive_every_new_reader(tmp_path, cell):
    root = tiny.make_root(str(tmp_path))
    ps._rows_cache.clear()
    res = run.execute(root, cell, 3000002511, tiny.SECONDS, True, device=tiny.CPU_DEVICE)
    suffix = ".blocksync" if "blocksync" in cell else ".light"
    mine = {m["name"] for m in NEW if m["name"].endswith(suffix)}
    got = {n for n in res["metrics"] if n in mine}
    assert got == CPU_READS[cell]
    for n in got:
        assert res["metrics"][n]["value"] >= 0
    # the split names most of what the outside metric lumps together
    v = {n: res["metrics"][n]["value"] for n in res["metrics"]}
    if cell == "tinyfull.blocksync":
        named = sum(v[f"{k}_ms_per_block.blocksync"] for k in ("build", "sync_idle", "store", "exec"))
        assert 0.5 * v["apply_ms_per_block.blocksync"] <= named <= 1.05 * v["apply_ms_per_block.blocksync"]
        assert v["verify_self_ms_per_block.blocksync"] <= v["verify_ms_per_block.blocksync"]
