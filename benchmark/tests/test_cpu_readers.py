"""The readers of the recorder's CPU readings (`benchmark/cpu_readers.py`):
arithmetic on hand-made rows, the window's edge, what they return on the rows
of a program without the readings and on a wrapped ring, the real recorder's
rows, and every metric file that uses them against BENCHMARK.json."""

import json
import os
import time
from types import SimpleNamespace

import pytest

from benchmark import cpu_readers as cr
from benchmark import harness
from benchmark import program_spans as ps

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
METRICS = os.path.join(ROOT, "benchmark", "metrics")


def _row(key, start, dur_ms, cpu_ms=None, proc_cpu_ms=None, thread="MainThread", **attrs):
    sub, name = key.split(".", 1)
    d = {"subsystem": sub, "name": name, "start": start, "end": start + dur_ms / 1e3,
         "start_s": start, "duration_ms": dur_ms, "thread": thread}
    if cpu_ms is not None:
        d["cpu_ms"] = cpu_ms
    if proc_cpu_ms is not None:
        d["proc_cpu_ms"] = proc_cpu_ms
    if attrs:
        d["attrs"] = attrs
    return d


ROWS = [
    # a root half inside the window [10, 12]: 1,000 ms of its 2,000, 1,500 of its 3,000 CPU
    _row("blocksync.range", 9.0, 2000.0, 900.0, 3000.0, first=1, n=64),
    _row("blocksync.range", 11.0, 1000.0, 500.0, 1000.0, first=65, n=64),
    _row("validation.collect", 10.1, 40.0, 30.0, thread="asyncio_0", commits=64, sigs=6464),
    _row("hub.submit", 10.2, 300.0, 60.0, thread="asyncio_0", n=6464),
    _row("tpu.resolve", 10.25, 4.0, 1.0, thread="hub-runner_0", n=512, chunk=0),
    _row("tpu.prep", 10.3, 12.0, 0.5, thread="hub-runner_0", n=512, bucket=512, devices=1),
    _row("tpu.prep", 10.4, 8.0, 0.5, thread="hub-runner_0", n=488, bucket=512, devices=1),
    # closed on another thread: no reading, and it counts nowhere
    _row("tpu.prep", 10.5, 50.0, thread="hub-runner_0", n=1000, bucket=512, devices=1),
    _row("tpu.dispatch", 10.31, 4.5, 1.5, thread="hub-runner_0", bucket=8192, devices=4),
    _row("tpu.dispatch", 10.41, 4.7, 1.7, thread="hub-runner_0", bucket=8192, devices=4),
    _row("tpu.dispatch", 10.51, 1.3, 1.2, thread="hub-runner_0", bucket=256, devices=1),
    # an awaiting span carries a reading too; no reader takes it for work
    _row("blocksync.apply", 10.6, 20.0, 19.0, height=1),
]


@pytest.fixture
def rows(monkeypatch):
    """Hand `cpu_readers` a window's rows, as `program_spans.window_rows` would."""
    held = {"rows": ROWS}
    monkeypatch.setattr(ps, "window_rows", lambda t0, t1: held["rows"])
    ps._said.clear()
    yield held
    ps._said.clear()


def _readings(t0=10.0, t1=12.0):
    return SimpleNamespace(t0=t0, t1=t1, units=64, counters={}, trace=None)


def test_cpu_per_ksig_is_cpu_over_the_spans_own_count(rows):
    r = _readings()
    assert cr.cpu_ms_per_ksig(r, "n", "tpu.prep") == pytest.approx(1.0 / 1.0)
    assert cr.cpu_ms_per_ksig(r, "n", "hub.submit") == pytest.approx(60.0 / 6.464)
    assert cr.cpu_ms_per_ksig(r, "sigs", "validation.collect") == pytest.approx(30.0 / 6.464)
    assert cr.cpu_ms_per_ksig(r, "absent", "tpu.prep") is None
    # a span the window's edge halves: half its CPU over half its signatures
    half = _readings(t0=10.12)
    assert cr.cpu_ms_per_ksig(half, "sigs", "validation.collect") == pytest.approx(30.0 / 6.464)


def test_off_cpu_share_is_what_the_pure_python_spans_spent_off_a_core(rows):
    wall = 40.0 + 300.0 + 4.0 + 12.0 + 8.0
    cpu = 30.0 + 60.0 + 1.0 + 0.5 + 0.5
    assert cr.off_cpu_share(_readings()) == pytest.approx(100.0 * (1.0 - cpu / wall))
    rows["rows"] = [_row("tpu.prep", 10.3, 5.0, 5.0, n=512)]
    assert cr.off_cpu_share(_readings()) == pytest.approx(0.0)


def test_cores_busy_clips_a_root_span_to_the_window_by_share(rows):
    # half of the first root (1,000 ms wall, 1,500 ms of process CPU) + the second whole
    assert cr.cores_busy(_readings()) == pytest.approx((1500.0 + 1000.0) / (1000.0 + 1000.0))
    assert cr.cores_busy(_readings(t0=11.0)) == pytest.approx(1.0)


def test_dispatch_cpu_counts_only_the_sharded_dispatches(rows):
    assert cr.dispatch_cpu_ms_per_dispatch(_readings()) == pytest.approx((1.5 + 1.7) / 2)
    rows["rows"] = [d for d in ROWS if (d.get("attrs") or {}).get("devices") != 4]
    assert cr.dispatch_cpu_ms_per_dispatch(_readings()) is None


READERS = [
    lambda r: cr.cpu_ms_per_ksig(r, "n", "tpu.prep"),
    lambda r: cr.cpu_ms_per_ksig(r, "n", "hub.submit"),
    lambda r: cr.cpu_ms_per_ksig(r, "sigs", "validation.collect"),
    cr.off_cpu_share, cr.cores_busy, cr.dispatch_cpu_ms_per_dispatch,
]


def test_the_parents_rows_read_none_with_one_line_saying_why(rows, capsys):
    strip = ("cpu_ms", "proc_cpu_ms", "thread")
    rows["rows"] = [{k: v for k, v in d.items() if k not in strip} for d in ROWS]
    r = _readings()
    for read in READERS + READERS:  # asked twice, said once
        assert read(r) is None
    said = [ln for ln in capsys.readouterr().err.splitlines() if "carries" in ln]
    assert len(said) == len(READERS)
    rows["rows"] = None  # window_rows refused (and said why itself)
    assert all(read(r) is None for read in READERS)


@pytest.fixture
def recorder():
    from tendermint_tpu.libs import trace

    old = trace.RECORDER
    ps._rows_cache.clear()
    ps._said.clear()
    yield trace
    trace.RECORDER = old
    ps._rows_cache.clear()


def _burn(cpu_s):
    t0 = time.thread_time()
    while time.thread_time() - t0 < cpu_s:
        sum(range(2000))


def test_the_recorders_own_rows_and_a_wrapped_ring(recorder):
    recorder.RECORDER = rec = recorder.FlightRecorder(enabled=True, ring_size=8)
    t0 = time.perf_counter()
    with rec.span("light", "window", root=True, n=1):
        with rec.span("tpu", "prep", n=500, devices=1):
            _burn(0.01)
        with rec.span("tpu", "resolve", n=500):
            time.sleep(0.02)
    r = _readings(t0, time.perf_counter() + 1)
    assert cr.cpu_ms_per_ksig(r, "n", "tpu.prep") >= 20.0  # 10 ms over half a thousand
    assert 30.0 < cr.off_cpu_share(r) < 100.0  # the sleep is off the core, the burn on it
    assert 0.0 < cr.cores_busy(r) < 1.5
    assert cr.dispatch_cpu_ms_per_dispatch(r) is None
    ps._rows_cache.clear()
    for _ in range(8):  # the window's first rows fall out of the ring
        with rec.span("light", "fetch", n=1):
            pass
    assert rec.dropped == 3
    assert cr.off_cpu_share(r) is None and cr.cores_busy(r) is None


# -- the fourteen entries and their files ------------------------------------------


def _uses_cpu_readers(name):
    with open(os.path.join(METRICS, name + ".py")) as f:
        return "cpu_readers" in f.read()


NEW = [m for m in BENCH["per_layer"] if _uses_cpu_readers(m["name"])]


def test_there_are_fourteen_and_none_names_the_recorders_reader():
    assert len(NEW) == 14
    for m in NEW:
        with open(os.path.join(METRICS, m["name"] + ".py")) as f:
            assert "program_spans" not in f.read(), m["name"]


@pytest.mark.parametrize("m", NEW, ids=lambda m: m["name"])
def test_entry_and_file_agree(m):
    mod = harness.load_module(os.path.join(METRICS, m["name"] + ".py"),
                              "cpu_" + m["name"].replace(".", "_"))
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        m["layer"], m["unit"], "program_span", m["moves"])
    assert m["source"] == "program_span" and callable(mod.read)
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert m["workloads"] and set(m["workloads"]) <= set(cells)
    for w in m["workloads"]:  # a metric moves an end-to-end metric its cells report
        e2e = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert w in e2e.get("workloads", [w])
        # the suffix names the cell's traffic: .blocksync is full150's, .churn churn150's
        suffix = m["name"].rsplit(".", 1)[1]
        assert {"blocksync": "full150.blocksync", "churn": "churn150.blocksync",
                "light": "light150.sequential", "mixed": "mixed150.sequential",
                "mesh4": "light150.mesh4"}[suffix] == w
