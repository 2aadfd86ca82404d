"""The reduction from a profiler trace to busy time, program time and
idle gaps: on hand-made rows, and on a small trace recorded on a TPU v5e
(three runs of a jitted `_kernel_eq` under bench.verify / bench.host_prep
spans; `_scratch/tiny_trace.py` of PR 24 made it)."""

import os

import pytest

from benchmark import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(__file__), "data", "tiny_tpu.xplane.pb")


def _rows():
    ms = 1e6
    return [
        {"plane": "/device:TPU:0", "line": "XLA Ops", "events": [
            ("fusion.1", 0 * ms, 2 * ms), ("fusion.2", 2 * ms + 1000, 1 * ms),
            ("copy.3", 10 * ms, 1 * ms), ("fusion.9", 10.5 * ms, 1.5 * ms)]},
        {"plane": "/device:TPU:0", "line": "XLA Modules", "events": [
            ("jit__kernel_eq(123)", 0, 3 * ms + 1000), ("jit__kernel(7)", 10 * ms, 2 * ms),
            ("jit_other(9)", 20 * ms, 1 * ms)]},
        {"plane": "/host:CPU", "line": "python", "events": [
            ("bench.verify", 0, 12 * ms), ("bench.host_prep", 3 * ms, 6.5 * ms),
            ("unrelated", 0, 50 * ms)]},
    ]


def test_union():
    total, merged = tr.union_ns([(0, 5), (3, 8), (10, 11), (11, 12)])
    assert total == 10 and merged == [(0, 8), (10, 12)]


def test_reduce_by_hand():
    out = tr.reduce(_rows(), window_s=0.020)
    # busy: [0,2] + [2.001,3.001] + [10,12] ms
    assert out["busy_s"] == pytest.approx(5e-3)
    assert out["window_s"] == 0.020
    assert out["programs"]["jit__kernel_eq"] == pytest.approx(3.001e-3)
    assert tr.kernel_seconds(out["programs"]) == pytest.approx(5.001e-3)
    assert out["device_ops"][0][0] == "fusion"
    assert out["device_ops"][0][1] == pytest.approx(4.5e-3)
    gaps = dict(out["idle_gaps"])
    # 1 us between two fusions is the program's own breath; the 7 ms gap
    # is shared out: host_prep (innermost) to 9.5 ms, then verify alone
    assert gaps["between_ops"] == pytest.approx(1e-6)
    assert gaps["host_prep"] == pytest.approx(6.499e-3)
    assert gaps["verify"] == pytest.approx(0.5e-3)
    assert "other" not in gaps
    assert out["device_planes"] == ["/device:TPU:0"] and out["device_events"] == 4


def test_no_device_plane_reads_nothing():
    out = tr.reduce([r for r in _rows() if not r["plane"].startswith("/device")], 1.0)
    assert out["busy_s"] == 0.0 and out["programs"] == {} and out["idle_gaps"] == []


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="recorded trace not kept")
def test_recorded_tpu_trace():
    rows = tr.load(RECORDED)
    out = tr.reduce(rows, window_s=0.05)
    assert any(p.startswith("/device:TPU") for p in out["device_planes"])
    assert 0 < out["busy_s"] < 0.05
    assert out["programs"].get("jit__kernel_eq", 0) > 0
    assert tr.kernel_seconds(out["programs"]) <= out["busy_s"] * 1.05
    assert out["device_ops"] and out["idle_gaps"]
    assert {"verify", "host_prep"} & {g[0] for g in out["idle_gaps"]}
