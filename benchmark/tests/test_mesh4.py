"""The four-chip cell's own files at a tiny size on the CPU: the
`light_mesh` driver on the host route (every device check reads false by
design, every other number compared holds), and the `mesh_readers`
arithmetic on readings made by hand — with a program that has the sharded
names and spans, and with one that lacks them (the parent of the PR that
added them): nothing to read, never a raise."""

from types import SimpleNamespace

import pytest

from benchmark import mesh_readers as mr
from benchmark import ops, readers, run
from benchmark import program_spans as ps
from benchmark.tests import tiny_mesh

#: what the host route cannot show: no device, no mesh, no sharded signature
HOST_ROUTE_CHECKS = {"probe_errors", "tpu_route_sigs", "mesh_devices_active",
                     "chips_without_signatures", "sharded_sigs_minus_range_needed"}


def test_host_route_run_holds_every_other_check(tmp_path):
    res = run.execute(tiny_mesh.make_root(str(tmp_path)), tiny_mesh.CELL, 3000002741, 0.3,
                      False, device=tiny_mesh.CPU_4)
    failed = {k for k, c in res["checks"].items() if not c["ok"]}
    assert failed == HOST_ROUTE_CHECKS and res["correct"] is False
    assert res["checks"]["sharded_sigs_minus_range_needed"]["value"] == 98 * (
        res["attempted"] // 7)  # 7 headers x 14 signatures a range call, none sharded
    assert res["metrics"]["light_headers_per_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0 and res["failed"] == 0


def _recorded(monkeypatch, rows):
    """Readings over [10, 20] (window) and [20, 23] (traced stretch) with
    `rows` as the recorder's."""
    def window_rows(t0, t1):
        return [d for d in rows if d["end"] > t0 and d["start"] < t1]

    monkeypatch.setattr(ps, "window_rows", window_rows)
    return SimpleNamespace(t0=10.0, t1=20.0, stretch=(20.0, 23.0), device_kind="TPU v5 lite",
                           trace=None, units=128, counters={})


def _row(key, start, end, **attrs):
    sub, name = key.split(".", 1)
    return {"subsystem": sub, "name": name, "start": start, "end": end, "attrs": attrs}


def test_shard_fill_is_least_over_most_loaded_chip(monkeypatch):
    rows = []
    for t, fill in ((11.0, [2048] * 4), (11.1, [2048, 2048, 640, 0])):
        rows += [_row(f"tpu.shard.{k}", t, t, n=n) for k, n in enumerate(fill)]
    rows += [_row("tpu.shard.0", 21.0, 21.0, n=999)]  # after the window: not counted
    r = _recorded(monkeypatch, rows)
    assert mr.shard_fill_min_share(r) == pytest.approx(100.0 * 2048 / 4096)
    assert mr.shard_fill_min_share(_recorded(monkeypatch, [])) is None


def test_sharded_kernel_time_and_roofline(monkeypatch):
    prep = [_row("tpu.prep", 20.5, 20.51, n=8192, bucket=8192, groups=127, devices=4),
            _row("tpu.prep", 20.6, 20.61, n=4736, bucket=8192, groups=127, devices=4),
            _row("tpu.prep", 20.7, 20.71, n=101, bucket=128, groups=127, devices=1),
            _row("tpu.prep", 12.0, 12.01, n=8192, bucket=8192, groups=127, devices=4)]
    r = _recorded(monkeypatch, prep)
    r.trace = {"programs": {"jit__kernel_eq_sharded": 0.030, "jit__kernel_sharded": 0.002,
                            "jit__kernel_eq": 0.5, "jit_other": 1.0}}
    assert mr.kernel_ms_per_ksig(r) == pytest.approx(1e3 * 0.032 / ((8192 + 4736) / 1e3))
    need = 2 * ops.needed_ops(8192, 127)
    assert mr.kernel_roofline_share(r) == pytest.approx(
        100.0 * need / 0.032 / (4 * readers.peak_flops("TPU v5 lite")))
    assert 0 < mr.kernel_roofline_share(r) < 1.0
    # the parent: its sharded program is called after a local function and
    # its spans carry no `devices`
    r.trace = {"programs": {"jit_local": 0.030, "jit__kernel": 0.002}}
    assert mr.kernel_ms_per_ksig(r) is None and mr.kernel_roofline_share(r) is None
    r = _recorded(monkeypatch, [_row("tpu.prep", 20.5, 20.51, n=8192, bucket=8192, groups=127)])
    r.trace = {"programs": {"jit__kernel_eq_sharded": 0.030}}
    assert mr.kernel_ms_per_ksig(r) is None
    r.trace = None
    assert mr.kernel_ms_per_ksig(r) is None


def test_phase_share_reads_the_sharded_program_alone(monkeypatch):
    scope = "jit(_kernel_eq_sharded)/jit(main)/shard_map"
    x = {"modules": [(0.0, 100.0, "jit__kernel_eq_sharded(123)"), (200.0, 300.0, "jit__kernel_eq(7)")],
         "ops": [(0.0, 50.0, f"{scope}/shard/msm_sigs/buckets/while:While"),
                 (50.0, 52.0, f"{scope}/gather/all_gather:AllGather"),
                 (52.0, 60.0, f"{scope}/gather/add:Add"),
                 (60.0, 100.0, f"{scope}/epilogue/msm_keys/fold:Mul"),
                 (70.0, 80.0, f"{scope}/epilogue/finish:Mul"),  # inside the former: a union
                 (200.0, 300.0, "jit(_kernel_eq)/msm_keys:Mul")]}
    monkeypatch.setattr(ps, "run_xplane", lambda r: x)
    assert mr.phase_share(None, "gather") == pytest.approx(10.0)
    assert mr.phase_share(None, "epilogue") == pytest.approx(40.0)
    assert mr.phase_share(None, "shard") == pytest.approx(50.0)
    x_parent = {"modules": [(0.0, 100.0, "jit_local(1)")], "ops": [(0.0, 50.0, "jit(local)/msm_sigs:Mul")]}
    monkeypatch.setattr(ps, "run_xplane", lambda r: x_parent)
    assert mr.phase_share(None, "gather") is None
    monkeypatch.setattr(ps, "run_xplane", lambda r: None)
    assert mr.phase_share(None, "gather") is None
