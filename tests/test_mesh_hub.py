"""Mesh-aware VerifyHub scheduling + backend mesh telemetry + tooling.

The kernel-level sharding equivalence lives in test_sharded_verify.py;
this file covers the scheduler half of the tentpole: the hub scaling its
micro-batch window/capacity by the active device count (and shrinking
again on degrade), surviving an 8→7→CPU breaker cascade without
wedging, the compile-cache hit/miss classification, the new backend_*
metric families, and tracectl's --per-device table.
"""

from __future__ import annotations

import pytest

from tendermint_tpu.crypto import backend_telemetry as bt
from tendermint_tpu.crypto.verify_hub import VerifyHub


@pytest.fixture
def fresh_bt():
    bt.reset()
    yield
    bt.reset()


@pytest.fixture
def fresh_mesh():
    from tendermint_tpu.crypto.tpu import mesh

    mesh.reset()
    yield mesh
    mesh.reset()


# ---------------------------------------------------------------------------
# hub mesh-occupancy-aware window


def test_hub_scales_capacity_by_mesh(monkeypatch):
    """max_batch is per-chip: the pack capacity and the adaptive-window
    ramp both scale with the active device count, and shrink back the
    moment the mesh degrades."""
    from tendermint_tpu.crypto import batch as B

    hub = VerifyHub(max_batch=16, window_ms=4.0, cache_size=0)
    monkeypatch.setattr(B, "mesh_parallelism", lambda: 8)
    assert hub._refresh_mesh() == 8
    assert hub._effective_max() == 128
    ceiling = hub.window_s  # unchanged by the mesh
    # the ramp needs 8x the occupancy to reach the full window now:
    # occupancy that saturates a single chip is 1/8 of the mesh ramp
    hub._ewma_occupancy = 9.0  # full-window occupancy for one chip
    w_mesh = hub._window()
    monkeypatch.setattr(B, "mesh_parallelism", lambda: 1)
    hub._refresh_mesh()
    assert hub._effective_max() == 16
    w_single = hub._window()
    assert w_single == ceiling  # saturated ramp on one chip
    assert w_mesh == pytest.approx(ceiling * (9.0 - 1.0) / (128 / 8.0))
    assert w_mesh < w_single

    # degraded mesh (breaker trip 8 -> 5) shrinks the same refresh
    monkeypatch.setattr(B, "mesh_parallelism", lambda: 5)
    assert hub._refresh_mesh() == 5
    assert hub._effective_max() == 80


def test_hub_mesh_scale_knob(monkeypatch):
    """mesh_scale=False (config or TMTPU_MESH_SCALE=0) pins single-chip
    sizing regardless of the mesh."""
    from tendermint_tpu.crypto import batch as B

    monkeypatch.setattr(B, "mesh_parallelism", lambda: 8)
    hub = VerifyHub(max_batch=16, mesh_scale=False)
    assert hub._refresh_mesh() == 1 and hub._effective_max() == 16

    monkeypatch.setenv("TMTPU_MESH_SCALE", "0")
    hub = VerifyHub(max_batch=16, mesh_scale=True)
    assert not hub.mesh_scale

    monkeypatch.delenv("TMTPU_MESH_SCALE")
    hub = VerifyHub(max_batch=16)
    assert hub.mesh_scale  # config default


def test_hub_stats_carry_mesh_fields(monkeypatch):
    from tendermint_tpu.crypto import batch as B

    monkeypatch.setattr(B, "mesh_parallelism", lambda: 4)
    hub = VerifyHub(max_batch=32)
    hub._refresh_mesh()
    s = hub.stats()
    assert s["mesh_devices"] == 4.0
    assert s["effective_max_batch"] == 128.0


def test_hub_survives_degrade_cascade_8_7_cpu(fresh_mesh, monkeypatch):
    """Acceptance: a per-device breaker trip mid-dispatch (8→7), then a
    whole-mesh death (→CPU), and the hub keeps resolving futures with
    correct verdicts — degradation costs throughput, never wedges."""
    import secrets

    import jax
    import numpy as np

    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.crypto.tpu import verify as V
    from tendermint_tpu.libs.retry import CircuitBreaker

    ids = [d.id for d in jax.devices()]
    calls = {"stub7": 0}

    def boom(*args, **kw):
        raise RuntimeError("chip died")

    def stub7(ua, r, ga, rd, zs, sv, gidx):
        calls["stub7"] += 1
        return np.asarray(sv), np.array(True)

    monkeypatch.setenv("TMTPU_FORCE_SHARDED", "1")
    monkeypatch.setitem(V._sharded_kernels, tuple(ids), (boom, boom))
    monkeypatch.setitem(V._sharded_kernels, tuple(ids[:7]), (stub7, boom))
    monkeypatch.setattr(B, "_tpu_available", True)
    monkeypatch.setattr(B, "MIN_TPU_BATCH", 2)
    monkeypatch.setattr(
        B, "_tpu_breaker",
        CircuitBreaker(failure_threshold=1, reset_timeout=60, name="t"),
    )
    fresh_mesh.force_fail(ids[7])

    def signed(n, tag):
        out = []
        for i in range(n):
            priv = ed25519.Ed25519PrivKey(secrets.token_bytes(32))
            msg = tag + b"-%d" % i
            out.append((priv.pub_key(), msg, priv.sign(msg)))
        return out

    hub = VerifyHub(max_batch=64, window_ms=1.0, cache_size=0)
    hub.start()
    try:
        # stage 1: chip 7 dies mid-dispatch -> re-verified on 7 devices
        assert all(hub.verify_many(signed(8, b"stage1"), timeout=30.0))
        assert calls["stub7"] >= 1
        assert fresh_mesh.active_count() == 7
        assert hub.stats()["verify_errors"] == 0  # degrade, not error

        # stage 2: the rest of the mesh dies too -> CPU fallback
        for i in ids[:7]:
            fresh_mesh.force_fail(i)
        monkeypatch.setitem(V._sharded_kernels, tuple(ids[:7]), (boom, boom))
        monkeypatch.setattr(V, "_get_kernel_eq", boom)
        monkeypatch.setattr(V, "_get_kernel", boom)
        assert all(hub.verify_many(signed(8, b"stage2"), timeout=30.0))
        assert fresh_mesh.active_count() == 0
        assert hub.is_running
        # and the hub still answers after the cascade
        pk, msg, sig = signed(1, b"after")[0]
        assert hub.verify_sync(pk, msg, sig, timeout=30.0)
    finally:
        hub.stop()


# ---------------------------------------------------------------------------
# telemetry + metrics


def test_compile_cache_classification(fresh_bt):
    """compile_ms ≈ 0 -> persistent-cache hit; a real compile -> miss.
    Both countable and carried per-shape in the snapshot."""
    bt.record_compile("floor", 0.02)
    bt.record_compile("max", 12.5)
    bt.record_compile("probe", 0.4, cache_hit=False)  # explicit override
    snap = bt.snapshot()
    assert snap["compile_cache"] == {
        "floor": "hit", "max": "miss", "probe": "miss",
    }
    assert snap["compile_cache_hits"] == 1.0
    assert snap["compile_cache_misses"] == 2.0


def test_mesh_telemetry_and_metrics_render(fresh_bt):
    from tendermint_tpu.libs.metrics import NodeMetrics

    bt.record_mesh(8, 8)
    bt.record_degrade(8, 7, "probe failed on [7]")
    bt.record_shard_dispatch([0, 1, 2], [64, 64, 22])
    bt.record_compile("floor", 0.01)
    snap = bt.snapshot()
    assert snap["mesh"]["devices_total"] == 8.0
    assert snap["mesh"]["devices_active"] == 7.0
    assert snap["mesh"]["degrade_transitions"] == 1.0
    assert snap["shard_sigs"] == {"0": 64.0, "1": 64.0, "2": 22.0}

    out = NodeMetrics().render()
    assert 'backend_mesh_devices{state="total"} 8' in out
    assert 'backend_mesh_devices{state="active"} 7' in out
    assert "backend_mesh_degrades 1" in out
    assert 'backend_shard_sigs{device="2"} 22' in out
    assert "backend_compile_cache_hits 1" in out
    assert "backend_compile_cache_misses 0" in out


def test_mesh_max_devices_cap(fresh_mesh, fresh_bt, monkeypatch):
    """TMTPU_MESH_MAX_DEVICES caps the dispatch mesh; telemetry keeps
    one definition — total = visible, active = dispatchable."""
    monkeypatch.setenv("TMTPU_MESH_MAX_DEVICES", "2")
    assert fresh_mesh.active_count() == 2
    assert bt.MESH["devices_total"] == 8.0
    assert bt.MESH["devices_active"] == 2.0


def test_degrade_recovery_reenters_mesh(fresh_mesh, fresh_bt, monkeypatch):
    """A tripped device re-joins through the breaker's half-open window
    once its recovery probe passes — recorded as an upward transition."""
    import jax

    ids = [d.id for d in jax.devices()]
    fresh_mesh.force_fail(ids[3])
    assert fresh_mesh.on_dispatch_failure(RuntimeError("x"))
    assert fresh_mesh.active_count() == 7

    # heal the chip and let the breaker's reset window elapse
    fresh_mesh.force_fail(ids[3], fail=False)
    br = fresh_mesh._breakers[ids[3]]
    monkeypatch.setattr(br, "clock", lambda: br._opened_at + 1e9)
    assert fresh_mesh.active_count() == 8
    assert bt.MESH["devices_active"] == 8.0
    assert bt.MESH["degrade_transitions"] == 2.0  # down, then up


# ---------------------------------------------------------------------------
# tracectl --per-device


def _load_tracectl():
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "tracectl", os.path.join(repo, "scripts", "tracectl.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tracectl_per_device_table(tmp_path, capsys):
    import json

    tracectl = _load_tracectl()

    spans = [
        {
            "subsystem": "hub", "name": "dispatch", "duration_ms": 3.0,
            "attrs": {
                "sigs": 140, "route": "tpu",
                "devices": [0, 1, 2, 3], "shards": [64, 64, 12, 0],
            },
        },
        {
            "subsystem": "hub", "name": "dispatch", "duration_ms": 2.0,
            "attrs": {
                "sigs": 60, "route": "tpu",
                "devices": [0, 1, 2, 3], "shards": [32, 28, 0, 0],
            },
        },
        # non-sharded dispatches and other spans are ignored
        {"subsystem": "hub", "name": "dispatch",
         "attrs": {"sigs": 5, "route": "cpu"}, "duration_ms": 1.0},
        {"subsystem": "p2p", "name": "receive", "duration_ms": 0.2},
    ]
    p = tmp_path / "dump.json"
    p.write_text(json.dumps({"spans": spans}))
    assert tracectl.main([str(p), "--per-device"]) == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    assert "device" in lines[0] and "share" in lines[0]
    row0 = lines[2].split()
    assert row0[0] == "0" and row0[1] == "2" and row0[2] == "96"
    assert "48.0%" in lines[2]  # 96 of 200 total sigs

    # no sharded spans -> explicit message, not an empty table
    p2 = tmp_path / "cpu.json"
    p2.write_text(json.dumps([{"subsystem": "hub", "name": "dispatch",
                               "attrs": {"route": "cpu"}}]))
    assert tracectl.main([str(p2), "--per-device"]) == 0
    assert "no sharded hub.dispatch" in capsys.readouterr().out


def test_hub_dispatch_span_carries_shards(monkeypatch):
    """The hub stamps devices/shards from the verifier's last sharded
    dispatch onto hub.dispatch spans (the tracectl --per-device feed)."""
    import secrets

    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.crypto import verify_hub as vh
    from tendermint_tpu.libs import trace

    class FakeBV:
        last_route = "tpu"
        last_dispatch = {"devices": [0, 1], "shards": [5, 3]}

        def __init__(self):
            self._items = []

        def add_many(self, items):
            self._items.extend(items)

        def verify(self):
            return True, [True] * len(self._items)

    monkeypatch.setattr(vh, "AdaptiveBatchVerifier", FakeBV)
    old = trace.RECORDER.enabled
    trace.RECORDER.enabled = True
    trace.RECORDER.clear()
    try:
        hub = VerifyHub(max_batch=8, window_ms=0.5, cache_size=0)
        hub.start()
        try:
            items = []
            for i in range(4):
                priv = ed25519.Ed25519PrivKey(secrets.token_bytes(32))
                msg = b"span-%d" % i
                items.append((priv.pub_key(), msg, priv.sign(msg)))
            assert all(hub.verify_many(items, timeout=30.0))
        finally:
            hub.stop()
        spans = [
            s for s in trace.RECORDER.dump()
            if s["subsystem"] == "hub" and s["name"] == "dispatch"
        ]
    finally:
        trace.RECORDER.enabled = old
    assert spans, "no hub.dispatch span recorded"
    attrs = spans[-1]["attrs"]
    assert attrs["devices"] == [0, 1] and attrs["shards"] == [5, 3]
    assert attrs["route"] == "tpu"
