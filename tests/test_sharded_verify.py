"""Multi-device batch verification on the virtual 8-device CPU mesh
(conftest provisions --xla_force_host_platform_device_count=8).

These exercise the PRODUCTION sharded path — the same code
`verify_resolved` selects on a real multi-chip topology (reference
crypto/crypto.go:46-54: one BatchVerifier interface regardless of
topology) — not just the dryrun demo: bad-signature attribution
fallback, sr25519/mixed batches, and batch sizes that do not divide the
mesh."""

import secrets

import numpy as np
import pytest

import jax

from tendermint_tpu.crypto import ed25519


def _signed_items(n, tag=b"shard"):
    items = []
    for i in range(n):
        priv = ed25519.Ed25519PrivKey(secrets.token_bytes(32))
        msg = tag + b"-%d" % i
        items.append((priv.pub_key().bytes(), msg, priv.sign(msg)))
    return items


@pytest.fixture
def force_sharded(monkeypatch):
    """Route verify_resolved through the sharded kernels regardless of
    batch size (the size gate exists to keep tiny production batches on
    one device)."""
    monkeypatch.setenv("TMTPU_FORCE_SHARDED", "1")


@pytest.fixture
def fresh_mesh():
    """Pristine per-device health registry before AND after: degrade
    tests trip breakers that would otherwise leak into later tests."""
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto.tpu import mesh

    mesh.reset()
    yield mesh
    mesh.reset()
    bt.reset()


def test_mesh_is_multi_device():
    assert len(jax.devices()) == 8


def test_sharded_selected_for_large_batches(monkeypatch):
    """The production selector picks the sharded path from the measured
    gate (`_SHARD_MIN_ROWS` padded rows) without any env override, for
    every raw count that pads to the gate's rung — and the single-device
    path one rung below it. The batch itself runs with the gate scaled
    to 512 rows (8,192 rows on the CPU's virtual devices is minutes)."""
    from tendermint_tpu.crypto.tpu import verify as V

    monkeypatch.delenv("TMTPU_FORCE_SHARDED", raising=False)
    monkeypatch.delenv("TMTPU_NO_SHARDED", raising=False)
    n_dev = V._shard_device_count()
    assert n_dev == 8
    gate = V._SHARD_MIN_ROWS
    assert V._plan_shape(gate // 2, 1, n_dev) == (False, gate // 2, 1)
    assert V._plan_shape(gate // 2 + 1, 1, n_dev) == (True, gate, n_dev)
    assert V._select_kernels(gate // 2, 1).devices is None
    assert len(V._select_kernels(gate // 2 + 1, 1).devices) == n_dev

    monkeypatch.setattr(V, "_SHARD_MIN_ROWS", 512)
    # a raw count UNDER the gate that pads to its rung: sharded too
    items = _signed_items(257, b"big")
    out = V.verify_batch_eq(items)
    assert out.all() and len(out) == len(items)
    # the production cache was used, keyed by the exact device set
    assert any(len(key) == n_dev for key in V._sharded_kernels)
    info = V.last_dispatch_info()
    assert info is not None and sum(info["shards"]) == len(items)
    assert V.verify_batch_eq(items[:256]).all() and V.last_dispatch_info() is None


def test_sharded_all_valid_non_divisible(force_sharded):
    """81 signatures over 8 devices: padding must round the bucket up to
    a mesh-divisible size and padded rows must stay inert."""
    from tendermint_tpu.crypto.tpu.verify import verify_batch_eq

    items = _signed_items(81, b"nd")
    out = verify_batch_eq(items)
    assert out.all() and len(out) == 81


def test_sharded_bad_signature_attribution(force_sharded):
    """A corrupted signature fails the batch equation; the SHARDED
    per-signature fallback kernel recovers exact attribution."""
    from tendermint_tpu.crypto.tpu.verify import verify_batch_eq

    items = _signed_items(24, b"bad")
    p, m, s = items[17]
    items[17] = (p, m, s[:40] + bytes([s[40] ^ 0x10]) + s[41:])
    out = verify_batch_eq(items)
    assert not out[17] and out.sum() == 23


def test_sharded_mixed_sr25519(force_sharded):
    """ed25519 and sr25519 resolve to the same Edwards-form check and ride
    one sharded MSM together; malformed entries stay False."""
    from tendermint_tpu.crypto import sr25519 as sr
    from tendermint_tpu.crypto.tpu.verify import (
        resolve_ed25519,
        resolve_sr25519,
        verify_resolved,
    )

    entries = []
    for i in range(5):
        priv = ed25519.Ed25519PrivKey(secrets.token_bytes(32))
        msg = b"mix-ed-%d" % i
        entries.append(resolve_ed25519(priv.pub_key().bytes(), msg, priv.sign(msg)))
    for i in range(5):
        priv = sr.Sr25519PrivKey(bytes([0x60 + i]) * 32)
        msg = b"mix-sr-%d" % i
        entries.append(
            resolve_sr25519(priv.pub_key().bytes(), msg, priv.sign(msg))
        )
    entries.append(None)  # malformed (e.g. wrong-size key) stays False
    out = verify_resolved(entries)
    assert out[:10].all() and not out[10]

    # tamper one sr25519 -> sharded per-sig fallback attributes it
    priv = sr.Sr25519PrivKey(b"\x71" * 32)
    sig = bytearray(priv.sign(b"y"))
    sig[5] ^= 1
    entries[7] = resolve_sr25519(priv.pub_key().bytes(), b"y", bytes(sig))
    out = verify_resolved(entries)
    assert not out[7] and not out[10] and out.sum() == 9


def test_sharded_matches_single_device(force_sharded, monkeypatch):
    """Sharded and single-device kernels agree bit-for-bit on the same
    batch (including a corrupted row)."""
    from tendermint_tpu.crypto.tpu import verify as V

    items = _signed_items(16, b"agree")
    p, m, s = items[3]
    items[3] = (p, m, s[:10] + bytes([s[10] ^ 1]) + s[11:])

    sharded = V.verify_batch_eq(items)
    monkeypatch.setenv("TMTPU_NO_SHARDED", "1")
    monkeypatch.delenv("TMTPU_FORCE_SHARDED", raising=False)
    single = V.verify_batch_eq(items)
    assert np.array_equal(sharded, single)
    assert not sharded[3] and sharded.sum() == 15


def test_sharded_same_seed_determinism(force_sharded):
    """Sharding ON, same mixed valid/invalid batch verified twice ->
    bit-identical verdict bitmaps (the chaos suite's reproducibility
    contract must survive the mesh)."""
    from tendermint_tpu.crypto.tpu.verify import verify_batch_eq

    items = _signed_items(24, b"det")
    p, m, s = items[5]
    items[5] = (p, m, s[:20] + bytes([s[20] ^ 0x40]) + s[21:])
    out1 = verify_batch_eq(items)
    out2 = verify_batch_eq(items)
    assert np.array_equal(out1, out2)
    assert not out1[5] and out1.sum() == 23


def test_shard_fill_and_dispatch_telemetry(force_sharded, fresh_mesh):
    """A sharded dispatch records per-device real-signature counts
    (padding excluded) into backend_telemetry and the thread's
    last-dispatch info (the hub's span attrs)."""
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto.tpu import verify as V

    bt.reset()
    items = _signed_items(20, b"fill")
    out = V.verify_batch_eq(items)
    assert out.all()
    info = V.last_dispatch_info()
    assert info is not None and len(info["devices"]) == 8
    assert sum(info["shards"]) == 20  # real rows only, padding excluded
    assert sum(bt.SHARD_SIGS.values()) == 20.0
    # contiguous shards: fill is front-loaded, never interleaved
    assert info["shards"] == V._shard_fill(20, 64, 8)


def test_per_device_breaker_degrade_plumbing(force_sharded, fresh_mesh, monkeypatch):
    """A chip failing its shard trips ITS breaker and the batch
    re-verifies on the N−1 survivors (kernel stubbed: the real degraded
    mesh compile is the slow test below). Telemetry records the
    transition."""
    import jax

    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto.tpu import verify as V

    bt.reset()
    ids = [d.id for d in jax.devices()]
    calls = {}

    def boom(*args):
        raise RuntimeError("chip 7 died mid-MSM")

    def stub7(ua, r, ga, rd, zs, sv, gidx):
        calls["stub7"] = True
        return np.asarray(sv), np.array(True)

    monkeypatch.setitem(V._sharded_kernels, tuple(ids), (boom, boom))
    monkeypatch.setitem(V._sharded_kernels, tuple(ids[:7]), (stub7, boom))
    fresh_mesh.force_fail(ids[7])

    entries = [V.resolve_ed25519(*it) for it in _signed_items(12, b"deg")]
    out = V.verify_resolved(entries)
    assert out.all() and len(out) == 12
    assert calls.get("stub7"), "degraded re-dispatch did not use the 7-dev mesh"
    assert fresh_mesh.active_count() == 7
    assert bt.MESH["devices_active"] == 7.0
    assert bt.MESH["degrade_transitions"] == 1.0
    # the dispatch info reflects the SURVIVING mesh the batch actually
    # ran on, not the stale 8-device selection
    info = V.last_dispatch_info()
    assert info and len(info["devices"]) == 7


def test_degrade_retry_without_new_breaker_trip(
    force_sharded, fresh_mesh, monkeypatch
):
    """Multi-chunk batches launch every chunk against the same selection
    before any is collected: a LATER failed chunk finds the dead chip's
    breaker already tripped (probes all pass) and must still retry on
    the survivors — only a genuinely unchanged mesh re-raises to CPU."""
    import jax

    from tendermint_tpu.crypto.tpu import verify as V

    ids = [d.id for d in jax.devices()]
    calls = {}

    def boom(*args):
        raise RuntimeError("x")

    def stub7(ua, r, ga, rd, zs, sv, gidx):
        calls["stub7"] = True
        return np.asarray(sv), np.array(True)

    entries = [V.resolve_ed25519(*it) for it in _signed_items(12, b"late")]
    sel8 = V._select_kernels(12, 1)
    assert sel8.devices is not None and len(sel8.devices) == 8

    # unchanged mesh + passing probes -> re-raise (CPU fallback's turn)
    with pytest.raises(RuntimeError, match="transient"):
        V._degrade_and_retry(entries, 1, RuntimeError("transient"), sel8)

    # an earlier chunk already tripped chip 7: no NEW trip to find, but
    # the active set no longer matches the pinned selection -> retry
    fresh_mesh._breakers[ids[7]].record_failure()
    monkeypatch.setitem(V._sharded_kernels, tuple(ids[:7]), (stub7, boom))
    out = V._degrade_and_retry(entries, 1, RuntimeError("late chunk"), sel8)
    assert out.all() and len(out) == 12 and calls.get("stub7")


def test_whole_mesh_dead_falls_back_to_cpu(fresh_mesh, monkeypatch):
    """8→7→…→CPU: when every device (including the single-device path)
    is dead, AdaptiveBatchVerifier returns the identical CPU verdicts —
    callers never see the device error."""
    import jax

    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
    from tendermint_tpu.crypto.tpu import verify as V
    from tendermint_tpu.libs.retry import CircuitBreaker

    ids = [d.id for d in jax.devices()]
    for i in ids:
        fresh_mesh.force_fail(i)

    def boom(*args, **kw):
        raise RuntimeError("mesh dead")

    monkeypatch.setenv("TMTPU_FORCE_SHARDED", "1")
    monkeypatch.setitem(V._sharded_kernels, tuple(ids), (boom, boom))
    monkeypatch.setattr(V, "_get_kernel_eq", boom)
    monkeypatch.setattr(V, "_get_kernel", boom)
    monkeypatch.setattr(B, "_tpu_available", True)
    monkeypatch.setattr(B, "MIN_TPU_BATCH", 1)
    monkeypatch.setattr(
        B, "_tpu_breaker",
        CircuitBreaker(failure_threshold=1, reset_timeout=30, name="t"),
    )

    items = _signed_items(8, b"dead")
    p, m, s = items[2]
    items[2] = (p, m, s[:1] + bytes([s[1] ^ 1]) + s[2:])
    bv = B.AdaptiveBatchVerifier()
    for pub, msg, sig in items:
        bv.add(Ed25519PubKey(pub), msg, sig)
    ok, bitmap = bv.verify()
    assert not ok and not bitmap[2] and sum(bitmap) == 7
    assert bv.last_route == "cpu-fallback"
    assert fresh_mesh.active_count() == 0  # every breaker tripped


def test_bucket_guard():
    """Dispatch shapes must come off the bucket ladder — anything else
    would be an inline cold XLA compile on the hot path."""
    from tendermint_tpu.crypto.tpu import verify as V

    assert V._is_warm_bucket(64)
    assert V._is_warm_bucket(128)
    assert V._is_warm_bucket(8192)
    assert V._is_warm_bucket(64, 8)  # 8-device mesh floor
    assert V._is_warm_bucket(70, 7)  # degraded 7-device mesh floor
    assert not V._is_warm_bucket(65)
    assert not V._is_warm_bucket(100)
    assert not V._is_warm_bucket(32)  # below the floor
    assert not V._is_warm_bucket(96, 8)  # not a rounded power of two
    # the ladder itself always satisfies the guard
    for n in (1, 63, 64, 65, 81, 150, 8100, 8192):
        for mult in (1, 7, 8):
            assert V._is_warm_bucket(V._bucket(n, mult), mult), (n, mult)


def test_dispatch_asserts_bucket_shape(monkeypatch):
    """A selection that escapes the bucket ladder trips the runtime
    guard (and therefore the CPU fallback) instead of compiling cold."""
    from tendermint_tpu.crypto.tpu import verify as V

    bad = V._Selection(lambda *a: None, lambda *a: None, 100, 1, None)
    monkeypatch.setattr(V, "_select_kernels", lambda n, m: bad)
    entries = [V.resolve_ed25519(*it) for it in _signed_items(4, b"guard")]
    with pytest.raises(AssertionError, match="not a bucket"):
        V.verify_resolved(entries)


@pytest.mark.slow
def test_degrade_8_to_7_real_kernel(force_sharded, fresh_mesh):
    """The full degraded-mesh path with REAL kernels: device 7 dies, the
    batch re-verifies on a 7-device mesh (non-power-of-two shards, fresh
    compile shape) with bit-identical verdicts. Slow: first run compiles
    the 7-device kernel (~100 s cold on the virtual CPU mesh)."""
    import jax

    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto.tpu import verify as V

    bt.reset()
    items = _signed_items(20, b"real-deg")
    p, m, s = items[9]
    items[9] = (p, m, s[:40] + bytes([s[40] ^ 2]) + s[41:])
    want = V.verify_batch_eq(items)  # healthy 8-device mesh

    ids = [d.id for d in jax.devices()]
    fresh_mesh.force_fail(ids[7])
    assert fresh_mesh.on_dispatch_failure(RuntimeError("injected"))
    assert fresh_mesh.active_count() == 7

    got = V.verify_batch_eq(items)  # real 7-device mesh
    assert np.array_equal(want, got)
    assert not got[9] and got.sum() == 19
    info = V.last_dispatch_info()
    assert info and len(info["devices"]) == 7
    assert bt.MESH["devices_active"] == 7.0
