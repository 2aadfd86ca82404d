"""The four-chip host as a deployment (cell `light150.mesh4`), at a small
size on four of the suite's virtual CPU devices:

(a) a seeded chain through `light.LightClient` and the benchmark's own
    `light_mesh` driver on a 4-device mesh: every verdict, the stored light
    blocks and the refusal height of a seeded corrupted commit equal the
    plain reference's, and every device carried real signatures;
(b) the share ties to the whole: the four per-device partial points of the
    sharded equation kernel, folded, are the single-device R-side sum, and
    the sharded kernels are right on valid and on corrupted rows — with a
    tail whose last shard is all padding;
(c) one plan per padded shape: what `warmup` compiles for a rung of the
    bucket ladder is what dispatch selects for every raw count that pads to
    it, on 1, 2, 4, 7 and 8 devices.

All cases live in this one file: the 4-device programs (about 40 s of XLA
CPU compile each) are compiled once and shared.
"""

import secrets

import numpy as np
import pytest

import jax

from tendermint_tpu.crypto import ed25519

LADDER = [64 << i for i in range(8)]  # 64 … 8192


@pytest.fixture
def mesh4(monkeypatch):
    """The dispatch mesh capped at four of the eight virtual devices, with
    a pristine health registry and telemetry before and after."""
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto.tpu import mesh

    monkeypatch.setenv("TMTPU_MESH_MAX_DEVICES", "4")
    monkeypatch.delenv("TMTPU_FORCE_SHARDED", raising=False)
    monkeypatch.delenv("TMTPU_NO_SHARDED", raising=False)
    mesh.reset()
    bt.reset()
    yield mesh
    mesh.reset()
    bt.reset()


# -- (c) plan invariance -------------------------------------------------------


class _Dev:
    def __init__(self, i):
        self.id = i


def _entries(n, keys=3):
    from tendermint_tpu.crypto.tpu.verify import ResolvedSig

    return [ResolvedSig((i % keys).to_bytes(4, "little") + b"\x00" * 28,
                        b"\x01" + b"\x00" * 31, 0, 0) for i in range(n)]


@pytest.mark.parametrize("n_dev", [1, 2, 4, 7, 8])
@pytest.mark.parametrize("rung", LADDER)
def test_warmup_compiles_the_plan_dispatch_selects(monkeypatch, mesh4, rung, n_dev):
    """Every (kernel, rows, key rows) a dispatch of a raw count that pads to
    `rung` calls is one `warmup(bucket=rung)` called — equation kernel and
    per-signature attribution alike. On the tree before the one plan this
    fails at rung 256 on four devices: warm-up took the sharded program,
    a 150-signature commit the single-device one nothing had warmed."""
    from tendermint_tpu.crypto.tpu import verify as V

    devices = [_Dev(i) for i in range(n_dev)]
    monkeypatch.setattr(V, "_shard_devices", lambda: devices if n_dev > 1 else [])
    calls, eq_verdict = [], [True]

    def kernels(kind):
        def eq(ua, r, ga, rd, zs, sv, gidx):
            calls.append((kind, "eq", r.shape[0], ua.shape[0]))
            return np.asarray(sv), np.array(eq_verdict[0])

        def sig(a, r, s, h, sv):
            calls.append((kind, "sig", a.shape[0]))
            return np.asarray(sv)

        return eq, sig

    monkeypatch.setattr(V, "_get_sharded", lambda devs: kernels(f"sharded{len(devs)}"))
    monkeypatch.setattr(V, "_get_kernel_eq", lambda: kernels("single")[0])
    monkeypatch.setattr(V, "_get_kernel", lambda: kernels("single")[1])

    V.warmup(bucket=rung, groups=3, fallback=True)
    warmed = set(calls)
    assert len(warmed) == 2
    for raw in sorted({rung // 2 + 1, 3 * rung // 4, rung - 1, rung}):
        for eq_verdict[0] in (True, False):  # False: the attribution kernel too
            del calls[:]
            out = V.verify_resolved(_entries(raw))
            assert len(out) == raw and calls
            assert set(calls) <= warmed, (raw, n_dev, calls, warmed)


@pytest.mark.parametrize("n_dev", [1, 2, 4, 7, 8])
def test_plan_is_a_function_of_the_padded_shape(monkeypatch, n_dev):
    """One plan a rung, sharded from the measured gate on a mesh, and
    always a shape of the ladder."""
    from tendermint_tpu.crypto.tpu import verify as V

    monkeypatch.delenv("TMTPU_FORCE_SHARDED", raising=False)
    for rung in LADDER:
        plans = {V._plan_shape(raw, 1, n_dev) for raw in range(rung // 2 + 1, rung + 1)}
        assert len(plans) == 1
        sharded, bucket, mult = plans.pop()
        assert sharded == (n_dev > 1 and rung >= V._SHARD_MIN_ROWS)
        assert bucket % mult == 0 and V._is_warm_bucket(bucket, mult)
        assert mult == (n_dev if sharded else 1)


# -- (b) the share ties to the whole -------------------------------------------


def _signed_entries(n, keys, tag):
    from tendermint_tpu.crypto.tpu.verify import resolve_ed25519

    privs = [ed25519.Ed25519PrivKey(secrets.token_bytes(32)) for _ in range(keys)]
    out = []
    for i in range(n):
        priv, msg = privs[i % keys], tag + b"-%d" % i
        out.append(resolve_ed25519(priv.pub_key().bytes(), msg, priv.sign(msg)))
    return out


def test_partials_fold_to_the_single_device_sum(mesh4):
    """70 real rows in a 128-row bucket over four devices: shards of 32
    hold 32, 32, 6 and 0 real signatures — the last is all padding."""
    from jax import shard_map
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from tendermint_tpu.crypto.tpu import curve
    from tendermint_tpu.crypto.tpu import verify as V
    from tendermint_tpu.crypto.tpu.curve import Point

    devices = V._shard_devices()
    assert len(devices) == 4
    assert V._shard_fill(70, 128, 4) == [32, 32, 6, 0]
    mesh = Mesh(np.asarray(devices), ("data",))
    entries = _signed_entries(70, 9, b"tie")
    args = V.prepare_batch_eq(entries, pad_to=128)
    _ua, r, _ga, rd, _zs, sv, _gidx = args

    per_device = jax.jit(shard_map(
        lambda r_, rd_, sv_: V._sigs_partial(r_, rd_, sv_)[0][None],
        mesh=mesh, in_specs=(P("data"), P(None, "data"), P("data")),
        out_specs=P("data"), check_vma=False))(r, rd, sv)
    assert per_device.shape == (4, 4, 32)
    folded = V._reduce_partials(Point(*(per_device[:, i] for i in range(4))))
    whole, used = jax.jit(V._sigs_partial)(r, rd, sv)
    assert int(np.asarray(used).sum()) == 70
    assert bool(curve.point_eq(folded, Point(*whole)))
    # the all-padding shard's partial is the identity, the others are not
    ident = [bool(curve.is_identity(Point(*per_device[k]))) for k in range(4)]
    assert ident == [False, False, False, True]

    # the whole kernel on the same rows (test_sharded_verify.py holds it
    # equal to the single-device kernel): valid rows pass the equation …
    sharded_eq, sharded_sig = V._get_sharded(devices)
    bitmap, eq_ok = sharded_eq(*args)
    assert bool(eq_ok)
    assert np.asarray(bitmap)[:70].all() and not np.asarray(bitmap)[70:].any()
    # … and one corrupted row, on the third device's short shard, fails
    # it and is named by the sharded per-signature kernel
    bad = list(entries)
    e = bad[66]
    bad[66] = V.ResolvedSig(e.a, e.r, e.s ^ 1, e.k)
    assert not bool(sharded_eq(*V.prepare_batch_eq(bad, pad_to=128))[1])
    per_sig = np.asarray(sharded_sig(*V.prepare_resolved(bad, pad_to=128)))
    assert not per_sig[66] and per_sig[:70].sum() == 69 and not per_sig[70:].any()


# -- (a) the light client on the mesh, through the cell's own driver -----------

def test_light_client_on_the_mesh_equals_the_reference(mesh4, monkeypatch, tmp_path):
    from benchmark import run
    from benchmark.tests import tiny_mesh
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.tpu import verify as V
    from tendermint_tpu.libs.retry import CircuitBreaker

    # the device route, on the suite's CPU devices; the gate scaled to the
    # chain (the real one asks for 2,048 padded rows): the 98-signature
    # ranges pad to 128 rows and are sharded, the 14-signature commits of
    # the trusted headers pad to 64 and stay on one device — as the cell's
    # 12,928-signature windows and 101-signature commits do on the chip
    monkeypatch.setattr(V, "_SHARD_MIN_ROWS", 128)
    monkeypatch.setattr(B, "_tpu_available", True)
    monkeypatch.setattr(B, "MIN_TPU_BATCH", 1)
    monkeypatch.setattr(B, "_tpu_breaker",
                        CircuitBreaker(failure_threshold=1, reset_timeout=30, name="t"))
    bt.set_active("tpu")

    res = run.execute(tiny_mesh.make_root(str(tmp_path)), tiny_mesh.CELL, 3000002701, 0.5, True,
                      device=tiny_mesh.CPU_4)
    failed = {k: c for k, c in res["checks"].items() if not c["ok"]}
    assert not failed and res["correct"] is True, failed
    checks = {k: c["value"] for k, c in res["checks"].items()}
    assert checks["verdict_mismatches"] == 0 and checks["stored_mismatches"] == 0
    assert checks["warmup_refusal_height_delta"] == 0
    assert checks["mesh_devices_active"] == 4
    assert checks["chips_without_signatures"] == 0
    assert checks["sharded_sigs_minus_range_needed"] == 0
    assert checks["degrade_retries"] == 0 and res["attempted"] > 0 and res["failed"] == 0
    assert len(bt.SHARD_SIGS) == 4 and all(v > 0 for v in bt.SHARD_SIGS.values())
    # what a CPU run can read of the cell's per-layer metrics: the spans
    # and counters (no device plane: the trace's shares are left out)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert {"verify_ms_per_header.mesh4", "tpu_prep_ms_per_ksig.mesh4",
            "tpu_dispatch_ms_per_dispatch.mesh4", "device_wait_ms_per_dispatch.mesh4",
            "device_route_share.mesh4", "inline_compiles.mesh4",
            "shard_fill_min_share.mesh4",
            "tpu_dispatch_cpu_ms_per_dispatch.mesh4"} <= set(m)
    # on-CPU ms of the SHARDED dispatches alone (the wall twin averages over
    # every `tpu.dispatch`, the one-chip ones of this tiny cell among them)
    assert m["tpu_dispatch_cpu_ms_per_dispatch.mesh4"] > 0
    assert not any(k.startswith(("kernel_", "device_idle")) for k in m)
    assert m["device_route_share.mesh4"] == 100.0
    # 98 real rows over shards of 32: 32, 32, 32, 2
    assert m["shard_fill_min_share.mesh4"] == pytest.approx(100.0 * 2 / 32)
