"""Differential tests: JAX field/curve/verify kernel vs the pure-Python
ed25519 oracle (crypto/ed25519_math.py). Runs on the CPU backend in CI; the
same code compiles for TPU unchanged."""

import secrets

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519, ed25519_math as em
from tendermint_tpu.crypto.tpu import field as F
from tendermint_tpu.crypto.tpu import curve as C
from tendermint_tpu.crypto.tpu.verify import prepare_batch, verify_batch

import jax.numpy as jnp


def rand_fe(n=4):
    return [secrets.randbelow(F.P_INT) for _ in range(n)]


def to_batch(vals):
    return jnp.asarray(np.stack([F.int_to_limbs(v) for v in vals]))


def test_field_mul_matches_bigint():
    a_vals, b_vals = rand_fe(8), rand_fe(8)
    out = F.mul(to_batch(a_vals), to_batch(b_vals))
    out = np.asarray(out)
    for i in range(8):
        assert F.limbs_to_int(out[i]) == a_vals[i] * b_vals[i] % F.P_INT
        assert out[i].max() < 2**9  # carry bound invariant


def test_field_chained_ops():
    a_vals, b_vals = rand_fe(4), rand_fe(4)
    a, b = to_batch(a_vals), to_batch(b_vals)
    # (a-b)*(a+b) == a^2 - b^2
    lhs = F.mul(F.sub(a, b), F.add(a, b))
    rhs = F.sub(F.square(a), F.square(b))
    assert bool(F.eq(lhs, rhs).all())
    for i in range(4):
        expect = (a_vals[i] ** 2 - b_vals[i] ** 2) % F.P_INT
        assert F.limbs_to_int(np.asarray(lhs)[i]) == expect


def test_field_canonical():
    vals = [0, 1, 19, F.P_INT - 1, F.P_INT, F.P_INT + 5, 2**255 - 1]
    # feed NON-canonical limb forms: add p again via limb arithmetic
    arrs = []
    for v in vals:
        limbs = F.int_to_limbs(v % F.P_INT).astype(np.int32)
        arrs.append(limbs + F.P_LIMBS)  # limbs ≤ 510, value v + p
    out = np.asarray(F.canonical(jnp.asarray(np.stack(arrs))))
    for i, v in enumerate(vals):
        assert F.limbs_to_int(out[i]) == v % F.P_INT
        assert (out[i] == F.int_to_limbs(v % F.P_INT)).all()


def test_field_is_zero_and_parity():
    a = to_batch([0, 1, F.P_INT - 1, 2])
    z = np.asarray(F.is_zero(a))
    assert list(z) == [True, False, False, False]
    par = np.asarray(F.parity(a))
    assert list(par) == [0, 1, (F.P_INT - 1) & 1, 0]


def test_pow22523():
    vals = rand_fe(2)
    out = np.asarray(F.pow22523(to_batch(vals)))
    e = (F.P_INT - 5) // 8  # 2^252 - 3
    for i, v in enumerate(vals):
        assert F.limbs_to_int(out[i]) == pow(v, e, F.P_INT)


def _point_to_ints(p, i):
    x = F.limbs_to_int(np.asarray(p.x)[i])
    y = F.limbs_to_int(np.asarray(p.y)[i])
    z = F.limbs_to_int(np.asarray(p.z)[i])
    zi = pow(z, F.P_INT - 2, F.P_INT)
    return x * zi % F.P_INT, y * zi % F.P_INT


def test_point_add_double_vs_oracle():
    ks = [1, 2, 5, 12345]
    pts = [em.BASE.scalar_mul(k) for k in ks]
    xs = to_batch([p.X * pow(p.Z, F.P_INT - 2, F.P_INT) % F.P_INT for p in pts])
    ys = to_batch([p.Y * pow(p.Z, F.P_INT - 2, F.P_INT) % F.P_INT for p in pts])
    P = C.Point(xs, ys, jnp.broadcast_to(jnp.asarray(F.ONE), xs.shape), F.mul(xs, ys))
    D = C.point_double(P)
    S = C.point_add(P, C.base_point((4,)))
    for i, k in enumerate(ks):
        expect_d = em.BASE.scalar_mul(2 * k)
        ex, ey = _point_to_ints(D, i)
        assert (ex, ey) == (
            expect_d.X * pow(expect_d.Z, F.P_INT - 2, F.P_INT) % F.P_INT,
            expect_d.Y * pow(expect_d.Z, F.P_INT - 2, F.P_INT) % F.P_INT,
        )
        expect_s = em.BASE.scalar_mul(k + 1)
        sx, sy = _point_to_ints(S, i)
        zi = pow(expect_s.Z, F.P_INT - 2, F.P_INT)
        assert (sx, sy) == (expect_s.X * zi % F.P_INT, expect_s.Y * zi % F.P_INT)


def test_point_add_identity_complete():
    idp = C.identity((2,))
    bp = C.base_point((2,))
    out = C.point_add(idp, bp)
    assert bool(C.point_eq(out, bp).all())
    assert bool(C.is_identity(C.point_add(idp, idp)).all())


def test_decompress_vs_oracle():
    ks = [1, 2, 7, 99, 123456789]
    encs = [em.BASE.scalar_mul(k).compress() for k in ks]
    # add one invalid encoding (y with no square root) and the identity
    encs.append((1).to_bytes(32, "little"))  # identity
    bad = bytearray(32)
    bad[0] = 2  # y=2 — happens to be off-curve for ed25519
    encs.append(bytes(bad))
    arr = jnp.asarray(
        np.stack([np.frombuffer(e, np.uint8).astype(np.int32) for e in encs])
    )
    pt, valid = C.decompress(arr)
    valid = np.asarray(valid)
    for i, k in enumerate(ks):
        assert valid[i]
        ex, ey = _point_to_ints(pt, i)
        oracle = em.Point.decompress(encs[i])
        zi = pow(oracle.Z, F.P_INT - 2, F.P_INT)
        assert (ex, ey) == (oracle.X * zi % F.P_INT, oracle.Y * zi % F.P_INT)
    assert valid[len(ks)]  # identity decompresses
    oracle_bad = em.Point.decompress(encs[-1])
    assert bool(valid[-1]) == (oracle_bad is not None)


def test_verify_batch_valid_and_invalid():
    n = 16
    keys = [ed25519.Ed25519PrivKey.generate() for _ in range(n)]
    msgs = [secrets.token_bytes(40 + i) for i in range(n)]
    items = []
    expected = []
    for i, (k, m) in enumerate(zip(keys, msgs)):
        sig = k.sign(m)
        if i % 5 == 1:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])  # corrupt s
            expected.append(False)
        elif i % 5 == 3:
            m = m + b"tampered"
            expected.append(False)
        else:
            expected.append(True)
        items.append((k.pub_key().bytes(), m, sig))
    bitmap = verify_batch(items)
    assert list(bitmap) == expected


def test_verify_batch_noncanonical_s_rejected():
    k = ed25519.Ed25519PrivKey.generate()
    m = b"msg"
    sig = bytearray(k.sign(m))
    s = int.from_bytes(sig[32:], "little")
    sig[32:] = (s + em.L).to_bytes(32, "little")
    bitmap = verify_batch([(k.pub_key().bytes(), m, bytes(sig))])
    assert not bitmap[0]


def test_verify_batch_zip215_edge_cases():
    # identity pubkey (small-order) with s=0, R=identity: 0*B == R + k*A holds
    # for any k iff R and k*A cancel; with A=R=identity and s=0 the cofactored
    # equation holds — ZIP-215 accepts.
    ident = (1).to_bytes(32, "little")
    sig = ident + (0).to_bytes(32, "little")
    bitmap = verify_batch([(ident, b"anything", sig)])
    assert em.verify_zip215(ident, b"anything", sig)
    assert bitmap[0]


def test_tpu_batch_verifier_interface():
    from tendermint_tpu.crypto.tpu.verify import TPUBatchVerifier
    from tendermint_tpu.crypto import secp256k1

    bv = TPUBatchVerifier()
    eds = [ed25519.Ed25519PrivKey.generate() for _ in range(3)]
    sec = secp256k1.Secp256k1PrivKey.generate()
    for i, k in enumerate(eds):
        m = f"m{i}".encode()
        bv.add(k.pub_key(), m, k.sign(m))
    bv.add(sec.pub_key(), b"sm", sec.sign(b"sm"))
    ok, bits = bv.verify()
    assert ok and bits == [True] * 4

    bv2 = TPUBatchVerifier()
    bv2.add(eds[0].pub_key(), b"a", eds[0].sign(b"b"))
    ok, bits = bv2.verify()
    assert not ok and bits == [False]


# -- batch-equation (MSM) kernel ---------------------------------------------


def _signed_items(n, n_vals=8):
    from tendermint_tpu import testing as tt

    chain_id = "eq-chain"
    vals, keys = tt.make_validator_set(n_vals)
    items = []
    h = 1
    while len(items) < n:
        bid = tt.make_block_id(b"eq%d" % h)
        c = tt.make_commit(chain_id, h, 0, bid, vals, keys)
        for i, cs in enumerate(c.signatures):
            if len(items) >= n:
                break
            items.append(
                (
                    vals.validators[i].pub_key.bytes(),
                    c.vote_sign_bytes(chain_id, i),
                    cs.signature,
                )
            )
        h += 1
    return items


def test_msm_matches_oracle():
    """MSM over random points/scalars vs the integer oracle."""
    import numpy as np
    import tendermint_tpu.crypto.ed25519_math as em
    from tendermint_tpu.crypto.tpu import curve, field as F, msm

    rng = np.random.default_rng(7)
    n = 5
    pts_int = [em.BASE.scalar_mul(int(k)) for k in rng.integers(1, 2**30, n)]
    scalars = [int.from_bytes(rng.bytes(32), "little") % em.L for _ in range(n)]

    # oracle
    want = em.Point.identity()
    for p, s in zip(pts_int, scalars):
        want = want.add(p.scalar_mul(s))

    # device: build affine limb points + digit rows
    import jax.numpy as jnp

    def to_limb_point(p):
        zinv = pow(p.Z, em.P - 2, em.P)
        x, y = p.X * zinv % em.P, p.Y * zinv % em.P
        return (
            F.int_to_limbs(x),
            F.int_to_limbs(y),
            F.int_to_limbs(1),
            F.int_to_limbs(x * y % em.P),
        )

    comps = list(zip(*(to_limb_point(p) for p in pts_int)))
    points = curve.Point(*(jnp.asarray(np.stack(c)) for c in comps))
    sc_bytes = np.stack(
        [
            np.frombuffer(s.to_bytes(32, "little"), np.uint8).astype(np.int32)
            for s in scalars
        ]
    )
    digit_rows = jnp.asarray(np.ascontiguousarray(sc_bytes.T))
    got = msm.msm(points, digit_rows)
    gx, gy, gz = (
        F.limbs_to_int(np.asarray(c)) for c in (got.x, got.y, got.z)
    )
    zinv = pow(gz, em.P - 2, em.P)
    wzinv = pow(want.Z, em.P - 2, em.P)
    assert gx * zinv % em.P == want.X * wzinv % em.P
    assert gy * zinv % em.P == want.Y * wzinv % em.P


def test_verify_batch_eq_happy_and_fallback():
    from tendermint_tpu.crypto.tpu.verify import verify_batch_eq

    items = _signed_items(20)
    out = verify_batch_eq(items)
    assert out.all() and len(out) == 20

    bad = list(items)
    p, m, s = bad[11]
    bad[11] = (p, m, s[:20] + bytes([s[20] ^ 1]) + s[21:])
    out = verify_batch_eq(bad)
    assert not out[11] and out.sum() == 19


def test_verify_batch_eq_malformed_entries():
    from tendermint_tpu.crypto.tpu.verify import L as ELL, verify_batch_eq

    items = _signed_items(8)
    items[2] = (items[2][0], items[2][1], items[2][2][:32] + (ELL + 9).to_bytes(32, "little"))
    items[5] = (b"\x01" * 31, items[5][1], items[5][2])  # short pubkey
    out = verify_batch_eq(items)
    assert not out[2] and not out[5] and out.sum() == 6


def test_verify_batch_eq_bad_shared_pubkey():
    """A-side grouping: one undecompressable pubkey shared by several
    signatures must fail exactly those rows (the bitmap gathers the
    per-GROUP decompression verdict through gidx)."""
    from tendermint_tpu.crypto.tpu.verify import verify_batch_eq

    from tendermint_tpu.crypto.ed25519_math import Point as IntPoint

    items = _signed_items(20, n_vals=4)  # each key signs ~5 times
    # find a y with no curve point (oracle-checked, deterministic)
    bad_key = next(
        k
        for b0 in range(256)
        for k in [bytes([b0]) + b"\x02" * 31]
        if IntPoint.decompress(k) is None
    )
    bad_rows = [i for i, it in enumerate(items) if it[0] == items[1][0]]
    items = [
        (bad_key, m, s) if p == items[1][0] else (p, m, s)
        for (p, m, s) in items
    ]
    out = verify_batch_eq(items)
    assert len(bad_rows) >= 2
    for i in range(20):
        assert out[i] == (i not in bad_rows)


def test_verify_resolved_sr25519():
    """sr25519 signatures route through the same MSM kernel."""
    from tendermint_tpu.crypto import sr25519 as sr
    from tendermint_tpu.crypto.tpu.verify import resolve_sr25519, verify_resolved

    entries = []
    for i in range(6):
        priv = sr.Sr25519PrivKey(bytes([0x30 + i]) * 32)
        msg = b"sr-batch-%d" % i
        sig = priv.sign(msg)
        entries.append(resolve_sr25519(priv.pub_key().bytes(), msg, sig))
    out = verify_resolved(entries)
    assert out.all()

    # tamper one -> per-sig fallback pinpoints it
    priv = sr.Sr25519PrivKey(b"\x55" * 32)
    sig = bytearray(priv.sign(b"x"))
    sig[3] ^= 1
    entries[4] = resolve_sr25519(priv.pub_key().bytes(), b"x", bytes(sig))
    out = verify_resolved(entries)
    assert not out[4] and out.sum() == 5


def test_pallas_field_mul_matches_gemm():
    """The Pallas VMEM convolution kernel (interpret mode on CPU) agrees
    with the GEMM formulation across random partially-reduced inputs."""
    import numpy as np

    from tendermint_tpu.crypto.tpu import field as F
    from tendermint_tpu.crypto.tpu import pallas_field as PF

    rng = np.random.default_rng(11)
    a = rng.integers(0, 512, (21, 32), dtype=np.int32)
    b = rng.integers(0, 512, (21, 32), dtype=np.int32)
    want = np.asarray(F.mul(a, b))
    got = np.asarray(PF.mul(a, b, interpret=True))
    for i in range(len(a)):
        assert F.limbs_to_int(want[i]) == F.limbs_to_int(got[i])


def test_pallas_pow22523_matches_xla_chain():
    """The fused VMEM pow22523 kernel (interpret mode on CPU) agrees with
    the portable XLA addition chain — and with exact integer math."""
    import numpy as np

    from tendermint_tpu.crypto.tpu import field as F
    from tendermint_tpu.crypto.tpu import pallas_field as PF

    rng = np.random.default_rng(13)
    z = rng.integers(0, 256, (9, 32), dtype=np.int32)
    want = np.asarray(F._pow22523_chain(z))
    got = np.asarray(PF.pow22523(z, interpret=True))
    for i in range(len(z)):
        zi = F.limbs_to_int(z[i])
        expect = pow(zi, 2**252 - 3, F.P_INT)
        assert F.limbs_to_int(want[i]) == expect
        assert F.limbs_to_int(got[i]) == expect
    assert got.max() < 512  # module invariant preserved

    # extreme-bound exactness (511 everywhere — the f32 worst case)
    am = np.full((5, 32), 511, np.int32)
    w = np.asarray(F.mul(am, am))
    g = np.asarray(PF.mul(am, am, interpret=True))
    for i in range(5):
        assert F.limbs_to_int(w[i]) == F.limbs_to_int(g[i])


def test_verify_resolved_chunked(monkeypatch):
    """Batches above _MAX_BUCKET split into pipelined chunks; a bad
    signature triggers the per-signature fallback ONLY for its chunk."""
    from tendermint_tpu.crypto.tpu import verify as V

    monkeypatch.setattr(V, "_MAX_BUCKET", 64)
    items = _signed_items(150, n_vals=8)
    p, m, s = items[100]  # chunk 2 (64..127)
    items[100] = (p, m, s[:63] + bytes([s[63] ^ 1]))
    out = V.verify_batch_eq(items)
    assert len(out) == 150
    assert not out[100] and out.sum() == 149


def test_pallas_scan_blocks_matches_xla_scan():
    """The fused within-block prefix-scan kernel (interpret mode on CPU)
    is limb-exact with the lax.scan of curve.add_cached it replaces."""


    import jax.numpy as jnp
    import numpy as np

    from tendermint_tpu.crypto.tpu import curve as C
    from tendermint_tpu.crypto.tpu import msm as M
    from tendermint_tpu.crypto.tpu import pallas_field as PF

    rng = np.random.default_rng(17)
    # 4-step blocks: the kernel is length-generic (production uses
    # M._BLOCK=16); a short chain keeps interpret-mode tracing cheap
    g, blk = 8, 4
    coords = [rng.integers(0, 256, (g, blk, 32), dtype=np.int32) for _ in range(4)]
    pts = C.Point(*(jnp.asarray(c) for c in coords))

    first = C.Point(*(c[:, 0] for c in pts))
    rest = C.Point(*(jnp.moveaxis(c[:, 1:], 1, 0) for c in pts))
    rest_cached = C.to_cached(rest)

    def xla_scan():
        def step(acc, nxt):
            acc = C.add_cached(acc, nxt)
            return acc, acc

        last, tail = __import__("jax").lax.scan(step, first, rest_cached)
        within = C.Point(
            *(
                jnp.concatenate([f[:, None], jnp.moveaxis(t, 0, 1)], axis=1)
                for f, t in zip(first, tail)
            )
        )
        return within, last

    want_within, want_last = xla_scan()
    got = PF.scan_blocks(tuple(first), tuple(rest_cached), interpret=True, tile=8)
    for w, gp in zip(want_within, got):
        assert np.array_equal(np.asarray(w), np.asarray(gp))
    for w, gp in zip(want_last, got):
        assert np.array_equal(np.asarray(w), np.asarray(gp[:, -1]))
