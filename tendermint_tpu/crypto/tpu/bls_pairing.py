"""Batched BLS12-381 pairing kernel: Miller loop + final exponentiation
over the bls_field limb tower, with all point-dependent work done on the
host (the prepare_batch_eq idiom).

Shape of the computation: one verification ITEM is a pairing-product
check  prod_i e(P_i, Q_i) == 1  (a single signature verify is the
2-pair instance e(-g1, sig) * e(pk, H(m)); an aggregate commit is one
item with n+1 pairs). The host precomputes, per pair, the G1 evaluation
point (px, py) and the 63-step Miller line schedule — `bls_math.
prepare_lines`, i.e. per line the Fq2 pair (a5, c3) with

    l(P) = py * w^0 + c3 * w^3 + (a5 * px) * w^5

so the device never touches G2 point arithmetic or inversions: the
kernel is a scan of Fq12 tower multiplies (GEMM-limb work, the part the
MXU is good at), a pair-axis product tree, and the final-exponentiation
scan. Both batch axes are bucket-padded (powers of two; pad pairs have
py = 1 and zero line coefficients, so every pad line evaluates to ONE
and pad items finish at exactly 1) — no cold shapes on the hot path,
same discipline the ed25519 kernels enforce.

Device routing is OPT-IN (TMTPU_BLS_TPU=1): a cold pairing-kernel
compile is minutes-scale, so tier-1 and default nodes stay on the
pure-Python path while benches/TPU deployments warm it explicitly.
Correctness does not depend on the backend: the kernel is exact integer
arithmetic mod p and is pinned bit-identical to bls_math in
tests/test_bls.py.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from .. import bls_math

# bit schedule of the Miller loop: 63 steps after |x|'s leading bit;
# 5 of them carry an addition line
X_STEP_BITS = np.array([int(b) for b in bls_math.X_BITS[1:]], dtype=np.int32)
N_STEPS = len(X_STEP_BITS)
# final-exponentiation hard part, leading bit dropped (acc seeds at f)
HARD_STEP_BITS = np.array(
    [int(b) for b in bls_math.HARD_BITS[1:]], dtype=np.int32
)

_MIN_ITEMS = 2
_MAX_ITEMS = 256
_MIN_PAIRS = 2

_kernel_cache: dict = {}
_kernel_lock = threading.Lock()


def bucket_items(n: int) -> int:
    """Power-of-two item bucket in [_MIN_ITEMS, _MAX_ITEMS]."""
    b = _MIN_ITEMS
    while b < n and b < _MAX_ITEMS:
        b *= 2
    return b


def bucket_pairs(n: int) -> int:
    b = _MIN_PAIRS
    while b < n:
        b *= 2
    return b


def device_enabled() -> bool:
    """The BLS device path is opt-in (see module docstring)."""
    return os.environ.get("TMTPU_BLS_TPU") == "1"


def prepare_pairing_batch(items: list, pad_to: int = 0, pair_pad: int = 0):
    """Host prep: items is a list of pair-lists [(P, Q), ...] with P a
    G1 affine int pair and Q a G2 affine Fq2 pair (both already
    subgroup-checked by the caller — crypto/bls.py caches). Returns the
    device arrays padded to (pad_to items, pair_pad pairs); both pads
    must be bucket shapes (the dispatch core asserts)."""
    from . import bls_field as F

    n = len(items)
    np_real = max((len(pairs) for pairs in items), default=0)
    m = max(pad_to or 0, n, _MIN_ITEMS)
    npairs = max(pair_pad or 0, np_real, _MIN_PAIRS)
    px = np.zeros((m, npairs, F.LIMBS), np.int32)
    py = np.zeros((m, npairs, F.LIMBS), np.int32)
    py[:, :, 0] = 1  # pad pairs evaluate every line to exactly 1
    dbl_a5 = np.zeros((N_STEPS, m, npairs, 2, F.LIMBS), np.int32)
    dbl_c3 = np.zeros_like(dbl_a5)
    add_a5 = np.zeros_like(dbl_a5)
    add_c3 = np.zeros_like(dbl_a5)
    for i, pairs in enumerate(items):
        for j, (p, q) in enumerate(pairs):
            px[i, j] = F.int_to_limbs(p[0])
            py[i, j] = F.int_to_limbs(p[1])
            lines = bls_math.prepare_lines(q)
            idx = 0
            for s, bit in enumerate(X_STEP_BITS):
                a5, c3 = lines[idx]
                idx += 1
                dbl_a5[s, i, j, 0] = F.int_to_limbs(a5[0])
                dbl_a5[s, i, j, 1] = F.int_to_limbs(a5[1])
                dbl_c3[s, i, j, 0] = F.int_to_limbs(c3[0])
                dbl_c3[s, i, j, 1] = F.int_to_limbs(c3[1])
                if bit:
                    a5, c3 = lines[idx]
                    idx += 1
                    add_a5[s, i, j, 0] = F.int_to_limbs(a5[0])
                    add_a5[s, i, j, 1] = F.int_to_limbs(a5[1])
                    add_c3[s, i, j, 0] = F.int_to_limbs(c3[0])
                    add_c3[s, i, j, 1] = F.int_to_limbs(c3[1])
            assert idx == len(lines)
    return (px, py, dbl_a5, dbl_c3, add_a5, add_c3), n


def _build_kernel(m: int, npairs: int):
    """JIT a pairing-product kernel for the (items, pairs) bucket shape.
    Returns (is_one bools (m,), canonical Fq12 (m, 6, 2, 49))."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from . import bls_field as F

    x_bits = jnp.asarray(X_STEP_BITS)
    hard_bits = jnp.asarray(HARD_STEP_BITS)

    def line_f12(a5, c3, px, py):
        # (…, 2, 49) line coeffs + (…, 49) eval point -> sparse Fq12
        w0 = jnp.stack([py, jnp.zeros_like(py)], axis=-2)
        w5 = F.fq2_scale(a5, px)
        z = jnp.zeros_like(w0)
        return jnp.stack([w0, z, z, c3, z, w5], axis=-3)

    def kernel(px, py, dbl_a5, dbl_c3, add_a5, add_c3):
        f = F.f12_one((m, npairs))

        def step(f, xs):
            bit, da5, dc3, aa5, ac3 = xs
            f = F.f12_mul(f, f)
            f = F.f12_mul(f, line_f12(da5, dc3, px, py))
            fa = F.f12_mul(f, line_f12(aa5, ac3, px, py))
            return jnp.where(bit > 0, fa, f), None

        f, _ = lax.scan(step, f, (x_bits, dbl_a5, dbl_c3, add_a5, add_c3))
        f = F.f12_conj(f)  # negative BLS parameter
        # pair-axis product tree (npairs is a power of two)
        while f.shape[1] > 1:
            half = f.shape[1] // 2
            f = F.f12_mul(f[:, :half], f[:, half:])
        f = f[:, 0]
        # final exponentiation: easy part…
        f1 = F.f12_mul(F.f12_conj(f), F.f12_inv(f))
        f2 = F.f12_mul(F.f12_frob2(f1), f1)

        # …then the hard part as a scan over the constant exponent bits
        def hstep(acc, bit):
            acc = F.f12_mul(acc, acc)
            return jnp.where(bit > 0, F.f12_mul(acc, f2), acc), None

        out, _ = lax.scan(hstep, f2, hard_bits)
        return F.f12_is_one(out), F.canonical(out)

    return jax.jit(kernel)


def _get_kernel(m: int, npairs: int):
    # explicit raise, not `assert`: python -O must not let a non-bucket
    # shape slip through to a minutes-scale inline cold compile
    if m != bucket_items(m) or npairs != bucket_pairs(npairs):
        raise ValueError(
            f"non-bucket pairing shape ({m}, {npairs}) would cold-compile "
            "inline on the hot path"
        )
    key = (m, npairs)
    with _kernel_lock:
        k = _kernel_cache.get(key)
        if k is None:
            # a pairing bucket compiles for minutes: persist it in the
            # same placeable cache as the verify kernels
            from .verify import _ensure_compile_cache

            _ensure_compile_cache()
            k = _kernel_cache[key] = _build_kernel(m, npairs)
        return k


def verify_pairs_batch(items: list, pad_to: int = 0, pair_pad: int = 0):
    """Run the batched pairing-product check; returns np.bool_ (len
    items,). Callers pass bucket pads (lint-enforced like the ed25519
    prep calls)."""
    arrays, n = prepare_pairing_batch(items, pad_to=pad_to, pair_pad=pair_pad)
    kern = _get_kernel(arrays[0].shape[0], arrays[0].shape[1])
    ok, _f12 = kern(*arrays)
    return np.asarray(ok)[:n]


def pairing_f12_ints(p, q) -> tuple:
    """Single pairing e(P, Q) through the device kernel, returned as the
    pure-Python 12-int tuple — the bit-identity test surface against
    bls_math.pairing."""
    from . import bls_field as F

    arrays, _ = prepare_pairing_batch(
        [[(p, q)]], pad_to=_MIN_ITEMS, pair_pad=_MIN_PAIRS
    )
    kern = _get_kernel(arrays[0].shape[0], arrays[0].shape[1])
    _ok, f12 = kern(*arrays)
    c = np.asarray(f12)[0]  # already canonical limbs
    out = []
    for i in range(6):
        out.append(F.limbs_to_int(c[i, 0]))
        out.append(F.limbs_to_int(c[i, 1]))
    return tuple(out)


def warmup(batch: int = _MIN_ITEMS, pairs: int = _MIN_PAIRS) -> None:
    """Pre-compile the (batch, pairs) bucket (benches / TPU deployments;
    a cold pairing compile must never land inline on the hot path)."""
    sk = 7
    pk = bls_math.sk_to_pk(sk)
    sig = bls_math.sign(sk, b"bls-warmup")
    h = bls_math.hash_to_point_g2(b"bls-warmup")
    item = [(bls_math.NEG_G1_GEN, sig), (pk, h)]
    verify_pairs_batch(
        [item] * batch, pad_to=bucket_items(batch), pair_pad=bucket_pairs(pairs)
    )


def verify_items(triples: list) -> np.ndarray:
    """Batched single-signature verification on the device: triples are
    (pubkey_point, msg_bytes, sig_point) with points already subgroup
    checked. Each becomes the 2-pair item e(-g1, sig) * e(pk, H(m)).
    Batches larger than the top item bucket run in _MAX_ITEMS chunks —
    bucket_items() caps there, and an over-cap shape would otherwise
    fail the bucket guard (tripping the shared breaker) instead of
    verifying."""
    items = [
        [(bls_math.NEG_G1_GEN, sig), (pk, bls_math.hash_to_point_g2(bytes(msg)))]
        for pk, msg, sig in triples
    ]
    outs = []
    for i in range(0, len(items), _MAX_ITEMS):
        chunk = items[i : i + _MAX_ITEMS]
        outs.append(
            verify_pairs_batch(
                chunk, pad_to=bucket_items(len(chunk)), pair_pad=_MIN_PAIRS
            )
        )
    return np.concatenate(outs) if outs else np.zeros(0, dtype=bool)
