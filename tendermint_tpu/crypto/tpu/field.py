"""GF(2^255-19) arithmetic on vectors of radix-2^8 limbs, in int32.

Representation: a field element is an int32 array of shape (..., 32), limb i
holding (partially reduced) coefficient of 256^i, all limbs non-negative.

The invariant maintained between operations is limbs < 2^9 = 512 (`mul`,
`sub`, `neg`, `mul_scalar` return limbs ≤ 293; `add` returns ≤ 369; `mul`
accepts anything < 2^9). That bound is what makes
the MXU formulation of the product exact: the 32×32 outer product has entries
≤ 511² < 2^18 (exact in float32), and the anti-diagonal contraction sums at
most 32 of them, so every partial sum is an integer < 2^23 < 2^24 and float32
GEMM accumulation is bit-exact.

`mul` computes the schoolbook convolution as

    outer = a ⊗ b                  (..., 32, 32)  — VPU elementwise
    conv  = outer.reshape(..., 1024) @ S           — MXU GEMM, S constant 0/1
                                                     with S[i·32+j, i+j] = 1

then folds 2^256 ≡ 38 and runs four vectorized carry passes in int32. This
is ~10 HLO ops per multiply (vs ~100 for an unrolled pad+add convolution),
which keeps XLA compile time of the verification scan in seconds, and it
routes the bulk of the MAC work onto the systolic array.

Carry-pass bound analysis (why four passes suffice): a pass keeps the low
byte (≤255) and adds the neighbour's carry; only limb 0 takes a ×38 carry
(from limb 31). Carries move one position per pass, so bounds are
positional — limbs 1..3 inherit limb 0's 38×-inflated carry with a lag.
From a uniform fold bound ≤ 39·2^23 < 2^28.3:
  pass 1: limb0 ≤ 2^25.6, limbs 1-31 ≤ 2^20.3
  pass 2: limb0 ≤ 2^17.9, limb1 ≤ 2^17.6 (limb 0's pass-1 carry),
          limbs 2-31 ≤ 5400
  pass 3: limb0 ≤ 1053, limb1 ≤ 1215, limb2 ≤ 1031, limbs 3-31 ≤ 276
  pass 4: limb0 ≤ 293, limbs 1-3 ≤ 259, limbs 4-31 ≤ 256
so every limb ends ≤ 293 < 2^9. (Three passes would NOT suffice: limbs
0-2 can still exceed 2^9 after pass 3.)

Canonicalization (exact byte form, for parity/equality/compression) uses a
`lax.scan` along the limb axis — sequential in the 32 limbs, vectorized over
the batch.

Why radix 2^8 / int32 and not wider limbs: TPUs have no native 64-bit
integer path (s64 is emulated), while int32 carry logic runs on the VPU at
full lane rate; 8-bit limbs also make byte-level I/O (keys, signatures) a
zero-cost reinterpretation, and keep the f32 GEMM exact (see above).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

LIMBS = 32
P_INT = 2**255 - 19
D_INT = (-121665 * pow(121666, P_INT - 2, P_INT)) % P_INT
SQRT_M1_INT = pow(2, (P_INT - 1) // 4, P_INT)


def int_to_limbs(v: int) -> np.ndarray:
    """Python int -> canonical limb vector (numpy, for constants/host prep)."""
    return np.frombuffer(int(v % P_INT).to_bytes(32, "little"), dtype=np.uint8).astype(
        np.int32
    )


def limbs_to_int(a) -> int:
    """Limb vector (possibly partially reduced) -> Python int mod p."""
    a = np.asarray(a, dtype=np.int64)
    return sum(int(x) << (8 * i) for i, x in enumerate(a)) % P_INT


# constant limb vectors
P_LIMBS = int_to_limbs(P_INT)
D_LIMBS = int_to_limbs(D_INT)
D2_LIMBS = int_to_limbs(2 * D_INT)
SQRT_M1_LIMBS = int_to_limbs(SQRT_M1_INT)
ONE = int_to_limbs(1)
ZERO = np.zeros(LIMBS, dtype=np.int32)
# 8p in limb form: every limb large enough to dominate a (<2^9)-bounded
# subtrahend, used to keep subtraction non-negative.
EIGHT_P = (8 * P_LIMBS).astype(np.int32)


def _carry_pass(c: jnp.ndarray) -> jnp.ndarray:
    """One vectorized carry: keep low byte, push high bits one limb up; the
    carry out of limb 31 wraps to limb 0 multiplied by 38 (2^256 ≡ 38)."""
    low = c & 0xFF
    hi = c >> 8
    hi_shift = jnp.concatenate([hi[..., 31:] * 38, hi[..., :31]], axis=-1)
    return low + hi_shift


# Anti-diagonal routing matrix: S[i*32+j, i+j] = 1. Contracting the flat
# outer product with S computes the polynomial convolution as one GEMM.
_S_CONV = np.zeros((LIMBS * LIMBS, 2 * LIMBS - 1), np.float32)
for _i in range(LIMBS):
    for _j in range(LIMBS):
        _S_CONV[_i * LIMBS + _j, _i + _j] = 1.0


# The ONE formulation switch of the kernels. On: `mul` is the Pallas
# VMEM-resident convolution (pallas_field.mul), `pow22523` the fused VMEM
# exponentiation chain, and msm._boundary_prefixes scans its blocks in one
# fused kernel. Off: the portable XLA formulations, which are the CPU path
# and the tests' oracle. verify._choose_formulation sets it once, from the
# platform, BEFORE any kernel is traced; a test substitutes through the
# setter (AOT compile for a described TPU from a CPU host).
_USE_PALLAS = False


def set_pallas(on: bool) -> None:
    global _USE_PALLAS
    _USE_PALLAS = bool(on)


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field multiply. Inputs: limbs < 2^9 (the module invariant).
    Output: limbs ≤ 293 (< 2^9). See module docstring for the exactness
    and carry-bound analysis."""
    if _USE_PALLAS:
        from . import pallas_field

        return pallas_field.mul(a, b)
    return _mul_gemm(a, b)


def _mul_gemm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The portable XLA formulation: the convolution as one MXU GEMM."""
    a, b = jnp.broadcast_arrays(a, b)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    outer = af[..., :, None] * bf[..., None, :]  # (..., 32, 32), ≤ 511² exact
    flat = outer.reshape(outer.shape[:-2] + (LIMBS * LIMBS,))
    # HIGHEST precision: the contraction must be true f32 (bit-exact for
    # integers < 2^24), not a bf16 multi-pass approximation.
    conv = jnp.matmul(
        flat, jnp.asarray(_S_CONV), precision=jax.lax.Precision.HIGHEST
    ).astype(jnp.int32)
    hi = jnp.pad(
        conv[..., LIMBS:], [(0, 0)] * (a.ndim - 1) + [(0, 1)], constant_values=0
    )
    c = conv[..., :LIMBS] + 38 * hi
    c = _carry_pass(_carry_pass(_carry_pass(_carry_pass(c))))
    return c


def square(a: jnp.ndarray) -> jnp.ndarray:
    return mul(a, a)


def mul_many(pairs: list[tuple[jnp.ndarray, jnp.ndarray]]) -> list[jnp.ndarray]:
    """Multiply several independent pairs with ONE convolution by stacking
    them along a new leading axis. Same MAC count as separate calls, but a
    fraction of the HLO ops — the dominant cost of this kernel is op
    dispatch/fusion, not arithmetic."""
    lhs = []
    rhs = []
    for a, b in pairs:
        a, b = jnp.broadcast_arrays(a, b)
        lhs.append(a)
        rhs.append(b)
    out = mul(
        jnp.stack(jnp.broadcast_arrays(*lhs)), jnp.stack(jnp.broadcast_arrays(*rhs))
    )
    return [out[i] for i in range(len(pairs))]


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a + b with one carry pass: inputs < 2^9 → output ≤ 369 (< 2^9),
    preserving the module invariant (mul's f32 path needs inputs < 2^9)."""
    return _carry_pass(a + b)


add_c = add


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b mod p, non-negative limbs via +8p, then two carry passes.
    Inputs < 2^9 → sum < 511+2040 < 2^12 → output ≤ 293 (< 2^9)."""
    return _carry_pass(_carry_pass(a + jnp.asarray(EIGHT_P) - b))


def neg(a: jnp.ndarray) -> jnp.ndarray:
    return _carry_pass(_carry_pass(jnp.asarray(EIGHT_P) - a))


def mul_scalar(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multiply by a small constant (k ≤ 16; larger constants must go
    through `mul` with a limb vector to respect the carry bounds)."""
    return _carry_pass(_carry_pass(a * k))


def pow2k(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """a^(2^k) via k squarings (lax loop to keep the trace small)."""
    return lax.fori_loop(0, k, lambda _, x: square(x), a)


def pow22523(z: jnp.ndarray) -> jnp.ndarray:
    """z^(2^252 - 3): the exponentiation used for inverse square roots in
    decompression (classic ed25519 addition chain). On Pallas-enabled
    backends the whole 254-multiply chain runs as ONE VMEM-resident
    kernel (pallas_field.pow22523) — per-squaring HBM round-trips cost
    more than the arithmetic."""
    if _USE_PALLAS:
        from . import pallas_field

        return pallas_field.pow22523(z)
    return _pow22523_chain(z)


def _pow22523_chain(z: jnp.ndarray) -> jnp.ndarray:
    """The portable XLA formulation."""
    t0 = square(z)  # 2
    t1 = square(square(t0))  # 8
    t1 = mul(z, t1)  # 9
    t0 = mul(t0, t1)  # 11
    t0 = square(t0)  # 22
    t0 = mul(t1, t0)  # 31 = 2^5 - 1
    t1 = pow2k(t0, 5)
    t0 = mul(t1, t0)  # 2^10 - 1
    t1 = pow2k(t0, 10)
    t1 = mul(t1, t0)  # 2^20 - 1
    t2 = pow2k(t1, 20)
    t1 = mul(t2, t1)  # 2^40 - 1
    t1 = pow2k(t1, 10)
    t0 = mul(t1, t0)  # 2^50 - 1
    t1 = pow2k(t0, 50)
    t1 = mul(t1, t0)  # 2^100 - 1
    t2 = pow2k(t1, 100)
    t1 = mul(t2, t1)  # 2^200 - 1
    t1 = pow2k(t1, 50)
    t0 = mul(t1, t0)  # 2^250 - 1
    t0 = pow2k(t0, 2)  # 2^252 - 4
    return mul(t0, z)  # 2^252 - 3


def _scan_carry(c: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact sequential carry along the limb axis (batch-vectorized).
    Returns (byte limbs, carry out of limb 31)."""
    c_t = jnp.moveaxis(c, -1, 0)  # (32, ...)

    def step(carry, limb):
        v = limb + carry
        return v >> 8, v & 0xFF

    # init derived from the data (c_t[0] * 0), NOT jnp.zeros: under
    # shard_map the data is varying over the mesh axis and a constant
    # init would make the scan's carry-in/carry-out types disagree
    carry_out, limbs = lax.scan(step, c_t[0] * 0, c_t)
    return jnp.moveaxis(limbs, 0, -1), carry_out


def canonical(a: jnp.ndarray) -> jnp.ndarray:
    """Fully reduce to the canonical byte representation in [0, p)."""
    v, carry = _scan_carry(a)
    # fold 2^256-carries back in; after two folds the carry is exhausted
    v, carry = _scan_carry(v.at[..., 0].add(carry * 38))
    v, carry = _scan_carry(v.at[..., 0].add(carry * 38))
    # v < 2^256 now; subtract p (conditionally) twice via the +19 trick:
    # v >= p  iff  v + 19 >= 2^255
    for _ in range(2):
        w, wcarry = _scan_carry(v.at[..., 0].add(19))
        ge = (wcarry > 0) | (w[..., 31] >= 0x80)
        w = w.at[..., 31].set(w[..., 31] & 0x7F)  # w - 2^255 == v - p
        v = jnp.where(ge[..., None], w, v)
    return v


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    """a ≡ 0 (mod p), elementwise over the batch. Returns bool (...,)."""
    return jnp.all(canonical(a) == 0, axis=-1)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return is_zero(sub(a, b))


def parity(a: jnp.ndarray) -> jnp.ndarray:
    """Low bit of the canonical representation."""
    return canonical(a)[..., 0] & 1
