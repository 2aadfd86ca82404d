"""Pallas TPU kernel for the GF(2^255−19) field multiply.

Why: the portable `field.mul` routes the 32-limb schoolbook convolution
through an MXU GEMM against a constant 0/1 anti-diagonal matrix — exact
and compile-friendly, but 63× MAC-inflated (the matmul's contraction does
routing, not math) and memory-bound (the (B,1024) outer product round-
trips HBM per multiply). This kernel computes the convolution directly on
the VPU with everything resident in VMEM.

Layout: TRANSPOSED — limbs along the sublane axis, batch along the lane
axis. An operand block is (32, T) int32: limb i of lane-batch element n
at [i, n]. That layout makes every step a full-lane vector op with only
static sublane slices/concats (Mosaic TC lowers neither scatter-add nor
lane-dimension reshapes, which sank the two earlier formulations):

  conv:   acc (64, T) f32; for j in 0..31 (static unroll):
            acc[j : j+32] += a * b[j]      (broadcast of one sublane row,
                                            shift-by-j as a zero-pad)
  fold:   2^256 ≡ 38, then the same four exact int32 carry passes as
          field.py (same bounds analysis — limbs < 2^9 in, ≤ 293 out);
          carries move one SUBLANE, i.e. a static concat, per pass.

Cost per element: 32 f32 MAC + ~6 carry vector-ops per limb-vector, all
VMEM-resident, vs the GEMM path's 64.5k routed MXU MACs + a materialized
(B,1024) intermediate. f32 products are exact (≤ 511² · 32 < 2^24).

The host-side wrapper transposes (…, 32) limbs-last operands to the
kernel layout and back; XLA fuses those transposes into neighbours where
it can. Enabled on TPU backends (verify._choose_formulation, after a
self-test against the host's integers); the GEMM path remains for CPU and
as the differential-testing oracle. Tests run this kernel in Pallas interpret
mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LIMBS = 32
SEG = 64  # conv scratch sublanes (63 coefficients + 1 structural zero)
TILE = 512  # lanes (batch elements) per grid step


def _conv_mod(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field multiply of (32, T) int32 limb blocks, used INSIDE Pallas
    kernels (pure array in/out; callers read/write the refs). Same
    exactness/carry-bound analysis as field.py's GEMM formulation."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    t = af.shape[1]
    acc = jnp.zeros((SEG, t), jnp.float32)
    for j in range(LIMBS):
        prod = af * bf[j : j + 1, :]  # (32, T), one sublane row broadcast
        acc = acc + jnp.pad(prod, ((j, SEG - LIMBS - j), (0, 0)))

    conv = acc.astype(jnp.int32)  # exact: every partial sum < 2^24
    lo = conv[:LIMBS]
    hi = conv[LIMBS:]
    # 2^256 ≡ 38: coefficient k+32 (= hi[k], k in 0..30) folds onto limb
    # k with weight 38; coefficient 63 is structural zero padding
    c = lo + 38 * jnp.concatenate([hi[:31], jnp.zeros_like(hi[:1])], axis=0)
    for _ in range(4):
        low = c & 0xFF
        carry = c >> 8
        c = low + jnp.concatenate([carry[31:] * 38, carry[:31]], axis=0)
    return c


def _mul_kernel(a_ref, b_ref, o_ref):
    o_ref[:] = _conv_mod(a_ref[:], b_ref[:])


# -- fused in-block prefix scan of cached point additions -------------------
#
def _eight_p():
    """8·p as (32, 1) limbs, built from scalar literals INSIDE the kernel
    (Pallas rejects captured array constants): p's little-endian bytes
    are [0xED, 0xFF×30, 0x7F], so 8p's limbs are [1896, 2040×30, 1016].
    Keeps subtraction non-negative with limbs < 2^12 before the carry
    passes (field.py's EIGHT_P, same bounds analysis)."""
    return jnp.concatenate(
        [
            jnp.full((1, 1), 8 * 0xED, jnp.int32),
            jnp.full((30, 1), 8 * 0xFF, jnp.int32),
            jnp.full((1, 1), 8 * 0x7F, jnp.int32),
        ],
        axis=0,
    )


def _carry1(c):
    low = c & 0xFF
    carry = c >> 8
    return low + jnp.concatenate([carry[31:] * 38, carry[:31]], axis=0)


def _fsub(a, b):
    return _carry1(_carry1(a + _eight_p() - b))


def _fadd(a, b):
    return _carry1(a + b)


def _add_cached(px, py, pz, pt, ymx, ypx, t2d, z2):
    """curve.add_cached in the (32, T) transposed layout: complete
    twisted-Edwards addition of an extended point and a cached ('Niels')
    operand — 8 field multiplies, all VMEM-resident."""
    a = _conv_mod(_fsub(py, px), ymx)
    b = _conv_mod(_fadd(py, px), ypx)
    c = _conv_mod(pt, t2d)
    d = _conv_mod(pz, z2)
    e = _fsub(b, a)
    f = _fsub(d, c)
    g = _fadd(d, c)
    h = _fadd(b, a)
    return _conv_mod(e, f), _conv_mod(g, h), _conv_mod(f, g), _conv_mod(e, h)


def _scan_block_kernel(fx, fy, fz, ft, ymx, ypx, t2d, z2, ox, oy, oz, ot):
    """Within-block inclusive prefix sums of point additions with the
    ENTIRE 16-step chain VMEM-resident (the MSM's dominant stage; as
    separate XLA ops every step round-trips four extended coordinates
    through HBM).

    Inputs: first point of each block (32, T) ×4; cached operands for
    steps 1..B-1 (B-1, 32, T) ×4. Outputs: inclusive prefixes
    (B, 32, T) ×4 (prefix 0 = the first point)."""
    px, py, pz, pt = fx[:], fy[:], fz[:], ft[:]
    ox[0], oy[0], oz[0], ot[0] = px, py, pz, pt
    steps = ymx.shape[0]
    for j in range(steps):  # static unroll: B-1 = 15 additions
        px, py, pz, pt = _add_cached(
            px, py, pz, pt, ymx[j], ypx[j], t2d[j], z2[j]
        )
        ox[j + 1], oy[j + 1], oz[j + 1], ot[j + 1] = px, py, pz, pt


def _pow22523_kernel(z_ref, o_ref):
    """z^(2^252 − 3) with the ENTIRE 254-multiply addition chain resident
    in VMEM. This is the inverse-square-root exponentiation that
    dominates point decompression; as separate XLA ops every squaring
    round-trips its (B,32) operand through HBM, which costs more than the
    arithmetic. One fused kernel touches HBM exactly twice (load z, store
    the result). Chain structure mirrors field.pow22523 (classic ed25519
    ladder)."""
    z = z_ref[:]

    def sq(x, k=1):
        # long squaring runs are ROLLED: unrolled, the kernel body is 266
        # convolutions of 32 unrolled steps each and Mosaic spends ~4
        # minutes compiling it — per enclosing program that embeds it
        if k < 5:
            for _ in range(k):
                x = _conv_mod(x, x)
            return x
        return jax.lax.fori_loop(0, k, lambda _, v: _conv_mod(v, v), x)

    t0 = sq(z)  # 2
    t1 = sq(t0, 2)  # 8
    t1 = _conv_mod(z, t1)  # 9
    t0 = _conv_mod(t0, t1)  # 11
    t0 = sq(t0)  # 22
    t0 = _conv_mod(t1, t0)  # 31 = 2^5 - 1
    t1 = sq(t0, 5)
    t0 = _conv_mod(t1, t0)  # 2^10 - 1
    t1 = sq(t0, 10)
    t1 = _conv_mod(t1, t0)  # 2^20 - 1
    t2 = sq(t1, 20)
    t1 = _conv_mod(t2, t1)  # 2^40 - 1
    t1 = sq(t1, 10)
    t0 = _conv_mod(t1, t0)  # 2^50 - 1
    t1 = sq(t0, 50)
    t1 = _conv_mod(t1, t0)  # 2^100 - 1
    t2 = sq(t1, 100)
    t1 = _conv_mod(t2, t1)  # 2^200 - 1
    t1 = sq(t1, 50)
    t0 = _conv_mod(t1, t0)  # 2^250 - 1
    t0 = sq(t0, 2)  # 2^252 - 4
    o_ref[:] = _conv_mod(t0, z)  # 2^252 - 3


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mul_limbs_first(a_t: jnp.ndarray, b_t: jnp.ndarray, interpret: bool = False):
    """(32, M) × (32, M) → (32, M), M a multiple of TILE."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m = a_t.shape[1]
    return pl.pallas_call(
        _mul_kernel,
        out_shape=jax.ShapeDtypeStruct((LIMBS, m), jnp.int32),
        grid=(m // TILE,),
        in_specs=[
            pl.BlockSpec((LIMBS, TILE), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((LIMBS, TILE), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (LIMBS, TILE), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(a_t, b_t)


def mul(a: jnp.ndarray, b: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Drop-in for field.mul: (..., 32) int32 limbs < 2^9 → (..., 32)
    limbs ≤ 293. Batch is flattened, padded to a TILE multiple, transposed
    to the kernel's limbs-first layout, multiplied in VMEM, and restored."""
    a, b = jnp.broadcast_arrays(a, b)
    shape = a.shape
    m = int(np.prod(shape[:-1])) if shape[:-1] else 1
    a2 = a.reshape(m, LIMBS)
    b2 = b.reshape(m, LIMBS)
    mp = -(-m // TILE) * TILE
    if mp != m:
        a2 = jnp.pad(a2, ((0, mp - m), (0, 0)))
        b2 = jnp.pad(b2, ((0, mp - m), (0, 0)))
    out = _mul_limbs_first(a2.T, b2.T, interpret=interpret)
    return out.T[:m].reshape(shape)


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _scan_blocks_limbs_first(first4, cached4, interpret: bool = False, tile: int = TILE):
    """first4: 4 × (32, M); cached4: 4 × (B-1, 32, M); -> 4 × (B, 32, M)
    inclusive prefixes. M a multiple of `tile`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m = first4[0].shape[1]
    nb = cached4[0].shape[0] + 1
    point_spec = pl.BlockSpec(
        (LIMBS, tile), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    cached_spec = pl.BlockSpec(
        (nb - 1, LIMBS, tile), lambda i: (0, 0, i), memory_space=pltpu.VMEM
    )
    out_spec = pl.BlockSpec(
        (nb, LIMBS, tile), lambda i: (0, 0, i), memory_space=pltpu.VMEM
    )
    outs = pl.pallas_call(
        _scan_block_kernel,
        out_shape=tuple(
            jax.ShapeDtypeStruct((nb, LIMBS, m), jnp.int32) for _ in range(4)
        ),
        grid=(m // tile,),
        in_specs=[point_spec] * 4 + [cached_spec] * 4,
        out_specs=tuple([out_spec] * 4),
        interpret=interpret,
    )(*first4, *cached4)
    return outs


def scan_blocks(first_pt, rest_cached, *, interpret: bool = False, tile: int = TILE):
    """Fused within-block prefix scan. first_pt: 4 coord arrays (G, 32);
    rest_cached: 4 cached arrays (B-1, G, 32). Returns 4 prefix arrays
    (G, B, 32) — inclusive, prefix 0 = first point. Drop-in for the
    lax.scan in msm._boundary_prefixes. `tile` shrinks the lane tile for
    cheap interpret-mode testing."""
    g = first_pt[0].shape[0]
    gp = -(-g // tile) * tile
    pad = gp - g

    def tr_point(c):  # (G, 32) -> (32, Gp)
        c = jnp.pad(c, ((0, pad), (0, 0))) if pad else c
        return c.T

    def tr_cached(c):  # (B-1, G, 32) -> (B-1, 32, Gp)
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0))) if pad else c
        return jnp.swapaxes(c, 1, 2)

    outs = _scan_blocks_limbs_first(
        tuple(tr_point(c) for c in first_pt),
        tuple(tr_cached(c) for c in rest_cached),
        interpret=interpret,
        tile=tile,
    )
    # (B, 32, Gp) -> (G, B, 32)
    return tuple(jnp.moveaxis(o, 2, 0)[:g] for o in outs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pow22523_limbs_first(z_t: jnp.ndarray, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m = z_t.shape[1]
    return pl.pallas_call(
        _pow22523_kernel,
        out_shape=jax.ShapeDtypeStruct((LIMBS, m), jnp.int32),
        grid=(m // TILE,),
        in_specs=[
            pl.BlockSpec((LIMBS, TILE), lambda i: (0, i), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec(
            (LIMBS, TILE), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(z_t)


def pow22523(z: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Drop-in for field.pow22523 — the fused VMEM exponentiation chain."""
    shape = z.shape
    m = int(np.prod(shape[:-1])) if shape[:-1] else 1
    z2 = z.reshape(m, LIMBS)
    mp = -(-m // TILE) * TILE
    if mp != m:
        z2 = jnp.pad(z2, ((0, mp - m), (0, 0)))
    out = _pow22523_limbs_first(z2.T, interpret=interpret)
    return out.T[:m].reshape(shape)
