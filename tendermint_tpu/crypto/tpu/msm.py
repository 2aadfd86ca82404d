"""Multi-scalar multiplication (MSM) on the ed25519 curve, batched for TPU.

This is the compute core of randomized linear-combination batch
verification (the algorithm behind the reference's
crypto/ed25519/ed25519.go:225 BatchVerifier.Verify, provided there by
curve25519-voi): one MSM over all signatures shares every doubling across
the batch, where per-signature double-scalar multiplication repeats them
N times.

Algorithm: Pippenger bucket method with radix-256 windows (digits are
simply the little-endian bytes of the scalars):

  MSM = Σ_i d_i·P_i = Σ_w 256^w · W_w,   W_w = Σ_j j·B_{w,j}

with B_{w,j} the sum of points whose window-w digit is j. Per window we
sort the points by digit and take ONE inclusive associative scan of
point additions (log-depth, fully batched — the TPU-friendly formulation
of bucket accumulation; cuZK uses the same sort+scan shape on GPUs).
Writing C_j for the scan prefix at the last point with digit ≤ j, the
weighted bucket sum telescopes:

  W_w = Σ_{j≥1} j·(C_j − C_{j−1}) = 255·C_255 − Σ_{k=0}^{254} C_k

so no per-bucket pass exists at all: gather 256 boundary prefixes, one
255× small multiply, one 256-leaf tree reduction. Points with digit 0
(including padding) cancel exactly (they carry +255 from C_255 and −1
from each of C_0..C_254).

All point math uses the complete (unified) a=-1 twisted Edwards formulas
from curve.py, so identity padding, equal points, and torsion components
need no special cases anywhere in the scan.

Costs per window: ~2M point-adds for the scan (M = number of points),
~270 for the collapse; windows are vmapped so XLA sees one big batch.
The Horner fold across windows costs 8 doublings + 1 add per window on a
single point — the doublings shared by the entire batch.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import curve
from . import field as F
from .curve import Point

WINDOW_BITS = 8
N_BUCKETS = 256


def _tree_reduce_points(p: Point, axis: int) -> Point:
    """Pairwise tree reduction with point_add along `axis` (length must be
    a power of two; pad with identity)."""
    n = p.x.shape[axis]
    assert n & (n - 1) == 0, "tree reduce needs a power-of-two length"
    while n > 1:
        half = n // 2

        def split(c):
            lo = jax.lax.slice_in_dim(c, 0, half, axis=axis)
            hi = jax.lax.slice_in_dim(c, half, n, axis=axis)
            return lo, hi

        lo_hi = [split(c) for c in p]
        lo = Point(*(a for a, _ in lo_hi))
        hi = Point(*(b for _, b in lo_hi))
        p = curve.point_add(lo, hi)
        n = half
    return Point(*(jnp.squeeze(c, axis=axis) for c in p))


def _mul_255(p: Point) -> Point:
    """255·P via r ← 2r + P seven times (255 = 2^8 − 1)."""
    cached = curve.to_cached(p)
    r = p
    for _ in range(7):
        r = curve.add_cached(curve.point_double(r), cached)
    return r


_BLOCK = 16  # sequential within-block scan length (see _boundary_prefixes)


def _block_prefixes(first: Point, rest_cached: curve.CachedPoint) -> tuple[Point, Point]:
    """Within-block inclusive prefix sums: `first` the first point of each
    of g blocks (g, 32), `rest_cached` the other _BLOCK-1 of each in cached
    form (_BLOCK-1, g, 32). Returns (prefixes (g, _BLOCK, 32), prefix 0 =
    the first point; block totals (g, 32)). THE reader of the formulation
    switch in this module: verify's self-test proves it under vmap."""
    from . import pallas_field

    g = first.x.shape[0]
    # the fused kernel pads the lane axis to its TILE: only route batches
    # that FILL a tile (the R-side MSM at the 8192 bucket, g=512) — small
    # windows (the grouped A-side, g≈16) would pay ~TILE/g× padding waste
    if F._USE_PALLAS and g % pallas_field.TILE == 0:
        prefixes = Point(*pallas_field.scan_blocks(tuple(first), tuple(rest_cached)))
        return prefixes, Point(*(p[:, -1] for p in prefixes))

    def step(acc: Point, nxt: curve.CachedPoint):
        acc = curve.add_cached(acc, nxt)
        return acc, acc

    last, tail = jax.lax.scan(step, first, rest_cached)
    prefixes = Point(
        *(
            jnp.concatenate([f[:, None], jnp.moveaxis(t, 0, 1)], axis=1)
            for f, t in zip(first, tail)
        )
    )
    return prefixes, last


def _boundary_prefixes(sorted_pts: Point, counts: jnp.ndarray) -> Point:
    """C_j = prefix sum of the first counts[j] sorted points (identity
    when counts[j] == 0), for the 256 bucket boundaries.

    Only those 256 prefixes are ever consumed, so materializing all M
    global prefixes (associative_scan: ~2M point-adds) is wasteful. The
    blocked scheme does ~M + 2·M/_BLOCK + 256 adds:

      reshape to (G, _BLOCK) blocks · sequential lax.scan of length
      _BLOCK for within-block inclusive prefixes (M adds, each step a
      G-wide batched add — full VPU occupancy for G = M/16) · exclusive
      associative_scan over the G block totals (~2G adds) · ONE add per
      boundary combining block offset + within-block prefix (256 adds).

    Falls back to the associative_scan formulation when the batch
    doesn't divide by _BLOCK (e.g. the 8-device sharded kernel's small
    per-shard remainders keep shapes divisible anyway)."""
    m = sorted_pts.x.shape[0]
    ident = curve.identity((1,))
    if m % _BLOCK or m // _BLOCK < 2:
        prefix = jax.lax.associative_scan(curve.point_add, sorted_pts, axis=0)
        padded = Point(
            *(jnp.concatenate([i_c, c], axis=0) for i_c, c in zip(ident, prefix))
        )
        return Point(*(jnp.take(c, counts, axis=0) for c in padded))

    g = m // _BLOCK
    blocks = Point(*(c.reshape(g, _BLOCK, -1) for c in sorted_pts))

    # within-block inclusive prefix: scan over the _BLOCK axis, carrying
    # the running sum per block ((g, 32)-shaped adds). The scanned-in
    # operands are converted to cached (Niels) form ONCE as a batch —
    # add_cached then saves a field multiply per step vs point_add's
    # inline conversion
    first = Point(*(c[:, 0] for c in blocks))
    rest = Point(*(jnp.moveaxis(c[:, 1:], 1, 0) for c in blocks))  # (B-1, g, 32)
    rest_cached = curve.to_cached(rest)

    prefixes, last = _block_prefixes(first, rest_cached)
    within = Point(*(p.reshape(m, -1) for p in prefixes))  # (M, 32)

    # exclusive block offsets: shift the inclusive totals scan right
    totals_prefix = jax.lax.associative_scan(curve.point_add, last, axis=0)
    offsets = Point(
        *(
            jnp.concatenate([i_c, c[:-1]], axis=0)
            for i_c, c in zip(ident, totals_prefix)
        )
    )  # (g, 32): sum of all blocks before this one

    # boundary p = counts[j]-1: C_j = offsets[p // _BLOCK] + within[p]
    p = jnp.maximum(counts - 1, 0)
    w_sel = Point(*(jnp.take(c, p, axis=0) for c in within))
    o_sel = Point(*(jnp.take(c, p // _BLOCK, axis=0) for c in offsets))
    c_pts = curve.point_add(o_sel, w_sel)
    empty = counts == 0
    return curve.point_select(
        ~empty, c_pts, curve.identity((counts.shape[0],))
    )


def _window_sum(points: Point, digits: jnp.ndarray) -> Point:
    """Σ_j j·B_j for one window. points: coords (M, 32); digits: (M,)."""
    order = jnp.argsort(digits)
    sorted_digits = jnp.take(digits, order)
    sorted_pts = Point(*(jnp.take(c, order, axis=0) for c in points))

    # C_j = prefix at the last position with digit ≤ j (identity if none):
    # counts c_j = #digits ≤ j
    counts = jnp.searchsorted(sorted_digits, jnp.arange(N_BUCKETS), side="right")
    C = _boundary_prefixes(sorted_pts, counts)  # (256, 32)

    c255 = Point(*(c[N_BUCKETS - 1] for c in C))
    # Σ_{k=0..254} C_k: overwrite slot 255 with identity, tree-reduce all 256
    ident1 = curve.identity(())
    partial_ = Point(*(c.at[N_BUCKETS - 1].set(i_c) for c, i_c in zip(C, ident1)))
    sum_c = _tree_reduce_points(partial_, axis=0)

    return curve.point_add(_mul_255(c255), curve.point_neg(sum_c))


def msm(points: Point, digit_rows: jnp.ndarray) -> Point:
    """Multi-scalar multiplication Σ_i scalar_i · P_i.

    points: extended coords, each (M, 32) int32 limbs.
    digit_rows: (W, M) int32 — radix-256 little-endian digits of the
    scalars, window w of point i at digit_rows[w, i]. Returns one Point
    with scalar batch shape ().
    """
    # named scopes: metadata for a device trace, the program is the same
    with jax.named_scope("buckets"):
        window_sums = jax.vmap(_window_sum, in_axes=(None, 0))(points, digit_rows)

    # Horner over windows, most-significant first: acc ← 256·acc + W_w
    with jax.named_scope("fold"):
        rev = Point(*(c[::-1] for c in window_sums))
        top = Point(*(c[0] for c in rev))
        rest = Point(*(c[1:] for c in rev))

        def step(acc: Point, w: Point):
            for _ in range(WINDOW_BITS):
                acc = curve.point_double(acc)
            return curve.point_add(acc, w), None

        acc, _ = jax.lax.scan(step, top, rest)
    return acc


def scalars_to_digit_rows(scalars: np.ndarray, n_windows: int = 32) -> np.ndarray:
    """(M, 32) little-endian scalar bytes -> (W, M) int32 digit rows."""
    return np.ascontiguousarray(scalars[:, :n_windows].T).astype(np.int32)
