"""Batched twisted-Edwards curve ops for ed25519 on TPU.

Points are extended coordinates (X:Y:Z:T) with each coordinate a limb vector
(see field.py), batched over leading axes. The addition formula is the
complete (unified) one for a=-1 twisted Edwards curves — valid for doubling,
the identity, and order-2 points alike, so the scalar-multiplication scan has
no branches.

Decompression implements ZIP-215 acceptance (reference semantics,
crypto/ed25519/ed25519.go:26-28 via curve25519-voi): non-canonical y
encodings fold mod p; x is recovered with the (p+3)/8 candidate-root method;
encodings with no square root, or x=0 with the sign bit set, are invalid.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import numpy as np
import jax.numpy as jnp

from . import field as F


class Point(NamedTuple):
    x: jnp.ndarray
    y: jnp.ndarray
    z: jnp.ndarray
    t: jnp.ndarray


def identity(batch_shape=()) -> Point:
    z = jnp.zeros(batch_shape + (F.LIMBS,), jnp.int32)
    one = jnp.broadcast_to(jnp.asarray(F.ONE), batch_shape + (F.LIMBS,))
    return Point(z, one, one, z)


# affine base point B = (x, 4/5)
_BY_INT = (4 * pow(5, F.P_INT - 2, F.P_INT)) % F.P_INT


def _recover_x_int(y: int, sign: int) -> int:
    p, d = F.P_INT, F.D_INT
    u, v = (y * y - 1) % p, (d * y * y + 1) % p
    x = u * pow(v, 3, p) % p * pow(u * pow(v, 7, p) % p, (p - 5) // 8, p) % p
    if v * x * x % p == (-u) % p:
        x = x * F.SQRT_M1_INT % p
    if x & 1 != sign:
        x = p - x
    return x


_BX_INT = _recover_x_int(_BY_INT, 0)
BASE_X = F.int_to_limbs(_BX_INT)
BASE_Y = F.int_to_limbs(_BY_INT)
BASE_T = F.int_to_limbs(_BX_INT * _BY_INT % F.P_INT)


def base_point(batch_shape=()) -> Point:
    bc = lambda a: jnp.broadcast_to(jnp.asarray(a), batch_shape + (F.LIMBS,))
    return Point(bc(BASE_X), bc(BASE_Y), bc(F.ONE), bc(BASE_T))


class CachedPoint(NamedTuple):
    """Precomputed ('Niels') form of an addition operand: (Y-X, Y+X, 2dT,
    2Z). Table points are converted once before the 256-step scan, saving a
    field multiply and two carries per addition."""

    ymx: jnp.ndarray
    ypx: jnp.ndarray
    t2d: jnp.ndarray
    z2: jnp.ndarray


def to_cached(p: Point) -> CachedPoint:
    (t2d,) = F.mul_many([(p.t, jnp.asarray(F.D2_LIMBS))])
    return CachedPoint(F.sub(p.y, p.x), F.add_c(p.y, p.x), t2d, F.mul_scalar(p.z, 2))


def cached_identity(batch_shape=()) -> CachedPoint:
    one = jnp.broadcast_to(jnp.asarray(F.ONE), batch_shape + (F.LIMBS,))
    zero = jnp.zeros(batch_shape + (F.LIMBS,), jnp.int32)
    return CachedPoint(one, one, zero, F.mul_scalar(one, 2))


def point_add(p: Point, q: Point) -> Point:
    """Complete addition (RFC 8032 §5.1.4 'add-2008-hwcd-3')."""
    return add_cached(p, to_cached(q))


def add_cached(p: Point, q: CachedPoint) -> Point:
    """Complete addition of an extended point and a cached point — two
    stacked convolutions total."""
    a, b, c, d = F.mul_many(
        [
            (F.sub(p.y, p.x), q.ymx),
            (F.add_c(p.y, p.x), q.ypx),
            (p.t, q.t2d),
            (p.z, q.z2),
        ]
    )
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add_c(d, c)
    h = F.add_c(b, a)
    x, y, z, t = F.mul_many([(e, f), (g, h), (f, g), (e, h)])
    return Point(x, y, z, t)


def point_double(p: Point) -> Point:
    """Doubling via EFD 'dbl-2008-hwcd' with a=-1 — square-only first
    stage, no d constant, exact for every input point.

    With a=-1: D=-A, E=(X+Y)²-A-B, G=B-A, F=G-C, H=-(A+B);
    X3=E·F, Y3=G·H, Z3=F·G, T3=E·H."""
    xys = F.add_c(p.x, p.y)
    xx, yy, zz, xy2 = F.mul_many([(p.x, p.x), (p.y, p.y), (p.z, p.z), (xys, xys)])
    apb = F.add_c(xx, yy)  # A+B
    e = F.sub(xy2, apb)  # E
    g = F.sub(yy, xx)  # G
    f = F.sub(g, F.mul_scalar(zz, 2))  # F = G - 2Z²
    negh = F.neg(apb)  # H = -(A+B)
    x, y, z, t = F.mul_many([(e, f), (g, negh), (f, g), (e, negh)])
    return Point(x, y, z, t)


def point_neg(p: Point) -> Point:
    return Point(F.neg(p.x), p.y, p.z, F.neg(p.t))


def point_select(mask: jnp.ndarray, p: Point, q: Point) -> Point:
    """Elementwise select: mask True -> p, False -> q. mask shape = batch."""
    m = mask[..., None]
    return Point(
        jnp.where(m, p.x, q.x),
        jnp.where(m, p.y, q.y),
        jnp.where(m, p.z, q.z),
        jnp.where(m, p.t, q.t),
    )


def point_eq(p: Point, q: Point) -> jnp.ndarray:
    """Projective equality: X1·Z2 == X2·Z1 and Y1·Z2 == Y2·Z1."""
    x1z2, x2z1, y1z2, y2z1 = F.mul_many(
        [(p.x, q.z), (q.x, p.z), (p.y, q.z), (q.y, p.z)]
    )
    return F.eq(x1z2, x2z1) & F.eq(y1z2, y2z1)


def is_identity(p: Point) -> jnp.ndarray:
    return F.is_zero(p.x) & F.eq(p.y, p.z)


def mul_by_cofactor(p: Point) -> Point:
    return point_double(point_double(point_double(p)))


@jax.named_scope("decompress")  # metadata for a device trace only
def decompress(y_bytes: jnp.ndarray) -> tuple[Point, jnp.ndarray]:
    """ZIP-215 point decompression.

    y_bytes: (..., 32) int32 byte limbs of the encoded point.
    Returns (Point, valid) — where invalid, the point's coordinates are
    well-defined garbage (callers must mask with `valid`)."""
    sign = (y_bytes[..., 31] >> 7) & 1
    y = y_bytes.at[..., 31].set(y_bytes[..., 31] & 0x7F)
    # fold non-canonical encodings: y < 2^255 < 2p, so subtract p at most once
    w = F.canonical(y)  # here y < p+? — canonical() handles the conditional subtract
    y = w

    y2 = F.square(y)
    u = F.sub(y2, jnp.asarray(F.ONE))
    v = F.add_c(F.mul(y2, jnp.asarray(F.D_LIMBS)), jnp.asarray(F.ONE))
    # candidate root of u/v: x = u·v^3·(u·v^7)^((p-5)/8)
    v3 = F.mul(F.square(v), v)
    v7 = F.mul(F.square(v3), v)
    x = F.mul(F.mul(u, v3), F.pow22523(F.mul(u, v7)))
    vx2 = F.mul(v, F.square(x))
    root_ok = F.eq(vx2, u)
    flip_ok = F.eq(vx2, F.neg(u))
    x = jnp.where(
        flip_ok[..., None] & ~root_ok[..., None],
        F.mul(x, jnp.asarray(F.SQRT_M1_LIMBS)),
        x,
    )
    valid = root_ok | flip_ok

    x_canon = F.canonical(x)
    x_is_zero = jnp.all(x_canon == 0, axis=-1)
    # adjust sign: negate when parity differs
    need_neg = (x_canon[..., 0] & 1) != sign
    x = jnp.where(need_neg[..., None], F.neg(x), x)
    # x = 0 with sign bit set has no representative (-0)
    valid &= ~(x_is_zero & (sign == 1))

    return Point(x, y, jnp.broadcast_to(jnp.asarray(F.ONE), y.shape), F.mul(x, y)), valid


# -- radix-16 double-scalar multiplication ----------------------------------
#
# Constant 16-entry table of j·B (j = 0..15) in affine Niels form
# (y-x, y+x, 2d·x·y; z2 = 2), computed on the host with exact integers.
# In an MSB-first radix-16 Horner scan  Q ← 16·Q + X_d,  a term X added
# while digit d remains to be processed is multiplied by 16^d by the
# remaining quadruplings — so the SAME affine table serves every step;
# no per-step comb table is needed.


def _affine_niels_int(x: int, y: int) -> tuple[int, int, int]:
    p = F.P_INT
    return ((y - x) % p, (y + x) % p, 2 * F.D_INT * x % p * y % p)


def _build_base_table() -> np.ndarray:
    from ..ed25519_math import Point as IntPoint

    b = IntPoint.from_affine(_BX_INT, _BY_INT)
    rows = []
    for j in range(16):
        pj = b.scalar_mul(j)
        zinv = pow(pj.Z, F.P_INT - 2, F.P_INT)
        x, y = pj.X * zinv % F.P_INT, pj.Y * zinv % F.P_INT
        rows.append([F.int_to_limbs(v) for v in _affine_niels_int(x, y)])
    return np.stack(rows).astype(np.int32)  # (16, 3, 32)


_BASE_TABLE = _build_base_table()
_TWO = F.int_to_limbs(2)


def _mul_table(a_neg: Point) -> list[CachedPoint]:
    """[j·A' for j in 0..15] in cached form (A' = -A), 7 doubles + 7 adds."""
    batch_shape = a_neg.x.shape[:-1]
    an_cached = to_cached(a_neg)
    exts: list[Point] = [identity(batch_shape), a_neg]
    for j in range(2, 16):
        if j % 2 == 0:
            exts.append(point_double(exts[j // 2]))
        else:
            exts.append(add_cached(exts[j - 1], an_cached))
    cached = [cached_identity(batch_shape), an_cached]
    cached += [to_cached(p) for p in exts[2:]]
    return cached


def scalar_mul_double(
    s_digits: jnp.ndarray, h_digits: jnp.ndarray, a_neg: Point
) -> Point:
    """Joint double-scalar multiplication: returns s·B + h·(-A), batched.

    s_digits, h_digits: (..., 64) int32 in [0, 16), little-endian radix-16
    digits. One 64-iteration lax.scan (MSB digit first), each step doing
    four doublings and two cached additions with branchless 16-way table
    lookups: the constant affine j·B table and a per-batch j·(-A) table
    built with 7 doubles + 7 adds before the scan. vs the bit-serial
    ladder (256 doubles + 256 adds) this does 256 doubles + 128 adds and
    a scan a quarter as long.
    """
    import jax

    batch_shape = s_digits.shape[:-1]
    idp = identity(batch_shape)

    ta = _mul_table(a_neg)
    # stack the 16 entries on a leading axis per component: (16, ..., 32)
    ta_arrs = tuple(
        jnp.stack([getattr(c, comp) for c in ta])
        for comp in ("ymx", "ypx", "t2d", "z2")
    )
    tb = jnp.asarray(_BASE_TABLE)  # (16, 3, 32) constant
    two = jnp.broadcast_to(jnp.asarray(_TWO), batch_shape + (F.LIMBS,))

    def gather_ta(d: jnp.ndarray) -> CachedPoint:
        idx = jnp.broadcast_to(d[None, ..., None], (1,) + batch_shape + (F.LIMBS,))
        parts = [jnp.take_along_axis(arr, idx, axis=0)[0] for arr in ta_arrs]
        return CachedPoint(*parts)

    def gather_tb(d: jnp.ndarray) -> CachedPoint:
        e = jnp.take(tb, d, axis=0)  # (..., 3, 32)
        return CachedPoint(e[..., 0, :], e[..., 1, :], e[..., 2, :], two)

    # scan over digits MSB->LSB: move digit axis to front, reversed
    sd = jnp.moveaxis(s_digits[..., ::-1], -1, 0)  # (64, ...)
    hd = jnp.moveaxis(h_digits[..., ::-1], -1, 0)

    def step(q: Point, digits):
        s_d, h_d = digits
        q = point_double(point_double(point_double(point_double(q))))
        q = add_cached(q, gather_ta(h_d))
        q = add_cached(q, gather_tb(s_d))
        return q, None

    q, _ = jax.lax.scan(step, idp, (sd, hd))
    return q
