"""Operations the batch-equation verification NEEDS for one dispatch,
counted from its shape: field multiplications x 2 x 32 x 32 (one 32-limb
schoolbook product is 32 x 32 multiply-adds).

Not what the program routes through the MXU: the GEMM formulation spends
32 x 32 x 32 MACs on a field multiply, thirty-two times what the product
needs, and a roofline share counted from that would flatter it.

A dispatch of `bucket` signature rows and `groups` key rows (the padded
shapes: the device computes every row) does
  - decompression of bucket + groups points: the (p-5)/8 power chain, 254
    squarings and 11 multiplies, and about 10 more around it (u, v, v^3,
    v^7, the candidate root, its check and the sign fix);
  - the R-side MSM, 16 radix-256 windows over `bucket` points, and the
    A-side MSM, 32 windows over groups + 1 points (the base point rides
    along). A window sorts its points into 256 buckets and adds them up
    with a blocked prefix scan (m + 2m/16 + 256 point additions), collapses
    the 256 bucket sums (264) and multiplies by the window's weight (14);
  - the Horner fold of the 48 window sums: 8 doublings and one addition a
    window;
  - the final cofactor multiplication (3 doublings).
A point addition is 9 field multiplications, a doubling 8 (extended
twisted Edwards, as crypto/tpu/curve.py computes them).
"""

from __future__ import annotations

MULS_PER_ADD = 9
MULS_PER_DOUBLE = 8
DECOMPRESS_MULS = 254 + 11 + 10
R_WINDOWS = 16
A_WINDOWS = 32
OPS_PER_FIELD_MUL = 2 * 32 * 32


def window_adds(m: int) -> int:
    """Point additions of one MSM window over m points."""
    return m + 2 * m // 16 + 256 + 264 + 14


def field_muls(bucket: int, groups: int) -> int:
    """Field multiplications of one `_kernel_eq` dispatch."""
    adds = R_WINDOWS * window_adds(bucket) + A_WINDOWS * window_adds(groups + 1)
    horner = (R_WINDOWS + A_WINDOWS) * (8 * MULS_PER_DOUBLE + MULS_PER_ADD)
    cofactor = 3 * MULS_PER_DOUBLE
    return adds * MULS_PER_ADD + horner + DECOMPRESS_MULS * (bucket + groups) + cofactor


def needed_ops(bucket: int, groups: int) -> int:
    """Multiply-add operations (counted as 2 each) one dispatch needs."""
    return field_muls(bucket, groups) * OPS_PER_FIELD_MUL
