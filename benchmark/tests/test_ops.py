"""The operation count on hand-worked shapes."""

from benchmark import ops


def test_window_adds():
    # 64 points: 64 + 8 (two blocked prefixes of m/16) + 256 + 264 + 14
    assert ops.window_adds(64) == 606
    assert ops.window_adds(8192) == 8192 + 1024 + 534


def test_floor_shape_by_hand():
    # bucket 64, 63 key rows (+1 base point): all 48 windows run over 64 points
    adds = 48 * 606
    muls = adds * 9 + 48 * (8 * 8 + 9) + 275 * (64 + 63) + 24
    assert muls == 300245
    assert ops.field_muls(64, 63) == muls
    assert ops.needed_ops(64, 63) == muls * 2048


def test_range_shape_by_hand():
    # 8192 signature rows, 127 key rows: 16 windows over 8192, 32 over 128
    adds = 16 * 9750 + 32 * (128 + 16 + 534)
    muls = adds * 9 + 3504 + 275 * (8192 + 127) + 24
    assert ops.field_muls(8192, 127) == muls == 3890517
    # the GEMM formulation would route 32x as many MACs: not counted
    assert ops.OPS_PER_FIELD_MUL == 2048
