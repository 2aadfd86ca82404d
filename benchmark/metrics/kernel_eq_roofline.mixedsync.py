"""kernel_eq_roofline.mixedsync

Operations the traced dispatches need (benchmark/ops.py needed_ops, on the shapes the
stretch dispatched: 8192 rows, gb255 — no new kernel, no new operation count) over the
kernels' device time over the chip's bf16 peak (benchmark/peaks.json). Compute-bound.
"""

from benchmark import readers

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return readers.kernel_roofline_share(r)
