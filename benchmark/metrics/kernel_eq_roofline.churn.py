"""kernel_eq_roofline.churn

Operations the traced dispatches need (benchmark/ops.py, on the shapes the stretch
dispatched: no new kernel) over the kernels' device time over the chip's bf16 peak.
"""

from benchmark import readers

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return readers.kernel_roofline_share(r)
