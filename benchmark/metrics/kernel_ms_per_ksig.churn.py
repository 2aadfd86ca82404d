"""kernel_ms_per_ksig.churn

Device time of the jit__kernel_eq / jit__kernel programs in the traced stretch, over
thousands of signatures dispatched in it.
"""

from benchmark import readers

LAYER = "kernels"
UNIT = "ms/ksig"
SOURCE = "device_trace"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return readers.kernel_ms_per_ksig(r)
