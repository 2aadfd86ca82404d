"""kernel_ms_per_ksig.mixedsync

Device time of the jit__kernel_eq / jit__kernel programs in the traced stretch, over
thousands of (Edwards) signatures dispatched in it: an 8,192-row program for about 3,200
real rows a range.
"""

from benchmark import readers

LAYER = "kernels"
UNIT = "ms/ksig"
SOURCE = "device_trace"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return readers.kernel_ms_per_ksig(r)
