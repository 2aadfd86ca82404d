"""kernel_ms_per_ksig.mesh4

Device time of the sharded programs (jit__kernel_eq_sharded,
jit__kernel_sharded) in the traced stretch, mean over the chips, over
thousands of signatures they were dispatched.
"""

from benchmark import mesh_readers

LAYER = "kernels"
UNIT = "ms/ksig"
SOURCE = "device_trace"
MOVES = "light_headers_per_s"


def read(r):
    return mesh_readers.kernel_ms_per_ksig(r)
