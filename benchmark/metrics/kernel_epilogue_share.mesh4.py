"""kernel_epilogue_share.mesh4

Device time under the named scope `epilogue` (key decompression, the
grouped A-side MSM, the identity check: what every chip repeats), over
jit__kernel_eq_sharded's, first chip.
"""

from benchmark import mesh_readers

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "light_headers_per_s"


def read(r):
    return mesh_readers.phase_share(r, "epilogue")
