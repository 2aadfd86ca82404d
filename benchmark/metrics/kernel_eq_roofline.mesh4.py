"""kernel_eq_roofline.mesh4

Operations the traced sharded dispatches need (benchmark/ops.py, the whole
dispatch: what every chip repeats is overhead, not need) over the sharded
programs' time over FOUR chips' bf16 peak (benchmark/peaks.json).
"""

from benchmark import mesh_readers

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "light_headers_per_s"


def read(r):
    return mesh_readers.kernel_roofline_share(r)
