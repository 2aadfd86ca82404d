"""kernel_eq_roofline.mixed

Operations the traced dispatches need (benchmark/ops.py needed_ops, the same
function at this cell's shape, 8192 rows over ~50 keys) over the kernels' device
time over the chip's bf16 peak (benchmark/peaks.json). Compute-bound.
"""

from benchmark import readers

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "light_headers_per_s"


def read(r):
    return readers.kernel_roofline_share(r)
