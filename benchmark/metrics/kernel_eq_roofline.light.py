"""kernel_eq_roofline.light

Operations the traced dispatches need (benchmark/ops.py) over the kernels'
device time over the chip's bf16 peak (benchmark/peaks.json). Compute-bound.
"""

from benchmark import readers

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "light_headers_per_s"


def read(r: readers.Readings):
    return readers.kernel_roofline_share(r)
