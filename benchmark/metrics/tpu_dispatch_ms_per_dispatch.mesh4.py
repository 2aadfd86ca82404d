"""tpu_dispatch_ms_per_dispatch.mesh4

`tpu.dispatch` (the jitted call: transfer of a chunk's operands to four
chips and the enqueue; it returns before the device is done) per dispatch.
"""

from benchmark import mesh_readers

LAYER = "host prep and dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return mesh_readers.ms_per_span(r, "tpu.dispatch")
