"""device_wait_ms_per_dispatch.mixed

`tpu.collect` (the host blocked on a chunk's eq_ok and bitmap) per dispatch
collected.
"""

from benchmark import mixed_readers

LAYER = "host prep and dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return mixed_readers.ms_per_span(r, "tpu.collect")
