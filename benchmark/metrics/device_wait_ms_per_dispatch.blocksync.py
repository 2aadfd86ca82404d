"""device_wait_ms_per_dispatch.blocksync

`tpu.collect` (the host blocked on a chunk's eq_ok and bitmap) per dispatch
collected.
"""

from benchmark import program_spans

LAYER = "host prep and dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return program_spans.ms_per_span(r, "tpu.collect")
