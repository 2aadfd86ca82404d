"""device_wait_ms_per_dispatch.mixedsync

`tpu.collect` (the hub's runner blocked on a chunk's eq_ok and bitmap) per dispatch collected.
"""

from benchmark import mixedsync_readers

LAYER = "host prep and dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.ms_per_span(r, "tpu.collect")
