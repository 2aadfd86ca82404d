"""device_wait_ms_per_dispatch.churn

`tpu.collect` (the host blocked on a chunk's result) per dispatch collected:
`.blocksync`'s twin.
"""

from benchmark import churn_readers

LAYER = "host prep and dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return churn_readers.ms_per_span(r, "tpu.collect")
