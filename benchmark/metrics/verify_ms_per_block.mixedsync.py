"""verify_ms_per_block.mixedsync

`blocksync.verify` (the reactor's own span around the await of a run's verification: collect,
the hub, both lanes, the verdict) over blocks applied. Read from INSIDE the program, not
from the harness's wrapper.
"""

from benchmark import mixedsync_readers

LAYER = "entry"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.ms_per_unit(r, "blocksync.verify")
