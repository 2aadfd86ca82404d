"""edwards_row_share.mixedsync

`validation.collect` [sigs, edwards]: rows whose key rides the Edwards batch over all rows
collected. The rest took the host lane.
"""

from benchmark import mixedsync_readers

LAYER = "entry"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.edwards_row_share(r)
