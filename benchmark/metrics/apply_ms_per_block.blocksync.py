"""apply_ms_per_block.blocksync

The rest of the window (part-set rebuild, hashing, ApplyBlock, the stores,
fetching), over blocks applied.
"""

from benchmark import readers

LAYER = "entry"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r: readers.Readings):
    return readers.rest_ms_per_unit(r)
