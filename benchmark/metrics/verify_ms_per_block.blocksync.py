"""verify_ms_per_block.blocksync

Wall time inside verify_commit_range as the reactor calls it (the wrapper the
harness puts on blocksync.reactor.verify_commit_range), over blocks applied.
"""

from benchmark import readers

LAYER = "entry"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r: readers.Readings):
    return readers.verify_ms_per_unit(r)
