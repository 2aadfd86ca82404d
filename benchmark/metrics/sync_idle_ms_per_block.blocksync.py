"""sync_idle_ms_per_block.blocksync

`blocksync.idle` (the sync routine slept: fewer than two blocks in hand,
waiting on the fetch) over blocks applied.
"""

from benchmark import program_spans

LAYER = "entry"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return program_spans.ms_per_unit(r, "blocksync.idle")
