"""store_ms_per_block.mixedsync

`blocksync.save_block` + `state.save_responses` + `state.save` over blocks applied:
`.blocksync`'s twin.
"""

from benchmark import mixedsync_readers

LAYER = "apply and stores"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.ms_per_unit(
        r, "blocksync.save_block", "state.save_responses", "state.save")
