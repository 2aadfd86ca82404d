"""store_ms_per_block.blocksync

`blocksync.save_block` + `state.save_responses` + `state.save` over blocks
applied.
"""

from benchmark import program_spans

LAYER = "apply and stores"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return program_spans.ms_per_unit(
        r, "blocksync.save_block", "state.save_responses", "state.save")
