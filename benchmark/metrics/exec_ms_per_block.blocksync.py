"""exec_ms_per_block.blocksync

`state.validate` + `state.exec` + `state.commit` (app commit and mempool
update) over blocks applied.
"""

from benchmark import program_spans

LAYER = "apply and stores"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return program_spans.ms_per_unit(r, "state.validate", "state.exec", "state.commit")
