"""valset_update_ms_per_block.churn

`state.valset_update` (the executor's update_with_change_set on a non-empty update, once a
change) over blocks applied.
"""

from benchmark import churn_readers

LAYER = "apply and stores"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return churn_readers.ms_per_unit(r, "state.valset_update")
