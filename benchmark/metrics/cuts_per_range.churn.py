"""cuts_per_range.churn

`blocksync.plan` spans whose `cut` is `third_set` (the plan ended at a header naming a set
the state does not know yet) over `blocksync.range` spans. 0 on a static set.
"""

from benchmark import churn_readers

LAYER = "entry"
UNIT = "cuts/range"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return churn_readers.cuts_per_range(r)
