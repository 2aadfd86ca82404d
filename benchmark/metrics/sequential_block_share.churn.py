"""sequential_block_share.churn

Blocks applied by the one-commit-at-a-time fallback (`applied` on `blocksync.sequential`)
over blocks applied. 0 on honest traffic.
"""

from benchmark import churn_readers

LAYER = "entry"
UNIT = "%"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return churn_readers.sequential_block_share(r)
