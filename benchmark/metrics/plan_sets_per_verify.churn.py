"""plan_sets_per_verify.churn

The most distinct validator sets ONE verify call carried (`sets` on `blocksync.plan`):
the planner holds at most the two the state knows, so 1 or 2.
"""

from benchmark import churn_readers

LAYER = "entry"
UNIT = "sets/call"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return churn_readers.plan_sets_per_verify(r)
