"""hub_submit_ms_per_ksig.churn

`hub.submit` over thousands of signatures submitted: `.blocksync`'s twin.
"""

from benchmark import churn_readers

LAYER = "scheduler"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return churn_readers.ms_per_ksig(r, "n", "hub.submit")
