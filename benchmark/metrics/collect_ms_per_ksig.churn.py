"""collect_ms_per_ksig.churn

`validation.collect` over thousands of the signatures it collected: `.blocksync`'s twin.
"""

from benchmark import churn_readers

LAYER = "entry"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return churn_readers.ms_per_ksig(r, "sigs", "validation.collect")
