"""tpu_prep_ms_per_ksig.churn

`tpu.prep` (prepare_batch_eq) over thousands of signatures prepared: `.blocksync`'s twin.
"""

from benchmark import churn_readers

LAYER = "host prep and dispatch"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return churn_readers.ms_per_ksig(r, "n", "tpu.prep")
