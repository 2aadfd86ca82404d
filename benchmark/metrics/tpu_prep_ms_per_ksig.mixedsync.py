"""tpu_prep_ms_per_ksig.mixedsync

`tpu.prep` (prepare_batch_eq: bigint z*k, grouping, packing) over thousands of signatures
prepared, with the host lane running beside it.
"""

from benchmark import mixedsync_readers

LAYER = "host prep and dispatch"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.ms_per_ksig(r, "n", "tpu.prep")
