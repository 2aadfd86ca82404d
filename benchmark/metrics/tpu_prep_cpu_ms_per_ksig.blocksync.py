"""tpu_prep_cpu_ms_per_ksig.blocksync

On-CPU ms of `tpu.prep` (prepare_batch_eq: bigint z*k, grouping, packing) over thousands of
signatures prepared, on the hub's runner thread, beside apply on the event loop: the WORK inside
`tpu_prep_ms_per_ksig.blocksync`, whose wall reading also holds the thread's wait for the GIL.
"""

from benchmark import cpu_readers

LAYER = "host prep and dispatch"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return cpu_readers.cpu_ms_per_ksig(r, "n", "tpu.prep")
