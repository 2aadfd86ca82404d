"""tpu_resolve_ms_per_ksig.mixedsync

`tpu.resolve` (one SHA-512 per Edwards signature, chunk by chunk) over thousands of
signatures resolved, with the host lane running beside it.
"""

from benchmark import mixedsync_readers

LAYER = "host prep and dispatch"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.ms_per_ksig(r, "n", "tpu.resolve")
