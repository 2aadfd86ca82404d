"""collect_ms_per_ksig.mixedsync

`validation.collect` (basic checks, sign-bytes, tally, add: one walk whatever the key types)
over thousands of the signatures it collected.
"""

from benchmark import mixedsync_readers

LAYER = "entry"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.ms_per_ksig(r, "sigs", "validation.collect")
