"""collect_cpu_ms_per_ksig.mixedsync

On-CPU ms of `validation.collect` over thousands of the signatures it collected: the WORK
inside `collect_ms_per_ksig.mixedsync`, whose wall reading also holds the thread's wait for
the GIL.
"""

from benchmark import mixedsync_readers

LAYER = "entry"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.on_cpu_ms_per_ksig(r, "sigs", "validation.collect")
