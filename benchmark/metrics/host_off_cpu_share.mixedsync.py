"""host_off_cpu_share.mixedsync

Over the window's `validation.collect`, `hub.submit`, `tpu.resolve` and `tpu.prep` rows:
100 x (1 - on-CPU ms / wall ms). Pure-Python spans with no await inside, so what is off the
core is the thread waiting for the GIL (or a lock, or the scheduler), not work.
"""

from benchmark import mixedsync_readers

LAYER = "host threads"
UNIT = "%"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.off_cpu_share(r)
