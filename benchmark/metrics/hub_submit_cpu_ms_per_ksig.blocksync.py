"""hub_submit_cpu_ms_per_ksig.blocksync

On-CPU ms of `hub.submit` (`verify_many`'s `_submit_group`: the cache keys, then one pass under
the hub's lock) over thousands of signatures
submitted: the WORK inside `hub_submit_ms_per_ksig.blocksync`, whose wall reading also holds the
worker thread's wait for the GIL and for the hub's lock.
"""

from benchmark import cpu_readers

LAYER = "scheduler"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return cpu_readers.cpu_ms_per_ksig(r, "n", "hub.submit")
