"""hub_sigs_per_dispatch.blocksync

VerifyHub.stats() deltas over the window: dispatched_sigs / dispatches.
"""

from benchmark import readers

LAYER = "scheduler"
UNIT = "sigs/dispatch"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r: readers.Readings):
    return readers.hub_sigs_per_dispatch(r)
