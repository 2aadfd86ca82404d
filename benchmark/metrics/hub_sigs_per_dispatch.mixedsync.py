"""hub_sigs_per_dispatch.mixedsync

VerifyHub.stats() deltas over the window: dispatched_sigs / dispatches. A range is one group
of every key type and one dispatch: about 6,400.
"""

from benchmark import readers

LAYER = "scheduler"
UNIT = "sigs/dispatch"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return readers.hub_sigs_per_dispatch(r)
