"""hub_sigs_per_dispatch.churn

VerifyHub.stats() deltas over the window: dispatched_sigs / dispatches. A plan of 16 commits
leaves a tail of 80 rows, so this reads under `.blocksync`'s.
"""

from benchmark import readers

LAYER = "scheduler"
UNIT = "sigs/dispatch"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return readers.hub_sigs_per_dispatch(r)
