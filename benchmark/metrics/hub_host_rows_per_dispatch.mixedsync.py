"""hub_host_rows_per_dispatch.mixedsync

VerifyHub.stats() deltas over the window: scheme_host_sigs / dispatches — rows a dispatch
handed to the verifier's host lane. None on a hub without the counter.
"""

from benchmark import mixedsync_readers

LAYER = "scheduler"
UNIT = "sigs/dispatch"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.counter_ratio(r, "hub.scheme_host_sigs", "hub.dispatches")
