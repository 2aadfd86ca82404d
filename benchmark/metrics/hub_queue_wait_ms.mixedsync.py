"""hub_queue_wait_ms.mixedsync

VerifyHub.stats() deltas over the window: queue_wait_s / dispatched_sigs — the mean
submit-to-pack wait of a signature.
"""

from benchmark import mixedsync_readers

LAYER = "scheduler"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.counter_ratio(r, "hub.queue_wait_s", "hub.dispatched_sigs", 1e3)
