"""hub_queue_wait_ms.blocksync

VerifyHub.stats() deltas over the window: queue_wait_s / dispatched_sigs —
the mean submit-to-pack wait of a signature.
"""

from benchmark import program_spans

LAYER = "scheduler"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return program_spans.counter_ratio(r, "hub.queue_wait_s", "hub.dispatched_sigs", 1e3)
