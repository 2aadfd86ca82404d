"""hub_submit_ms_per_ksig.mixedsync

`hub.submit` (a group's one pass: a SHA-256 a row for the cache keys, the verdict LRU and
coalescing per row under one acquisition of the lock) over thousands of signatures submitted.
"""

from benchmark import mixedsync_readers

LAYER = "scheduler"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.ms_per_ksig(r, "n", "hub.submit")
