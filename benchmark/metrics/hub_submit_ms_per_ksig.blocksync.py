"""hub_submit_ms_per_ksig.blocksync

`hub.submit` (since PR 39 `VerifyHub.verify_many`'s `_submit_group`: a SHA-256
a row for the cache keys, then one pass over the group under one acquisition
of the hub's lock) over thousands of signatures submitted.
"""

from benchmark import program_spans

LAYER = "scheduler"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return program_spans.ms_per_ksig(r, "n", "hub.submit")
