"""hub_submit_ms_per_ksig.blocksync

`hub.submit` (VerifyHub.verify_many's submit_nowait loop and flush) over
thousands of signatures submitted.
"""

from benchmark import program_spans

LAYER = "scheduler"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return program_spans.ms_per_ksig(r, "n", "hub.submit")
