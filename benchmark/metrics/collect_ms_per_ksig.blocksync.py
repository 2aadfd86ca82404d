"""collect_ms_per_ksig.blocksync

`validation.collect` (basic checks, sign-bytes, tally, add — the whole loop
of verify_commit_range) over thousands of the signatures it collected.
"""

from benchmark import program_spans

LAYER = "entry"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return program_spans.ms_per_ksig(r, "sigs", "validation.collect")
