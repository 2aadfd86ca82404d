"""verify_self_ms_per_block.churn

`blocksync.verify` minus what `validation.*`, `hub.submit` and `hub.dispatch` under it cover,
over blocks applied: `.blocksync`'s twin (four verify calls a range here, one there).
"""

from benchmark import churn_readers

LAYER = "entry"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return churn_readers.self_ms_per_unit(
        r, "blocksync.verify", "validation.collect", "validation.locate", "hub.submit",
        "hub.dispatch")
