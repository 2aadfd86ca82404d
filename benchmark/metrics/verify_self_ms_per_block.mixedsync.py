"""verify_self_ms_per_block.mixedsync

`blocksync.verify` minus what `validation.*`, `hub.submit` and `hub.dispatch` under it cover,
over blocks applied: `.blocksync`'s twin.
"""

from benchmark import mixedsync_readers

LAYER = "entry"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.self_ms_per_unit(
        r, "blocksync.verify", "validation.collect", "validation.locate", "hub.submit",
        "hub.dispatch")
