"""verify_self_ms_per_block.blocksync

`blocksync.verify` (the await of to_thread(verify_commit_range)) minus what
`validation.*`, `hub.submit` and `hub.dispatch` under it cover: thread and
GIL hand-off, waiting for a dispatch to start. Over blocks applied.
"""

from benchmark import program_spans

LAYER = "entry"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return program_spans.self_ms_per_unit(
        r, "blocksync.verify", "validation.collect", "validation.locate", "hub.submit",
        "hub.dispatch")
