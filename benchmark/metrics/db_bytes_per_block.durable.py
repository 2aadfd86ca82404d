"""db_bytes_per_block.durable

Key and value bytes of the rows the node's three files were handed over the window
(`store.db.COUNTERS[*]["bytes_written"]`: block, state and app) per block applied. What
`save_block`, `StateStore.save` and the app's commit write, as bytes on a disk.
"""

from benchmark import durable_readers

LAYER = "apply and stores"
UNIT = "bytes/block"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return durable_readers.counter_per_unit(r, "bytes_written")
