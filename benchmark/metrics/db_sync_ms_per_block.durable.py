"""db_sync_ms_per_block.durable

`db.sync` (the synced `commit()` alone, inside a `db.write` with sync=True: under `PRAGMA
synchronous=FULL` SQLite writes the transaction's WAL frames there, fsyncs the WAL and, every
~1,000 pages, checkpoints) over blocks applied: what a height's three synced commits cost. The
suspect where the cell's runs spread.
"""

from benchmark import durable_readers

LAYER = "apply and stores"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return durable_readers.sync_ms_per_unit(r)
