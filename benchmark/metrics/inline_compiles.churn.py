"""inline_compiles.churn

Programs jax lowered or compiled inside the window. 0: a changing set brings no new shape
(101-103 distinct keys a dispatch stay in gb127).
"""

from benchmark import readers

LAYER = "host prep and dispatch"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return readers.inline_compiles(r)
