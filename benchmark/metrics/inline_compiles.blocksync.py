"""inline_compiles.blocksync

Programs jax lowered or compiled inside the window (jax.monitoring events).
Steady state is 0: every shape was warmed in set-up.
"""

from benchmark import readers

LAYER = "host prep and dispatch"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r: readers.Readings):
    return readers.inline_compiles(r)
