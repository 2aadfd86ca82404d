"""inline_compiles.mixedsync

Programs jax lowered or compiled inside the window (jax.monitoring events). Steady state
is 0: a range's Edwards rows run the 8192/gb255 program every start warms, its ECDSA rows
no program.
"""

from benchmark import readers

LAYER = "host prep and dispatch"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return readers.inline_compiles(r)
