"""inline_compiles.mixed

Programs jax lowered or compiled inside the window (jax.monitoring events).
Steady state is 0: the warm-up session compiled the 8192/gb63 shape.
"""

from benchmark import readers

LAYER = "host prep and dispatch"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "light_headers_per_s"


def read(r):
    return readers.inline_compiles(r)
