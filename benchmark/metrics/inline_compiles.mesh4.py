"""inline_compiles.mesh4

Programs jax lowered or compiled inside the window (jax.monitoring events).
Steady state is 0: every shape the plan selects was warmed in set-up.
"""

from benchmark import readers

LAYER = "host prep and dispatch"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "light_headers_per_s"


def read(r):
    return readers.inline_compiles(r)
