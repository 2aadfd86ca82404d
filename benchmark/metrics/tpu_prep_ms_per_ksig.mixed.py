"""tpu_prep_ms_per_ksig.mixed

`tpu.prep` (prepare_batch_eq: bigint z*k, grouping, packing) over thousands of
signatures prepared, with the host lane running beside it.
"""

from benchmark import mixed_readers

LAYER = "host prep and dispatch"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return mixed_readers.ms_per_ksig(r, "n", "tpu.prep")
