"""tpu_prep_ms_per_ksig.light

`tpu.prep` (prepare_batch_eq: bigint z*k, grouping, packing) over thousands
of signatures prepared.
"""

from benchmark import program_spans

LAYER = "host prep and dispatch"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return program_spans.ms_per_ksig(r, "n", "tpu.prep")
