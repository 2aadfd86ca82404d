"""tpu_prep_cpu_ms_per_ksig.mixed

On-CPU ms of `tpu.prep` (prepare_batch_eq: bigint z*k, grouping, packing) over thousands of
signatures prepared, with the host lane's thirteen threads running beside it: the WORK inside
`tpu_prep_ms_per_ksig.mixed`, whose wall reading also holds the thread's wait for the GIL.
"""

from benchmark import cpu_readers

LAYER = "host prep and dispatch"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return cpu_readers.cpu_ms_per_ksig(r, "n", "tpu.prep")
