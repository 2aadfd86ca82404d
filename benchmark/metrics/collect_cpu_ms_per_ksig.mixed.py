"""collect_cpu_ms_per_ksig.mixed

On-CPU ms of `validation.collect` (basic checks, sign-bytes, tally, add) over thousands of the
signatures it collected: the WORK inside `collect_ms_per_ksig.mixed`, whose wall reading also
holds the thread's wait for the GIL.
"""

from benchmark import cpu_readers

LAYER = "entry"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return cpu_readers.cpu_ms_per_ksig(r, "sigs", "validation.collect")
