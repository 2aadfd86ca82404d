"""collect_ms_per_ksig.mixed

`validation.collect` (basic checks, sign-bytes, tally, add: one walk whatever the
key types) over thousands of the signatures it collected.
"""

from benchmark import mixed_readers

LAYER = "entry"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return mixed_readers.ms_per_ksig(r, "sigs", "validation.collect")
