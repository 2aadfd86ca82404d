"""edwards_row_share.mixed

`validation.collect` [sigs, edwards]: rows whose key rides the Edwards batch over
all rows collected. The rest took the host lane.
"""

from benchmark import mixed_readers

LAYER = "entry"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "light_headers_per_s"


def read(r):
    return mixed_readers.edwards_row_share(r)
