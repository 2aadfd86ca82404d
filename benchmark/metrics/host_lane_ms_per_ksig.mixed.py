"""host_lane_ms_per_ksig.mixed

`batch.host_lane` (the rows of key types with no device kernel, verified on the
host pool: from the lane's start to the end of its join) over thousands of rows.
"""

from benchmark import mixed_readers

LAYER = "routing"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return mixed_readers.ms_per_ksig(r, "n", "batch.host_lane")
