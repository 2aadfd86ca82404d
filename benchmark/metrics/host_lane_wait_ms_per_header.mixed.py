"""host_lane_wait_ms_per_header.mixed

`batch.host_lane_wait` (the caller blocked on the lane's join AFTER the Edwards
partition had answered: what the overlap did not hide) over headers verified.
"""

from benchmark import mixed_readers

LAYER = "routing"
UNIT = "ms/header"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return mixed_readers.ms_per_unit(r, "batch.host_lane_wait")
