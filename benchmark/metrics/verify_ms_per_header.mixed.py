"""verify_ms_per_header.mixed

`light.verify` (the light client's own span around verify_commit_range: collect,
both lanes, the verdict) over headers verified.
"""

from benchmark import mixed_readers

LAYER = "entry"
UNIT = "ms/header"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return mixed_readers.ms_per_unit(r, "light.verify")
