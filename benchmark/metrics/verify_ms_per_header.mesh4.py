"""verify_ms_per_header.mesh4

Wall time inside verify_commit_range as verify_adjacent_chain calls it, over
headers verified, on a host whose verifier spans four chips.
"""

from benchmark import readers

LAYER = "entry"
UNIT = "ms/header"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return readers.verify_ms_per_unit(r)
