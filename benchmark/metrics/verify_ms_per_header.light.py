"""verify_ms_per_header.light

Wall time inside verify_commit_range as verify_adjacent_chain calls it, over
headers verified.
"""

from benchmark import readers

LAYER = "entry"
UNIT = "ms/header"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r: readers.Readings):
    return readers.verify_ms_per_unit(r)
