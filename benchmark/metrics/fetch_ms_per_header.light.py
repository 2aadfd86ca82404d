"""fetch_ms_per_header.light

`light.fetch` (the gather of one window's light blocks from the primary)
over headers verified.
"""

from benchmark import program_spans

LAYER = "entry"
UNIT = "ms/header"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return program_spans.ms_per_unit(r, "light.fetch")
