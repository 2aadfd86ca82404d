"""tpu_resolve_ms_per_ksig.light

`tpu.resolve` (SHA-512 per signature as the device verifier adds them) over
thousands of signatures resolved.
"""

from benchmark import program_spans

LAYER = "host prep and dispatch"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return program_spans.ms_per_ksig(r, "n", "tpu.resolve")
