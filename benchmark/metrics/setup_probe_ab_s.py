"""setup_probe_ab_s

`backend.pallas_ab`: seconds the start-up A/B of the kernel formulations
took (field multiply, pow22523, in-block scan).
"""

from benchmark import program_spans

LAYER = "start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(r):
    return program_spans.setup_span_s("backend.pallas_ab")
