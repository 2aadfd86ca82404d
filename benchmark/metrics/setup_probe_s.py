"""setup_probe_s

`backend.probe`'s `available_s`: seconds from the start of the program's
device probe (attach, the Pallas self-test, floor warm-up, cut-off) until routing
could use the device.
"""

from benchmark import program_spans

LAYER = "start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(r):
    return program_spans.setup_span_s("backend.probe", "available_s")
