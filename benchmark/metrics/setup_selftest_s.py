"""setup_selftest_s

`backend.pallas_ab` [chosen, programs, stages]: seconds of the start-up SELF-TEST
of the Pallas kernels against known answers the host computes over Python
integers (the multiply, pow22523, the in-block scan: three device programs).
The span keeps its old name; there has been no A/B of formulations since PR 29
and no XLA twin since PR 36, so the metric (until PR 38 `setup_probe_ab_s`)
says what it times.
"""

from benchmark import program_spans

LAYER = "start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(r):
    return program_spans.setup_span_s("backend.pallas_ab")
