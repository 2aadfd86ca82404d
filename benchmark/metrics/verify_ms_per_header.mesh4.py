"""verify_ms_per_header.mesh4

`light.verify` (the light client's own span around verify_commit_range: collect,
resolve, prep, the four-way dispatch and the wait for the chunks, the verdict) MINUS the `tpu.fill` spans that ran inside it, over headers
verified, on a host whose verifier spans four chips. Since PR 34 the dispatch loop runs the caller's owed work there (the
trusted store's encode of the window's light blocks, `fill_ms_per_header.mesh4`)
between the last chunk's dispatch and the first collect: verification's own
time is what is left. Until PR 38 this read the harness's wrapper on
`verify_commit_range`, encode included.
"""

from benchmark import program_spans

LAYER = "entry"
UNIT = "ms/header"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return program_spans.ms_per_unit_less(r, "light.verify", "tpu.fill")
