"""fill_ms_per_header.mesh4

`tpu.fill` [chunks, ran] over headers verified, on a host whose verifier spans four chips: the calling thread's
`verify.while_in_flight` registration, run by the dispatch loop once every chunk
of a window is dispatched and before the first `tpu.collect` — in the light
client the trusted store's encode of the window's light blocks
(`light.encode_ahead` under it). Host time that hides under the kernels as far
as they last; `verify_ms_per_header.mesh4` is `light.verify` without it. None where
the program registers nothing (no `tpu.fill` row: the parent of PR 34, the host
route).
"""

from benchmark import program_spans

LAYER = "host prep and dispatch"
UNIT = "ms/header"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return program_spans.ms_per_unit(r, "tpu.fill")
