"""tpu_dispatch_cpu_ms_per_dispatch.mesh4

On-CPU ms of a `tpu.dispatch` that went to four chips: the Python-and-runtime part of
`tpu_dispatch_ms_per_dispatch.mesh4`; the rest of that wall reading is the thread blocked on the
four-way transfer.
"""

from benchmark import cpu_readers

LAYER = "host prep and dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return cpu_readers.dispatch_cpu_ms_per_dispatch(r)
