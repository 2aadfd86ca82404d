"""host_cores_busy.mixed

Process CPU ms over wall ms of the window's root spans (`light.window`), each clipped to the
window by share: how many of the host's cores the process really used. 1.0 is a process that is
GIL-bound however many threads it has. A reading, not a target.
"""

from benchmark import cpu_readers

LAYER = "host threads"
UNIT = "cores"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return cpu_readers.cores_busy(r)
