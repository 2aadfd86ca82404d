"""device_idle_share.mesh4

1 - union of a device's operation intervals over the traced stretch, mean
over the four chips.
"""

from benchmark import readers

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "light_headers_per_s"


def read(r):
    return readers.device_idle_share(r)
