"""device_idle_share.light

1 - union of the device's operation intervals over the traced stretch.
"""

from benchmark import readers

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "light_headers_per_s"


def read(r: readers.Readings):
    return readers.device_idle_share(r)
