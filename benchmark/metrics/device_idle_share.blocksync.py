"""device_idle_share.blocksync

1 - union of the device's operation intervals over the traced stretch.
"""

from benchmark import readers

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "blocksync_blocks_per_s"


def read(r: readers.Readings):
    return readers.device_idle_share(r)
