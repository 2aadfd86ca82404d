"""device_idle_share.mixedsync

1 - union of the device's operation intervals over the traced stretch.
"""

from benchmark import readers

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return readers.device_idle_share(r)
