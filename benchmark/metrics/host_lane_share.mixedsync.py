"""host_lane_share.mixedsync

Seconds inside `batch.host_lane_wait` over seconds inside `blocksync.verify`: the share of
a run's verification in which only the host lane was running. What a secp256k1 device
kernel could take out of verify.
"""

from benchmark import mixedsync_readers

LAYER = "routing"
UNIT = "%"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.host_lane_share(r)
