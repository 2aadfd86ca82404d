"""host_lane_wait_ms_per_block.mixedsync

`batch.host_lane_wait` (the hub's runner blocked on the lane's join AFTER the Edwards
partition had answered: what the overlap did not hide) over blocks applied.
"""

from benchmark import mixedsync_readers

LAYER = "routing"
UNIT = "ms/block"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.ms_per_unit(r, "batch.host_lane_wait")
