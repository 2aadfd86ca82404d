"""host_lane_ms_per_ksig.mixedsync

`batch.host_lane` (a dispatch's rows of key types with no device kernel, verified on the
host pool: from the lane's start, BEFORE the Edwards partition is routed, to the end of its
join) over thousands of rows. Recorded under `hub.dispatch`, on the hub's runner.
"""

from benchmark import mixedsync_readers

LAYER = "routing"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.ms_per_ksig(r, "n", "batch.host_lane")
