"""host_cores_busy.mixedsync

Process CPU ms over wall ms of the window's root spans (`blocksync.range`), each clipped to
the window by share: how many of the host's cores the process really used — the event loop's
one, plus the lane's pool threads while a range's ECDSA rows are verified. A reading, not a
target.
"""

from benchmark import mixedsync_readers

LAYER = "host threads"
UNIT = "cores"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return mixedsync_readers.cores_busy(r)
