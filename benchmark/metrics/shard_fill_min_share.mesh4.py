"""shard_fill_min_share.mesh4

The window's `tpu.shard.<id>` events: real signatures of the least-loaded
chip over the most-loaded's. Shards are contiguous, so a padded tail chunk
leaves the last chips short.
"""

from benchmark import mesh_readers

LAYER = "host prep and dispatch"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "light_headers_per_s"


def read(r):
    return mesh_readers.shard_fill_min_share(r)
