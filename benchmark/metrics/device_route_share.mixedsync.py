"""device_route_share.mixedsync

backend_telemetry.ROUTES deltas: signatures on route `tpu` over all routed. The secp256k1
rows (route `host-ecdsa`) are host work by design: about half here.
"""

from benchmark import readers

LAYER = "routing"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return readers.device_route_share(r)
