"""device_route_share.churn

backend_telemetry.ROUTES deltas: signatures on route `tpu` over all routed. The remainder is
the plans' tails under the process's measured cut-off: host work by design.
"""

from benchmark import readers

LAYER = "routing"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return readers.device_route_share(r)
