"""device_route_share.mesh4

backend_telemetry.ROUTES deltas: signatures on route `tpu` over all routed.
Under 100% is host work by design (batches under the measured cut-off).
"""

from benchmark import readers

LAYER = "routing"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "light_headers_per_s"


def read(r):
    return readers.device_route_share(r)
