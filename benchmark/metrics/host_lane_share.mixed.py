"""host_lane_share.mixed

Seconds inside `batch.host_lane_wait` over seconds inside `light.verify`: the
share of verification in which only the host lane was running. What a secp256k1
device kernel could take out of a header.
"""

from benchmark import mixed_readers

LAYER = "routing"
UNIT = "%"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return mixed_readers.host_lane_share(r)
