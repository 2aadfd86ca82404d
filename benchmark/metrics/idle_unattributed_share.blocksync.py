"""idle_unattributed_share.blocksync

Share of the device's idle time in the traced stretch in which the host was
inside NO `tm.*` span of the program.
"""

from benchmark import program_spans

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return program_spans.idle_unattributed_share(program_spans.run_xplane(r))
