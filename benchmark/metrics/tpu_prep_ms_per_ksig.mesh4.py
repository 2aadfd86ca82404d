"""tpu_prep_ms_per_ksig.mesh4

`tpu.prep` (prepare_batch_eq: bigint z*k, grouping, packing) over thousands
of signatures prepared. The host's share is the same whatever the mesh.
"""

from benchmark import mesh_readers

LAYER = "host prep and dispatch"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r):
    return mesh_readers.ms_per_ksig(r, "n", "tpu.prep")
