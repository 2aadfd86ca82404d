"""host_prep_ms_per_ksig.light

Host seconds inside tpu.verify.resolve (SHA-512 per signature) and
prepare_batch_eq (bigint z*k, packing), over thousands of signatures
dispatched to the device.
"""

from benchmark import readers

LAYER = "host prep and dispatch"
UNIT = "ms/ksig"
SOURCE = "program_span"
MOVES = "light_headers_per_s"


def read(r: readers.Readings):
    return readers.host_prep_ms_per_ksig(r)
