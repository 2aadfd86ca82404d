"""kernel_msm_share.light

Device time under the named scopes `msm_keys` + `msm_sigs`, over
`jit__kernel_eq`'s, in the traced stretch (union of the operations'
intervals).
"""

from benchmark import program_spans

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "light_headers_per_s"


def read(r):
    return program_spans.kernel_phase_share(program_spans.run_xplane(r), "msm_keys", "msm_sigs")
