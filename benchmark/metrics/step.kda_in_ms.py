"""Device milliseconds per step in front of the chunked delta rule, the
program's scope ``phase_kda_in`` (``models/moelm.py`` ``_kda``): the norm, the
q, k and v projections with their causal convolutions, SiLU and the l2 norm,
the rate's two products and ``g``, beta and the head-major transposes,
forward, rematerialised forward and backward (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "kda", "in")
