"""Device milliseconds per step behind the chunked delta rule, the program's
scope ``phase_kda_out`` (``models/moelm.py`` ``_kda`` and ``_mix``): the way
back from head-major, the low-rank gate, the gated head norm, ``W_o``, the
decay's mean and the residual sum, forward, rematerialised forward and
backward (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "kda", "out")
