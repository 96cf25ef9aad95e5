"""Device milliseconds per step behind the attention kernels, the program's
scope ``phase_attn_out`` (``models/moelm.py`` ``_attention`` and ``_mix``):
the way back from head-major, the sigmoid gate where the layer has one,
``W_o`` and the residual sum, forward, rematerialised forward and backward
(``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "attn", "out")
