"""Device milliseconds per step under the program's scope ``phase_gdn_out``:
what stands behind the Gated DeltaNet recurrence, the way back from
head-major, the gate ``silu(W_g h)``, the gated head norm, ``W_o``, the
decay's mean, the output's norm under OLMo 2's residual order and the
residual sum, forward and backward (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "gdn", "out")
