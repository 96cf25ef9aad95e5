"""Device milliseconds per step under the program's scope ``phase_attn_core``
(``models/moelm.py`` ``_attention``, around the ``flash_attention`` call
alone): the ``flash_attention_fwd/dq/dkv`` kernels and what the call does
around them in XLA (``delta``, the operand casts), forward, rematerialised
forward and backward (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "attn", "core")
