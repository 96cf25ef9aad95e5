"""Device milliseconds per step under the program's ``phase_attn`` scope:
the attention block: norms, the five MLA projections, rotary, the attention kernels, forward and backward (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "attn")
