"""Device milliseconds per step under the program's ``phase_experts`` scope:
the routed experts held: rows gathered by expert, the grouped products, SwiGLU, the gate-weighted combine, forward and backward (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "experts")
