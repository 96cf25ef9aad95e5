"""Device milliseconds per step under the program's ``phase_fused`` scope:
the grouped SGNS kernel (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "fused")
