"""Device milliseconds per step under the program's ``phase_dense`` scope:
the forward, backward and dense update of a CTR model (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "dense")
