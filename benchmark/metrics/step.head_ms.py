"""Device milliseconds per step under the program's ``phase_head`` scope:
embedding rows, the final norm, the output head and the loss in chunks, forward and backward (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "head")
