"""Device milliseconds per step under the program's ``phase_opt`` scope:
AdamW over every leaf and the selection bias's step (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "opt")
