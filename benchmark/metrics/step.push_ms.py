"""Device milliseconds per step under the program's ``phase_push`` scope:
updating table rows (the duplicate merge and the scatter kernel) (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "push")
