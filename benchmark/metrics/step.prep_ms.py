"""Device milliseconds per step under the program's ``phase_prep`` scope:
turning ids into rows, negatives, copy lists and merge plans (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "prep")
