"""Device milliseconds per step under the program's ``phase_pull`` scope:
reading table rows (the gather kernel and its unpack) (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "pull")
