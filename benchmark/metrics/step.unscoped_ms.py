"""Device milliseconds per step under no phase scope of the program (scan
plumbing, key derivation, what XLA adds): small, or a phase is missing its
scope (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.unscoped_ms(run)
