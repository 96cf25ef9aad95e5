"""Device milliseconds per step under the program's ``phase_route`` scope:
the router's product and sigmoid, top-k, gates, the balance loss, the counts and the sort of the held assignments by expert (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "route")
