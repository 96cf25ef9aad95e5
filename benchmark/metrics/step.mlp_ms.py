"""Device milliseconds per step under the program's ``phase_mlp`` scope:
the dense layer's feed-forward and the mixture layers' shared experts, forward and backward (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "mlp")
