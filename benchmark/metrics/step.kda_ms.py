"""Device milliseconds per step under the program's ``phase_kda`` scope: a
gated delta-rule mixer: norm, the q, k and v projections with their causal
convolutions, the two low-rank gates, beta, the chunked recurrence
(``ops/gated_delta.py``), the gated head norm and the output projection,
forward and backward (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "kda")
