"""Device milliseconds per step under the program's ``phase_gdn`` scope: a
Gated DeltaNet mixer: the q, k and v projections with their causal
convolutions, the rate and beta, the chunked recurrence with a decay per head
(``ops/gated_delta.py``), the SiLU-gated head norm, the output projection and,
under OLMo 2's residual order, the output's norm, forward and backward
(``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "gdn")
