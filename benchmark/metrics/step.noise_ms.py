"""Device milliseconds per step under the program's ``phase_noise`` scope:
what turns a block-diffusion batch into the stack's input: the mask id where the batch says noised, the two copies, their positions, the loss's weights (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    return scopes.phase_ms(run, "noise")
