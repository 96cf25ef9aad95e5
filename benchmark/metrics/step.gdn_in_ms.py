"""Device milliseconds per step under the program's scope ``phase_gdn_in``:
what stands in front of the Gated DeltaNet recurrence, the q, k and v
projections with their causal convolutions, SiLU and the l2 norm, the rate
``W_a h`` and ``g`` a head, beta and the head-major transposes, forward and
backward (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "gdn", "in")
