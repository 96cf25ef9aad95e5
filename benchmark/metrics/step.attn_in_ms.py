"""Device milliseconds per step in front of the attention kernels, the
program's scope ``phase_attn_in`` (``models/moelm.py`` ``_attention``): the
block's norm, the projections to q, k and v (MLA's four with the latent norm;
under grouped queries three with the norm of every head's query and key),
rotary and the head-major transposes, forward, rematerialised forward and
backward (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "attn", "in")
