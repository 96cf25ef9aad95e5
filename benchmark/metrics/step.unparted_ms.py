"""Device milliseconds per step that ``lib/scopes.py`` files under ``attn``,
``kda``, ``experts`` or ``route`` and whose scope path names no part of the
phase: the operations XLA left without a name and the neighbour rule placed,
and what the program left outside its parts. Small, or a part is missing its
scope (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.unparted_ms(run)
