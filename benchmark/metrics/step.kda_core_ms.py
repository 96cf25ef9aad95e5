"""Device milliseconds per step under the program's scope ``phase_kda_core``
(``ops/gated_delta.py``, the rule's forward and its backward): the chunked
recurrence's own operations, the time ``kernel.kda_roofline`` divides by,
a step (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "kda", "core")
