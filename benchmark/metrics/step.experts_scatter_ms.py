"""Device milliseconds per step under the program's scope
``phase_experts_scatter`` (``models/moelm.py`` ``_experts``, around
``tokens_of_rows``): the rows' outputs added to their tokens under the gates
a live tile at a time, and in the backward pass the gather of the tokens'
gradient to the rows and the gates' gradient (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "experts", "scatter")
