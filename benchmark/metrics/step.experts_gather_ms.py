"""Device milliseconds per step under the program's scope
``phase_experts_gather`` (``models/moelm.py`` ``_experts``, around
``rows_of_tokens``): tokens moved to the expert rows' layout a live tile at
a time, and in the backward pass the scatter-add of the rows' gradient to the
tokens (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "experts", "gather")
