"""Device milliseconds per step under the program's scope
``phase_experts_products`` (``models/moelm.py`` ``_experts``, around
``grouped_swiglu``): the ``grouped_matmul*`` kernels of the held experts'
feed-forward, the stacked weights' casts to the operands' type and ``dw``,
forward, rematerialised forward and backward (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "experts", "products")
