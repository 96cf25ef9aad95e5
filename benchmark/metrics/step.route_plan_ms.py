"""Device milliseconds per step under the program's scope
``phase_route_plan`` (``models/moelm.py`` ``_experts``, inside
``phase_experts``, around ``ops/grouped_matmul.plan_rows``): the sort of the
held assignments by expert into the row layout, ``dropped`` and the live
tiles' share (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "route", "plan")
