"""Device milliseconds per step under the program's scope
``phase_route_score`` (``models/moelm.py`` ``_moe_layer``): the feed-forward
part's norm, the router's product at the highest precision, sigmoid or
softmax, top-k, the gates, the balance loss and the counts, forward,
rematerialised forward and backward (``lib/parts.py``)."""

from lib import parts


def read(run):
    return parts.part_ms(run, "route", "score")
