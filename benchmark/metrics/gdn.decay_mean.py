"""The mean decay ``alpha = exp(g)`` of the Gated DeltaNet layers over the
warm steps' tokens and held heads, the layers averaged: strictly between 0
and 1; a gate that stopped decaying reads 1, one that wipes the state 0. From
the ``gdn_decay`` the program's state carries."""


def read(run):
    gdn = run.counters.get("gdn")
    return None if gdn is None else float(gdn["decay_mean"])
