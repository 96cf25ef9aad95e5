"""The mean decay ``alpha = exp(g)`` of the delta-rule layers over the warm
steps' tokens, heads and channels, the layers averaged: strictly between 0
and 1; a gate that stopped decaying reads 1, one that wipes the state 0. From
the ``kda_decay`` the program's state carries."""


def read(run):
    kda = run.counters.get("kda")
    return None if kda is None else float(kda["decay_mean"])
