"""The busiest held expert's assignments over the held experts' mean, averaged over
the warm steps and the mixture layers: the skew the dropless layout has to take.
From the ``counts`` the program's state carries."""


def read(run):
    moe = run.counters.get("moe")
    return None if moe is None else float(moe["load_max_over_mean"])
