"""Held assignments that found no row, summed over the warm steps; must read 0.
From the ``dropped`` count the program's state carries."""


def read(run):
    moe = run.counters.get("moe")
    return None if moe is None else float(moe["dropped"])
