"""Share of the warm steps' (token, expert) assignments, over the mixture layers,
whose expert this chip holds: ``experts_held / router_experts`` (12.5%) give or take
the skew. From the ``counts`` the program's state carries."""


def read(run):
    moe = run.counters.get("moe")
    return None if moe is None else float(moe["held_share_pct"])
