"""The comparison that decides ``correct``: each number compared has a limit
of its own, kept in ``benchmark/limits/<workload>.json`` with the readings
it was set from in ``PERF.md``. A number passes when it is at or under its
limit; a number that is missing or not finite fails.
"""

import json
import math
import statistics


def rel_gap(program: float, reference: float) -> float:
    return abs(program - reference) / max(abs(reference), 1e-30)


def worst_leaf_gap(program_sumsq: dict, reference_sumsq: dict, skip=()):
    """The widest gap, over the leaves, between the program's norm and the
    reference's (not the norm of a difference), measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (of an even number of leaves, the lower median: with two leaves
    each is then held to its own norm or the smaller one's, never excused
    by the larger). Returns (gap, leaf)."""
    ref = {k: math.sqrt(max(v, 0.0)) for k, v in reference_sumsq.items() if k not in skip}
    median = statistics.median_low(ref.values())
    worst, leaf = 0.0, None
    for k, r in ref.items():
        p = math.sqrt(max(program_sumsq[k], 0.0))
        gap = abs(p - r) / max(r, median, 1e-30)
        if not math.isfinite(gap):
            return float("inf"), k
        if gap >= worst:
            worst, leaf = gap, k
    return worst, leaf


def still_leaves(reference_grad_sumsq: dict) -> tuple:
    """Leaves whose first gradient, in the reference, is under a thousandth
    of the median leaf's: they move by round-off alone and are left out of
    the change."""
    norms = {k: math.sqrt(max(v, 0.0)) for k, v in reference_grad_sumsq.items()}
    median = statistics.median_low(norms.values())
    return tuple(k for k, v in norms.items() if v < 1e-3 * median)


def train_numbers(reference: dict, other: dict) -> dict:
    """A training cell's numbers with ``other`` in the program's place (the
    program itself, the control or a fault). Both are {"loss": [per step],
    "grad1": {leaf: sumsq}, "change": {leaf: [sumsq after each step]}};
    ``worst_leaves`` names the leaves that gave the two norms' gaps."""
    out = {f"loss_step{i + 1}": rel_gap(other["loss"][i], ref_loss)
           for i, ref_loss in enumerate(reference["loss"])}
    out["grad1_worst_leaf"], leaf1 = worst_leaf_gap(other["grad1"], reference["grad1"])
    last = lambda tree: {k: v[-1] for k, v in tree["change"].items()}  # noqa: E731
    out["change3_worst_leaf"], leaf3 = worst_leaf_gap(
        last(other), last(reference), skip=still_leaves(reference["grad1"]))
    out["worst_leaves"] = [leaf1, leaf3]
    return out


def best_reference(other: dict, make_reference, variants, limits: dict):
    """(numbers, variant): ``other`` (the program, or what stands in its
    place) against the reference as the configuration states it
    (``variants[0]``) and, only if that fails, against each further variant
    the configuration allows (for Word2Vec the other legal pipeline depths):
    a result has to agree, in every number, with ONE stated behaviour. The
    first variant that passes gives the numbers; if none does, the first
    one's stand. ``make_reference(variant)`` computes a reference."""
    first = None
    for variant in variants:
        numbers = train_numbers(make_reference(variant), other)
        mine = {k: v for k, v in limits.items() if k in numbers}
        if first is None:
            first = (numbers, variant)
        if judge(numbers, mine)[0]:
            return numbers, variant
    return first


def judge(numbers: dict, limits: dict):
    """(correct, compared): compared maps each name to {"value", "limit"}.
    Every limit must meet a number: a comparison that was not made fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and math.isfinite(value) and value <= limit
        ok = ok and good
        compared[name] = {"value": value, "limit": limit}
    return ok, compared


def load_limits(path: str) -> dict:
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}
