"""Device time by the part of a phase that the program says an operation
belongs to: the second level under ``lib/scopes.py``'s phases.

Inside four phases of a language-model step the program names what stands in
front of, inside and behind the kernels (``swiftsnails_tpu/utils/profiling.py``
``PARTS``: ``phase_attn_in`` / ``_core`` / ``_out``, ``phase_kda_in`` /
``_core`` / ``_out``, ``phase_experts_gather`` / ``_products`` / ``_scatter``,
``phase_route_score`` / ``_plan``). A part is one more named scope, so it
reaches the xplane as a phase does, in the scope path of each operation
(``jit(_step)/transpose(jvp(jvp()))/checkpoint/phase_attn/phase_attn_in/dot_general``),
and ``scopes.phase_of`` reads the same phase from it as before (its pattern
stops at the second ``_``).

An operation's part is that of the LAST ``phase_*`` token of its path
(``phase_experts/phase_route_plan/sort`` is ``route`` / ``plan``); one whose
last token is a bare phase, and one XLA left without a name that
``scopes.phases`` filed by its neighbours, has none. The window, the step
count and the own-time rule are ``scopes.read``'s, so for each phase its
parts and its share of :func:`unparted_ms` add up to ``scopes.phase_ms``:
one reduction under two labellings.
"""

import re

from . import scopes, trace

# the phases that have parts: what ``unparted_ms`` is the rest of
PARTED = ("attn", "kda", "experts", "route")
_TOKEN = re.compile(r"phase_([a-z]+)(?:_([a-z]+))?")


def part_of(scope: str):
    """(phase, part) of the last ``phase_*`` token of a scope path; the part
    is None where the token is a bare phase, the pair where there is none."""
    found = _TOKEN.findall(scope or "")
    if not found:
        return None, None
    phase, part = found[-1]
    return phase, part or None


def part_seconds(planes: dict, window) -> dict:
    """{(phase or "unscoped", part or None): own seconds inside the window};
    the phase as ``scopes.phases`` gives it, so that the sum over a phase's
    keys is ``scopes.phase_seconds``' reading of it."""
    return scopes.own_seconds(
        planes, window, lambda name, scope, phase: (phase or scopes.UNSCOPED, part_of(scope)[1]))


def read(run):
    """The run's split by part, computed once: ``{"part_ms": {(phase, part):
    ms a step}, "unparted_ms": {phase: ms a step}}``; None where there is no
    device trace, no ``step`` span in the window, or no part in any path."""
    if "parts" not in run.extra:
        run.extra["parts"] = _read(run)
    return run.extra["parts"]


def _read(run):
    base = scopes.read(run)  # the same run, window and steps, or nothing to read
    if base is None:
        return None
    profile = run.extra["profile"]
    planes = scopes.load_scoped(trace.find_xplane(profile.dir))
    w0 = profile.window[0]
    own = part_seconds(planes, (w0, w0 + round(run.window_s * 1e9)))
    if not any(part for _, part in own):
        return None
    per_step = {key: 1e3 * s / base["steps"] for key, s in own.items()}
    return {"part_ms": {key: ms for key, ms in per_step.items() if key[1]},
            "unparted_ms": {phase: ms for (phase, part), ms in per_step.items()
                            if part is None and phase in PARTED}}


def part_ms(run, phase: str, part: str):
    """Device ms a step of the operations whose path's last ``phase_*`` token
    is ``phase_<phase>_<part>``; None when the trace names no part at all, or
    not this one."""
    got = read(run)
    return None if got is None else got["part_ms"].get((phase, part))


def unparted_ms(run):
    """Device ms a step that ``scopes.phases`` files under a phase of
    :data:`PARTED` and whose path names no part: what XLA left without a name
    and the neighbour rule placed, and what the program left outside its
    parts; None for a program that names no part at all."""
    got = read(run)
    return None if got is None else sum(got["unparted_ms"].values())
