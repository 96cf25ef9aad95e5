"""Device time by what the program says an operation is for.

The program's jitted train steps name their parts with ``jax.named_scope``
(``swiftsnails_tpu/utils/profiling.py`` ``PHASES``: ``phase_prep``,
``phase_fused``, ``phase_pull``, ``phase_push``, ``phase_dense``). XLA keeps
the scope path as each instruction's ``op_name``, and the profiler writes it
into the device plane of the ``.xplane.pb`` as the ``tf_op`` stat of the
operation's *event metadata* (``jit(_step)/phase_pull/ssn_pull_packed_small/
jit(gather_rows)/pallas_call:``). jax 0.9.0's ``ProfileData`` shows an
event's own stats only, and its TPU planes have no "Framework Name Scope"
line, so ``load_scoped`` reads the file's protobuf wire format itself, the
device planes only: names and times as ``lib/trace.py``'s neutral form has
them, plus the scope path per event.

Each operation's own time (``lib/trace.py``'s nesting rule: a ``while`` holds
its body's operations on the same line, and is counted for what they leave)
goes to the innermost phase in its path; one whose path names no phase takes
its neighbours' where they agree (``phases``), and is ``unscoped`` otherwise. The sum over
phases and ``unscoped`` is the busy union's operation time. Steps are the
program's ``step`` spans (``lib/spans.py``) of the run's whole window, in
which each runs on the device exactly once (see ``_read``). A trace without
any phase scope (a program from before them) gives no phase reading and
raises nothing.
"""

import re

from . import spans, trace

_PHASE = re.compile(r"phase_([a-z]+)")
_SCOPE_STAT = "tf_op"
UNSCOPED = "unscoped"


def phase_of(scope: str):
    """The innermost phase of a scope path, or None."""
    found = _PHASE.findall(scope or "")
    return found[-1] if found else None


# ------------------------------------------------- the xplane's wire ---
# XSpace.planes=1; XPlane: name=2 lines=3 event_metadata=4 stat_metadata=5
# (maps: key=1 value=2); XLine: name=2 timestamp_ns=3 events=4; XEvent:
# metadata_id=1 offset_ps=2 duration_ps=3; XEventMetadata: name=2 stats=5;
# XStat: metadata_id=1 str_value=5 ref_value=7; XStatMetadata: name=2.


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, i, end):
    """(field number, value) of a message: an int for a varint, a (start,
    end) pair of the bytes for everything else."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = (i, i + n), i + n
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, plane, field):
    """(key, (start, end) of the value message) of a map field."""
    for f, v in _fields(buf, *plane):
        if f == field:
            entry = dict(_fields(buf, *v))
            if 1 in entry and 2 in entry:
                yield entry[1], entry[2]


def _plane(buf, plane):
    """[[short name, start_ns, dur_ns, scope path], ...] of a device plane's
    operation lines."""
    stat_names = {}
    for key, v in _map_entries(buf, plane, 5):
        stat_names[key] = _text(buf, dict(_fields(buf, *v)).get(2, (0, 0)))
    meta = {}  # event metadata id -> (short name, scope path)
    for key, v in _map_entries(buf, plane, 4):
        name, scope = "", ""
        for f, x in _fields(buf, *v):
            if f == 2:
                name = _text(buf, x)
            elif f == 5:
                stat = dict(_fields(buf, *x))
                if stat_names.get(stat.get(1)) == _SCOPE_STAT:
                    scope = (_text(buf, stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7), ""))
        meta[key] = (trace.short_name(name), scope.rstrip(":"))
    ops = []
    for f, v in _fields(buf, *plane):
        if f != 3:
            continue
        line = list(_fields(buf, *v))
        scalars = dict(line)  # name and timestamp; the events repeat
        if _text(buf, scalars.get(2, (0, 0))) not in trace._OP_LINES:
            continue
        t_line = scalars.get(3, 0)
        for lf, x in line:
            if lf == 4:
                ev = dict(_fields(buf, *x))
                name, scope = meta.get(ev.get(1), ("?", ""))
                # both ends floored to ns: operations that do not overlap in
                # ps do not overlap in ns either
                start = t_line + ev.get(2, 0) // 1000
                end = t_line + (ev.get(2, 0) + ev.get(3, 0)) // 1000
                ops.append([name, start, end - start, scope])
    return ops


def load_scoped(path: str) -> dict:
    """{device plane: [[name, start_ns, dur_ns, scope path], ...]}."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name = next((_text(buf, v) for pf, v in _fields(buf, *plane) if pf == 2), "")
        if name.startswith("/device:") and "CPU" not in name:
            ops = _plane(buf, plane)
            if ops:
                out[name] = ops
    return out


# ---------------------------------------------------------- reduction ---


def phases(ops) -> list:
    """The phase of each operation of one plane, in the order given: that of
    its own scope path, and for an operation whose path names none, its
    neighbours' - when the nearest operation with a phase before it and the
    nearest after it, among those directly inside the same operation (the
    ``while``'s body, or the top of the line), name the same phase.

    Why neighbours: XLA drops the op-name of the operations it builds when
    it expands a ``scatter`` or splits a ``cumsum``'s window (the compiled
    Word2Vec step shows ``fusion.101``/``fusion.102``, the ``reduce-window``s
    and ``fusion.98`` with no metadata at all), and the profiler then shows
    them under the enclosing ``jit(_step)/while``. On the device's one
    operation line they run between the operations of the phase whose values
    they compute. What runs between two phases (the scan's slicing after the
    kernel and before the next substep's draw) stays unscoped."""
    own = [phase_of(scope) for _, _, _, scope in ops]
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    groups, stack = {}, []  # parent index (None: top of the line) -> children in time order
    for i in order:
        start, end = ops[i][1], ops[i][1] + ops[i][2]
        while stack and (stack[-1][1] <= start or stack[-1][1] < end):
            stack.pop()  # over, or not holding this one whole: no parent of it
        groups.setdefault(stack[-1][0] if stack else None, []).append(i)
        stack.append((i, end))
    out = list(own)
    for members in groups.values():
        before, pending = None, []
        for i in members:
            if own[i] is None:
                pending.append(i)
                continue
            if before == own[i]:
                for j in pending:
                    out[j] = own[i]
            before, pending = own[i], []
    return out


def own_seconds(planes: dict, window, label) -> dict:
    """{label(name, scope, phase): own seconds inside the window}, the mean
    over the device planes; own time by ``lib/trace.py``'s nesting rule,
    ``phase`` as :func:`phases` gives it."""
    total = {}
    for ops in planes.values():
        labelled = [[label(name, scope, phase), s, d]
                    for (name, s, d, scope), phase in zip(ops, phases(ops))]
        for key, ns in trace._self_ns(trace._clip(labelled, *window)).items():
            total[key] = total.get(key, 0) + ns
    return {k: v / len(planes) / 1e9 for k, v in total.items()}


def phase_seconds(planes: dict, window) -> dict:
    """{phase or "unscoped": own seconds}."""
    return own_seconds(planes, window, lambda name, scope, phase: phase or UNSCOPED)


def read(run):
    """The run's phase split, computed once: ``steps``, ``device_ms`` (the
    window's device operation seconds per step) and ``phase_ms`` ({phase or
    "unscoped": ms per step}); None where there is no device trace or no
    ``step`` span in the window."""
    if "scopes" not in run.extra:
        run.extra["scopes"] = _read(run)
    return run.extra["scopes"]


def _read(run):
    profile = run.extra.get("profile")
    if run.trace is None or profile is None or profile.window is None:
        return None
    # The whole measured window, not the traced run's cut of it: the host
    # dispatches some dozen steps ahead of the device, so the steps that
    # START in a cut window are more than the device runs in it. Between the
    # window's opening (the device idle, after block_until_ready) and
    # TrainLoop.run's return (drained) every dispatched step runs exactly
    # once, and the profiler is on until after that.
    steps = spans.steps_in(run, run.t0, run.t1)
    if not steps:
        return None
    planes = load_scoped(trace.find_xplane(profile.dir))
    if not planes:
        return None
    w0 = profile.window[0]
    phase_s = phase_seconds(planes, (w0, w0 + round(run.window_s * 1e9)))
    return {"steps": steps,
            "device_ms": 1e3 * sum(phase_s.values()) / steps,
            "phase_ms": {k: 1e3 * v / steps for k, v in phase_s.items()}}


def phase_ms(run, phase: str):
    """Device ms per step under a phase; None when the trace names no phase
    at all, or not this one."""
    got = read(run)
    if got is None:
        return None
    return got["phase_ms"].get(phase)


def unscoped_ms(run):
    """Device ms per step under no phase scope; None for a program that has
    no phase scopes (all of its time would read as unscoped)."""
    got = read(run)
    if got is None or set(got["phase_ms"]) <= {UNSCOPED}:
        return None
    return got["phase_ms"].get(UNSCOPED, 0.0)


def device_ms(run):
    got = read(run)
    return None if got is None else got["device_ms"]
