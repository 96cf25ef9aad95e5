"""From the profiler's trace to numbers, in two steps, so that every PR
computes the same numbers the same way.

1. ``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (with
   nothing but jax) into a neutral form: ``{"device": {plane: [[name, start_ns,
   dur_ns], ...]}, "host": [[name, start_ns, dur_ns], ...]}`` on one clock.
2. ``reduce_trace`` takes that form and a window and gives the busy union,
   the idle share, the time per operation and the idle gaps named by the host
   span that covered them. ``benchmark/data/trace_small.json`` is a recorded
   trace in the neutral form; ``tests/benchmark`` checks step 2 on it.
"""

import glob
import os
import re

# lines of a device plane that hold single operations; module and step lines
# cover the same time again and are left out of the union
_OP_LINES = ("XLA Ops",)
_SKIP_LINES = ("XLA Modules", "Steps", "Step", "XLA TraceMe", "Framework Ops",
               "Framework Name Scope", "Source code", "SparseCoreV0")


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def short_name(hlo: str) -> str:
    """An operation's event is named by its whole HLO line; keep the
    instruction's name, and say where it is a Mosaic (Pallas) kernel:
    ``fused_sgns_grouped_step.7 tpu_custom_call``."""
    name = hlo.split(" = ")[0].lstrip("%")
    return name + " tpu_custom_call" if "tpu_custom_call" in hlo else name


def load_xplane(path: str, host_keep=None) -> dict:
    """The neutral form of a profile. ``host_keep`` is a regex: only host
    events whose name matches are kept (the host planes hold millions)."""
    import jax

    keep = re.compile(host_keep) if host_keep else None
    data = jax.profiler.ProfileData.from_file(path)
    out = {"device": {}, "host": [], "lines": {}}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "CPU" not in plane.name
        names = [ln.name for ln in plane.lines]
        out["lines"][plane.name] = names
        if is_device:
            ops = []
            op_lines = [ln for ln in plane.lines if ln.name in _OP_LINES] or [
                ln for ln in plane.lines if ln.name not in _SKIP_LINES]
            for ln in op_lines:
                for ev in ln.events:
                    ops.append([short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)])
            if ops:
                out["device"][plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.duration_ns > 0 and (keep is None or keep.search(ev.name)):
                        out["host"].append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    return out


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(ops, w0, w1):
    for name, s, d in ops:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b


def _self_ns(clipped):
    """{name: ns} of each operation's own time: its interval less what the
    operations nested directly inside it cover (a ``while`` holds its body's
    operations on the same line; counting both would count the body twice)."""
    out, stack = {}, []  # stack of [name, end, own_ns]

    def close(item):
        out[item[0]] = out.get(item[0], 0) + max(item[2], 0)

    for name, a, b in sorted(clipped, key=lambda e: (e[1], -(e[2] - e[1]))):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    while stack:
        close(stack.pop())
    return out


def reduce_trace(neutral: dict, window=None, top: int = 10, max_gaps: int = 1000) -> dict:
    """busy_s (mean over device planes of the union of operation intervals
    inside the window), window_s, idle_share, op_seconds {name: own seconds,
    mean over planes}, device_ops (top by own time) and idle_gaps (the
    ``max_gaps`` longest gaps of the first device plane, summed by the name
    of the innermost host span that covers the gap's middle, or "(no host
    span)")."""
    import numpy as np

    planes = neutral["device"]
    if not planes:
        raise ValueError("the trace holds no device plane with operations")
    if window is None:
        starts = [s for ops in planes.values() for _, s, _ in ops]
        ends = [s + d for ops in planes.values() for _, s, d in ops]
        window = (min(starts), max(ends))
    w0, w1 = window
    busy, op_ns, first_union = [], {}, None
    for ops in planes.values():
        clipped = list(_clip(ops, w0, w1))
        u = _union([[a, b] for _, a, b in clipped])
        busy.append(sum(e - s for s, e in u))
        if first_union is None:
            first_union = u
        for name, ns in _self_ns(clipped).items():
            op_ns[name] = op_ns.get(name, 0) + ns
    n = len(planes)
    busy_s = sum(busy) / n / 1e9
    window_s = (w1 - w0) / 1e9
    op_seconds = {k: v / n / 1e9 for k, v in op_ns.items()}
    gaps, edge = [], w0
    for s, e in first_union + [[w1, w1]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    host = neutral.get("host", [])
    h_start = np.array([s for _, s, _ in host], np.int64)
    h_dur = np.array([d for _, _, d in host], np.int64)
    by_name = {}
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:max_gaps]:
        mid = (a + b) // 2
        cover = np.flatnonzero((h_start <= mid) & (h_start + h_dur > mid))
        name = host[cover[np.argmin(h_dur[cover])]][0] if len(cover) else "(no host span)"
        by_name[name] = by_name.get(name, 0) + (b - a)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "op_seconds": op_seconds,
        "device_ops": [[k, v] for k, v in sorted(op_seconds.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
    }


def kernel_seconds(reduced: dict, patterns) -> float:
    """Device seconds of the operations whose name matches any pattern."""
    pats = [re.compile(p) for p in patterns]
    return sum(v for k, v in reduced["op_seconds"].items() if any(p.search(k) for p in pats))
