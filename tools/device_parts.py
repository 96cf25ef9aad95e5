#!/usr/bin/env python
"""Device time of a ``profile_dir`` capture by phase and part, and its top
operations each with its phase / part beside its XLA name.

The jitted train steps name what an operation is for (``utils/profiling.py``
``phase_scope`` and ``part_scope``); XLA renumbers its fusions whenever a step
changes, the scopes stay. This reads the capture's ``.xplane.pb`` through the
benchmark's own reader (``benchmark/lib/scopes.py``, ``lib/parts.py``: own
time by nesting, an operation without a name filed by its neighbours), so an
operator's listing and the benchmark's ``step.*_ms`` are one reduction.

    python tools/device_parts.py PROFILE_DIR                # ms in the capture
    python tools/device_parts.py PROFILE_DIR --steps 10     # ms a step (profile_steps: 10,20 holds ten)
    python tools/device_parts.py PROFILE_DIR --top 40 --kinds 60

No accelerator or jax import involved: safe anywhere.
"""

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

from lib import parts, scopes, trace  # noqa: E402


def report(profile_dir: str, steps: int = 0, top: int = 25, kinds: int = 40, window=None) -> str:
    """The listing as text. ``window`` is (start ns, end ns) on the capture's
    clock (the whole capture if None); ``steps`` the steps it holds (ms a step
    if given, ms in the window otherwise)."""
    planes = scopes.load_scoped(trace.find_xplane(profile_dir))
    if not planes:
        return f"no device plane with operations under {profile_dir}"
    if window is None:
        window = (min(op[1] for ops in planes.values() for op in ops),
                  max(op[1] + op[2] for ops in planes.values() for op in ops))
    own = scopes.own_seconds(
        planes, window, lambda name, scope, phase: (phase or scopes.UNSCOPED, parts.part_of(scope)[1] or "-", name))
    scale, unit = (1e3 / steps, "ms/step") if steps else (1e3, "ms")
    busy = sum(own.values())
    out = [f"{len(planes)} device plane(s), window {(window[1] - window[0]) / 1e9:.4f} s, operations "
           f"{busy:.4f} s" + (f", {steps} steps" if steps else "")]

    def table(title, rows, name_width):
        out.append(f"\n{title}")
        for (phase, part, name), (n, s) in rows:
            count = f" x{n:<4d}" if n else ""
            out.append(f"{phase:9s} {part:9s} {name:{name_width}s}{count} {scale * s:10.3f} {unit} {100 * s / busy:6.2f}%")

    by_part, by_kind = {}, {}
    for (phase, part, name), s in own.items():
        by_part[(phase, part, "")] = (0, by_part.get((phase, part, ""), (0, 0.0))[1] + s)
        kind = (phase, part, re.sub(r"\.\d+", "", name))  # fusion.219 -> fusion
        n, t = by_kind.get(kind, (0, 0.0))
        by_kind[kind] = (n + 1, t + s)
    by_phase = {}
    for (phase, _, _), (_, s) in by_part.items():
        by_phase[phase] = by_phase.get(phase, 0.0) + s
    table("by phase and part (a phase's largest first; '-': no part named)",
          sorted(by_part.items(), key=lambda kv: (-by_phase[kv[0][0]], kv[0][0], -kv[1][1])), 0)
    table(f"by kind of operation (XLA's name without its number), the first {kinds}",
          sorted(by_kind.items(), key=lambda kv: -kv[1][1])[:kinds], 56)
    table(f"the {top} longest operations",
          [(key, (0, s)) for key, s in sorted(own.items(), key=lambda kv: -kv[1])[:top]], 56)
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("profile_dir", help="what profile_dir named: the directory that holds plugins/profile/*/")
    ap.add_argument("--steps", type=int, default=0, help="the steps the capture holds: print ms a step")
    ap.add_argument("--top", type=int, default=25, help="how many single operations to list")
    ap.add_argument("--kinds", type=int, default=40, help="how many kinds of operation to list")
    args = ap.parse_args(argv)
    print(report(args.profile_dir, args.steps, args.top, args.kinds))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
