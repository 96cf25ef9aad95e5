#!/usr/bin/env python
"""Compile every Pallas kernel at the north-star shape; time it; check it.

For each kernel the TPU dispatch can select (``ops/kernel_check.KERNELS``):
cold compile seconds at ``[1048576, 2, 128]`` f32 tables, one run, and
agreement with its reference on collision-free inputs. One process (it
holds the chip); the composed dedup+resident kernel goes last, and every
kernel runs under a deadline on a watchdog thread — a hung Mosaic compile or
kernel cannot be interrupted from Python, so the watchdog
(``utils/watchdog.py``) prints what was in flight and leaves with
``os._exit(3)``.

    python tools/compile_probe.py [--deadline 300] [kernel ...]

Prints one line per kernel and writes ``chiprun_out/kernel_table.json`` as
it goes, so a killed run still leaves the rows it finished.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from swiftsnails_tpu.ops.kernel_check import KERNELS

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("kernels", nargs="*", default=list(KERNELS))
    p.add_argument("--deadline", type=float, default=300.0,
                   help="seconds allowed per kernel (compile + run + check)")
    p.add_argument("--capacity", type=int, default=1 << 20)
    p.add_argument("--dim", type=int, default=200)
    p.add_argument("--centers", type=int, default=8192)
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                 "kernel_table.json"))
    args = p.parse_args(argv)

    from swiftsnails_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    from swiftsnails_tpu.ops.kernel_check import check_kernel
    from swiftsnails_tpu.ops.rowdma import on_tpu
    from swiftsnails_tpu.utils.watchdog import Watchdog

    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}", flush=True)

    wd = Watchdog("compile_probe", exit_code=3)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rows, failed = [], False
    for name in args.kernels:
        with wd.watch(f"kernel {name}", args.deadline):
            wd.note("compile, run or reference")
            try:
                row = check_kernel(
                    name, capacity=args.capacity, dim=args.dim,
                    n=args.centers, interpret=not on_tpu(),
                    default_precision_too=True)
            except Exception as e:  # recorded AND fatal: rc != 0 below
                row = {"kernel": name, "agrees": False,
                       "error": f"{type(e).__name__}: {str(e)[:2000]}"}
        failed |= not row["agrees"]
        rows.append(row)
        print("KERNEL " + json.dumps(row), flush=True)
        with open(args.out, "w") as f:
            json.dump({"platform": dev.platform, "device_kind": dev.device_kind,
                       "capacity": args.capacity, "dim": args.dim,
                       "centers": args.centers, "kernels": rows}, f, indent=1)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
