#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and, by the names there, its
configuration (``benchmark/configs/<config>.json``), its traffic mix
(``benchmark/traffic/<traffic>.json``), its limits
(``benchmark/limits/<cell>.json``) and the readers of its per-layer metrics
(``benchmark/metrics/<metric>.py``); the mix's ``job`` picks the driver loop
(``benchmark/jobs/<job>.py``) and the configuration's ``model`` the file under
``benchmark/models/`` that knows the model. A new cell, mix, metric, model or
job is new files and one entry.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``compared`` (each number compared beside its
limit; the same goes to standard error). It runs on the machine it is
started on and fails, with no result, unless jax finds a TPU with the chips
the cell asks for. ``JAX_PLATFORMS=cpu``, set explicitly, is the rehearsal:
it prints counts (``program_counter`` metrics) and no time, rate or share.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str):
    """(workload, configuration file's content, traffic mix, limits)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(ROOT, entry["file"])
    mix = load_json(HERE, "traffic", w["traffic"] + ".json")
    limits = load_json(HERE, "limits", name + ".json")["limits"]
    return w, config, mix, limits


def metrics_of(bench: dict, group: str, cell: str):
    """The cell's metrics of a group: those that list it, or list nothing."""
    return [m for m in bench[group] if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_device(chips: int):
    """(device info, rehearsal): fails unless a TPU with the chips is there,
    or the CPU was asked for by name."""
    import jax

    from lib import jobs

    info = jobs.device_info()
    if info["platform"] == "tpu":
        if info["count"] < chips:
            raise SystemExit(f"the cell needs {chips} chips, jax finds {info['count']}")
        return info, False
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return info, True
    raise SystemExit(
        f"jax finds no TPU (platform {info['platform']!r}); a rehearsal on the "
        "CPU sets JAX_PLATFORMS=cpu itself")


def configure_jax():
    """The persistent compile cache at the program's fixed path (or where
    JAX_COMPILATION_CACHE_DIR says), keeping every program however small."""
    import jax

    from swiftsnails_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def execute(args, bench, work_dir):
    """One run, from the cell's name to the ``Run`` and the result line's
    object (the tests call this with the program broken underneath)."""
    from lib import compare, jobs
    from lib.compile_log import CompileLog

    workload, config, mix, limits = find_cell(bench, args.workload)
    device, rehearsal = require_device(int(workload["chips"]))
    configure_jax()
    seconds = float(args.seconds)
    traced = bool(int(args.trace))
    if traced:
        seconds = min(seconds, float(mix.get("trace_seconds", seconds)))
    run = jobs.Run(config=config, mix=mix, seed=int(args.seed),
                   seconds=seconds, traced=traced, chips=int(workload["chips"]), limits=limits,
                   device=device,
                   compile_log=CompileLog())
    jobs.load_job(mix["job"]).run(run, work_dir, T_PROCESS)
    correct, compared = compare.judge(run.numbers, limits)
    correct = correct and run.failed == 0

    values = dict(run.end_to_end, setup_s=run.setup_s)
    metrics = {}
    if traced:
        for m in metrics_of(bench, "per_layer", workload["name"]):
            if rehearsal and m["source"] != "program_counter":
                continue
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    elif not rehearsal:
        for m in metrics_of(bench, "end_to_end", workload["name"]):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics,
           "device": dict(device, memory_peak_bytes=run.memory_peak_bytes)}
    if traced and run.trace is not None and not rehearsal:
        out["device"].update(busy_s=run.trace["busy_s"], window_s=run.trace["window_s"])
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["compared"] = compared
    return run, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    work_dir = tempfile.mkdtemp(prefix="snails-bench-")
    try:
        run, out = execute(args, bench, work_dir)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    log = run.compile_log
    print(f"set-up {run.setup_s:.1f} s; marks {run.counters.get('setup_marks')}; backend compiles "
          f"{log.seconds():.1f} s {log.by_function(5)}; persistent cache {log.hits} hits of "
          f"{log.requests} requests", file=sys.stderr)
    variant = (run.counters.get("readings") or {}).get("reference_variant")
    if variant:
        print(f"agrees with the reference's variant {variant}", file=sys.stderr)
    for name, c in out["compared"].items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # daemon threads of the program (prefetcher, batchers) may still hold
    # native resources; the result is out, leave without their teardown
    os._exit(code)
