#!/usr/bin/env python3
"""The readings a cell's limits are set from. The benchmark's own runs do not
run this; ``PERF.md`` lists what it printed on the chip.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 --seconds 3 [--program-control 1]

For each seed, in one process: a short run of the cell as ``run.py`` makes it
(the lower readings: the program against the reference), then, on the first
``--parts-seeds`` seeds, with something else put in the program's place and
judged by the same numbers at the cell's own limits
(``benchmark/limits/<cell>.json``):

* ``control``: the reference in the control's precision (bfloat16 storage);
* ``half_batch``: the reference with half of every batch left out, the mean
  taken over the rest;
* whatever else the model's file names (``Adapter.parts``: for Word2Vec the
  reference at the other legal pipeline depths, which have to PASS; for
  Wide&Deep a step that returns its state unchanged; ``Adapter.extra_faults``:
  for Word2Vec samplers that draw from another distribution, which have to
  fail).

``--program-control 1`` runs the program itself with its own lower-precision
path switched on (``control.program_keys`` in the configuration's file:
``table_dtype: bfloat16``) in place of the first part. One JSON line per seed
and part, with ``correct`` as ``compare.judge`` gives it and the numbers that
failed, also appended to ``chiprun_out/control.<cell>.jsonl``.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import types

import run as bench_run  # noqa: E402  (sets sys.path for lib and the program)

from lib import compare, jobs  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program-control", type=int, default=0)
    ap.add_argument("--parts-seeds", type=int, default=3,
                    help="the control's and the faults' readings on the first N seeds only")
    a = ap.parse_args()
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    limits = bench_run.find_cell(bench, a.workload)[3]
    out_dir = os.path.join(bench_run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"control.{a.workload}.jsonl"), "a")

    def say(**row):
        line = json.dumps(row, default=lambda o: o.tolist() if hasattr(o, "tolist") else str(o))
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    def judged(numbers):
        ok, compared = compare.judge(numbers, limits)
        return {"correct": ok, "numbers": numbers,
                "failed": [k for k, c in compared.items()
                           if c["value"] is None or not c["value"] <= c["limit"]]}

    for n_seed, seed in enumerate(int(s) for s in a.seeds.split(",")):
        args = types.SimpleNamespace(workload=a.workload, seed=seed, seconds=a.seconds, trace=0)
        work_dir = tempfile.mkdtemp(prefix="snails-control-")
        try:
            job = jobs.load_job(bench_run.find_cell(bench, a.workload)[2]["job"])
            real = job.run
            if a.program_control:
                job.run = lambda r, w, t: real(r, w, t, precision="control")
            try:
                run, out = bench_run.execute(args, bench, work_dir)
            finally:
                job.run = real
            say(workload=a.workload, seed=seed,
                part="program:" + ("control" if a.program_control else "float32"),
                **judged(run.numbers), metrics=out["metrics"],
                memory_peak_bytes=run.memory_peak_bytes,
                readings=run.counters.get("readings"), counters={
                    k: v for k, v in run.counters.items() if k != "readings"})
            if a.program_control or n_seed >= a.parts_seeds:
                continue
            ad, batches = run.extra["adapter"], run.extra["batches"]
            variants = ad.reference_variants()
            sound_extra = ad.extra_numbers(batches)
            parts = {"control": {"precision": run.config["control"]["precision"]},
                     "half_batch": {"fault": "half_batch"}, **ad.parts()}
            readings = run.counters["readings"]
            refs = {v: readings["reference"] for v in variants[:1]
                    if dict(v) == readings["reference_variant"]}  # the run's own, kept

            def make_reference(variant):
                if variant not in refs:
                    refs[variant] = ad.reference(batches, **dict(variant))
                return refs[variant]

            ref = make_reference(variants[0])
            as_variant = {tuple(sorted(v)): v for v in variants}
            for part, kw in parts.items():
                same = as_variant.get(tuple(sorted(kw.items())))
                other = make_reference(same) if same is not None else ad.reference(batches, **kw)
                numbers, variant = compare.best_reference(other, make_reference, variants, limits)
                numbers.pop("worst_leaves")
                say(workload=a.workload, seed=seed, part="reference:" + part,
                    reference_variant=dict(variant), **judged({**sound_extra, **numbers}))
            sound = compare.train_numbers(ref, ref)
            sound.pop("worst_leaves")
            for part, extra in ad.extra_faults().items():
                say(workload=a.workload, seed=seed, part="fault:" + part,
                    **judged({**sound, **extra}))
        finally:
            shutil.rmtree(work_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
