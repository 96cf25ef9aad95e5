"""A benchmark tree at a size a test run can hold: a copy of ``benchmark/``
with tiny configurations, mixes, limits, one more metric, one more MODEL
(logistic regression, which no cell of record runs) and one more JOB (the
train loop under another name) ADDED as new files, and a ``BENCHMARK.json``
that names them. Nothing in the copy is edited, which is
the point: a later PR adds cells the same way."""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cells")
CONFIGS = {"w2v-enwiki-200": "tiny-w2v", "widedeep-criteo": "tiny-widedeep"}
MIXES = {"train": "tiny-train"}
_TRAIN = {"loss_step1": 1e-3, "loss_step2": 1e-3, "loss_step3": 1e-3,
          "grad1_worst_leaf": 0.02, "change3_worst_leaf": 0.02}
LIMITS = {
    "tiny-w2v.tiny-train": {**_TRAIN, "negatives_dist_z": 6.0},
    "tiny-widedeep.tiny-train": _TRAIN,
    "tiny-logreg.tiny-train-again": _TRAIN,
}
NEW_METRIC = '''"""Steps dispatched inside the window (a count, so a rehearsal prints it)."""


def read(run):
    return float(run.counters["steps"]) if run.counters.get("steps") else None
'''


def build(dst: str) -> str:
    bdir = os.path.join(dst, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bdir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in list(CONFIGS.values()) + ["tiny-logreg"]:
        shutil.copy(os.path.join(CELLS, name + ".json"), os.path.join(bdir, "configs"))
    for name in list(MIXES.values()) + ["tiny-train-again"]:
        shutil.copy(os.path.join(CELLS, name + ".json"), os.path.join(bdir, "traffic"))
    shutil.copy(os.path.join(CELLS, "models", "logreg.py"), os.path.join(bdir, "models"))
    shutil.copy(os.path.join(CELLS, "jobs", "train-again.py"), os.path.join(bdir, "jobs"))
    for cell, limits in LIMITS.items():
        with open(os.path.join(bdir, "limits", cell + ".json"), "w") as f:
            json.dump({"limits": limits}, f)
    with open(os.path.join(bdir, "metrics", "train.steps_in_window.py"), "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    renamed = {}
    for c in bench["configs"]:
        c["name"] = CONFIGS[c["name"]]
        c["file"] = f"benchmark/configs/{c['name']}.json"
    for w in bench["workloads"]:
        old = w["name"]
        w["config"], w["traffic"] = CONFIGS[w["config"]], MIXES[w["traffic"]]
        w["name"] = renamed[old] = f"{w['config']}.{w['traffic']}"
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = [renamed[x] for x in m["workloads"]]
    # the added model's cell, on the added mix and job: one entry each, beside the files
    bench["configs"].append({
        "name": "tiny-logreg", "source": "a test", "file": "benchmark/configs/tiny-logreg.json",
        "reduced": [], "why": "a model the benchmark of record does not know"})
    bench["workloads"].append({
        "name": "tiny-logreg.tiny-train-again", "config": "tiny-logreg",
        "traffic": "tiny-train-again", "chips": 1, "why": "a model, a mix and a job added as files"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and m["name"] != "kernel.train_roofline":
            m["workloads"].append("tiny-logreg.tiny-train-again")
    bench["per_layer"].append({
        "name": "train.steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "training driver", "moves": "train_items_per_s",
        "workloads": ["tiny-logreg.tiny-train-again", "tiny-w2v.tiny-train"]})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return dst
