"""The benchmark harness on the CPU: names resolve, the result line has the
contract's keys, the yardstick's arithmetic gives hand-computed values, the
references agree with the package's step, and the trace reduction gives the
recorded trace's numbers. No test here describes a TPU."""

import importlib.util
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, HERE)
import tiny_tree  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _lib(name):
    spec = importlib.util.spec_from_file_location(
        f"benchlib_{name}", os.path.join(BENCH, "lib", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------- names resolve ---


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_cell_resolves_its_files_by_name(cell):
    bench = _bench()
    w = {x["name"]: x for x in bench["workloads"]}[cell]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    assert w["name"] == f"{w['config']}.{w['traffic']}" and w["chips"] in (1, 4)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["reduced"] == entry["reduced"] and config["source"] == entry["source"]
    assert not [k for k in entry["reduced"] if k.endswith(("_dim", "_rank")) or k in ("dim",)]
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        assert os.path.isfile(os.path.join(BENCH, "jobs", json.load(f)["job"] + ".py"))
    assert os.path.isfile(os.path.join(BENCH, "models", config["model"] + ".py"))
    with open(os.path.join(BENCH, "limits", cell + ".json")) as f:
        limits = json.load(f)["limits"]
    assert limits and all(v >= 0 for v in limits.values())
    e2e = {m["name"] for m in bench["end_to_end"]}
    mine = [m for m in bench["per_layer"] if cell in m.get("workloads", [cell])]
    assert mine and any("mfu" in m["name"] for m in mine)
    for m in mine:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py")), m["name"]
    reported = {m["name"] for m in bench["end_to_end"] if cell in m.get("workloads", [cell])}
    assert "setup_s" in reported and len(reported) >= 2
    assert all(m["moves"] in reported for m in mine)


# ------------------------------------------------- the result's line ---


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny_tree.build(str(tmp_path_factory.mktemp("bench") / "tree"))


def _run(tree, cell, trace, seconds="1"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)  # one CPU device, as a one-chip machine has one chip
    p = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmark", "run.py"), "--workload", cell,
         "--seed", "2147483999", "--seconds", seconds, "--trace", str(trace)],
        cwd=tree, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("cell,trace,counts", [
    ("tiny-widedeep.tiny-train", 0, []),
    ("tiny-w2v.tiny-train", 1, ["entry.compiles_in_window", "train.steps_in_window"]),
    ("tiny-logreg.tiny-train-again", 1, ["entry.compiles_in_window", "train.steps_in_window"]),
])
def test_rehearsal_prints_the_contracts_line(tree, cell, trace, counts):
    """Cells, mixes, one metric, one model (logistic regression, which no
    file of the benchmark of record names) and one job that exist only as
    files ADDED to a copy of the benchmark run by name; the line has the contract's keys, the
    numbers compared come last, and a CPU run writes counts and nothing that
    is a time, a rate or a share of a device."""
    line, err = _run(tree, cell, trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared" and set(line) <= {
        "correct", "attempted", "failed", "metrics", "device", "breakdown", "compared"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and "memory_peak_bytes" in line["device"]
    assert sorted(line["metrics"]) == sorted(counts)
    assert line["metrics"].get("entry.compiles_in_window", {"value": 0.0})["value"] == 0.0
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"] and f"compared {name}:" in err


def test_no_accelerator_no_result(tree):
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env.update(PYTHONPATH=ROOT, JAX_PLATFORM_NAME="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmark", "run.py"), "--workload",
         "tiny-w2v.tiny-train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "correct" not in p.stdout


@pytest.fixture(scope="module")
def inproc(tree):
    """The tiny tree's run.py and lib, imported once into this process."""
    sys.path.insert(0, os.path.join(tree, "benchmark"))
    spec = importlib.util.spec_from_file_location("bench_run_tiny", os.path.join(tree, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------- the yardstick's arithmetic ---

W2V = {"dim": 200, "window": 5, "pool_size": 64, "centers_per_block": 256}
WD = {"num_fields": 26, "embed_dim": 16, "hidden_dims": "256,128", "batch_size": 8192}


@pytest.mark.parametrize("fn,args,want", [
    # 6*200*6 + 6*200*64 + 2*200*(1 + 6 + 64/256)
    ("word2vec.flops_per_item", (W2V,), 7200 + 76800 + 2900),
    # 2 * 1024 * 7.25 rows
    ("word2vec.bytes_per_item", (W2V, 1024), 14848),
    # weights 416*256 + 256*128 + 128 = 139392, biases 385:
    # 6*139392 + 2*385 + 2*26 + 6*26*17 + 6*(139392+385)/8192
    ("widedeep.flops_per_item", (WD,), 836352 + 770 + 52 + 2652 + 6 * 139777 / 8192),
    ("widedeep.bytes_per_item", (WD, 256), 13312),
    # logistic regression, the deep side left out: 2*6 + 6*6*1
    ("widedeep.flops_per_item", ({"num_fields": 6, "batch_size": 256},), 48),
])
def test_operations_and_bytes_by_hand(inproc, fn, args, want):
    from lib import jobs

    model, name = fn.split(".")
    assert getattr(jobs.load_model(model), name)(*args) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("case", ["known", "unknown", "over"])
def test_peaks_table(case):
    peaks = _lib("peaks")
    if case == "known":
        p = peaks.peaks_for("TPU v5 lite")
        assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
        assert peaks.share_pct(81.9e9, p["hbm_bytes_per_s"], "x") == pytest.approx(10.0)
    elif case == "unknown":
        with pytest.raises(KeyError):
            peaks.peaks_for("TPU v9 imaginary")
    else:
        with pytest.raises(ValueError):
            peaks.share_pct(2.0, 1.0, "x")


@pytest.mark.parametrize("case", ["worst_leaf", "still", "judge", "variants"])
def test_comparison_rules(case):
    cmp = _lib("compare")
    if case == "worst_leaf":
        ref = {"a": 1.0, "b": 100.0 ** 2}
        # each of two leaves is held to its own norm: the larger never excuses the smaller
        gap, leaf = cmp.worst_leaf_gap({"a": 0.0, "b": 100.0 ** 2}, ref)
        assert (gap, leaf) == (1.0, "a")
        gap, leaf = cmp.worst_leaf_gap({"a": 1.0, "b": 110.0 ** 2}, ref)
        assert gap == pytest.approx(0.1) and leaf == "b"
    elif case == "still":
        assert cmp.still_leaves({"a": 1.0, "b": 1.0, "c": 1e-8}) == ("c",)
    elif case == "variants":
        tree = lambda x: {"loss": [1.0], "grad1": {"a": x, "b": 1.0},  # noqa: E731
                          "change": {"a": [x], "b": [1.0]}}
        limits = {"loss_step1": 1e-6, "grad1_worst_leaf": 0.01, "change3_worst_leaf": 0.01}
        made = []
        ref = lambda v: made.append(v) or tree(dict(v)["x"])  # noqa: E731
        variants = [(("x", 1.0),), (("x", 4.0),), (("x", 9.0),)]
        # the stated behaviour first, and nothing further when it agrees
        numbers, v = cmp.best_reference(tree(1.0), ref, variants, limits)
        assert v == variants[0] and made == variants[:1] and numbers["grad1_worst_leaf"] == 0
        # a result that agrees with another legal variant passes by that one
        numbers, v = cmp.best_reference(tree(4.0), ref, variants, limits)
        assert v == variants[1] and cmp.judge(numbers, limits)[0]
        # one that agrees with none keeps the first one's numbers, and fails
        numbers, v = cmp.best_reference(tree(2.0), ref, variants, limits)
        assert v == variants[0] and not cmp.judge(numbers, limits)[0]
    else:
        ok, compared = cmp.judge({"x": 0.5, "y": float("nan")}, {"x": 1.0, "y": 1.0, "z": 1.0})
        assert not ok and compared["z"]["value"] is None
        assert cmp.judge({"x": 0.0}, {"x": 0})[0]


# ----------------------------------------------------- trace reduction ---


def _sweep_busy(ops, w0, w1):
    """Busy time by counting open intervals at every endpoint: another
    method than the reduction's merge."""
    points = []
    for _, s, d in ops:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            points += [(a, 1), (b, -1)]
    busy = depth = 0
    last = None
    for t, step in sorted(points):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_trace_reduction_on_the_recorded_trace():
    trace = _lib("trace")
    with open(os.path.join(BENCH, "data", "trace_small.json")) as f:
        rec = json.load(f)
    neutral, window = rec["neutral"], tuple(rec["window"])
    red = trace.reduce_trace(neutral, window)
    ops = next(iter(neutral["device"].values()))
    busy = _sweep_busy(ops, *window)
    assert red["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    assert red["window_s"] == pytest.approx((window[1] - window[0]) / 1e9)
    assert red["idle_share"] == pytest.approx(1 - busy / (window[1] - window[0]))
    # own times add up to the busy time when one line nests properly
    assert sum(red["op_seconds"].values()) == pytest.approx(red["busy_s"], rel=1e-9)
    # every gap is given to a host span or to none, and they add up to the idle time
    idle = sum(s for _, s in red["idle_gaps"])
    assert idle <= red["window_s"] - red["busy_s"] + 1e-12
    for key in ("busy_s", "idle_share"):
        assert red[key] == pytest.approx(rec["expected"][key], rel=1e-9)
    assert red["device_ops"][0][0] == rec["expected"]["top_op"]
    assert red["idle_gaps"][0][0] == rec["expected"]["top_gap"]
    pats = rec["expected"]["kernel_patterns"]
    assert trace.kernel_seconds(red, pats) == pytest.approx(rec["expected"]["kernel_s"], rel=1e-9)


# ------------------------------- references against the package's step ---


def _trainer(inproc, cell, seed, tmp_path):
    from lib import jobs

    bench = inproc.load_json(inproc.ROOT, "BENCHMARK.json")
    _, config, mix, _ = inproc.find_cell(bench, cell)
    run = jobs.Run(config=config, mix=mix, seed=seed, seconds=1, traced=False)
    adapter_cls = run.model.Adapter
    path = adapter_cls.dataset(run, str(tmp_path))
    cfg = jobs.load_job(mix["job"]).program_config(run, str(tmp_path), path)
    from swiftsnails_tpu import cli

    trainer = cli._build_trainer(cfg)
    return run, adapter_cls(run, trainer), trainer


@pytest.mark.parametrize("cell", ["tiny-w2v.tiny-train", "tiny-widedeep.tiny-train",
                                  "tiny-logreg.tiny-train-again"])
def test_reference_agrees_with_the_packages_step(inproc, cell, tmp_path):
    """Three steps of the package's own train_step from the benchmark's
    weights against the reference, which for Word2Vec holds the block
    semantics (one block of staleness, later write wins) exactly."""
    import jax
    import jax.numpy as jnp

    seed = 7
    run, adapter, trainer = _trainer(inproc, cell, seed, tmp_path)
    state, read = adapter.state(), adapter.readings()
    gen = trainer.batches()
    batches = [next(gen) for _ in range(3)]
    gen.close()
    root, step = jax.random.PRNGKey(seed), jax.jit(trainer.train_step)
    losses, reads = [], []
    for i, b in enumerate(batches):
        state, m = step(state, {k: jnp.asarray(v) for k, v in b.items()}, jax.random.fold_in(root, i))
        reads.append(jax.device_get(read(state, np.uint32(seed))))
        losses.append(float(m["loss"]))
    ref = adapter.reference(batches)
    np.testing.assert_allclose(losses, ref["loss"], rtol=2e-6)
    for leaf, sumsq in ref["change"].items():
        got = [float(r["change"][leaf]) for r in reads]
        np.testing.assert_allclose(got, sumsq, rtol=1e-4, err_msg=leaf)
