"""``correct`` has to come out false: for the control (the nearest precision
below the configuration's) and for each fault a cell can have, planted under
a run that is otherwise whole. The harness's look for a chip is answered by
``JAX_PLATFORMS=cpu``; everything after it runs as on the chip, at a size a
test run can hold. A sound run of the same seed comes out true first."""

import importlib.util
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny_tree  # noqa: E402


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tree = tiny_tree.build(str(tmp_path_factory.mktemp("control") / "tree"))
    sys.path.insert(0, os.path.join(tree, "benchmark"))
    spec = importlib.util.spec_from_file_location(
        "bench_run_control", os.path.join(tree, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _execute(bench, cell, tmp_path, seed=2147483659):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.makedirs(tmp_path, exist_ok=True)
    args = types.SimpleNamespace(workload=cell, seed=seed, seconds=0.5, trace=0)
    return bench.execute(args, bench.load_json(bench.ROOT, "BENCHMARK.json"),
                         str(tmp_path))


# ---- faults, planted in the program underneath the timed path ------------


def _patch_trainer(monkeypatch, wrap):
    """Every trainer the entry point builds gets its train_step wrapped."""
    from swiftsnails_tpu import cli

    real = cli._build_trainer

    def build(cfg):
        trainer = real(cfg)
        trainer.train_step = wrap(trainer.train_step)
        return trainer

    monkeypatch.setattr(cli, "_build_trainer", build)


def state_unchanged(step):
    def broken(state, batch, rng):
        _, metrics = step(state, batch, rng)
        return state, metrics
    return broken


def half_batch(step):
    def broken(state, batch, rng):
        half = {k: (v[: v.shape[0] // 2] if getattr(v, "ndim", 0) else v)
                for k, v in batch.items()}
        return step(state, half, rng)
    return broken


def _patch_sampler(monkeypatch):
    """The negatives drawn uniformly over the vocabulary, wherever the
    program draws them: the step and the harness's redraw agree, so only the
    sampler's own distribution can show it."""
    import jax

    from swiftsnails_tpu.data import sampler
    from swiftsnails_tpu.models import word2vec

    def uniform(table, rng, shape):
        return jax.random.randint(jax.random.split(rng)[0], shape, 0, table.n, dtype="int32")

    monkeypatch.setattr(sampler, "alias_sample", uniform)
    monkeypatch.setattr(word2vec, "alias_sample", uniform)


TRAIN_FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch}


@pytest.mark.parametrize("cell,fault", [
    ("tiny-widedeep.tiny-train", "state_unchanged"),
    ("tiny-widedeep.tiny-train", "half_batch"),
    ("tiny-w2v.tiny-train", "state_unchanged"),
    ("tiny-w2v.tiny-train", "half_batch"),
    ("tiny-w2v.tiny-train", "sampler"),
])
def test_fault_under_the_timed_path_is_not_correct(bench, cell, fault, tmp_path, monkeypatch):
    sound = _execute(bench, cell, tmp_path / "sound")[1]
    assert sound["correct"] is True, sound["compared"]
    if fault in TRAIN_FAULTS:
        _patch_trainer(monkeypatch, TRAIN_FAULTS[fault])
    else:
        _patch_sampler(monkeypatch)
    _, out = _execute(bench, cell, tmp_path / "fault")
    failed = [k for k, c in out["compared"].items() if not c["value"] <= c["limit"]]
    assert out["correct"] is False and failed, out["compared"]
    if fault == "sampler":
        assert failed == ["negatives_dist_z"], out["compared"]


# ---- the control: one precision below the configuration's -----------------


@pytest.mark.parametrize("cell", ["tiny-w2v.tiny-train"])
def test_programs_own_lower_precision_is_not_correct(bench, cell, tmp_path):
    """table_dtype: bfloat16, switched on through the configuration's
    ``control`` keys, in the program's place."""
    from lib import jobs

    mix = bench.find_cell(bench.load_json(bench.ROOT, "BENCHMARK.json"), cell)[2]
    job = jobs.load_job(mix["job"])
    real = job.run
    job.run = lambda r, w, t: real(r, w, t, precision="control")
    try:
        _, out = _execute(bench, cell, tmp_path)
    finally:
        job.run = real
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("cell", ["tiny-widedeep.tiny-train", "tiny-w2v.tiny-train"])
def test_reference_in_bfloat16_is_not_correct(bench, cell, tmp_path):
    """The reference, computed in bfloat16 and put in the program's place,
    fails one of the cell's numbers at the cell's limits (Wide&Deep has no
    lower-precision path of its own, so this is its control)."""
    run, out = _execute(bench, cell, tmp_path)
    assert out["correct"] is True, out["compared"]
    ex = run.extra
    ref = ex["adapter"].reference(ex["batches"])
    low = ex["adapter"].reference(ex["batches"], precision="bfloat16")
    from lib import compare

    numbers = compare.train_numbers(ref, low)
    limits = {k: c["limit"] for k, c in out["compared"].items()}
    assert any(numbers[k] > limits[k] for k in limits), (numbers, limits)


@pytest.mark.parametrize("cell,fail,passes", [
    ("tiny-w2v.tiny-train",
     ["reference:control", "reference:half_batch", "fault:sampler_uniform",
      "fault:sampler_unigram_1.0"],
     # a legal reschedule, or the other legal operands, agrees with the
     # reference as that variant has it, and passes
     {"reference:depth0": {"depth": 0, "operands": "float32"},
      "reference:depth2": {"depth": 2, "operands": "float32"},
      # (at this size the two operand variants lie within the tests' limits of each other)
      "reference:operands_bfloat16": None}),
    ("tiny-widedeep.tiny-train",
     ["reference:control", "reference:half_batch", "reference:state_unchanged"], {}),
])
def test_control_script_judges_at_the_cells_limits(bench, tmp_path, cell, fail, passes):
    """``control.py`` puts the control, the faults and whatever else the
    model's file names in the program's place and says ``correct`` for each
    as ``judge`` does at the cell's own limits."""
    import json
    import subprocess

    tree = os.path.dirname(os.path.dirname(bench.__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=tiny_tree.ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmark", "control.py"), "--workload",
         cell, "--seeds", "2147483777", "--seconds", "0.5"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    rows = {r["part"]: r for r in map(json.loads, p.stdout.strip().splitlines())}
    assert rows["program:float32"]["correct"] is True
    for part in fail:
        assert rows[part]["correct"] is False and rows[part]["failed"], part
    if "fault:sampler_uniform" in fail:
        assert rows["fault:sampler_uniform"]["failed"] == ["negatives_dist_z"]
    if "reference:state_unchanged" in fail:
        assert {"loss_step2", "loss_step3"} <= set(rows["reference:state_unchanged"]["failed"])
    for part, variant in passes.items():
        assert rows[part]["correct"] is True, part
        assert variant is None or rows[part]["reference_variant"] == variant, part


@pytest.mark.parametrize("drawn,limit_side", [(0.75, "under"), (1.0, "over"), (0.0, "over")])
def test_negatives_distribution_number(bench, drawn, limit_side):
    from lib import gen, jobs

    w2v = jobs.load_model("word2vec")
    counts = gen.corpus_counts(2048, 40000, 1.05)
    p = counts.astype(np.float64) ** drawn
    words = np.random.default_rng(3).choice(len(p), size=65536, p=p / p.sum())
    z = w2v.negatives_z(words, counts, 0.75)
    assert (z < 5) if limit_side == "under" else (z > 20), z


@pytest.mark.parametrize("variant", [{"depth": 0}, {"depth": 2}, {"operands": "bfloat16"}])
def test_reference_honours_its_variants(bench, tmp_path, variant):
    """The reference at another legal pipeline depth (at this size, four
    blocks to a substep over 2,048 words, far other), or with the pool
    contractions' operands rounded to bfloat16, gives other rows and all but
    the same loss."""
    from lib import compare

    run, out = _execute(bench, "tiny-w2v.tiny-train", tmp_path)
    ad, batches = run.extra["adapter"], run.extra["batches"]
    n = compare.train_numbers(ad.reference(batches), ad.reference(batches, **variant))
    assert n["change3_worst_leaf"] > 0 and n["grad1_worst_leaf"] > 0, n
    assert max(n[f"loss_step{i}"] for i in (1, 2, 3)) < 1e-3, n
