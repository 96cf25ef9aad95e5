"""The training loop is one body whether traced or not, set-up and the
producer thread have spans, and the jitted steps name their phases.

* telemetry on and off give the same state, items and steps; off, the loop
  holds no tracer and every ``with span(...)`` is the one shared no-op;
* a traced run through ``cli._build_trainer`` -> ``TrainLoop`` yields
  ``build-trainer`` > ``load-data``/``alias-table``, ``produce``,
  ``prefetch-wait``, ``h2d``, ``step``, ``drain``, ``finalize``;
* the lowered steps carry ``utils.profiling.PHASES`` as named scopes, the
  Mosaic calls under the phase that owns them, and the comm audit's
  ``ssn_*`` attribution does not see them.
"""

import contextlib
import io
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(__file__))

from swiftsnails_tpu.framework.trainer import TrainLoop, Trainer, _Prefetcher
from swiftsnails_tpu.telemetry import tracer as tracer_mod
from swiftsnails_tpu.telemetry.tracer import NO_SPAN, Tracer, span_fn, tracer_from_config
from swiftsnails_tpu.utils.config import Config
from swiftsnails_tpu.utils.metrics import MetricsLogger
from swiftsnails_tpu.utils.profiling import PHASES, phase_scope

TRACED = {"telemetry": "1", "goodput": "0", "blackbox_steps": "0"}


class ToyTrainer(Trainer):
    """Seven small batches through a step that uses its rng, so that the
    step number reaches the state."""

    name = "toy"

    def init_state(self):
        return {"w": jnp.zeros((4,), jnp.float32)}

    def batches(self):
        for i in range(7):
            yield {"x": np.full((8, 4), i + 1, np.float32)}

    def train_step(self, state, batch, rng):
        w = state["w"] + batch["x"].mean(0) * jax.random.uniform(rng, (4,))
        return {"w": w}, {"loss": w.sum()}


def _toy_run(keys, max_steps=None, log_every=3):
    loop = TrainLoop(ToyTrainer(Config(dict(keys))),
                     metrics=MetricsLogger(echo=False, stream=io.StringIO()), log_every=log_every)
    state = loop.run(seed=5, max_steps=max_steps)
    return loop, np.asarray(state["w"])


def _windows(loop):
    """(step, items, loss) of every metrics window the run flushed."""
    recs = [json.loads(line) for line in loop.metrics._stream.getvalue().splitlines()]
    return [(r["step"], r["items"], r.get("loss")) for r in recs if "items_per_sec" in r]


# ------------------------------------------------------ one loop body ---


@pytest.mark.parametrize("max_steps", [None, 4])
@pytest.mark.parametrize("extra", [{}, {"prefetch_batches": "0"}, {"profile_cadence": "2"}])
def test_traced_and_plain_runs_agree_bit_for_bit(extra, max_steps, tmp_path):
    extra = dict(extra, incident_dir=str(tmp_path / "inc"))
    plain, w_plain = _toy_run(extra, max_steps)
    traced, w_traced = _toy_run({**TRACED, **extra}, max_steps)
    assert plain.tracer is None and traced.tracer is not None
    assert w_plain.tobytes() == w_traced.tobytes()
    steps = max_steps or 7
    assert plain._items_seen == traced._items_seen == 8 * steps
    assert sum(e["name"] == "step" for e in traced.tracer.events()) == steps
    assert _windows(plain) == _windows(traced)
    assert sum(items for _, items, _ in _windows(plain)) == 8 * steps


def test_run_has_one_dispatch_site():
    """The jitted step is called from one source line of ``run`` (a second
    would compile Mosaic kernels again: they carry their call site)."""
    import inspect

    src = inspect.getsource(TrainLoop.run)
    assert len(re.findall(r"self\._step_fn\(", src)) == 1
    assert len(re.findall(r"self\._resilient_step\(", src)) == 1
    assert len(re.findall(r"\bnext\(it, _STREAM_END\)", src)) == 2  # resume's skip, the loop


def test_no_span_object_without_telemetry(monkeypatch):
    made = []
    real = tracer_mod._SpanCtx.__init__

    def counting(self, *a, **k):
        made.append(1)
        real(self, *a, **k)

    monkeypatch.setattr(tracer_mod._SpanCtx, "__init__", counting)
    loop, _ = _toy_run({})
    assert loop.tracer is None and loop.registry is None and made == []
    span = span_fn(None)
    assert span("step", step=3) is NO_SPAN and span("h2d") is NO_SPAN
    with span("anything") as got:
        assert got is None
    assert tracer_from_config(Config({})) is None
    # and with telemetry every step makes its spans
    _toy_run(TRACED)
    assert len(made) >= 7 * 4


def test_traced_toy_run_spans_and_step_numbers():
    loop, _ = _toy_run(TRACED)
    evs = loop.tracer.events()
    names = {e["name"] for e in evs}
    assert {"produce", "prefetch-wait", "toy", "h2d", "step", "metrics-flush",
            "drain", "finalize"} <= names, names
    for name in ("step", "h2d", "toy"):
        assert [e["args"]["step"] for e in evs if e["name"] == name] == list(range(7))
    # the loop asks once more than it gets a batch; so does the producer
    assert sum(e["name"] == "prefetch-wait" for e in evs) == 8
    assert sum(e["name"] == "produce" for e in evs) == 8
    main = {e["tid"] for e in evs if e["name"] == "step"}
    assert {e["tid"] for e in evs if e["name"] == "produce"}.isdisjoint(main)
    # drain, then the end-of-run finalize, after every step; a teardown
    # finalize before the drain
    last_step = max(e["ts_us"] + e["dur_us"] for e in evs if e["name"] == "step")
    drain = next(e for e in evs if e["name"] == "drain")
    fin = [e for e in evs if e["name"] == "finalize"]
    assert len(fin) == 2 and drain["ts_us"] >= last_step
    assert fin[0]["ts_us"] + fin[0]["dur_us"] <= drain["ts_us"] + 1e-3
    assert fin[1]["ts_us"] >= drain["ts_us"] + drain["dur_us"] - 1e-3
    # step and h2d nest in the step's outer span
    outer = {e["args"]["step"]: e for e in evs if e["name"] == "toy"}
    for e in evs:
        if e["name"] in ("step", "h2d"):
            o = outer[e["args"]["step"]]
            assert e["depth"] == o["depth"] + 1
            assert o["ts_us"] <= e["ts_us"] and e["ts_us"] + e["dur_us"] <= o["ts_us"] + o["dur_us"] + 1e-3


def test_producer_waits_in_queue_full_spans():
    tr = Tracer()
    pre = _Prefetcher(iter(range(6)), depth=1, span=tr.span)
    import time

    time.sleep(0.3)  # the producer fills the one slot and then waits
    got = list(pre)
    pre.close()
    assert got == list(range(6))
    evs = tr.events()
    assert sum(e["name"] == "produce" for e in evs) == 7
    full = [e for e in evs if e["name"] == "queue-full"]
    assert full and max(e["dur_us"] for e in full) > 1e5


def test_finalize_span_on_error_and_trace_written(tmp_path):
    class Failing(ToyTrainer):
        def batches(self):
            yield {"x": np.ones((8, 4), np.float32)}
            raise RuntimeError("injected data failure")

    path = tmp_path / "trace.json"
    loop = TrainLoop(Failing(Config({"trace_path": str(path), "blackbox_steps": "0"})),
                     metrics=MetricsLogger(echo=False), log_every=0)
    with pytest.raises(RuntimeError):
        loop.run()
    names = [e["name"] for e in json.load(open(path))["traceEvents"]]
    assert "finalize" in names and "step" in names and "drain" not in names


# ------------------------------------------------------ set-up spans ---


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(40)]
    path = tmp_path_factory.mktemp("corpus") / "corpus.txt"
    with open(path, "w") as f:
        for _ in range(300):
            f.write(" ".join(rng.choice(words, 20)) + "\n")
    return str(path)


def _w2v_cfg(corpus, **extra):
    keys = {"model": "word2vec", "data": corpus, "dim": "16", "window": "2", "negatives": "2",
            "batch_size": "64", "min_count": "1", "subsample": "0", "num_iters": "1",
            "local_train": "1", "packed": "1", "fused": "1", "grouped": "1",
            "steps_per_call": "2", "centers_per_block": "32", "pool_size": "8"}
    keys.update(extra)
    return Config({k: str(v) for k, v in keys.items()})


def _inside(inner, outer):
    return (outer["ts_us"] <= inner["ts_us"]
            and inner["ts_us"] + inner["dur_us"] <= outer["ts_us"] + outer["dur_us"] + 1e-3)


@pytest.mark.parametrize("model", ["word2vec", "widedeep"])
def test_build_trainer_spans_reach_the_loops_tracer(model, corpus, tmp_path):
    from swiftsnails_tpu import cli

    if model == "word2vec":
        cfg = _w2v_cfg(corpus, **TRACED)
        inner = ["load-data", "alias-table"]
    else:
        rng = np.random.default_rng(1)
        path = tmp_path / "ctr.txt"
        with open(path, "w") as f:
            for _ in range(600):
                f.write(f"{rng.integers(2)} " + " ".join(
                    str(rng.integers(1, 50)) for _ in range(4)) + "\n")
        cfg = Config({"model": "widedeep", "data": str(path), "num_fields": "4",
                      "capacity": "1024", "batch_size": "64", "embed_dim": "4",
                      "hidden_dims": "8", "local_train": "1", **TRACED})
        inner = ["load-data"]
    trainer = cli._build_trainer(cfg)
    assert trainer.tracer is not None
    loop = TrainLoop(trainer, metrics=MetricsLogger(echo=False), log_every=0)
    assert loop.tracer is trainer.tracer  # adopted, not a second one
    loop.run(max_steps=3)
    evs = loop.tracer.events()
    build = [e for e in evs if e["name"] == "build-trainer"]
    assert len(build) == 1 and build[0]["depth"] == 0
    for name in inner:
        (e,) = [e for e in evs if e["name"] == name]
        assert e["depth"] == 1 and _inside(e, build[0])
    first_step = min(e["ts_us"] for e in evs if e["name"] == "step")
    assert build[0]["ts_us"] + build[0]["dur_us"] <= first_step
    assert {"produce", "prefetch-wait", "h2d", "step", "drain", "finalize"} <= {e["name"] for e in evs}
    # the run record's decomposition starts at run(), not at set-up
    assert loop._run_event_idx == len(build) + len(inner)


def test_build_trainer_makes_no_tracer_without_telemetry(corpus):
    from swiftsnails_tpu import cli

    trainer = cli._build_trainer(_w2v_cfg(corpus))
    assert trainer.tracer is None and trainer.span("load-data") is NO_SPAN
    assert TrainLoop(trainer, log_every=0).tracer is None


# ------------------------------------------------------ phase scopes ---


def _lowered_for_tpu(trainer, monkeypatch):
    """The step's StableHLO as lowered for a TPU (no chip needed), with the
    row-DMA plane's kernels instead of their XLA twins."""
    from swiftsnails_tpu.ops import rowdma

    monkeypatch.setattr(rowdma, "on_tpu", lambda: True)
    state = trainer.init_state()
    batch = next(iter(trainer.batches()))
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    traced = jax.jit(trainer.train_step).trace(state, dev, jax.random.PRNGKey(0))
    return traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


def _custom_call_paths(txt):
    """The name-scope path of every Mosaic call, through the private
    functions that inner jits lower to: [path from the entry function]."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', txt, re.M))
    func, calls, customs = None, [], []
    for line in txt.splitlines():
        m = re.match(r"\s*func\.func (?:public |private )?@([\w.\-]+)", line)
        if m:
            func = m.group(1)
        ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
        path = locs.get(ref.group(1), "") if ref else ""
        m = re.search(r"(?:func\.)?call @([\w.\-]+)\(", line)
        if m:
            calls.append((func, m.group(1), path))
        if "@tpu_custom_call" in line:
            customs.append((func, path))

    def prefixes(f, seen=()):
        sites = [(c, p) for c, callee, p in calls if callee == f and c not in seen]
        if not sites:
            return [""]
        return [pre + "/" + p for c, p in sites for pre in prefixes(c, seen + (f,))]

    return [pre + "/" + p for f, p in customs for pre in prefixes(f)]


def test_word2vec_grouped_step_names_its_phases(corpus, monkeypatch):
    from swiftsnails_tpu import cli

    trainer = cli._build_trainer(_w2v_cfg(corpus, dim="128"))
    txt = _lowered_for_tpu(trainer, monkeypatch)
    assert set(re.findall(r"phase_(\w+)", txt)) == {"prep", "fused"}
    paths = _custom_call_paths(txt)
    # under `fused`, and still named after the kernel's function (XLA names
    # the instruction, and so the device timeline's event, by the innermost scope)
    assert paths and all("phase_fused/fused_sgns_grouped_step/pallas_call" in p for p in paths), paths
    # the negatives' draw and the copy lists are prep
    assert re.search(r'loc\("[^"]*phase_prep/[^"]*(threefry|random)', txt)
    assert re.search(r'loc\("[^"]*phase_prep/[^"]*(sort|scatter)', txt)


def test_word2vec_interpret_step_carries_the_same_scopes(corpus):
    """Off the chip the kernel runs in interpret mode; the scopes are there
    all the same (they are metadata, not a path)."""
    from swiftsnails_tpu import cli

    trainer = cli._build_trainer(_w2v_cfg(corpus))
    state = trainer.init_state()
    batch = next(iter(trainer.batches()))
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    txt = jax.jit(trainer.train_step).lower(state, dev, jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert set(re.findall(r"phase_(\w+)", txt)) == {"prep", "fused"}
    assert re.search(r'loc\("[^"]*phase_fused/fused_sgns_grouped_step/pallas_call"', txt)


def test_widedeep_step_names_its_phases(monkeypatch):
    from test_ctr_models import NUM_FIELDS, VOCAB_PER_FIELD, make_cfg, synth_ctr

    from swiftsnails_tpu.models.registry import get_model

    labels, feats, _ = synth_ctr(2048, NUM_FIELDS, VOCAB_PER_FIELD, seed=3)
    trainer = get_model("widedeep")(make_cfg(), mesh=None, data=(labels, feats))
    txt = _lowered_for_tpu(trainer, monkeypatch)
    assert set(re.findall(r"phase_(\w+)", txt)) == {"prep", "pull", "dense", "push"}
    paths = _custom_call_paths(txt)
    pull = [p for p in paths if "gather_rows" in p]
    push = [p for p in paths if "scatter_adagrad_fused_rows" in p]
    assert len(pull) == 1 and len(push) == 1 and len(paths) == 2, paths
    assert "phase_pull/ssn_pull_packed_small" in pull[0] and "phase_push" not in pull[0]
    assert "phase_push/ssn_push_packed_small" in push[0] and "phase_pull" not in push[0]
    # forward, backward and the dense update are `dense`
    assert re.search(r'loc\("[^"]*phase_dense/jvp\(\)/dot_general', txt)
    assert re.search(r'loc\("[^"]*phase_dense/transpose\(jvp\(\)\)/dot_general', txt)


def test_phase_scope_knows_its_names():
    assert PHASES == ("prep", "fused", "pull", "push", "dense",
                      "attn", "mlp", "route", "experts", "head", "opt", "noise", "kda", "gdn")
    assert all(p.isalpha() and p.islower() for p in PHASES)  # benchmark/lib/scopes.py reads phase_[a-z]+
    with pytest.raises(ValueError):
        phase_scope("warmup")
    from swiftsnails_tpu.telemetry.audit import _SCOPE_RE

    for p in PHASES:
        assert not _SCOPE_RE.search("jit(_step)/phase_" + p + "/mul")


@pytest.mark.parametrize("packed", ["0", "1"])
def test_comm_audit_by_scope_unchanged_by_phase_scopes(packed, monkeypatch):
    """``telemetry/audit.py`` groups a mesh step's collective bytes by the
    first ``ssn_*`` label; the phase scopes around them change nothing."""
    from test_ctr_models import NUM_FIELDS, VOCAB_PER_FIELD, make_cfg, synth_ctr

    import swiftsnails_tpu.models.sparse_base as sparse_base
    from swiftsnails_tpu.models.registry import get_model
    from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
    from swiftsnails_tpu.telemetry.audit import audit_step

    labels, feats, _ = synth_ctr(2048, NUM_FIELDS, VOCAB_PER_FIELD, seed=3)
    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})

    def by_scope():
        tr = get_model("logreg")(make_cfg(packed=packed), mesh=mesh, data=(labels, feats))
        batch = {k: jnp.asarray(v) for k, v in next(iter(tr.batches())).items()}
        rep = audit_step(jax.jit(tr.train_step), tr.init_state(), batch, jax.random.PRNGKey(0))
        assert "error" not in rep
        return rep["by_scope"], rep["by_table"]

    with_scopes = by_scope()
    monkeypatch.setattr(sparse_base, "phase_scope", lambda phase: contextlib.nullcontext())
    without = by_scope()
    assert with_scopes == without
    assert with_scopes[0] and all(k.startswith("ssn_") for k in with_scopes[0])
    assert sum(with_scopes[0].values()) > 0
