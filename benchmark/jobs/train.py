"""The job ``train``: ``TrainLoop(cli._build_trainer(cfg)).run()`` on the
configuration's feed, generated from the seed, with a probe around the loop's
one jitted step. The first ``WARM_STEPS`` calls are set-up (the first
compiles) and are what the reference follows; the window opens before the next
call on the same compiled step and state, and closes by
``request_preemption``. ``lib/jobs.py`` loads this file by the mix's ``job``.
"""

import gc
import os
import time

import numpy as np

from lib import compare
from lib.jobs import Profile, Run, memory_peak

WARM_STEPS = 3  # the reference follows these; the window starts after them


def _write_conf(path, keys: dict):
    with open(path, "w") as f:
        for k, v in keys.items():
            f.write(f"{k}: {v}\n")


def program_config(run: Run, work_dir: str, data_path: str):
    """The configuration's keys as the program's own config, parsed by its
    entry contract."""
    from swiftsnails_tpu.utils.config import global_config
    from swiftsnails_tpu.utils.flags import parse_role_argv

    keys = dict(run.config["keys"])
    keys.update({"data": data_path, "seed": run.seed & 0x7FFFFFFF})
    if run.chips == 1:
        keys["local_train"] = 1  # a one-chip cell, wherever it runs
    keys.update(run.mix.get("keys", {}))
    if run.traced:
        # the program's own spans, with nothing else of its telemetry
        keys.update({"telemetry": 1, "goodput": 0, "blackbox_steps": 0})
    conf = os.path.join(work_dir, "job.conf")
    _write_conf(conf, keys)
    global_config().clear()
    return parse_role_argv(["-config", conf])


class StepProbe:
    """Stands around the loop's jitted step: the first ``WARM_STEPS`` calls
    are set-up (the first compiles) and are read for the comparison; the
    window opens before the next call and closes ``seconds`` later by asking
    the loop to drain."""

    def __init__(self, run: Run, loop, read, t_process: float, profile=None):
        self.run, self.loop, self.read = run, loop, read
        self.inner = loop._step_fn
        self.t_process, self.profile = t_process, profile
        self.calls = 0
        self.items = 0
        self.batches, self.reads, self.losses = [], [], []
        self.stop_at = None

    def __call__(self, state, batch, rng, step):
        import jax

        n = self.calls
        if n == WARM_STEPS:
            jax.block_until_ready(state)
            if self.profile is not None:
                self.profile.start()
            self.run.t0 = time.perf_counter()
            self.run.setup_s = self.run.t0 - self.t_process
            self.stop_at = self.run.t0 + self.run.seconds
        if n < WARM_STEPS:
            self.batches.append({k: np.asarray(v) for k, v in batch.items()})
        if self.run.traced and n >= WARM_STEPS:
            with jax.profiler.TraceAnnotation("bench:train_step"):
                out, metrics = self.inner(state, batch, rng, step)
        else:
            out, metrics = self.inner(state, batch, rng, step)
        if n < WARM_STEPS:
            self.reads.append(self.read(out, np.uint32(self.run.seed & 0xFFFFFFFF)))
            self.losses.append(metrics["loss"])
        else:
            self.items += self.loop.trainer.items_per_batch(batch)
            self.run.attempted += 1
            if time.perf_counter() >= self.stop_at:
                self.loop.request_preemption("benchmark window closed")
        self.calls = n + 1
        return out, metrics


def run(run: Run, work_dir: str, t_process: float, precision="float32") -> Run:
    import jax

    from swiftsnails_tpu import cli
    from swiftsnails_tpu.framework.trainer import TrainLoop
    from swiftsnails_tpu.utils.metrics import MetricsLogger

    marks = run.counters["setup_marks"] = {}  # seconds since process start, for stderr

    def mark(name):
        marks[name] = round(time.perf_counter() - t_process, 2)

    mark("start")
    adapter_cls = run.model.Adapter
    data_path = adapter_cls.dataset(run, work_dir)
    mark("data")
    if precision != "float32":
        run.mix = {**run.mix, "keys": {**run.mix.get("keys", {}),
                                       **run.config["control"]["program_keys"]}}
    cfg = program_config(run, work_dir, data_path)
    trainer = cli._build_trainer(cfg)
    if (trainer.mesh is None) != (run.chips == 1):
        raise RuntimeError(f"a cell on {run.chips} chip(s) got the mesh {trainer.mesh}")
    mark("trainer")
    adapter = adapter_cls(run, trainer)

    def init_state():  # the benchmark makes the weights
        state = adapter.state()
        mark("weights")
        return state

    trainer.init_state = init_state
    loop = TrainLoop(trainer, metrics=MetricsLogger(echo=False),
                     log_every=int(run.mix.get("log_every", 100)))
    profile = Profile(work_dir) if run.traced else None
    probe = StepProbe(run, loop, adapter.readings(), t_process, profile)
    loop._step_fn = probe
    state = loop.run(seed=run.seed & 0x7FFFFFFF)
    run.t1 = time.perf_counter()
    if probe.calls <= WARM_STEPS:
        raise RuntimeError("the feed ended before the window opened")
    if not loop.preempted:
        raise RuntimeError("the feed ended inside the window: more epochs")
    run.end_to_end["train_items_per_s"] = probe.items / run.window_s
    run.counters["items"] = probe.items
    run.counters["steps"] = probe.calls - WARM_STEPS
    if loop.tracer is not None:
        epoch = loop.tracer._epoch_ns
        run.spans = [(e["name"], (e["ts_us"] * 1e3 + epoch) / 1e9, e["dur_us"] / 1e6)
                     for e in loop.tracer.events()]
    if profile is not None:
        profile.stop(run, run.spans)
    run.memory_peak_bytes = memory_peak()
    reads = jax.device_get(probe.reads)
    losses = [float(x) for x in jax.device_get(probe.losses)]
    del state, probe.reads, loop, trainer.init_state
    gc.collect()

    program = {"loss": losses, "grad1": adapter.program_grad1(reads),
               "change": {k: [r["change"][k] for r in reads] for k in reads[0]["change"]}}
    refs = {}

    def make_reference(variant):
        refs[variant] = adapter.reference(probe.batches, **dict(variant))
        return refs[variant]

    run.numbers, variant = compare.best_reference(
        program, make_reference, adapter.reference_variants(), run.limits)
    run.numbers.update(adapter.extra_numbers(probe.batches))
    run.counters["readings"] = {"program": program, "reference": refs[variant],
                                "reference_variant": dict(variant),
                                "worst_leaves": run.numbers.pop("worst_leaves")}
    run.extra = {"adapter": adapter, "batches": probe.batches, "profile": profile}
    return run
