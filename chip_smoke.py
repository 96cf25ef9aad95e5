#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls, at the full width of ``examples/word2vec_1m.conf`` (Word2Vec, a
1,048,576 x 200 f32 table, the fused center-major kernel), with random
weights made from a seed:

* leg K — the grouped SGNS kernel compiled for the chip against the same
  call with ``interpret=True`` on collision-free inputs
  (``ops/kernel_check.py``), and ``framework.quality.probe_top1`` on the
  leg-A step path (>= ``MIN_TOP1``): together they catch a DMA wait that
  retires early, which no CPU run can see;
* leg A — ``parse_role_argv(["-config", ...])`` -> ``cli._build_trainer`` ->
  ``TrainLoop(trainer).run(max_steps=N)``, exactly what ``snails train``
  does, on one chip; the compiled step's HLO must hold the Mosaic custom
  call (the Pallas kernel ran — not interpret mode, not the XLA twin);
* leg B — ``Servant.from_checkpoint`` on the checkpoint leg A wrote; ``pull``
  on the f32 wire must be bit-identical to the trained rows, ``topk`` finite
  and self-consistent;
* leg C — only with >= 4 devices: the same config through the same entry
  point on the mesh ``_build_trainer`` picks (2x2), checking shard
  placement, per-device memory, the collectives in the compiled step and the
  first-macro loss against a 1x1-mesh run of the same seed.

The corpus is generated here from a seed: every one of V = capacity words
appears once (a permutation) plus a zipf(1.05) stream over the same V, then
the whole is shuffled and written as text. ``min_count: 1`` keeps all V, so
the vocabulary the trainer builds from the file is V words ranked by count
and its row ids span the whole table, not its first rows.

It fails — non-zero exit, no result line — unless ``jax.devices()[0]`` is a
TPU whose ``device_kind`` is in the peaks table; any leg's exception is
fatal; every leg runs under a deadline on a watchdog thread that says which
leg and what was in flight and leaves through ``os._exit`` (a hung Mosaic
kernel cannot be interrupted from Python). Timings it prints are smoke
information, not benchmark results. Small text/JSON goes to
``chiprun_out/``; the ~2 GB checkpoint and the corpus go to a temporary
directory outside the tree and are removed.

    python3 chip_smoke.py            # on the chip; last stdout line is the result
    python3 chip_smoke.py --size tiny   # any platform: the tier-1 test's size
"""

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")
CONF = os.path.join(ROOT, "examples", "word2vec_1m.conf")

# what each size overrides on the command line of the shared config file,
# and how much work each leg does
SIZES = {
    "full": {
        "overrides": {},
        "zipf_tokens": 2_000_000, "steps": 12, "timed_steps": 4,
        "mesh_steps": 3, "check": {},
    },
    "tiny": {
        "overrides": {"capacity": "2048", "dim": "32", "batch_size": "256",
                      "steps_per_call": "2", "centers_per_block": "64"},
        "zipf_tokens": 40_000, "steps": 4, "timed_steps": 2,
        "mesh_steps": 2,
        "check": {"capacity": 8192, "dim": 32, "n": 256,
                  "centers_per_block": 64},
    },
}
LEG_DEADLINE_S = {"K": 300, "A": 420, "B": 240, "C": 420}
TOTAL_DEADLINE_S = 1150  # the whole script, compilation included
# leg C: |loss(2x2) - loss(1x1)| / loss(1x1) on the first macro-step. Same
# seed, same batches, same merged-update math; only the f32 summation order
# of the psum/all_gather assembly differs.
MESH_LOSS_RTOL = 1e-4


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def check(ok, msg) -> None:
    """The smoke's assertions; unlike ``assert`` they survive ``python -O``."""
    if not ok:
        raise AssertionError(msg)


@contextlib.contextmanager
def leg(wd, name: str):
    """One leg under its deadline, with start/ok lines around it."""
    t0 = time.monotonic()
    say(f"leg {name}: start (deadline {LEG_DEADLINE_S[name]} s)")
    with wd.watch(f"leg {name}", LEG_DEADLINE_S[name]):
        yield
    say(f"leg {name}: ok in {time.monotonic() - t0:.1f} s")


class CompileLog:
    """Backend compile seconds per jitted function and persistent-cache
    traffic, from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.by_fun, self.count, self.requests, self.hits = {}, {}, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.by_fun[name] = self.by_fun.get(name, 0.0) + secs
            self.count[name] = self.count.get(name, 0) + 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def report(self) -> dict:
        top = sorted(self.by_fun.items(), key=lambda kv: -kv[1])[:8]
        return {
            "compile_seconds_total": round(sum(self.by_fun.values()), 2),
            "compile_seconds_by_function": {
                f"{k} x{self.count[k]}": round(v, 2) for k, v in top},
            "persistent_cache_requests": self.requests,
            "persistent_cache_hits": self.hits,
        }


def write_corpus(path: str, vocab: int, zipf_tokens: int, seed: int = 0) -> int:
    """The generated corpus described in the module docstring; returns the
    token count."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks**1.05)
    cdf /= cdf[-1]
    ids = np.concatenate([
        rng.permutation(vocab),
        np.searchsorted(cdf, rng.random(zipf_tokens)),
    ])
    rng.shuffle(ids)
    with open(path, "w") as f:
        for lo in range(0, len(ids), 1 << 18):
            f.write(" ".join(f"w{i}" for i in ids[lo : lo + (1 << 18)]))
            f.write("\n")
    return len(ids)


def parse_config(extra_argv):
    """The reference entry contract on a clean global config."""
    from swiftsnails_tpu.utils.config import global_config
    from swiftsnails_tpu.utils.flags import parse_role_argv

    global_config().clear()
    return parse_role_argv(["-config", CONF] + list(extra_argv))


def size_argv(size: str):
    argv = []
    for k, v in SIZES[size]["overrides"].items():
        argv += [f"-{k}", v]
    return argv


def make_loop(trainer, out_dir: str, tag: str):
    """(TrainLoop, metrics path): the loop logs every step's loss to a fresh
    JSONL file in the output directory."""
    from swiftsnails_tpu.framework.trainer import TrainLoop
    from swiftsnails_tpu.utils.metrics import MetricsLogger

    path = os.path.join(out_dir, f"{tag}_metrics.jsonl")
    if os.path.exists(path):
        os.remove(path)
    return TrainLoop(trainer, metrics=MetricsLogger(path=path),
                     log_every=1), path


def read_losses(path: str):
    with open(path) as f:
        records = [json.loads(line) for line in f if line.strip()]
    return [r["loss"] for r in records if "loss" in r]


def compile_step(loop, trainer, rng):
    """AOT-compile the loop's train step on the trainer's first batch and a
    throwaway state; returns (optimized HLO text, seconds). Done ahead of
    the run so the seconds and the HLO are in hand; the run's own first call
    then finds the program already compiled."""
    gen = trainer.batches()
    first = next(gen)
    gen.close()
    state0 = trainer.init_state()
    t0 = time.perf_counter()
    compiled = loop._step_fn.lower(
        state0, loop._device_batch(first), rng, np.uint32(0)).compile()
    return compiled.as_text(), time.perf_counter() - t0


# ------------------------------------------------------------------ leg K ---


def leg_kernel(size: str, wd) -> dict:
    from swiftsnails_tpu.framework.quality import MIN_TOP1, probe_top1
    from swiftsnails_tpu.ops.kernel_check import check_kernel, release_reference
    from swiftsnails_tpu.ops.rowdma import on_tpu

    wd.note("fused_sgns_grouped_step: compile + run + interpret reference")
    row = check_kernel("fused_sgns_grouped_step", interpret=not on_tpu(),
                       **SIZES[size]["check"])
    release_reference()
    say(f"kernel check: {json.dumps(row)}")
    check(row["agrees"],
          f"compiled grouped kernel disagrees with interpret=True: {row}")
    wd.note("probe_top1 on the leg-A step path (grouped kernel, probe shape)")
    cfg = parse_config([])
    # the keys that select leg A's step path; the probe keeps its own sizes
    top1 = probe_top1({
        k: cfg.get_str(k) for k in ("packed", "neg_mode", "fused", "grouped")})
    say(f"quality probe: pair top-1 {top1:.3f} (bar {MIN_TOP1})")
    check(top1 >= MIN_TOP1, f"probe_top1 {top1:.3f} < {MIN_TOP1}")
    return {"kernel_check": row, "probe_top1": top1}


# ------------------------------------------------------------------ leg A ---


def leg_train(size: str, work_dir: str, wd, out_dir: str) -> dict:
    import jax

    from swiftsnails_tpu import cli
    from swiftsnails_tpu.ops.rowdma import on_tpu, unpack_rows

    spec = SIZES[size]
    wd.note("generating the corpus")
    corpus = os.path.join(work_dir, "corpus.txt")
    ckpt_root = os.path.join(work_dir, "ckpt")
    argv = size_argv(size) + [
        "-data", corpus, "-param_backup_root", ckpt_root,
        "-param_backup_period", str(spec["steps"])]
    if jax.device_count() > 1:
        argv += ["-local_train", "1"]  # leg A is the one-chip leg
    cfg = parse_config(argv)
    seed = cfg.get_int("seed", 0)
    n_tokens = write_corpus(corpus, cfg.get_int("capacity"),
                            spec["zipf_tokens"])
    wd.note("building the trainer (vocab from the corpus file)")
    trainer = cli._build_trainer(cfg)
    check(trainer.mesh is None, "leg A must be the single-device path")
    vocab_n = len(trainer.vocab)
    say(f"leg A: corpus {n_tokens:,} tokens, vocab {vocab_n:,} words over a "
        f"{trainer.capacity:,} x {trainer.dim} table; step path "
        f"fused={trainer.fused} grouped={trainer.grouped}")
    check(vocab_n == trainer.capacity == cfg.get_int("capacity"),
          "the vocabulary does not span the table")
    check(trainer.fused and trainer.grouped and trainer.packed,
          "the config did not select the fused-grouped step path")

    loop, metrics_path = make_loop(trainer, out_dir, "leg_a")
    wd.note("train step: lower + compile (fused_sgns_grouped_step inside)")
    root_rng = jax.random.PRNGKey(seed)
    hlo, compile_s = compile_step(loop, trainer, root_rng)
    mosaic_calls = hlo.count("tpu_custom_call")
    check(mosaic_calls > 0 or not on_tpu(),
          "the compiled train step holds no Mosaic custom call: the Pallas "
          "kernel is not what would run")
    say(f"leg A: train step compiled in {compile_s:.1f} s; Mosaic custom "
        f"calls in its HLO: {mosaic_calls}"
        + ("" if on_tpu() else " (not on a TPU: interpret mode, not checked)"))

    wd.note(f"TrainLoop.run(max_steps={spec['steps']}) + final checkpoint")
    t0 = time.perf_counter()
    state = loop.run(seed=seed, max_steps=spec["steps"])
    run_s = time.perf_counter() - t0
    loop.metrics.close()
    losses = read_losses(metrics_path)
    check(len(losses) >= spec["steps"] and all(np.isfinite(losses)),
          f"missing or non-finite loss: {losses}")
    say(f"leg A: {spec['steps']} macro-steps + checkpoint in {run_s:.1f} s; "
        f"loss first {losses[0]:.5f} last {losses[-1]:.5f}")

    # the rows leg B must get back, taken before the timed steps move them
    ids = np.unique(np.linspace(0, vocab_n - 1, 96).astype(np.int32))
    want = {
        name: np.asarray(unpack_rows(getattr(state, name).table[ids],
                                     trainer.dim))
        for name in ("in_table", "out_table")}
    check(np.abs(want["out_table"]).max() > 0, "out_table never trained")

    # smoke information: warm step seconds, each ended by block_until_ready
    wd.note("timed warm steps (block_until_ready)")
    gen = trainer.batches()
    dev = [loop._device_batch(next(gen)) for _ in range(spec["timed_steps"])]
    gen.close()
    step_s = []
    for i, b in enumerate(dev):
        t0 = time.perf_counter()
        state, m = loop._step_fn(state, b, root_rng,
                                 np.uint32(spec["steps"] + i))
        jax.block_until_ready((state, m))
        step_s.append(time.perf_counter() - t0)
    # does block_until_ready force the queued work? queue the same steps
    # without blocking, block, then fetch a scalar: the fetch must find
    # nothing left to wait for
    t0 = time.perf_counter()
    for i, b in enumerate(dev):
        state, m = loop._step_fn(state, b, root_rng, np.uint32(100 + i))
    jax.block_until_ready((state, m))
    wait_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    last_loss = float(m["loss"])
    fetch_s = time.perf_counter() - t0
    check(np.isfinite(last_loss), f"non-finite loss {last_loss}")
    check(fetch_s < max(0.02, 0.25 * wait_s),
          f"scalar fetch after block_until_ready still waited {fetch_s:.3f} s "
          f"(block_until_ready {wait_s:.3f} s): it does not block here")
    words = dev[0]["centers"].shape[0]
    say(f"smoke-info (not a benchmark result): warm macro-step "
        f"{min(step_s) * 1e3:.1f}..{max(step_s) * 1e3:.1f} ms for "
        f"{words:,} words, ended by block_until_ready; "
        f"{len(dev)} queued steps: block_until_ready waited "
        f"{wait_s * 1e3:.1f} ms, the scalar fetch after it "
        f"{fetch_s * 1e3:.2f} ms")
    del state, dev
    return {
        "cfg": cfg, "ckpt_root": ckpt_root, "ids": ids, "want": want,
        "report": {
            "vocab": vocab_n, "capacity": trainer.capacity,
            "dim": trainer.dim, "steps": spec["steps"],
            "train_step_compile_s": round(compile_s, 2),
            "mosaic_custom_calls": mosaic_calls,
            "loss_first": losses[0], "loss_last": losses[-1],
            "warm_step_ms": [round(s * 1e3, 2) for s in step_s],
            "block_until_ready_wait_ms": round(wait_s * 1e3, 2),
            "fetch_after_block_ms": round(fetch_s * 1e3, 3),
        },
    }


# ------------------------------------------------------------------ leg B ---


def leg_serve(a: dict, wd) -> dict:
    from swiftsnails_tpu.serving import Servant

    cfg, ids = a["cfg"], a["ids"]
    wd.note("Servant.from_checkpoint (restore + CRC verify + normalize)")
    t0 = time.perf_counter()
    with Servant.from_checkpoint(a["ckpt_root"], cfg) as sv:
        load_s = time.perf_counter() - t0
        check(sv.step == a["report"]["steps"],
              f"served step {sv.step}, trained {a['report']['steps']}")
        wd.note("pull requests (serving/kernels.pull_rows)")
        for name, want in a["want"].items():
            got = np.asarray(sv.pull(ids, table=name))
            check(got.shape == want.shape and got.dtype == np.float32
                  and np.array_equal(got.view(np.uint32), want.view(np.uint32)),
                  f"pull({name}) is not bit-identical to the trained rows")
        wd.note("topk requests (serving/kernels.topk_tiled)")
        capacity = a["report"]["capacity"]
        for pos in (1, len(ids) // 2, len(ids) - 1):
            row = int(ids[pos])
            hits = sv.topk(a["want"]["in_table"][pos], k=5)
            scores = [s for _, s in hits]
            check(len(hits) == 5 and all(np.isfinite(scores))
                  and all(0 <= i < capacity for i, _ in hits)
                  and scores == sorted(scores, reverse=True),
                  f"topk malformed: {hits}")
            check(hits[0][0] == row and abs(hits[0][1] - 1.0) < 1e-3,
                  f"topk of row {row}'s own vector did not return it: {hits}")
    say(f"leg B: checkpoint step {a['report']['steps']} served after "
        f"{load_s:.1f} s; {2 * len(ids)} pulled rows bit-identical, 3 topk "
        "requests returned their own row at cosine 1.0")
    return {"load_s": round(load_s, 2), "pulled_rows": 2 * len(ids)}


# ------------------------------------------------------------------ leg C ---


def leg_mesh(size: str, work_dir: str, wd, out_dir: str) -> dict:
    import jax

    from swiftsnails_tpu import cli
    from swiftsnails_tpu.ops.rowdma import on_tpu
    from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh

    spec = SIZES[size]
    cfg = parse_config(size_argv(size) + [
        "-data", os.path.join(work_dir, "corpus.txt"),
        "-param_backup_root", ""])
    seed = cfg.get_int("seed", 0)
    # leg B's Servant sits in reference cycles (its batcher threads hold
    # bound methods), so its 2 x 0.8 GB of tables on device 0 outlive
    # close() until the cycle collector runs; the per-device balance below
    # is about this leg's tables only
    gc.collect()
    wd.note("building the mesh trainer")
    trainer = cli._build_trainer(cfg)
    mesh = trainer.mesh
    check(mesh is not None, "_build_trainer picked no mesh on >= 4 devices")
    data, model = mesh.shape[DATA_AXIS], mesh.shape[MODEL_AXIS]
    say(f"leg C: _build_trainer picked a {data}x{model} (data, model) mesh "
        f"over {mesh.size} devices")

    state0 = trainer.init_state()
    for name in ("in_table", "out_table"):
        shards = getattr(state0, name).table.addressable_shards
        check(len({s.device for s in shards}) == len(shards) == mesh.size
              and {s.data.shape[0] for s in shards}
              == {trainer.capacity // model},
              f"{name} is not 1/{model} of the rows on each of {mesh.size} "
              f"distinct devices: {shards}")
    del state0
    loop, path = make_loop(trainer, out_dir, "leg_c_mesh")
    wd.note("mesh train step: lower + compile (shard-local row-DMA kernels "
            "inside shard_map)")
    hlo, _ = compile_step(loop, trainer, jax.random.PRNGKey(seed))
    found = {op: hlo.count(op) for op in
             ("all-reduce", "all-gather", "tpu_custom_call")}
    check(found["all-reduce"] > 0 and found["all-gather"] > 0
          and (found["tpu_custom_call"] > 0 or not on_tpu()),
          f"the compiled mesh step lacks its collectives or kernels: {found}")
    say(f"leg C: each table in {mesh.size} shards on distinct devices, "
        f"{trainer.capacity // model:,} rows each (1/{model}); compiled "
        f"step holds {found}")

    wd.note(f"mesh TrainLoop.run(max_steps={spec['mesh_steps']})")
    state = loop.run(seed=seed, max_steps=spec["mesh_steps"])
    loop.metrics.close()
    losses = read_losses(path)
    check(len(losses) >= spec["mesh_steps"] and all(np.isfinite(losses)),
          f"missing or non-finite mesh loss: {losses}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh.devices.flat]
    check(None in in_use  # the CPU backend reports no memory stats
          or max(in_use) <= 1.25 * min(in_use) + (64 << 20),
          f"per-device bytes_in_use not balanced: {in_use}")
    del state

    wd.note("1x1-mesh run of the same seed (reference for the first loss)")
    mesh1 = make_mesh({DATA_AXIS: 1, MODEL_AXIS: 1},
                      devices=list(mesh.devices.flat)[:1])
    ref = type(trainer)(cfg, mesh=mesh1, corpus_ids=trainer.corpus_ids,
                        vocab=trainer.vocab)
    loop1, path1 = make_loop(ref, out_dir, "leg_c_1x1")
    loop1.run(seed=seed, max_steps=1)
    loop1.metrics.close()
    loss1 = read_losses(path1)[0]
    rel = abs(losses[0] - loss1) / abs(loss1)
    say(f"leg C: first-macro loss {data}x{model} {losses[0]:.7f} vs 1x1 "
        f"{loss1:.7f} (rel diff {rel:.2e}, tolerance {MESH_LOSS_RTOL:.0e}); "
        f"bytes_in_use per device {in_use}")
    check(rel <= MESH_LOSS_RTOL,
          f"first-macro loss differs between meshes: {losses[0]} vs {loss1}")
    return {"mesh": {"data": data, "model": model},
            "rows_per_shard": trainer.capacity // model,
            "hlo_ops": found, "bytes_in_use": in_use,
            "loss_mesh": losses[0], "loss_1x1": loss1, "loss_rel_diff": rel}


# ------------------------------------------------------------------- main ---


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--size", choices=sorted(SIZES), default="full",
                   help="full (default): north-star width, TPU required; "
                        "tiny: the tier-1 test's size, any platform")
    args = p.parse_args(argv)

    from swiftsnails_tpu.telemetry.goodput import peaks_for
    from swiftsnails_tpu.telemetry.ledger import env_fingerprint
    from swiftsnails_tpu.utils.compile_cache import configure_compile_cache
    from swiftsnails_tpu.utils.watchdog import Watchdog

    cache_dir = configure_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    env = env_fingerprint()
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']} jax={env.get('jax')} "
        f"jaxlib={env.get('jaxlib')} libtpu={env.get('libtpu')} "
        f"compile_cache={cache_dir}")
    if args.size == "full" and device["platform"] != "tpu":
        print(f"chip_smoke: platform is {device['platform']!r}, not 'tpu' "
              f"({device['kind']}, {device['count']} device(s)): no "
              "accelerator, nothing checked", file=sys.stderr)
        return 2
    peaks_for(device["kind"], device["platform"])  # unknown TPU kind raises

    os.makedirs(OUT_DIR, exist_ok=True)
    compiles = CompileLog()
    wd = Watchdog("smoke", total_s=TOTAL_DEADLINE_S)
    work_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    report = {"size": args.size, "device": device, "env": env}
    try:
        with leg(wd, "K"):
            report["leg_k"] = leg_kernel(args.size, wd)
        with leg(wd, "A"):
            a = leg_train(args.size, work_dir, wd, OUT_DIR)
            report["leg_a"] = a["report"]
        with leg(wd, "B"):
            report["leg_b"] = leg_serve(a, wd)
        del a
        if device["count"] >= 4:
            with leg(wd, "C"):
                report["leg_c"] = leg_mesh(args.size, work_dir, wd, OUT_DIR)
        else:
            say(f"leg C: not run ({device['count']} device)")
    finally:
        wd.close()
        shutil.rmtree(work_dir, ignore_errors=True)
    report["compile"] = compiles.report()
    say("smoke-info (not a benchmark result): "
        + json.dumps(report["compile"]))
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
