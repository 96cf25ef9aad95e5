#!/usr/bin/env python
"""North-star benchmark: Word2Vec skip-gram words/sec/chip.

BASELINE.json: "Word2Vec words/sec/chip (text8, 1M vocab, dim=200)" on real
TPU, target >=10x an 8-node CPU parameter-server baseline. The reference
published no numbers (BASELINE.md), so the baseline is calibrated here from
compiled code: the single-node C SGNS worker loop in libsnails.cpp
(word2vec.c-shaped gather -> sigmoid -> scatter; the reference worker's
per-node hot path was C++, SwiftWorker.h:88-124), scaled by the reference's
Hadoop deployment width (8 worker reducers, hadoop-worker.sh
mapred.reduce.tasks=8).

Zero-egress environment: text8 is synthesized as a zipf-distributed token
stream with the same vocab size/shape; words/sec counts corpus tokens
consumed, derived from measured pairs/sec via the sampler's pairs-per-token
ratio (identical accounting for TPU and baseline).

No fallback hides the device: the run fails (non-zero exit, no result line)
unless ``jax.devices()[0].platform`` is ``tpu`` — or ``JAX_PLATFORMS``
explicitly names ``cpu``, the signal the CPU lane tests set, in which case
every line printed says ``platform: cpu``. The device check happens in this
process (a chip belongs to one process; nothing here starts a child). A
kernel that fails to compile, a stage that raises, or the deadline watchdog
firing all fail the run; no old number is ever replayed.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import sys
import threading
import time

import numpy as np

BENCH_DEADLINE_S = int(os.environ.get("SSN_BENCH_DEADLINE_S", "1500"))
# do not start a new TPU path with less budget than this (compile ~20-40s +
# measure; a path that can't finish would turn into a watchdog exit)
PATH_MIN_BUDGET_S = int(os.environ.get("SSN_PATH_MIN_BUDGET_S", "180"))

# -- workload shape (north-star config) --------------------------------------
# SSN_BENCH_SMALL=1 shrinks everything for CI/smoke runs (not a valid bench).
_SMALL = os.environ.get("SSN_BENCH_SMALL") == "1"
VOCAB = 20_000 if _SMALL else 1_000_000
DIM = 32 if _SMALL else 200
WINDOW = 5
NEGATIVES = 5
BATCH = 1_024 if _SMALL else 16_384
MEASURE_STEPS = 10 if _SMALL else 40  # macro-steps (= STEPS_PER_CALL substeps each)
WARMUP_STEPS = 3
BASELINE_NODES = 8  # reference deployment width (hadoop-worker.sh)
# fast-path knobs (see models/word2vec.py)
POOL_SIZE = 64
POOL_BLOCK = 512
STEPS_PER_CALL = 8
TABLE_DTYPE = "float32"
# VMEM-resident zipf head for the fused-resident path (tools/kernel_lab.py
# --resident sweep: hot=2048 @ cpb=256 wins on the v5e chip)
HOT_ROWS = 2048
# unique-row capacity for the fused-dedup path (block-ordered batches hit
# ~190 distinct ctx rows per 256-center block at the north-star shape)
U_CAP = 384
BASELINE_RUNS = 3  # median-of-N C-loop baseline (VERDICT r2 weak #1)

_T0 = time.monotonic()

# The run ledger every lane appends its record to (append-only; listed in
# .gitignore so a run does not dirty the tree).
LEDGER_PATH = os.environ.get(
    "SSN_LEDGER_PATH",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "RUN_LEDGER.jsonl"),
)


def _ledger():
    """The run ledger (lazy import: keeps bench importable stdlib-light)."""
    from swiftsnails_tpu.telemetry.ledger import Ledger

    return Ledger(LEDGER_PATH)


# Shared mutable result state: the main thread fills it in; the watchdog
# thread (GIL-serialized) reads it to emit the best result obtained so far.
_state = {
    "best": 0.0,
    "best_path": None,
    "paths": {},  # name -> words/sec
    "quality": {},  # name -> held-out per-pair SGNS eval loss (lower=better)
    "quality_pair_top1": {},  # name -> structured-corpus probe score in [0,1]
    "baseline_node": None,  # per-node words/sec (median of BASELINE_RUNS)
    "baseline_kind": None,  # "c-loop" | "numpy"
    "baseline_runs": [],  # per-run per-node words/sec (spread evidence)
    "spread": {},  # name -> relative spread between repeated measure windows
    "pairs_per_token": None,
    "input_words_per_sec": None,  # flat-pair host pipeline (non-grouped paths)
    "input_words_per_sec_grouped": None,  # window-schema pipeline (grouped path)
    "input_words_per_sec_production": None,  # the pipeline feeding the headline
    "platform": None,
    "at_scale": None,  # planted-pair structure at bench scale (dict)
    "scaling": None,  # multi-chip throughput lane (dict; see measure_scaling)
    "chaos": None,  # resilience lane (dict; see measure_chaos / --lane chaos)
    "serving": None,  # read-path latency lane (dict; see --lane serve)
    "fleet": None,  # replica-pool QPS-at-SLO lane (dict; see --lane fleet)
    "tiered": None,  # host-tier parameter store lane (dict; see --lane tiered)
    "chaos_serve": None,  # serving availability drill (dict; --lane chaos-serve)
    "chaos_cluster": None,  # cluster membership drill (dict; --lane chaos-cluster)
    "freshness": None,  # trainer->fleet delta pipeline lane (dict; --lane freshness)
    "drift": None,  # training-plane drift drill (dict; --lane drift)
    "profile_overhead": None,  # continuous profiler on-vs-off cost (--lane drift)
    "zero": None,  # sharded-optimizer-state lane (dict; see --lane zero)
    "net": None,  # TCP serving/liveness/delta-stream lane (dict; --lane net)
    "lane": "full",  # which lane emitted this line (full | chaos | serve | tiered | chaos-serve | chaos-cluster | freshness | drift | zero | net)
    "copies_per_pair": {},  # grouped/resident kernel row-copy census
    "best_overrides": None,  # headline path's trainer config overrides
    "attempted": set(),  # paths that ran to completion OR failed (not skipped)
    "comm_audit": {},  # name -> compiled-HLO communication audit (telemetry)
    "goodput": {},  # name -> MFU / roofline block (telemetry.goodput)
    "device_kind": None,  # set by require_device(), with platform above
    "device_count": None,
    "errors": [],
}
# divergence guard on the held-out eval loss: a path whose loss exceeds the
# untrained value ln2*(1+K) by this factor has blown up (NaN is also caught).
# Cross-path eval-loss comparison is deliberately NOT used — the paths train
# different pair counts per substep (grouped ~3x the flat paths), so only an
# absolute guard is fair; the real quality discriminator is the
# structured-corpus probe, which runs each path on identical footing.
DIVERGENCE_FACTOR = 1.05
_emit_lock = threading.Lock()
_emitted = False


def _emit_once(extra_error=None) -> bool:
    """Print the result JSON exactly once, process-wide.

    Both the main thread and the watchdog race to emit at the deadline; the
    lock + flag guarantee the driver sees ONE complete JSON line.
    """
    global _emitted
    with _emit_lock:
        if _emitted:
            return False
        _emitted = True
        print(_result_json(extra_error), flush=True)
        return True


def _finite(v, ndigits):
    """round() for JSON: non-finite floats become None (json null)."""
    import math

    return round(v, ndigits) if isinstance(v, (int, float)) and math.isfinite(v) else None


def _pinned_baseline():
    """The calibrated 8-node constant (tools/calibrate_baseline.py), or None.

    The live per-round baseline swings with machine load (r02: 134.7k,
    r03: 44.0k for the identical loop), so the pinned best-of-N constant —
    the strongest baseline this machine produces when idle — anchors the
    multiple; both are reported."""
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BASELINE_PINNED.json")) as f:
            return json.load(f)
    except Exception:
        return None


def _result_json(extra_error=None):
    errors = list(_state["errors"])
    if extra_error:
        errors.append(extra_error)
    node = _state["baseline_node"]
    baseline = BASELINE_NODES * node if node else 0.0
    value = _state["best"]
    pinned = _pinned_baseline()
    pinned_8 = (pinned or {}).get("baseline_words_per_sec_8node_pinned")
    return json.dumps(
        {
            "metric": "word2vec_words_per_sec_per_chip",
            "value": round(value, 1),
            "unit": "words/sec/chip",
            "vs_baseline": round(value / baseline, 3) if baseline else 0.0,
            "vs_baseline_pinned": (
                round(value / pinned_8, 3) if pinned_8 else None
            ),
            "baseline_words_per_sec_8node_pinned": pinned_8,
            "baseline_pinned_at": (pinned or {}).get("calibrated_at"),
            "baseline_words_per_sec_8node_cpu": round(baseline, 1),
            "baseline_kind": _state["baseline_kind"],
            "baseline_runs_words_per_sec_8node": [
                round(BASELINE_NODES * r, 1) for r in _state["baseline_runs"]
            ],
            "path": _state["best_path"],
            "paths": {k: round(v, 1) for k, v in _state["paths"].items()},
            "measure_spread": {k: _finite(v, 4) for k, v in _state["spread"].items()},
            # NaN (failed/skipped probe or diverged loss) -> null: the result
            # line must stay strict RFC 8259 JSON for the driver
            "quality": {k: _finite(v, 4) for k, v in _state["quality"].items()},
            "quality_pair_top1": {
                k: _finite(v, 3) for k, v in _state["quality_pair_top1"].items()
            },
            "pairs_per_token": (
                round(_state["pairs_per_token"], 3)
                if _state["pairs_per_token"]
                else None
            ),
            "input_words_per_sec": _finite(_state["input_words_per_sec"] or 0, 1) or None,
            "input_words_per_sec_grouped": _finite(
                _state["input_words_per_sec_grouped"] or 0, 1
            ) or None,
            "input_words_per_sec_production": _finite(
                _state.get("input_words_per_sec_production") or 0, 1
            ) or None,
            "platform": _state["platform"],
            "device": {"platform": _state["platform"],
                       "kind": _state["device_kind"],
                       "count": _state["device_count"]},
            "at_scale": _state["at_scale"],
            "scaling": _state["scaling"],
            "chaos": _state["chaos"],
            "serving": _state["serving"],
            "fleet": _state["fleet"],
            "tiered": _state["tiered"],
            "chaos_serve": _state["chaos_serve"],
            "chaos_cluster": _state["chaos_cluster"],
            "freshness": _state["freshness"],
            "drift": _state["drift"],
            "profile_overhead": _state["profile_overhead"],
            "zero": _state["zero"],
            "net": _state["net"],
            "lane": _state["lane"],
            "comm_audit": _state["comm_audit"],
            "goodput": _state["goodput"],
            "copies_per_pair": {
                k: _finite(v, 3) for k, v in _state["copies_per_pair"].items()
            },
            "elapsed_s": round(time.monotonic() - _T0, 1),
            "errors": errors,
            "config": {
                "vocab": VOCAB,
                "dim": DIM,
                "window": WINDOW,
                "negatives": NEGATIVES,
                "batch": BATCH,
                "steps_per_call": STEPS_PER_CALL,
                "pool": [POOL_BLOCK, POOL_SIZE],
                "table_dtype": TABLE_DTYPE,
            },
        }
    )


def _deadline():
    """Watchdog thread body: the hang is inside a single native PJRT call, so
    a SIGALRM handler would never run on the blocked main thread — a daemon
    thread reports what was measured so far on stderr and fails the run.
    No result line: a run that did not finish has no result."""
    _say(f"deadline {BENCH_DEADLINE_S}s hit; paths measured so far: "
         f"{ {k: round(v, 1) for k, v in _state['paths'].items()} }; "
         "failing the run")
    os._exit(1)


def _say(msg: str) -> None:
    """Progress line on stderr; always names the platform it ran on."""
    print(f"bench [platform: {_state['platform']}]: {msg}",
          file=sys.stderr, flush=True)


class NoAcceleratorError(RuntimeError):
    """The bench found no TPU and ``JAX_PLATFORMS`` does not name ``cpu``."""


def require_device() -> None:
    """In-process device check, first thing ``main`` does for every lane.

    Records platform / device kind / count in ``_state`` (they go into every
    result line) and refuses to measure anywhere but a TPU unless
    ``JAX_PLATFORMS`` explicitly says ``cpu``. Also places the compile cache
    (utils/compile_cache.py) before the first jit.
    """
    from swiftsnails_tpu.utils.compile_cache import configure_compile_cache
    from swiftsnails_tpu.utils.platform_pin import cpu_requested

    configure_compile_cache()
    import jax

    devs = jax.devices()
    _state["platform"] = devs[0].platform
    _state["device_kind"] = devs[0].device_kind
    _state["device_count"] = len(devs)
    if devs[0].platform != "tpu" and not cpu_requested():
        raise NoAcceleratorError(
            f"platform is {devs[0].platform!r} "
            f"({devs[0].device_kind}, {len(devs)} device(s)), not 'tpu': "
            "refusing to measure. Set JAX_PLATFORMS=cpu explicitly to run "
            "the CPU-valid lanes; their lines then say platform: cpu.")


def synth_corpus(n_tokens: int, vocab: int, seed: int = 0,
                 s: float = 1.05) -> np.ndarray:
    """Zipf-ish token stream over [0, vocab) — text8-shaped frequencies.

    ``s`` is the zipf exponent: 1.05 (default) is text8-flat; the skewed
    placement leg uses a steeper ``s`` so a small head carries most slots."""
    rng = np.random.default_rng(seed)
    # zipf via inverse-CDF over harmonic weights (bounded support)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    w = 1.0 / ranks**s
    cdf = np.cumsum(w) / w.sum()
    u = rng.random(n_tokens)
    return np.searchsorted(cdf, u).astype(np.int32)


def _compact_audit(report):
    """Trim a telemetry.audit report to the fields worth a JSON line."""
    out = {
        "collectives": report.get("ops", {}),
        "collective_bytes": report.get("total_bytes", 0),
    }
    if report.get("by_scope"):
        out["by_scope"] = report["by_scope"]
    cost = report.get("cost", {})
    for k in ("flops", "bytes_accessed"):
        if k in cost:
            out[k] = cost[k]
    mem = report.get("memory", {})
    for k in ("peak_memory_in_bytes", "temp_size_in_bytes",
              "argument_size_in_bytes"):
        if k in mem:
            out[k] = mem[k]
    return out


def _measure_tpu_config(counts, batches, pairs_per_token, overrides,
                        grouped=False, centers_per_macro=None,
                        audit_key=None):
    """Words/sec of one trainer config: a window of ``MEASURE_STEPS``
    macro-steps on a warm step function, timed on the host clock and ended
    by ``jax.block_until_ready`` on the donated state chain (dispatch is
    asynchronous; without it the clock measures the enqueue).
    """
    import jax
    import jax.numpy as jnp

    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    conf = {
        "dim": str(DIM),
        "window": str(WINDOW),
        "negatives": str(NEGATIVES),
        "learning_rate": "0.025",
        "batch_size": str(BATCH),
        "subsample": "0",
        "num_iters": "1",
        "steps_per_call": str(STEPS_PER_CALL),
        "table_dtype": TABLE_DTYPE,
    }
    conf.update(overrides)
    cfg = Config(conf)
    vocab = Vocab([f"w{i}" for i in range(VOCAB)], counts)
    trainer = Word2VecTrainer(
        cfg, mesh=None, corpus_ids=np.zeros(2, np.int32), vocab=vocab
    )
    state = trainer.init_state()
    step = jax.jit(trainer.train_step, donate_argnums=(0,))
    rng = jax.random.PRNGKey(0)
    dev_batches = [{k: jnp.asarray(v) for k, v in b.items()} for b in batches]
    for i in range(WARMUP_STEPS):
        state, m = step(
            state, dev_batches[i % len(dev_batches)], jax.random.fold_in(rng, i)
        )
    jax.block_until_ready((state, m))

    def timed_run(n_steps, base):
        nonlocal state, m
        t0 = time.perf_counter()
        for i in range(n_steps):
            state, m = step(
                state,
                dev_batches[(base + i) % len(dev_batches)],
                jax.random.fold_in(rng, base + i),
            )
        jax.block_until_ready((state, m))
        return time.perf_counter() - t0

    audit_report = None
    if audit_key is not None:
        # compiled-HLO communication audit of this exact step function
        # (collective op counts/bytes + cost/memory analysis). Compile-only
        # — never touches the measured timings — but it IS a fresh compile,
        # so it respects the same minimum path budget, and a failure only
        # costs the audit field.
        if BENCH_DEADLINE_S - (time.monotonic() - _T0) < PATH_MIN_BUDGET_S:
            _state["errors"].append(
                f"{audit_key}: communication audit skipped (budget)")
        else:
            try:
                from swiftsnails_tpu.telemetry.audit import audit_step

                audit_report = audit_step(
                    step, state, dev_batches[0], jax.random.fold_in(rng, 0))
                _state["comm_audit"][audit_key] = _compact_audit(audit_report)
            except Exception as e:
                _state["errors"].append(
                    f"{audit_key} communication audit failed: {e}")

    # two independent windows: machine load only ever inflates time, so the
    # min is the estimate, and the relative spread is reported with it so a
    # noise-dominated number is visible
    t_longs = [timed_run(MEASURE_STEPS, 200 + 100 * i) for i in range(2)]
    t_long = min(t_longs)
    spread = (max(t_longs) - t_long) / t_long
    quality = _eval_quality(trainer, state)
    dt = t_long / MEASURE_STEPS
    if grouped:  # one batch row = one corpus word
        words_per_macro = centers_per_macro
        wps = centers_per_macro / dt
    else:
        pairs_per_sec = STEPS_PER_CALL * BATCH / dt
        words_per_macro = STEPS_PER_CALL * BATCH / pairs_per_token
        wps = pairs_per_sec / pairs_per_token
    if audit_report is not None and audit_key is not None:
        # hardware-utilization block: the audit gives FLOPs/bytes of one
        # macro-step dispatch; dt is its measured duration — MFU and the
        # words/sec-vs-roofline ratio follow (telemetry.goodput)
        try:
            from swiftsnails_tpu.telemetry.goodput import (
                goodput_report, peaks_for,
            )

            g = goodput_report(
                audit=audit_report, steps=1, items=int(words_per_macro),
                step_seconds=dt,
                peaks=peaks_for(_state["device_kind"], _state["platform"]),
            )
            _state["goodput"][audit_key] = {
                k: (_finite(v, 6) if isinstance(v, float) else v)
                for k, v in g.items()
                if k in ("mfu", "vs_roofline", "items_per_sec",
                         "roofline_items_per_sec", "roofline_step_seconds",
                         "step_seconds", "flops_per_step",
                         "hbm_bytes_per_step", "collective_bytes_per_step")
            }
        except Exception as e:
            _state["errors"].append(f"{audit_key} goodput failed: {e}")
    return wps, quality, spread


_EVAL = {}  # fixed held-out (centers, contexts, negs), built once


# Structured-corpus quality probe (shared with the CI gate so the bar and
# corpus cannot drift: swiftsnails_tpu/framework/quality.py). The held-out
# eval loss above cannot discriminate at bench scale — out tables start at
# zero, word2vec.c-style, so logits stay ~0 within the measurement window —
# while the probe's 128-word paired corpus learns structure in seconds. On
# TPU the fused path runs the REAL racy kernel (hardware hogwild), not the
# serialized interpret-mode approximation CI sees.


def _eval_quality(trainer, state) -> float:
    """Held-out per-pair SGNS eval loss of a trained state.

    One metric for every path (per-pair loss, fixed pairs, fixed uniform
    negatives), so pooled/hogwild semantic changes are measured on the
    reference-faithful objective. Used as an ABSOLUTE divergence guard only
    (~ln2*(1+K) = 4.16 means untrained; well above = diverged): paths train
    different pair counts per substep (grouped ~3x the flat paths), so
    cross-path loss comparison would be biased.
    """
    import jax.numpy as jnp

    from swiftsnails_tpu.models.word2vec import sgns_loss
    from swiftsnails_tpu.ops.rowdma import unpack_rows
    from swiftsnails_tpu.parallel.store import pull

    c = jnp.asarray(_EVAL["centers"])
    x = jnp.asarray(_EVAL["contexts"])
    negs = jnp.asarray(_EVAL["negs"])
    b, k = negs.shape
    in_rows = trainer._rows(c)
    out_rows = trainer._rows(jnp.concatenate([x, negs.reshape(-1)]))
    if trainer.packed:
        v = unpack_rows(
            state.in_table.table.at[in_rows].get(mode="promise_in_bounds"), trainer.dim
        )
        u = unpack_rows(
            state.out_table.table.at[out_rows].get(mode="promise_in_bounds"), trainer.dim
        )
    else:
        v = pull(state.in_table, in_rows)
        u = pull(state.out_table, out_rows)
    return float(sgns_loss(v.astype(jnp.float32), u[:b].astype(jnp.float32),
                           u[b:].reshape(b, k, -1).astype(jnp.float32)))


def _grouped_batches(ids_train, block=0):
    """Window-schema macro batches for the grouped kernel paths.

    ``ids_train`` must already EXCLUDE the eval-tail corpus positions (see
    main: training on held-out pairs would bias the grouped path's eval
    loss and defeat the headline quality gate). Centers per substep is
    capped by SMEM (the kernel's scalar-prefetch context arrays):
    8192 centers x 2*window x 2 arrays x 4B ~ 0.7 MB. ``block`` > 0 keeps
    corpus order within blocks of that size (the dedup kernel's batching).
    """
    import itertools

    from swiftsnails_tpu.data.sampler import (
        batch_stream, batch_stream_blocks, skipgram_windows,
    )

    rng = np.random.default_rng(3)
    b = min(BATCH, 8192)
    macro = b * STEPS_PER_CALL
    g_c, g_x = skipgram_windows(ids_train, WINDOW, rng)
    stream = (
        batch_stream_blocks(g_c, g_x, macro, rng, block=block)
        if block
        else batch_stream(g_c, g_x, macro, rng)
    )
    return b, list(itertools.islice(stream, 8))


def measure_tpu_paths(counts, ids, batches, pairs_per_token):
    """Measure every step path; the best quality-gated one is the headline.

    Headline eligibility (fast-but-wrong cannot ship): the dense path is
    reference-faithful by definition and qualifies with a non-diverged eval
    loss; a FAST path must additionally score >= MIN_TOP1 on the
    structured-corpus probe (shared with CI; identical footing per path). A
    probe skipped for budget leaves the fast path's quality UNPROVEN:
    throughput is recorded, eligibility is withheld. A path that raises —
    a Mosaic compile failure included — fails the whole run.
    """
    pool = {
        "packed": "1",
        "neg_mode": "pool",
        "pool_size": str(POOL_SIZE),
        "pool_block": str(POOL_BLOCK),
    }
    paths = [
        ("dense", {"packed": "0"}),
        ("fused-dedup", {**pool, "fused": "1", "grouped": "1",
                         "dedup": "1", "u_cap": str(U_CAP)}),
        # composed: zipf head VMEM-resident + cold contexts dedup'd (u_cap >=
        # hot_rows required by the kernel); compiles in seconds on jax 0.9.0
        # / libtpu 0.0.34 (tools/compile_probe.py; CHANGES.md PR 21)
        ("fused-dedup-res", {**pool, "fused": "1", "grouped": "1",
                             "dedup": "1", "resident": "1",
                             "u_cap": str(U_CAP), "hot_rows": "256"}),
        ("fused-grouped", {**pool, "fused": "1", "grouped": "1"}),
        ("fused-resident", {**pool, "fused": "1", "grouped": "1",
                            "resident": "1", "hot_rows": str(HOT_ROWS)}),
        ("fused-hogwild", {**pool, "fused": "1"}),
        ("packed+pool", pool),
    ]
    gcache = {}  # block-size -> grouped window batches (0 = shuffled)
    for name, overrides in paths:
        remaining = BENCH_DEADLINE_S - (time.monotonic() - _T0)
        if remaining < PATH_MIN_BUDGET_S:
            _state["errors"].append(
                f"skipped {name}: only {remaining:.0f}s of budget left"
            )
            break
        _state["attempted"].add(name)
        # no try/except: a kernel that fails to compile fails the run
        grouped = overrides.get("grouped") == "1"
        if grouped:
            block = 256 if overrides.get("dedup") == "1" else 0
            if block not in gcache:
                gcache[block] = _grouped_batches(ids, block=block)
            gb, gbatches = gcache[block]
            if name not in _state["copies_per_pair"]:
                hot = int(overrides.get("hot_rows", 0) or 0)
                ucap = int(overrides.get("u_cap", 0) or 0)
                _state["copies_per_pair"][name] = kernel_copies_per_pair(
                    gbatches, counts, hot_n=hot, u_cap=ucap)
            wps, qual, spread = _measure_tpu_config(
                counts, gbatches, pairs_per_token,
                {**overrides, "batch_size": str(gb)},
                grouped=True, centers_per_macro=gb * STEPS_PER_CALL,
                audit_key=name,
            )
        else:
            wps, qual, spread = _measure_tpu_config(
                counts, batches, pairs_per_token, overrides,
                audit_key=name,
            )
        _state["spread"][name] = spread
        from swiftsnails_tpu.framework.quality import MIN_TOP1, probe_top1

        _state["paths"][name] = wps
        _state["quality"][name] = qual
        top1 = float("nan")
        if name != "dense":  # dense is reference-faithful; no probe needed
            if BENCH_DEADLINE_S - (time.monotonic() - _T0) < 60:
                _state["errors"].append(
                    f"{name}: quality probe skipped (budget); not headline-eligible"
                )
            else:
                top1 = probe_top1(dict(overrides))
            _state["quality_pair_top1"][name] = top1
        untrained = float(np.log(2.0)) * (1 + NEGATIVES)
        not_diverged = qual == qual and qual <= untrained * DIVERGENCE_FACTOR
        if name == "dense":
            eligible = not_diverged
        else:
            eligible = not_diverged and top1 == top1 and top1 >= MIN_TOP1
            if not eligible:
                _state["errors"].append(
                    f"{name}: quality unproven or failed (eval loss {qual:.4f}"
                    f", pair top-1 {top1:.3f}, bar {MIN_TOP1}); throughput "
                    "recorded but not eligible for the headline"
                )
        if eligible and wps > _state["best"]:
            _state["best"] = wps
            _state["best_path"] = name
            _state["best_overrides"] = dict(overrides)
        _say(f"{name}: {wps:,.0f} words/sec, eval loss {qual:.4f}, "
             f"pair top-1 {top1:.3f}")


def kernel_copies_per_pair(gbatches, counts, hot_n=0, u_cap=0, pc=256,
                           pn=POOL_SIZE):
    """Exact per-pair row-copy accounting of the grouped/resident kernels.

    The kernels issue exactly these DMA counts by construction
    (host-compacted copy lists, last-occurrence write skips, VMEM-resident
    head with ``hot_n > 0``), so this host-side census of the real bench
    batches IS the measured copies/pair — the metric VERDICT r2 asked the
    read-dedup work to move below 2.0. The resident head is the dedup
    mechanism: zipf duplicates concentrate in the head, and head rows cost
    zero per-row copies (two bulk DMAs per substep amortize over all
    blocks).
    """
    p = counts.astype(np.float64) ** 0.75
    p /= p.sum()
    rng = np.random.default_rng(13)
    n_blocks = sum(len(np.asarray(b["centers"])) // pc for b in gbatches[:2])
    all_pools = rng.choice(len(p), (n_blocks, pn), p=p)  # one O(vocab) setup
    blk = 0
    total_copies = 0
    total_pairs = 0
    for batch in gbatches[:2]:
        c = np.asarray(batch["centers"])
        x = np.asarray(batch["contexts"])
        for lo in range(0, len(c), pc):
            cb, xb = c[lo : lo + pc], x[lo : lo + pc]
            if len(cb) < pc:
                break
            valid = xb >= 0
            pools = all_pools[blk]
            blk += 1
            if u_cap:
                # dedup kernel: one read + one merged write per distinct ctx
                # row (up to u_cap); overflow is direct. With hot_n (the
                # composed kernel) hot rows rank first, cost zero per-row
                # copies, and centers/pool drop their hot fraction too.
                uniq = np.unique(xb[valid])
                if hot_n:
                    hot_u = uniq[uniq < hot_n]
                    cold_u = uniq[uniq >= hot_n]
                    in_cold = cold_u[: max(u_cap - len(hot_u), 0)]
                    over = cold_u[max(u_cap - len(hot_u), 0):]
                    ctx_copies = 2 * len(in_cold)
                else:
                    in_list, over = uniq[:u_cap], uniq[u_cap:]
                    ctx_copies = 2 * len(in_list)
                n_over_slots = int(np.isin(xb[valid], over).sum())
                ctx_copies += n_over_slots + len(over)
                cold = lambda a: a[a >= hot_n] if hot_n else a
                c_cold = cold(cb)
                p_cold = cold(pools)
                reads = len(c_cold) + len(p_cold)
                # plain dedup writes ALL pool slots per block (no
                # last-occurrence flags on its pool path); only the composed
                # kernel's cold-pool writes are deduplicated
                pool_writes = len(np.unique(p_cold)) if hot_n else len(p_cold)
                writes = len(np.unique(c_cold)) + pool_writes
                total_copies += reads + writes + ctx_copies
                total_pairs += int(valid.sum())
                continue
            cold = lambda a: a[a >= hot_n] if hot_n else a
            ctx_cold = cold(xb[valid])
            c_cold = cold(cb)
            p_cold = cold(pools)
            reads = len(c_cold) + len(ctx_cold) + len(p_cold)
            writes = (len(np.unique(c_cold)) + len(np.unique(ctx_cold))
                      + len(np.unique(p_cold)))
            total_copies += reads + writes
            total_pairs += int(valid.sum())
        if hot_n:
            # the resident head moves as 4 BULK DMA issues per substep (both
            # tables, in+out) — the per-copy issue cost this metric counts is
            # 4 issues, not 4*hot_n (bandwidth is not the measured bound)
            total_copies += 4 * (len(c) // 8192 + 1)
    return total_copies / max(total_pairs, 1)


# -- scale-out throughput lane -----------------------------------------------
#
# The fused-grouped-mesh path measured at 1 device and at N devices (real
# devices on TPU; `--xla_force_host_platform_device_count=8` makes the CPU
# smoke run meaningful), per comm_dtype wire format: aggregate words/sec,
# weak-scaling efficiency ((wps_N / N) / wps_1), audited per-collective
# payload bytes, and a short-run loss-parity check vs f32. The block lands
# in the result JSON line and the run ledger (`scaling`), and
# `ledger-report --check-regression` gates on its aggregate words/sec
# alongside the headline.
SCALING_MIN_BUDGET_S = int(os.environ.get("SSN_SCALING_MIN_BUDGET_S", "240"))
SCALING_COMM_DTYPES = ("float32", "bfloat16", "int8", "int4")
SCALING_BATCH_PER_SHARD = 512 if _SMALL else 8192
SCALING_STEPS_PER_CALL = 2 if _SMALL else 8
SCALING_MEASURE_STEPS = 4 if _SMALL else 16


def _scaling_mesh_shape(n: int):
    """(data, model) split for the lane: prefer a real model axis."""
    model = 1
    for cand in (4, 2):
        if n % cand == 0 and n > cand:
            model = cand
            break
    return n // model, model


def _scaling_lane_config(vocab_size: int, dim: int, batch: int,
                         steps_per_call: int, comm_dtype: str, overlap: bool):
    conf = {
        "dim": str(dim), "window": str(WINDOW), "negatives": str(NEGATIVES),
        "learning_rate": "0.025", "batch_size": str(batch), "subsample": "0",
        "num_iters": "1", "steps_per_call": str(steps_per_call),
        "table_dtype": TABLE_DTYPE, "packed": "1", "neg_mode": "pool",
        "pool_size": str(POOL_SIZE), "pool_block": str(POOL_BLOCK),
        "fused": "1", "grouped": "1", "comm_dtype": comm_dtype,
    }
    if overlap:
        conf["overlap"] = "1"
    return conf


def measure_scaling(counts, ids, n_devices=None, comm_dtypes=SCALING_COMM_DTYPES,
                    dim=None, batch_per_shard=None, steps_per_call=None,
                    measure_steps=None,
                    include_overlap=True) -> None:
    """Populate ``_state['scaling']`` with the multi-chip throughput lane.

    Weak scaling: the per-data-shard batch is fixed, so the N-device run
    processes ``data_axis`` x the words per dispatch; efficiency is
    ``(wps_N / N) / wps_1x1`` with the 1-device number measured on a 1x1
    mesh of the SAME collective plane (isolating communication cost, not a
    plane switch). A single real device records a structured skip reason
    instead of silently omitting the block.
    """
    import itertools

    import jax
    import jax.numpy as jnp

    from swiftsnails_tpu.data.sampler import batch_stream, skipgram_windows
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, batch_sharding, make_mesh,
    )
    from swiftsnails_tpu.telemetry.audit import audit_step
    from swiftsnails_tpu.utils.config import Config

    devices = jax.devices()
    n = min(n_devices or len(devices), len(devices))
    dim = dim or DIM
    b_shard = batch_per_shard or SCALING_BATCH_PER_SHARD
    spc = steps_per_call or SCALING_STEPS_PER_CALL
    measure_steps = measure_steps or SCALING_MEASURE_STEPS
    if n < 2:
        _state["scaling"] = {
            "skipped": f"single accelerator device (n_devices={n}); "
                       "multi-chip lane needs >= 2 (CPU smoke: set "
                       "--xla_force_host_platform_device_count=8)",
            "n_devices": n,
        }
        _state["errors"].append("scaling lane skipped: single device")
        return
    data, model = _scaling_mesh_shape(n)
    vocab_size = len(counts)
    vocab = Vocab([f"w{i}" for i in range(vocab_size)], np.maximum(counts, 1))

    # window-schema macro batches once, at the N-device (largest) size; the
    # 1-device lane slices the same arrays down to its smaller macro
    rng = np.random.default_rng(17)
    g_c, g_x = skipgram_windows(ids, WINDOW, rng)
    macro_n = b_shard * data * spc
    batches_n = [
        w for w in itertools.islice(batch_stream(g_c, g_x, macro_n, rng), 6)
        if w["centers"].shape[0] == macro_n
    ]
    if not batches_n:
        _state["scaling"] = {
            "skipped": f"corpus too small for one {macro_n}-word macro batch",
            "n_devices": n,
        }
        _state["errors"].append("scaling lane skipped: corpus too small")
        return

    def run_lane(mesh, lane_batches, comm_dtype, overlap=False,
                 want_audit=True):
        batch = lane_batches[0]["centers"].shape[0] // spc
        cfg = Config(_scaling_lane_config(
            vocab_size, dim, batch, spc, comm_dtype, overlap))
        trainer = Word2VecTrainer(
            cfg, mesh=mesh, corpus_ids=np.zeros(2, np.int32), vocab=vocab)
        state = trainer.init_state()
        step = jax.jit(trainer.train_step, donate_argnums=(0,))
        bs = batch_sharding(mesh)
        dev_batches = [
            {k: jax.device_put(v, bs) for k, v in b.items()}
            for b in lane_batches
        ]
        key = jax.random.PRNGKey(0)
        for i in range(2):  # compile + warm
            state, m = step(state, dev_batches[i % len(dev_batches)],
                            jax.random.fold_in(key, i))
        loss = float(m["loss"])

        audit_report = None
        if want_audit:
            try:
                audit_report = audit_step(
                    step, state, dev_batches[0], jax.random.fold_in(key, 0))
            except Exception as e:
                _state["errors"].append(
                    f"scaling lane audit ({comm_dtype}) failed: {e}")

        def timed(n_steps, base):
            nonlocal state, m
            t0 = time.perf_counter()
            for i in range(n_steps):
                state, m = step(state, dev_batches[(base + i) % len(dev_batches)],
                                jax.random.fold_in(key, base + i))
            jax.block_until_ready((state, m))
            return time.perf_counter() - t0

        dt = timed(measure_steps, 20) / measure_steps
        words_per_macro = batch * spc
        return {
            "words_per_sec": words_per_macro / dt,
            "step_seconds": dt,
            "loss": loss,
            "audit": audit_report,
        }

    def compact_bytes(audit_report):
        if not audit_report:
            return None, None
        scoped = audit_report.get("by_scope", {}) or {}
        exchange = sum(v for k, v in scoped.items()) or None
        return audit_report.get("total_bytes"), exchange

    # 1-device reference: same collective plane on a 1x1 mesh, f32 wire
    mesh1 = make_mesh({DATA_AXIS: 1, MODEL_AXIS: 1}, devices=devices[:1])
    macro_1 = b_shard * spc
    batches_1 = [
        {k: v[:macro_1] if k != "progress" else v for k, v in b.items()}
        for b in batches_n
    ]
    lane1 = run_lane(mesh1, batches_1, "float32", want_audit=False)
    wps_1 = lane1["words_per_sec"]

    mesh_n = make_mesh(
        {DATA_AXIS: data, MODEL_AXIS: model}, devices=devices[:n])
    per_dtype = {}
    f32_loss = None
    f32_exchange = None
    for comm_dtype in comm_dtypes:
        lane = run_lane(mesh_n, batches_n, comm_dtype)
        total_b, exchange_b = compact_bytes(lane["audit"])
        entry = {
            "aggregate_words_per_sec": round(lane["words_per_sec"], 1),
            "scaling_efficiency": round(lane["words_per_sec"] / (n * wps_1), 4),
            "step_seconds": round(lane["step_seconds"], 6),
            "loss": _finite(lane["loss"], 6),
            "collective_bytes_per_step": total_b,
            "exchange_bytes_per_step": exchange_b,
        }
        if comm_dtype == "float32":
            f32_loss = lane["loss"]
            f32_exchange = exchange_b
        else:
            if f32_loss:
                entry["loss_parity_vs_f32"] = _finite(
                    abs(lane["loss"] - f32_loss) / abs(f32_loss), 6)
            if f32_exchange and exchange_b:
                entry["payload_reduction_vs_f32"] = round(
                    f32_exchange / exchange_b, 3)
        # collective-time split cross-check: audited bytes over the chip's
        # ICI peak vs the measured step — telemetry.goodput's model-based
        # split, recorded so an overlap/quantization win is attributable
        if lane["audit"] is not None:
            try:
                from swiftsnails_tpu.telemetry.goodput import (
                    goodput_report, peaks_for,
                )

                g = goodput_report(
                    audit=lane["audit"], steps=1,
                    items=int(b_shard * data * spc),
                    step_seconds=lane["step_seconds"],
                    peaks=peaks_for(_state["device_kind"],
                                    _state["platform"]), n_chips=n,
                )
                split = g.get("step_split_est")
                if split:
                    entry["step_split_est"] = {
                        k: _finite(v, 6) for k, v in split.items()
                    }
            except Exception as e:
                _state["errors"].append(
                    f"scaling lane goodput ({comm_dtype}) failed: {e}")
        per_dtype[comm_dtype] = entry
        _say(
            f"scaling[{comm_dtype}] {n}dev "
            f"{lane['words_per_sec']:,.0f} words/s agg "
            f"(eff {entry['scaling_efficiency']:.2f}), "
            f"exchange {exchange_b or 0:,} B/step"
        )

    block = {
        "n_devices": n,
        "mesh": {"data": data, "model": model},
        "batch_per_shard": b_shard,
        "steps_per_call": spc,
        "single_device_words_per_sec": round(wps_1, 1),
        "per_dtype": per_dtype,
        # the gateable headline numbers (f32 lane)
        "aggregate_words_per_sec": per_dtype["float32"]["aggregate_words_per_sec"],
        "scaling_efficiency": per_dtype["float32"]["scaling_efficiency"],
    }
    if include_overlap:
        try:
            lane_ov = run_lane(mesh_n, batches_n, "float32", overlap=True,
                               want_audit=False)
            block["overlap"] = {
                "aggregate_words_per_sec": round(lane_ov["words_per_sec"], 1),
                "speedup_vs_sequential": round(
                    lane_ov["words_per_sec"]
                    / per_dtype["float32"]["aggregate_words_per_sec"], 3),
                "loss": _finite(lane_ov["loss"], 6),
            }
        except Exception as e:
            _state["errors"].append(f"scaling overlap lane failed: {e}")
    _state["scaling"] = block

    # zipf-skewed leg: uniform vs `placement: auto` exchange bytes at each
    # wire format — the hybrid-placement acceptance lane
    try:
        measure_skewed_placement(
            n_devices=n, comm_dtypes=comm_dtypes, dim=dim,
            batch_per_shard=b_shard, steps_per_call=spc)
    except Exception as e:
        _state["errors"].append(
            f"skewed placement leg failed ({type(e).__name__}: {e})")


# zipf exponent of the skewed placement leg: steep enough that a ~1k-row
# head covers most of the batch slots (the regime hybrid placement targets)
SKEWED_ZIPF_S = 1.4
SKEWED_VOCAB = 1024 if _SMALL else 4096


def measure_skewed_placement(n_devices=None,
                             comm_dtypes=SCALING_COMM_DTYPES, dim=None,
                             batch_per_shard=None, steps_per_call=None,
                             vocab_size=None) -> None:
    """Attach the zipf-skewed uniform-vs-hybrid leg to ``_state['scaling']``.

    A steep-zipf corpus (``s=SKEWED_ZIPF_S``) where vocab id == frequency
    rank, so ``placement: auto`` can read the CDF. Per comm_dtype: compile
    and audit the grouped-mesh step twice — uniform sharding, then the
    auto-cut hybrid split calibrated with the uniform lane's measured
    exchange bytes — and record the audited exchange-byte reduction plus a
    short-run loss-parity check on identical batches/keys. Bytes come from
    compiled HLO shapes (static), so the leg is valid on CPU;
    ``ledger-report --check-regression`` gates reduction >= 2x.
    """
    import itertools

    import jax

    from swiftsnails_tpu.data.sampler import batch_stream, skipgram_windows
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, batch_sharding, make_mesh,
    )
    from swiftsnails_tpu.parallel.placement import PlacementManager
    from swiftsnails_tpu.telemetry.audit import audit_step
    from swiftsnails_tpu.utils.config import Config

    scal = _state.get("scaling")
    if not isinstance(scal, dict) or scal.get("skipped"):
        return
    devices = jax.devices()
    n = min(n_devices or len(devices), len(devices))
    if n < 2:
        return
    data, model = _scaling_mesh_shape(n)
    dim = dim or DIM
    b_shard = batch_per_shard or SCALING_BATCH_PER_SHARD
    spc = steps_per_call or SCALING_STEPS_PER_CALL
    macro_n = b_shard * data * spc
    vocab_size = vocab_size or SKEWED_VOCAB
    n_tokens = max(2 * macro_n, 16_000)
    ids = synth_corpus(n_tokens, vocab_size, seed=23, s=SKEWED_ZIPF_S)
    counts = np.bincount(ids, minlength=vocab_size).astype(np.int64)
    # the zipf stream's id is already ~its frequency rank; sampling noise can
    # swap neighbors, so re-rank exactly (auto's CDF cut assumes id == rank)
    order = np.argsort(-counts, kind="stable")
    inv = np.empty_like(order)
    inv[order] = np.arange(vocab_size)
    ids = inv[ids].astype(np.int32)
    counts = counts[order]
    vocab = Vocab([f"w{i}" for i in range(vocab_size)],
                  np.maximum(counts, 1))

    rng = np.random.default_rng(29)
    g_c, g_x = skipgram_windows(ids, WINDOW, rng)
    batches = [
        w for w in itertools.islice(batch_stream(g_c, g_x, macro_n, rng), 4)
        if w["centers"].shape[0] == macro_n
    ]
    if not batches:
        _state["errors"].append(
            "skewed placement leg skipped: corpus too small for a "
            f"{macro_n}-word macro batch")
        return
    mesh_n = make_mesh(
        {DATA_AXIS: data, MODEL_AXIS: model}, devices=devices[:n])
    bs = batch_sharding(mesh_n)
    dev_batches = [
        {k: jax.device_put(v, bs) for k, v in b.items()} for b in batches
    ]

    def lane(comm_dtype, placement, calib_bytes=None):
        conf = _scaling_lane_config(
            vocab_size, dim, macro_n // spc, spc, comm_dtype, overlap=False)
        conf["placement"] = placement
        if calib_bytes:
            conf["placement_calib_bytes"] = str(int(calib_bytes))
        trainer = Word2VecTrainer(
            Config(conf), mesh=mesh_n, corpus_ids=np.zeros(2, np.int32),
            vocab=vocab)
        state = trainer.init_state()
        pm = PlacementManager(trainer, mesh_n)
        if pm.active:
            state = pm.adopt(state)
        step = jax.jit(trainer.train_step, donate_argnums=(0,))
        key = jax.random.PRNGKey(3)
        m = None
        for i in range(4):  # compile + identical short run for loss parity
            state, m = step(state, dev_batches[i % len(dev_batches)],
                            jax.random.fold_in(key, i))
        loss = float(m["loss"])
        audit_report = audit_step(
            step, state, dev_batches[0], jax.random.fold_in(key, 0))
        exchange = sum((audit_report.get("by_scope") or {}).values()) or None
        return trainer, exchange, loss, audit_report

    per = {}
    decision = None
    for comm_dtype in comm_dtypes:
        u_tr, u_x, u_loss, _u_audit = lane(comm_dtype, "uniform")
        h_tr, h_x, h_loss, h_audit = lane(comm_dtype, "auto", calib_bytes=u_x)
        entry = {
            "uniform_exchange_bytes": u_x,
            "hybrid_exchange_bytes": h_x,
            "exchange_reduction": (
                round(u_x / h_x, 3) if u_x and h_x else None),
            "cut": h_tr.placement_cut,
            "loss_uniform": _finite(u_loss, 6),
            "loss_hybrid": _finite(h_loss, 6),
            "loss_delta": _finite(
                abs(h_loss - u_loss) / max(abs(u_loss), 1e-9), 6),
        }
        if h_audit.get("by_table"):
            entry["by_table_bytes"] = dict(h_audit["by_table"])
        per[comm_dtype] = entry
        if decision is None:
            decision = dict(h_tr.placement_decision or {})
            if h_x:
                decision["measured_exchange_bytes"] = h_x
        _say(
            f"scaling skewed[{comm_dtype}] exchange "
            f"{u_x or 0:,} -> {h_x or 0:,} B/step "
            f"({entry['exchange_reduction']}x, cut={h_tr.placement_cut}), "
            f"loss_delta={entry['loss_delta']}"
        )
    scal["skewed"] = {
        "zipf_s": SKEWED_ZIPF_S,
        "vocab": vocab_size,
        "per_dtype": per,
        "decision": decision,
    }


# -- resilience (chaos) lane --------------------------------------------------
#
# The word2vec hot path under a scripted fault sequence (NaN burst ->
# checkpoint corruption -> simulated preemption + auto-resume), plus the
# guardrail's on-path overhead on a no-fault control leg. Recovery is
# correctness, not throughput, so the lane is valid on CPU; the block lands
# in the result JSON (`chaos`), the run ledger, and the
# `ledger-report --check-regression` gate (`swiftsnails_tpu/resilience/`).
CHAOS_MIN_BUDGET_S = int(os.environ.get("SSN_CHAOS_MIN_BUDGET_S", "240"))


def measure_chaos() -> None:
    """Populate ``_state['chaos']`` with the resilience lane block."""
    from swiftsnails_tpu.resilience.drill import chaos_bench

    block = chaos_bench(small=_SMALL)
    _state["chaos"] = block
    if not block.get("recovered_all"):
        bad = [k for k, v in (block.get("drills") or {}).items()
               if not v.get("recovered")]
        _state["errors"].append(
            "chaos lane: unrecovered drill(s): " + (", ".join(bad) or "?"))
    over = block.get("guard_overhead_pct")
    _say(
        f"chaos lane: recovered_all={block.get('recovered_all')} "
        f"guard overhead {over}% "
        f"loss parity {block.get('loss_parity')}"
    )


def run_scaling_lane() -> int:
    """``--lane scaling``: the scale-out lane alone (incl. the zipf-skewed
    uniform-vs-hybrid placement leg), one JSON line out."""
    _state["lane"] = "scaling"
    n_tokens = 120_000 if _SMALL else 1_500_000
    ids = synth_corpus(n_tokens, VOCAB, seed=5)
    counts = np.maximum(np.bincount(ids, minlength=VOCAB), 1).astype(np.int64)
    measure_scaling(counts, ids)
    block = _state["scaling"]
    if block.get("skipped"):
        _emit_once()
        return 1
    # the lane's headline is the f32 aggregate words/sec across the mesh
    _state["best"] = block.get("aggregate_words_per_sec") or 0.0
    _state["best_path"] = "scaling-f32"
    _record_run()
    _emit_once()
    sk = block.get("skewed") or {}
    reductions = [
        e.get("exchange_reduction")
        for e in (sk.get("per_dtype") or {}).values()
    ]
    ok = bool(reductions) and all(
        isinstance(r, (int, float)) and r >= 2.0 for r in reductions)
    return 0 if ok else 1


def run_chaos_lane() -> int:
    """``--lane chaos``: the resilience lane alone, one JSON line out."""
    _state["lane"] = "chaos"
    measure_chaos()
    block = _state["chaos"]
    # the lane's headline is the GUARDED no-fault control leg: the words/sec
    # a protected production run actually gets
    _state["best"] = block.get("guard_words_per_sec") or 0.0
    _state["best_path"] = "chaos-guarded-control"
    _record_run()
    _emit_once()
    return 0 if block.get("recovered_all") else 1


# -- serving (read-path) lane -------------------------------------------------
#
# `--lane serve` measures the query subsystem (`swiftsnails_tpu/serving/`):
# two tiny verified checkpoints are loaded through Servant.from_checkpoint
# and all three query kernels (pull, top-k, CTR score) run at two batch
# buckets. Latency distribution + cache/shed behavior is correctness of the
# serving machinery, so the lane is valid on CPU; the block lands in the
# result JSON (`serving`), the run ledger, and the
# `ledger-report --check-regression` gate (qps floor + p99 ceiling).


def measure_serving() -> None:
    """Populate ``_state['serving']`` with the read-path lane block."""
    from swiftsnails_tpu.serving.bench_lane import serve_bench
    from swiftsnails_tpu.telemetry.ledger import Ledger

    block = serve_bench(small=_SMALL, ledger=Ledger(LEDGER_PATH))
    _state["serving"] = block
    _say(
        f"serve lane: pull qps {block.get('qps')} "
        f"p99 {block.get('p99_ms')}ms "
        f"cache hit rate {block.get('cache_hit_rate')} "
        f"shed {block.get('shed_count')}"
    )


def run_serve_lane() -> int:
    """``--lane serve``: the read-path latency lane alone, one JSON line."""
    _state["lane"] = "serve"
    measure_serving()
    block = _state["serving"]
    # the lane's headline is pull qps at the largest bucket: the lookup
    # traffic a serving replica actually absorbs
    _state["best"] = block.get("qps") or 0.0
    _state["best_path"] = "serve-pull"
    _record_run()
    _emit_once()
    return 0


# -- fleet (replica pool) lane -------------------------------------------------
#
# `--lane fleet` measures the serving fleet (`swiftsnails_tpu/serving/
# fleet.py`): max sustainable QPS at a fixed p99 SLO for 1 vs N replicas
# under an open-loop zipf workload, with device service time modeled as an
# injected per-dispatch stall so the lane measures the routing machinery
# (affinity, spill, hedging, queueing) and is valid on CPU. Two controlled
# comparisons ride along: affinity vs random routing (aggregate LRU hit
# rate) and hedge vs no-hedge with one stalling replica (p99). The block
# lands in the result JSON (`fleet`), the run ledger, and the
# `ledger-report --check-regression` gate (QPS floor + p99 SLO ceiling +
# scaling floor).


def measure_fleet() -> None:
    """Populate ``_state['fleet']`` with the replica-pool lane block."""
    from swiftsnails_tpu.serving.fleet_lane import fleet_bench
    from swiftsnails_tpu.telemetry.ledger import Ledger

    block = fleet_bench(small=_SMALL, ledger=Ledger(LEDGER_PATH))
    _state["fleet"] = block
    _say(
        f"fleet lane: fleet qps {block.get('qps')} "
        f"(single {block.get('single', {}).get('max_qps')}, "
        f"scaling {block.get('scaling_x')}x) "
        f"p99 {block.get('p99_ms')}ms @ SLO {block.get('slo_p99_ms')}ms "
        f"affinity {block.get('affinity', {}).get('affinity_hit_rate')} "
        f"vs random {block.get('affinity', {}).get('random_hit_rate')}"
    )


def run_fleet_lane() -> int:
    """``--lane fleet``: the replica-pool lane alone, one JSON line."""
    _state["lane"] = "fleet"
    measure_fleet()
    block = _state["fleet"]
    # the lane's headline is the fleet's max sustainable QPS at the p99 SLO
    _state["best"] = block.get("qps") or 0.0
    _state["best_path"] = "fleet-pull"
    _record_run()
    _emit_once()
    return 0


# -- tiered (host parameter store) lane ---------------------------------------
#
# `--lane tiered` measures the tiered parameter store (`swiftsnails_tpu/
# tiered/`): words/sec of `table_tier: host` vs the resident store at equal
# vocab (with bit-parity of the final tables), plus an over-budget leg where
# the masters are 4x the HBM cache budget and the full train -> checkpoint ->
# serve round trip must hold exact parity. The budget is synthetic, so the
# lane is valid on CPU; the block lands in the result JSON (`tiered`), the
# run ledger, and the `ledger-report --check-regression` gate.


def measure_tiered() -> None:
    """Populate ``_state['tiered']`` with the host-tier lane block."""
    from swiftsnails_tpu.telemetry.ledger import Ledger
    from swiftsnails_tpu.tiered.bench_lane import tiered_bench

    block = tiered_bench(small=_SMALL, ledger=Ledger(LEDGER_PATH))
    _state["tiered"] = block
    _say(
        f"tiered lane: {block.get('words_per_sec')} words/s "
        f"({block.get('tiered_over_resident')}x resident) "
        f"parity {block.get('parity_bit_identical')} "
        f"over-budget round trip {block.get('round_trip_ok')}"
    )


def run_tiered_lane() -> int:
    """``--lane tiered``: the host-tier store lane alone, one JSON line."""
    _state["lane"] = "tiered"
    measure_tiered()
    block = _state["tiered"]
    # the lane's headline is the tiered path's own words/sec at equal vocab
    _state["best"] = block.get("words_per_sec") or 0.0
    _state["best_path"] = "tiered-host"
    _record_run()
    _emit_once()
    return 0


# -- chaos-serve (availability drill) lane -------------------------------------
#
# `--lane chaos-serve` runs the serving availability drill (`swiftsnails_tpu/
# serving/chaos_lane.py`): a seeded fault matrix (read-error storms + stalls)
# against a live Servant, once with circuit breakers + degraded stale-LRU
# reads (availability must hold the floor) and once unprotected (the same
# matrix must hard-fail), plus the corrupt-reload rejection drill and the
# tiered bit-flip recovery drill. Availability under fault is correctness,
# so the lane is valid on CPU; the block lands in the result JSON
# (`chaos_serve`), the run ledger, and the `ledger-report
# --check-regression` gate on any platform.


def measure_chaos_serve() -> None:
    """Populate ``_state['chaos_serve']`` with the availability-drill block."""
    from swiftsnails_tpu.serving.chaos_lane import chaos_serve_bench
    from swiftsnails_tpu.telemetry.ledger import Ledger

    block = chaos_serve_bench(small=_SMALL, ledger=Ledger(LEDGER_PATH))
    _state["chaos_serve"] = block
    _say(
        f"chaos-serve lane: availability {block.get('availability_pct')}% "
        f"(floor {block.get('floor_pct')}%) "
        f"degraded share {block.get('degraded_share_pct')}% "
        f"p99 under fault {block.get('p99_under_fault_ms')}ms "
        f"control hard-failure {block.get('unprotected_hard_failure')}"
    )


def run_chaos_serve_lane() -> int:
    """``--lane chaos-serve``: the availability drill alone, one JSON line."""
    _state["lane"] = "chaos-serve"
    measure_chaos_serve()
    block = _state["chaos_serve"]
    # the lane's headline is availability under fault, not a rate — leave
    # the perf headline empty and gate on the lane's own pass criteria
    _state["best_path"] = "chaos-serve"
    _record_run()
    _emit_once()
    ok = (
        (block.get("availability_pct") or 0) >= block.get("floor_pct", 99.0)
        and block.get("unprotected_hard_failure")
        and block.get("reload_corrupt_rejected")
        and (block.get("tier_bitflip") is None
             or block["tier_bitflip"].get("recovered"))
    )
    return 0 if ok else 1


# -- chaos-cluster (membership drill) lane --------------------------------------
#
# `--lane chaos-cluster` runs the cluster supervisor drill (`swiftsnails_tpu/
# cluster/chaos_lane.py`): a virtual-clock simulated fleet under a seeded
# membership storm (silent worker death + straggler window + partition),
# once supervised (lease expiry -> elastic reassignment; the exactly-once
# batch-accounting ledger must prove 0 lost / 0 double-applied and loss must
# stay within parity of an undisturbed in-order control) and once with the
# supervisor off (the same storm must demonstrably lose the dead worker's
# range). Membership correctness is platform-independent, so the lane is
# valid on CPU; the block lands in the result JSON (`chaos_cluster`), the
# run ledger, and the `ledger-report --check-regression` gate.


def measure_chaos_cluster() -> None:
    """Populate ``_state['chaos_cluster']`` with the membership-drill block."""
    from swiftsnails_tpu.cluster.chaos_lane import chaos_cluster_bench
    from swiftsnails_tpu.telemetry.ledger import Ledger

    block = chaos_cluster_bench(small=_SMALL, ledger=Ledger(LEDGER_PATH))
    _state["chaos_cluster"] = block
    _say(
        f"chaos-cluster lane: {block.get('committed')}/"
        f"{block.get('total_batches')} exactly-once "
        f"(lost {block.get('lost_count')}, dup {block.get('duplicated_count')}, "
        f"dup_discarded {block.get('dup_discarded')}) "
        f"workers_lost {block.get('workers_lost')} "
        f"reassignments {block.get('reassignments')} "
        f"loss parity {block.get('loss_parity')} "
        f"control hard-failure {block.get('unprotected_hard_failure')}"
    )


def run_chaos_cluster_lane() -> int:
    """``--lane chaos-cluster``: the membership drill alone, one JSON line."""
    _state["lane"] = "chaos-cluster"
    measure_chaos_cluster()
    block = _state["chaos_cluster"]
    # the lane's headline is exactly-once recovery, not a rate — leave the
    # perf headline empty and gate on the drill's own recovery verdict
    _state["best_path"] = "chaos-cluster"
    _record_run()
    _emit_once()
    return 0 if block.get("recovered") else 1


# -- freshness (trainer -> fleet delta pipeline) lane --------------------------
#
# `--lane freshness` runs the hot-row delta pipeline (`swiftsnails_tpu/
# freshness/`): train to a checkpoint, serve it from a 2-replica fleet, then
# resume training with `freshness_publish: 1` while a DeltaSubscriber applies
# every version-stamped batch under concurrent open-loop load. Gates: delta-
# applied rows bit-identical to the same-watermark checkpoint, delta lag p99
# under the lane ceiling, serve p99 within the SLO while applying, and a
# forced-gap drill recovering via the full-reload fallback. Correctness is
# platform-independent, so the lane is valid on CPU; the block lands in the
# result JSON (`freshness`), the run ledger, and the `ledger-report
# --check-regression` gate.


def measure_freshness() -> None:
    """Populate ``_state['freshness']`` with the delta-pipeline lane block."""
    from swiftsnails_tpu.freshness.bench_lane import freshness_bench
    from swiftsnails_tpu.telemetry.ledger import Ledger

    block = freshness_bench(small=_SMALL, ledger=Ledger(LEDGER_PATH))
    _state["freshness"] = block
    _say(
        f"freshness lane: lag p99 {block.get('lag_p99_ms')}ms "
        f"(ceiling {block.get('lag_ceiling_ms')}ms) "
        f"serve p99 {block.get('serve_p99_ms')}ms "
        f"(SLO {block.get('slo_p99_ms')}ms) "
        f"bit parity {block.get('bit_parity')} "
        f"gap drill recovered {(block.get('gap_drill') or {}).get('recovered')}"
    )


def run_freshness_lane() -> int:
    """``--lane freshness``: the delta pipeline lane alone, one JSON line."""
    _state["lane"] = "freshness"
    measure_freshness()
    block = _state["freshness"]
    # the lane's headline is freshness correctness + bounded staleness, not
    # a rate — leave the perf headline empty and gate on the lane's own
    # pass criteria (mirrored by _check_freshness_regression)
    _state["best_path"] = "freshness"
    _record_run()
    _emit_once()
    gap = block.get("gap_drill") or {}
    ok = (
        block.get("bit_parity") == 0.0
        and gap.get("recovered")
        and gap.get("parity") == 0.0
        and block.get("cutover_atomic")
        and (block.get("lag_p99_ms") or 0.0) <= block.get(
            "lag_ceiling_ms", 0.0)
        and (block.get("serve_p99_ms") or 0.0) <= block.get(
            "slo_p99_ms", 0.0)
    )
    return 0 if ok else 1


# -- net (TCP serving + liveness + delta streaming) lane -----------------------
#
# `--lane net` runs the transport lane (`swiftsnails_tpu/net/`): the same
# checkpoint served by an in-process fleet (control), by a NetFleet of two
# spawned `replica_server` processes over the SSD1 stream RPC (p99 envelope +
# pull bit parity over the wire), and by the same TCP fleet under a fault
# storm — a mid-load SIGKILL recovered via lease expiry -> drain -> respawn ->
# rejoin with availability >= 99%, a partition whose stale write is refused
# typed on heal, and a TCP delta-stream publisher kill reconverging to bit
# parity 0.0. Correctness is platform-independent, so the lane is valid on
# CPU; the block lands in the result JSON (`net`), the run ledger, and the
# `ledger-report --check-regression` gate.


def measure_net() -> None:
    """Populate ``_state['net']`` with the transport lane block."""
    from swiftsnails_tpu.net.bench_lane import net_bench
    from swiftsnails_tpu.telemetry.ledger import Ledger

    block = net_bench(small=_SMALL, ledger=Ledger(LEDGER_PATH))
    _state["net"] = block
    _say(
        f"net lane: p99 tcp {block.get('p99_tcp_ms')}ms vs local "
        f"{block.get('p99_local_ms')}ms ({block.get('envelope_x'):.1f}x, "
        f"limit {block.get('envelope_limit_x')}x) "
        f"availability {block.get('availability_pct')}% "
        f"proc_kill recovered "
        f"{(block.get('proc_kill') or {}).get('recovered')} "
        f"stale write refused "
        f"{(block.get('partition') or {}).get('stale_write_refused')} "
        f"delta parity {(block.get('delta') or {}).get('parity')}"
    )


def run_net_lane() -> int:
    """``--lane net``: the transport lane alone, one JSON line."""
    _state["lane"] = "net"
    measure_net()
    block = _state["net"]
    # the lane's headline is transport correctness + availability, not a
    # rate — leave the perf headline empty and gate on the lane's own pass
    # criteria (mirrored by _check_net_regression)
    _state["best_path"] = "net"
    _record_run()
    _emit_once()
    pk = block.get("proc_kill") or {}
    pt = block.get("partition") or {}
    dl = block.get("delta") or {}
    ok = (
        block.get("tcp_parity") == 0.0
        and pk.get("recovered")
        and (pk.get("availability_pct") or 0.0)
        >= block.get("availability_floor_pct", 99.0)
        and pt.get("stale_write_refused")
        and dl.get("parity") == 0.0
        and (block.get("envelope_x") or 0.0)
        <= block.get("envelope_limit_x", 0.0)
    )
    return 0 if ok else 1


# -- training-plane drift drill + profiler-overhead lane -----------------------
#
# `--lane drift` runs the observability drill (`swiftsnails_tpu/telemetry/
# drift_lane.py`): a control run and a `slow_step@A-B` chaos run share one
# ledger; the run's own EWMA/CUSUM sentinel must confirm the injected
# slow-step within the window, emit exactly one transition-edged `drift`
# ledger event, leave a complete incident bundle behind, and the
# before/after `--diff` attribution must name host-blocked dominant. The
# ride-along leg measures the continuous profiler's own words/sec cost
# (sampler + sentinel on vs off at equal work) against the 3% ceiling.
# Correctness is platform-independent, so the lane is valid on CPU; the
# blocks land in the result JSON (`drift`, `profile_overhead`), the run
# ledger, and the `ledger-report --check-regression` gate.


def measure_drift() -> None:
    """Populate ``_state['drift']`` / ``_state['profile_overhead']``."""
    from swiftsnails_tpu.telemetry.drift_lane import drift_bench

    block = drift_bench(small=_SMALL)
    _state["drift"] = block["drift"]
    _state["profile_overhead"] = block["profile_overhead"]
    d, po = block["drift"], block["profile_overhead"]
    _say(
        f"drift lane: detected={d.get('detected')} "
        f"(inject {d.get('inject_step')}, confirm {d.get('detect_step')}) "
        f"events={d.get('drift_events')} "
        f"bundle_complete={d.get('bundle_complete')} "
        f"dominant={(d.get('attribution') or {}).get('dominant')} "
        f"profiler overhead {po.get('overhead_pct')}% "
        f"(ceiling {po.get('overhead_ceil_pct')}%, "
        f"noise {po.get('noise_pct')}%)"
    )


def run_drift_lane() -> int:
    """``--lane drift``: the drift drill + profiler-overhead leg alone."""
    _state["lane"] = "drift"
    measure_drift()
    d, po = _state["drift"], _state["profile_overhead"]
    # correctness lane: no perf headline — gate on the drill's own criteria
    # (mirrored by _check_drift_regression / _check_profiler_overhead_...)
    _state["best_path"] = "drift"
    _record_run()
    _emit_once()
    ok = (
        d.get("detected")
        and d.get("drift_events") == 1
        and d.get("bundle_complete")
        and (d.get("attribution") or {}).get("dominant") == "host_blocked"
        and isinstance(po.get("overhead_pct"), (int, float))
        and po["overhead_pct"] <= max(
            po.get("overhead_ceil_pct") or 3.0, po.get("noise_pct") or 0.0)
    )
    return 0 if ok else 1


# -- sharded optimizer state (zero) lane --------------------------------------
#
# `--lane zero` measures `optimizer_sharding: zero` (ZeRO-style weight-update
# sharding over the data axis): per-replica HBM of the replicated optimizer/
# parameter planes before vs after sharding (ZeroManager's adoption census),
# audited exchange bytes of the dense-grad reduce (reduce-scatter + slice
# all-gather vs the psum baseline — compiled-HLO shapes, so valid on CPU),
# f32 loss parity and checkpoint byte-identity vs the unsharded run, and an
# `overlap: 2` goodput ride-along (compute/collective step split). The block
# lands in the result JSON (`zero`), the run ledger, and the
# `ledger-report --check-regression` gate (`_check_zero_regression`).
ZERO_MIN_BUDGET_S = int(os.environ.get("SSN_ZERO_MIN_BUDGET_S", "180"))
ZERO_VOCAB = 1024 if _SMALL else 4096
ZERO_DIM = 32 if _SMALL else 64
ZERO_HEAD_ROWS = 256
ZERO_BATCH_PER_SHARD = 256 if _SMALL else 1024
ZERO_STEPS_PER_CALL = 2


def _zero_mesh_shape(n: int):
    """data-major (data, model) split: zero shards over the data axis, so
    give it the bigger side — the scaling lane's model-major split would cap
    the replicated-plane reduction at 2x on 8 devices."""
    model = 2 if n % 2 == 0 and n > 2 else 1
    return n // model, model


def measure_zero(n_devices=None) -> None:
    """Populate ``_state['zero']`` with the sharded-optimizer-state lane."""
    import itertools

    import jax

    from swiftsnails_tpu.data.ctr import synth_ctr
    from swiftsnails_tpu.data.sampler import batch_stream, skipgram_windows
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.framework.checkpoint import build_manifest
    from swiftsnails_tpu.models.registry import get_model
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, batch_sharding, make_mesh,
    )
    from swiftsnails_tpu.parallel.placement import PlacementManager
    from swiftsnails_tpu.parallel.zero import ZeroManager
    from swiftsnails_tpu.telemetry.audit import audit_step
    from swiftsnails_tpu.utils.config import Config

    devices = jax.devices()
    n = min(n_devices or len(devices), len(devices))
    if n < 2:
        _state["zero"] = {
            "skipped": f"single accelerator device (n_devices={n}); the "
                       "sharding lane needs >= 2 (CPU smoke: set "
                       "--xla_force_host_platform_device_count=8)",
            "n_devices": n,
        }
        _state["errors"].append("zero lane skipped: single device")
        return
    data, model = _zero_mesh_shape(n)
    mesh = make_mesh(
        {DATA_AXIS: data, MODEL_AXIS: model}, devices=devices[:n])
    bs = batch_sharding(mesh)

    # word2vec hybrid-head leg: skewed corpus so the hybrid head is real
    vocab_size = ZERO_VOCAB
    spc = ZERO_STEPS_PER_CALL
    macro_n = ZERO_BATCH_PER_SHARD * data * spc
    ids = synth_corpus(max(2 * macro_n, 16_000), vocab_size, seed=31,
                       s=SKEWED_ZIPF_S)
    counts = np.bincount(ids, minlength=vocab_size).astype(np.int64)
    order = np.argsort(-counts, kind="stable")
    inv = np.empty_like(order)
    inv[order] = np.arange(vocab_size)
    ids = inv[ids].astype(np.int32)
    counts = counts[order]
    vocab = Vocab([f"w{i}" for i in range(vocab_size)],
                  np.maximum(counts, 1))
    rng = np.random.default_rng(37)
    g_c, g_x = skipgram_windows(ids, WINDOW, rng)
    batches = [
        w for w in itertools.islice(batch_stream(g_c, g_x, macro_n, rng), 4)
        if w["centers"].shape[0] == macro_n
    ]
    if not batches:
        _state["zero"] = {
            "skipped": f"corpus too small for one {macro_n}-word macro batch",
            "n_devices": n,
        }
        _state["errors"].append("zero lane skipped: corpus too small")
        return
    dev_batches = [
        {k: jax.device_put(v, bs) for k, v in b.items()} for b in batches
    ]

    def w2v_lane(zero, overlap="0"):
        conf = _scaling_lane_config(
            vocab_size, ZERO_DIM, macro_n // spc, spc, "float32",
            overlap=False)
        conf["placement"] = "hybrid"
        conf["placement_head_rows"] = str(ZERO_HEAD_ROWS)
        if overlap != "0":
            conf["overlap"] = overlap
        if zero:
            conf["optimizer_sharding"] = "zero"
        trainer = Word2VecTrainer(
            Config(conf), mesh=mesh, corpus_ids=np.zeros(2, np.int32),
            vocab=vocab)
        state = trainer.init_state()
        pm = PlacementManager(trainer, mesh)
        if pm.active:
            state = pm.adopt(state)
        zm = ZeroManager(trainer, mesh)
        if zm.active:
            state = zm.adopt(state)
        step = jax.jit(trainer.train_step, donate_argnums=(0,))
        key = jax.random.PRNGKey(7)
        m = None
        for i in range(3):  # compile + identical short run for loss parity
            state, m = step(state, dev_batches[i % len(dev_batches)],
                            jax.random.fold_in(key, i))
        loss = float(m["loss"])
        t0 = time.perf_counter()
        n_timed = 2
        for i in range(n_timed):
            state, m = step(state, dev_batches[i % len(dev_batches)],
                            jax.random.fold_in(key, 10 + i))
        jax.block_until_ready((state, m))
        dt = (time.perf_counter() - t0) / n_timed
        audit = audit_step(
            step, state, dev_batches[0], jax.random.fold_in(key, 0))
        ops = audit.get("ops") or {}
        return {
            "loss": loss,
            "words_per_sec": macro_n / dt,
            "step_seconds": dt,
            "audit": audit,
            "head_push_bytes": (audit.get("by_scope") or {}).get(
                "ssn_zero_head_push" if zero else "ssn_hybrid_head_push"),
            # the grad-reduce component alone: reduce-scatter only appears
            # in the zero head push on this lane (f32 wire), so the op-level
            # total is exactly the summed-gradient exchange — the param
            # all-gather that replaces the baseline's redundant update is
            # the remainder of the head-push scope
            "reduce_scatter_bytes": (
                (ops.get("reduce-scatter") or {}).get("bytes", 0)
                + (ops.get("all-reduce-scatter") or {}).get("bytes", 0)),
        }

    base = w2v_lane(zero=False)
    shard = w2v_lane(zero=True)
    block = {
        "n_devices": n,
        "mesh": {"data": data, "model": model},
        "head_rows": ZERO_HEAD_ROWS,
        "words_per_sec": {
            "baseline": round(base["words_per_sec"], 1),
            "zero": round(shard["words_per_sec"], 1),
        },
        "loss_parity_f32": _finite(abs(shard["loss"] - base["loss"]), 9),
        # audited exchange bytes of the dense-grad REDUCE: the zero path's
        # reduce-scatter vs the psum baseline. A ring all-reduce is
        # internally reduce-scatter + all-gather but the audit bills it
        # once (its defining shape), so the scatter leg is compared
        # like-for-like; the param all-gather that replaces the baseline's
        # redundant full-plane update is recorded separately
        "grad_reduce": {
            "baseline_bytes": base["head_push_bytes"],
            "zero_bytes": shard["reduce_scatter_bytes"],
            "param_gather_bytes": (
                (shard["head_push_bytes"] or 0)
                - shard["reduce_scatter_bytes"]) or None,
            "head_push_total_bytes": shard["head_push_bytes"],
        },
    }

    # CTR AdaGrad leg: the replicated-plane HBM census (dense optax slots +
    # the hybrid head's accumulator plane) and checkpoint byte-identity
    labels, feats, _ = synth_ctr(64 * data * 4, 4, 64, seed=3)
    ctr_conf = {
        "num_fields": "4", "capacity": "1024",
        "batch_size": str(64 * data), "learning_rate": "0.1",
        "num_iters": "1", "seed": "0", "hidden_dims": "64,32",
        "embed_dim": "8", "optimizer": "adagrad", "packed": "0",
        "placement": "hybrid", "placement_head_rows": "128",
    }

    def ctr_lane(zero):
        conf = dict(ctr_conf)
        if zero:
            conf["optimizer_sharding"] = "zero"
        tr = get_model("widedeep")(
            Config(conf), mesh=mesh, data=(labels, feats))
        state = tr.init_state()
        pm = PlacementManager(tr, mesh)
        if pm.active:
            state = pm.adopt(state)
        zm = ZeroManager(tr, mesh)
        if zm.active:
            state = zm.adopt(state)
        step = jax.jit(tr.train_step)
        batch = next(iter(tr.batches()))
        dev = {k: jax.device_put(np.asarray(v)) for k, v in batch.items()}
        state, m = step(state, dev, jax.random.PRNGKey(0))
        if zm.active:
            state = zm.master_state(state)
        if pm.active:
            state = pm.master_state(state)
        return zm, state, float(m["loss"])

    zm, ctr_shard_state, ctr_zero_loss = ctr_lane(zero=True)
    _, ctr_base_state, ctr_base_loss = ctr_lane(zero=False)
    hbm = dict(zm.summary() or {})
    block["hbm"] = {
        "planes": hbm.get("planes"),
        "replicated_bytes": hbm.get("replicated_bytes"),
        "sharded_bytes_per_replica": hbm.get("sharded_bytes_per_replica"),
        "reduction": hbm.get("reduction"),
    }
    block["ctr_loss_parity_f32"] = _finite(
        abs(ctr_zero_loss - ctr_base_loss), 9)
    # checkpoint byte-identity: the manifest (per-array CRC of the exact
    # bytes orbax writes) of the merged sharded state must equal the
    # unsharded run's after identical steps
    m_shard = build_manifest(ctr_shard_state, 0)["arrays"]
    m_base = build_manifest(ctr_base_state, 0)["arrays"]
    block["checkpoint_identical"] = bool(m_shard == m_base)

    # overlap: 2 ride-along under zero: the goodput compute/collective split
    try:
        ov = w2v_lane(zero=True, overlap="2")
        entry = {
            "aggregate_words_per_sec": round(ov["words_per_sec"], 1),
            "speedup_vs_sequential": round(
                ov["words_per_sec"] / shard["words_per_sec"], 3),
            "loss": _finite(ov["loss"], 6),
        }
        try:
            from swiftsnails_tpu.telemetry.goodput import (
                goodput_report, peaks_for,
            )

            g = goodput_report(
                audit=ov["audit"], steps=1, items=macro_n,
                step_seconds=ov["step_seconds"],
                peaks=peaks_for(_state["device_kind"],
                                _state["platform"]), n_chips=n,
            )
            split = g.get("step_split_est")
            if split:
                entry["step_split_est"] = {
                    k: _finite(v, 6) for k, v in split.items()
                }
        except Exception as e:
            _state["errors"].append(f"zero lane goodput failed: {e}")
        block["overlap"] = entry
    except Exception as e:
        _state["errors"].append(f"zero overlap ride-along failed: {e}")

    _state["zero"] = block
    gr = block["grad_reduce"]
    _say(
        f"zero lane: {n}dev (data={data}) HBM "
        f"{block['hbm']['replicated_bytes'] or 0:,} -> "
        f"{block['hbm']['sharded_bytes_per_replica'] or 0:,} B/replica "
        f"({block['hbm']['reduction']}x), grad reduce "
        f"{gr['baseline_bytes'] or 0:,} -> {gr['zero_bytes'] or 0:,} B, "
        f"loss parity {block['loss_parity_f32']}, "
        f"ckpt identical {block['checkpoint_identical']}"
    )


def run_zero_lane() -> int:
    """``--lane zero``: the sharded-optimizer-state lane alone, one JSON
    line out."""
    _state["lane"] = "zero"
    measure_zero()
    block = _state["zero"]
    if block.get("skipped"):
        _emit_once()
        return 1
    # the lane's headline is the sharded run's words/sec (the cost side of
    # the HBM trade must stay visible)
    _state["best"] = (block.get("words_per_sec") or {}).get("zero") or 0.0
    _state["best_path"] = "zero-f32"
    _record_run()
    _emit_once()
    gr = block.get("grad_reduce") or {}
    hbm = block.get("hbm") or {}
    ok = (
        isinstance(hbm.get("reduction"), (int, float))
        and hbm["reduction"] >= 2.0
        and isinstance(block.get("loss_parity_f32"), (int, float))
        and block["loss_parity_f32"] <= 1e-6
        and block.get("checkpoint_identical") is True
        and isinstance(gr.get("zero_bytes"), int)
        and isinstance(gr.get("baseline_bytes"), int)
        and gr["zero_bytes"] <= gr["baseline_bytes"]
    )
    return 0 if ok else 1


AT_SCALE_PAIRS = 255  # planted co-occurrence pairs for the structure stage
AT_SCALE_TRAIN_S = 5.0 if _SMALL else 45.0  # wall-clock training budget
AT_SCALE_MIN_BUDGET_S = 240  # skip the stage below this remaining budget


def measure_at_scale_structure(counts, path_overrides=None) -> None:
    """Learned-structure evidence AT BENCH SCALE (VERDICT r2 missing #5).

    The 128-word probe can't witness what only happens at 1M vocab / dim 200
    (resident hot/cold row split, packed init scaling, head-row contention),
    so: plant AT_SCALE_PAIRS exclusive co-occurrence pairs across the zipf
    head/mid/tail, train the HEADLINE path for a fixed wall-clock at the
    full north-star config, and score partner retrieval (in-out logit of the
    partner vs 8192 random candidates + every other planted partner).
    Reported as ``at_scale_partner_top1`` with per-band detail; an untrained
    table scores ~1/8448.
    """
    import jax
    import jax.numpy as jnp

    from swiftsnails_tpu.data.sampler import batch_stream, skipgram_windows
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.ops.rowdma import unpack_rows
    from swiftsnails_tpu.utils.config import Config

    rng = np.random.default_rng(7)
    # planted words span the frequency bands: resident-hot head, mid, tail
    if _SMALL:
        bands = {"head": (50, 400), "mid": (1_000, 5_000), "tail": (8_000, 18_000)}
    else:
        bands = {
            "head": (100, 1500),
            "mid": (5_000, 50_000),
            "tail": (100_000, 800_000),
        }
    per_band = AT_SCALE_PAIRS // len(bands)
    pair_a, pair_b, band_of = [], [], []
    for name, (lo, hi) in bands.items():
        words = rng.choice(np.arange(lo, hi - 1, 2), per_band, replace=False)
        pair_a += list(words)
        pair_b += list(words + 1)
        band_of += [name] * per_band
    pair_a = np.asarray(pair_a, np.int32)
    pair_b = np.asarray(pair_b, np.int32)

    # corpus: zipf background with planted bigrams interleaved (~30% of
    # tokens), so each pair co-occurs ~1k times per epoch
    n_bg = 200_000 if _SMALL else 1_400_000
    bg = synth_corpus(n_bg, VOCAB, seed=8)
    n_big = len(pair_a) * 1200
    which = rng.integers(0, len(pair_a), n_big)
    bigrams = np.stack([pair_a[which], pair_b[which]], axis=1).reshape(-1)
    # splice bigram pairs into the background at random cut points
    cuts = np.sort(rng.integers(0, n_bg, n_big))
    corpus = np.insert(bg, np.repeat(cuts, 2), bigrams).astype(np.int32)

    # candidate set for partner retrieval: 8192 random + every other
    # planted partner + CONFUSABLE distractors (frequency neighbors b±2 of
    # every true partner: same band, never co-occur with a — the
    # distractors a frequency-prior shortcut would pick). VERDICT r3 weak
    # #5: 1.0-across-bands needed harder negatives and a margin readout.
    confus = np.unique(np.concatenate([pair_b + 2, np.maximum(pair_b - 2, 0)]))
    confus = confus[~np.isin(confus, pair_b)].astype(np.int32)
    cand = rng.choice(VOCAB, 8192, replace=False).astype(np.int32)
    # a true partner duplicated among the random candidates would tie its
    # own score and zero the margin readout spuriously — exclude
    cand = cand[~np.isin(cand, pair_b)]
    cand_all = np.concatenate([pair_b, confus, cand])

    # window generation, vocab, and batch assembly are identical across the
    # main + stress legs (leg overrides only change table dtype / hashing,
    # which apply inside the trainer) — build once, outside the per-leg
    # deadline budget
    base_overrides = {
        "packed": "1", "neg_mode": "pool", "pool_size": str(POOL_SIZE),
        "pool_block": str(POOL_BLOCK), "fused": "1", "grouped": "1",
        "dim": str(DIM), "window": str(WINDOW),
        "negatives": str(NEGATIVES), "learning_rate": "0.025",
        "batch_size": "8192", "subsample": "0", "num_iters": "1",
        "steps_per_call": str(STEPS_PER_CALL), "table_dtype": TABLE_DTYPE,
    }
    shared = {**base_overrides, **(path_overrides or {})}
    dedup_mode = shared.get("dedup") == "1"
    cpb = int(shared.get("centers_per_block", 256) or 256)
    vocab = Vocab([f"w{i}" for i in range(VOCAB)], np.maximum(counts, 1))
    # small mode: interpret-mode kernels on CPU make the full macro batch
    # ~64x too slow for a smoke run
    at_b = 1024 if _SMALL else 8192
    base_overrides["batch_size"] = str(at_b)
    macro = at_b * STEPS_PER_CALL
    srng = np.random.default_rng(9)
    g_c, g_x = skipgram_windows(corpus, WINDOW, srng)
    import itertools

    from swiftsnails_tpu.data.sampler import batch_stream_blocks

    stream = (
        batch_stream_blocks(g_c, g_x, macro, srng, block=cpb)
        if dedup_mode
        else batch_stream(g_c, g_x, macro, srng)
    )
    batches = [
        {k: jnp.asarray(v) for k, v in w.items()}
        for w in itertools.islice(stream, 24)
        if w["centers"].shape[0] == macro
    ]

    def run_leg(leg_overrides, train_s):
        """Train one config on the shared planted corpus; score retrieval."""
        overrides = {**base_overrides, **leg_overrides}
        trainer = Word2VecTrainer(
            Config(overrides), mesh=None, corpus_ids=np.zeros(2, np.int32),
            vocab=vocab,
        )
        state = trainer.init_state()
        step = jax.jit(trainer.train_step, donate_argnums=(0,))
        key = jax.random.PRNGKey(5)
        # warm up (compile) outside the clock, then train for the budget
        state, m = step(state, batches[0], jax.random.fold_in(key, 0))
        _ = float(m["loss"])
        t0 = time.monotonic()
        i = 1
        while time.monotonic() - t0 < train_s:
            state, m = step(state, batches[i % len(batches)],
                            jax.random.fold_in(key, i))
            i += 1
            if i % 16 == 0:
                _ = float(m["loss"])  # drain the dispatch queue
        _ = float(m["loss"])
        trained_words = i * macro

        # partner retrieval: v_in[a] . u_out[partners ∪ confusables ∪ rand];
        # row ids go through the trainer's own mapping (hash_keys legs)
        va = unpack_rows(
            state.in_table.table.at[
                trainer._rows(jnp.asarray(pair_a))
            ].get(mode="promise_in_bounds"), DIM).astype(jnp.float32)
        ub = unpack_rows(
            state.out_table.table.at[
                trainer._rows(jnp.asarray(cand_all))
            ].get(mode="promise_in_bounds"), DIM).astype(jnp.float32)
        scores = np.asarray(va @ ub.T)  # [P, P + C + 8192]
        p = len(pair_a)
        # margin: true-partner logit minus best distractor logit — how far
        # retrieval is from flipping, where top-1 alone saturates at 1.0
        true_s = scores[np.arange(p), np.arange(p)]
        masked = scores.copy()
        masked[np.arange(p), np.arange(p)] = -np.inf
        margin = true_s - masked.max(axis=1)
        # STRICT inequality: an exact score tie (e.g. the hash-collision leg
        # mapping a distractor onto the partner's row) must count as a miss —
        # argmax's first-occurrence bias would otherwise hide collisions
        top1 = margin > 0
        by_band = {
            name: float(
                top1[[i for i, bn in enumerate(band_of) if bn == name]].mean())
            for name in bands
        }
        # raw logit scale is tiny at bench scale (batch-mean normalized
        # updates over 1M rows) — report margins at full precision plus the
        # true-score scale, and the scale-free relative margin
        denom = np.abs(true_s) + 1e-12
        return {
            "partner_top1": float(top1.mean()),
            "by_band": by_band,
            "margin_mean": float(margin.mean()),
            "margin_p10": float(np.percentile(margin, 10)),
            "margin_rel_mean": round(float((margin / denom).mean()), 4),
            "true_score_mean": float(true_s.mean()),
            "confusable_distractors": int(len(confus)),
            "planted_pairs": int(p),
            "trained_words": int(trained_words),
            "train_seconds": round(time.monotonic() - t0, 1),
            # which config actually trained (the headline path's when
            # grouped; plain grouped otherwise — never claim more than ran)
            "trained_overrides": {
                k: overrides[k]
                for k in ("fused", "grouped", "resident", "dedup", "hot_rows",
                          "u_cap", "centers_per_block", "table_dtype",
                          "hash_keys", "capacity")
                if k in overrides
            },
        }

    result = run_leg(dict(path_overrides or {}), AT_SCALE_TRAIN_S)
    # stress legs (VERDICT r3 next #6): the two configs where saturation is
    # least likely to survive — reduced-precision rows, and hash collisions
    # at capacity < vocab (uniform hashing at 2:1 load collides ~39% of
    # rows; colliding words share an embedding, so retrieval MUST degrade —
    # the leg demonstrates the probe can show it)
    legs = {}
    for leg_name, leg_cfg in (
        ("bf16", {"table_dtype": "bfloat16"}),
        ("hash_capacity_half",
         # capacity must be a power of two (hash_row): largest pow2 < vocab
         {"hash_keys": "1",
          "capacity": str(1 << ((VOCAB - 1).bit_length() - 1))}),
    ):
        if BENCH_DEADLINE_S - (time.monotonic() - _T0) < AT_SCALE_MIN_BUDGET_S:
            _state["errors"].append(
                f"at-scale {leg_name} leg skipped (budget)")
            continue
        try:
            legs[leg_name] = run_leg(
                {**(path_overrides or {}), **leg_cfg},
                min(AT_SCALE_TRAIN_S, 20.0),
            )
        except Exception as e:
            _state["errors"].append(f"at-scale {leg_name} leg failed: {e}")
    if legs:
        result["legs"] = legs
    _state["at_scale"] = result
    top1_mean = result["partner_top1"]
    by_band = result["by_band"]
    trained_words = result["trained_words"]
    _say(
        f"at-scale structure: partner top-1 {top1_mean:.3f} "
        f"{by_band} margin {result['margin_mean']:.3f} "
        f"after {trained_words:,} words"
    )
    for leg_name, leg in legs.items():
        _say(
            f"at-scale [{leg_name}]: top-1 {leg['partner_top1']:.3f} "
            f"margin {leg['margin_mean']:.3f}"
        )
    if top1_mean < 0.5:
        _state["errors"].append(
            f"at-scale partner top-1 {top1_mean:.3f} < 0.5: structure "
            "evidence weak at bench scale"
        )


def measure_input_pipeline(ids, pairs_per_token: float) -> None:
    """Host-side input rate: tokens -> pairs -> shuffled macro-batches.

    The native chunk path (skipgram pairgen + C++ PairPrefetcher, the
    product path in Word2VecTrainer.batches). Recorded as words/sec so it
    compares directly against the device rate: the pipeline must sustain
    the chip (survey build item 7) or the bench flags it.
    """
    from swiftsnails_tpu.data import native

    # the grouped (headline) path's window pipeline — native C producer
    # when built (the production path in Word2VecTrainer.batches), Python
    # fallback otherwise. Measured FIRST and unconditionally: the TrainLoop
    # thread prefetcher overlaps it with the device, but the production
    # rate must sustain the chip.
    from swiftsnails_tpu.data.sampler import batch_stream, skipgram_windows

    rng = np.random.default_rng(11)
    t0 = time.perf_counter()
    n_words = 0
    if native.available():
        # the PRODUCTION grouped pipeline: native window fill + native
        # block-ordered batch assembly (the dedup headline path's producer,
        # Word2VecTrainer.batches)
        g_c, g_x = native.skipgram_windows(ids, WINDOW, seed=11)
        wp = native.WindowPrefetcher(
            g_c, g_x, min(BATCH, 8192) * STEPS_PER_CALL, block=256,
            capacity=8, seed=11,
        )
        for w in wp:
            n_words += w["centers"].size
        wp.close()
    else:
        g_c, g_x = skipgram_windows(ids, WINDOW, rng)
        for w in batch_stream(g_c, g_x, min(BATCH, 8192) * STEPS_PER_CALL, rng):
            n_words += w["centers"].size
    dt = time.perf_counter() - t0
    _state["input_words_per_sec_grouped"] = n_words / dt

    if not native.available():
        _state["errors"].append("flat input pipeline not measured (no native lib)")
        return
    t0 = time.perf_counter()
    centers, contexts = native.skipgram_pairs(ids, WINDOW, seed=11)
    pf = native.PairPrefetcher(
        centers, contexts, BATCH * STEPS_PER_CALL, epochs=1, capacity=8, seed=11
    )
    n_pairs = 0
    for b in pf:
        n_pairs += b["centers"].size
    pf.close()
    dt = time.perf_counter() - t0
    _state["input_words_per_sec"] = n_pairs / dt / pairs_per_token


def measure_cpu_baseline(batches, pairs_per_token: float, counts) -> None:
    """Calibrated per-node CPU PS worker rate, words/sec.

    Prefers the compiled C loop (libsnails.cpp ssn_sgns_train); falls back to
    a vectorized-numpy approximation when the native toolchain is missing
    (recorded in baseline_kind — the numpy figure is ~10-50x slower on the
    scatter side and unfair to the reference).
    """
    rng = np.random.default_rng(0)
    centers = np.concatenate([b["centers"] for b in batches])
    contexts = np.concatenate([b["contexts"] for b in batches])
    try:
        from swiftsnails_tpu.data import native

        if not native.available():
            raise RuntimeError(native.build_error() or "native unavailable")
        # median-of-N: the C loop's rate swings with machine load (~50% in
        # round 2's artifacts); the median + per-run list make the baseline
        # reproducible and its noise visible
        runs = []
        for _ in range(BASELINE_RUNS):
            syn0 = (rng.random((VOCAB, DIM), dtype=np.float32) - 0.5) / DIM
            syn1 = np.zeros((VOCAB, DIM), dtype=np.float32)
            dt = native.sgns_train(
                syn0, syn1, centers, contexts, counts, negatives=NEGATIVES, lr=0.025
            )
            runs.append(centers.size / dt / pairs_per_token)
        _state["baseline_runs"] = runs
        _state["baseline_node"] = float(np.median(runs))
        _state["baseline_kind"] = "c-loop"
        return
    except Exception as e:
        _state["errors"].append(f"C baseline failed, using numpy: {e}")

    syn0 = (rng.random((VOCAB, DIM), dtype=np.float32) - 0.5) / DIM
    syn1 = np.zeros((VOCAB, DIM), dtype=np.float32)
    lr = np.float32(0.025)

    def sigmoid(x):
        return 1.0 / (1.0 + np.exp(-x))

    n = min(centers.size, 4 * BATCH)
    t0 = time.perf_counter()
    for lo in range(0, n, BATCH):
        c, x = centers[lo : lo + BATCH], contexts[lo : lo + BATCH]
        negs = rng.integers(0, VOCAB, size=(len(c), NEGATIVES)).astype(np.int32)
        v = syn0[c]
        u_pos = syn1[x]
        u_neg = syn1[negs.reshape(-1)].reshape(len(c), NEGATIVES, DIM)
        g_pos = sigmoid(np.einsum("bd,bd->b", v, u_pos)) - 1.0
        g_neg = sigmoid(np.einsum("bd,bkd->bk", v, u_neg))
        dv = g_pos[:, None] * u_pos + np.einsum("bk,bkd->bd", g_neg, u_neg)
        np.add.at(syn0, c, -lr * dv)
        np.add.at(syn1, x, -lr * (g_pos[:, None] * v))
        np.add.at(
            syn1, negs.reshape(-1), -lr * (g_neg[..., None] * v[:, None, :]).reshape(-1, DIM)
        )
    dt = time.perf_counter() - t0
    _state["baseline_node"] = n / dt / pairs_per_token
    _state["baseline_kind"] = "numpy"


def main(argv=None):
    import argparse

    lanes = {
        "full": run_full_bench, "scaling": run_scaling_lane,
        "chaos": run_chaos_lane, "serve": run_serve_lane,
        "fleet": run_fleet_lane, "tiered": run_tiered_lane,
        "chaos-serve": run_chaos_serve_lane,
        "chaos-cluster": run_chaos_cluster_lane,
        "freshness": run_freshness_lane, "drift": run_drift_lane,
        "zero": run_zero_lane, "net": run_net_lane,
    }
    parser = argparse.ArgumentParser(
        prog="bench", description="word2vec words/sec/chip benchmark")
    parser.add_argument(
        "--lane",
        choices=tuple(lanes),
        default="full",
        help="full = the headline bench (default); scaling = the scale-out "
             "lane alone (grouped-mesh 1-vs-N throughput per comm_dtype plus "
             "the zipf-skewed uniform-vs-hybrid placement leg; exchange "
             "bytes are compiled-HLO shapes, so valid on CPU); "
             "chaos = the resilience "
             "lane alone (guardrail overhead + scripted-fault recovery "
             "drills; valid on CPU); serve = the read-path latency lane "
             "(pull/top-k/CTR-score qps + p50/p95/p99; valid on CPU); "
             "fleet = the replica-pool lane (max sustainable QPS at a fixed "
             "p99 SLO for 1 vs N replicas behind the affinity/hedging "
             "router, open-loop zipf load; valid on CPU); "
             "tiered = the host-tier parameter store lane (words/sec vs "
             "resident + over-budget round trip; valid on CPU); chaos-serve "
             "= the serving availability drill (fault matrix vs a live "
             "Servant with breakers + degraded reads, corrupt-reload and "
             "tier bit-flip drills; valid on CPU); chaos-cluster = the "
             "cluster membership drill (simulated fleet under a kill/"
             "straggle/partition storm; exactly-once accounting + elastic "
             "reassignment vs an unsupervised control; valid on CPU); "
             "freshness = the trainer->fleet delta pipeline lane (hot-row "
             "delta publish/apply under load: bit parity at the watermark, "
             "lag p99, serve p99 while applying, forced-gap fallback; "
             "valid on CPU); drift = the training-plane drift drill "
             "(slow_step injection vs the online EWMA/CUSUM sentinel: "
             "detection + one drift event + complete incident bundle + "
             "host-blocked --diff attribution, plus the continuous "
             "profiler's own overhead vs the 3% ceiling; valid on CPU); "
             "zero = the sharded-optimizer-state lane "
             "(optimizer_sharding: zero — per-replica HBM of the replicated "
             "slot planes before/after sharding, audited reduce-scatter vs "
             "psum exchange bytes, f32 loss parity + checkpoint "
             "byte-identity vs unsharded, overlap: 2 goodput ride-along; "
             "bytes/parity are compiled shapes + bit checks, so valid on "
             "CPU); net = the TCP serving lane (three legs: in-process "
             "control vs a TCP fleet of spawned replica_server processes "
             "vs the same fleet under a proc_kill/net_partition/publisher-"
             "kill fault storm — availability through a SIGKILL'd replica, "
             "lease-expiry drain + respawn + rejoin, stale-write refusal "
             "on partition heal, TCP delta-stream bit parity, and the "
             "TCP-vs-in-process p99 envelope; valid on CPU)",
    )
    args = parser.parse_args(argv)
    watchdog = threading.Timer(BENCH_DEADLINE_S - (time.monotonic() - _T0), _deadline)
    watchdog.daemon = True  # don't keep the process alive after success
    watchdog.start()
    try:
        require_device()  # every lane: first thing, in this process
        return lanes[args.lane]()
    except NoAcceleratorError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    finally:
        watchdog.cancel()


def run_full_bench() -> int:
    """``--lane full``: every step path, then the ride-along lanes."""
    from swiftsnails_tpu.data.sampler import batch_stream, skipgram_pairs

    rng = np.random.default_rng(1)
    n_tokens = 600_000
    ids = synth_corpus(n_tokens, VOCAB)
    counts = np.bincount(ids, minlength=VOCAB).astype(np.int64)
    counts = np.maximum(counts, 1)
    centers, contexts = skipgram_pairs(ids, WINDOW, rng)
    pairs_per_token = len(centers) / n_tokens
    _state["pairs_per_token"] = pairs_per_token
    # held-out eval pairs for the per-path quality gate — training batches
    # come from the rest. Restricted to frequent-word pairs with unigram
    # negatives: rows touched often enough in a ~1-minute run that a wrong
    # update rule visibly moves the eval loss (rare-row logits stay ~0 and
    # would pin every path at the untrained ln2*(1+K)).
    tail = slice(len(centers) - 200_000, len(centers))
    hot = np.argsort(counts)[-2000:]
    hot_mask = np.isin(centers[tail], hot) & np.isin(contexts[tail], hot)
    n_eval = 4096
    ev_idx = np.flatnonzero(hot_mask)[:n_eval]
    if len(ev_idx) < 256:  # degenerate counts: fall back to unrestricted
        ev_idx = np.arange(min(n_eval, tail.stop - tail.start))
    _EVAL["centers"] = centers[tail][ev_idx]
    _EVAL["contexts"] = contexts[tail][ev_idx]
    neg_pool = np.repeat(np.arange(VOCAB), np.minimum(counts, 1000))
    _EVAL["negs"] = rng.choice(
        neg_pool, size=(len(ev_idx), NEGATIVES)
    ).astype(np.int32)
    centers, contexts = centers[: tail.start], contexts[: tail.start]
    macro = BATCH * STEPS_PER_CALL
    batches = list(batch_stream(centers, contexts, macro, rng))[:8]
    batches = [b for b in batches if b["centers"].shape[0] == macro]

    # 1. CPU baseline (the compiled C loop) for the vs_baseline fields.
    flat = [
        {k: v[i * BATCH : (i + 1) * BATCH] for k, v in b.items()}
        for b in batches[:2]
        for i in range(STEPS_PER_CALL)
    ]
    measure_cpu_baseline(flat, pairs_per_token, counts)

    # 2. Every step path on the device. A stage that raises fails the run.
    #    Grouped batches must not touch the eval-tail corpus positions (the
    #    last 200k pairs ~ 200k/ppt positions feed _EVAL) — training on the
    #    held-out pairs would bias that path through its own quality gate.
    eval_span = int(200_000 / pairs_per_token) + WINDOW + 1
    ids_train = ids[: max(len(ids) - eval_span, 0)]
    measure_tpu_paths(counts, ids_train, batches, pairs_per_token)

    # 3. At-scale structure evidence, then the scale-out, resilience and
    #    sharded-optimizer lanes — each only if the budget still holds it (a
    #    skip is recorded in ``errors``); one that runs and raises fails the
    #    run.
    if BENCH_DEADLINE_S - (time.monotonic() - _T0) >= AT_SCALE_MIN_BUDGET_S:
        best_ov = _state["best_overrides"]
        if best_ov and best_ov.get("grouped") != "1":
            _state["errors"].append(
                f"at-scale stage: headline path {_state['best_path']} has "
                "no window schema; trained the grouped kernel instead "
                "(see at_scale.trained_overrides)"
            )
            best_ov = None
        measure_at_scale_structure(counts, best_ov)
    else:
        _state["errors"].append("at-scale structure stage skipped (budget)")
    for lane_name, min_budget, lane_fn in (
        ("scaling", SCALING_MIN_BUDGET_S,
         lambda: measure_scaling(counts, ids_train)),
        ("chaos", CHAOS_MIN_BUDGET_S, measure_chaos),
        ("zero", ZERO_MIN_BUDGET_S, measure_zero),
    ):
        if BENCH_DEADLINE_S - (time.monotonic() - _T0) >= min_budget:
            lane_fn()
        else:
            _state["errors"].append(f"{lane_name} lane skipped (budget)")

    # 4. Host input-pipeline rate must sustain the device rate.
    measure_input_pipeline(ids, pairs_per_token)
    grouped_family = {"fused-grouped", "fused-resident", "fused-dedup",
                      "fused-dedup-res"}
    in_rate = (
        _state["input_words_per_sec_grouped"]
        if _state["best_path"] in grouped_family
        else _state["input_words_per_sec"]
    )
    # the rate of the pipeline that actually feeds the headline path — the
    # number the >=2x-the-chip producer target is judged against
    _state["input_words_per_sec_production"] = in_rate
    if in_rate and _state["best"] and in_rate < _state["best"]:
        _state["errors"].append(
            f"input pipeline ({in_rate:,.0f} words/s) below device rate "
            f"({_state['best']:,.0f} words/s): host-bound at full scale"
        )

    _record_run()
    _emit_once()
    return 0 if _state["best"] > 0 else 1


def _record_run():
    """Append this run's result to the ledger (a record, never a source:
    nothing reads a result back out of the ledger to print it again)."""
    payload = json.loads(_result_json())
    payload["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    from swiftsnails_tpu.telemetry.ledger import config_hash, env_fingerprint

    _ledger().append(
        "bench",
        {
            "payload": payload,
            "config_hash": config_hash(payload.get("config", {})),
            "device_kind": _state["device_kind"],
        },
        env=env_fingerprint(include_devices=True),
    )


if __name__ == "__main__":
    raise SystemExit(main())
