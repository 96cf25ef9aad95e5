"""Trainer contract and the training driver loop.

Capability parity with the reference's worker framework
(``src/core/framework/SwiftWorker.h``):

* ``BaseAlgorithm<Key,Val,Grad,Record>`` (``SwiftWorker.h:19-57``: virtual
  ``train()`` / ``parse_record()``, a data path, a private thread channel)
  -> :class:`Trainer`: subclasses provide ``init_state`` / ``batches`` /
  ``train_step`` and the framework owns the loop;
* ``SwiftWorker::operator()`` (``SwiftWorker.h:88-124``: cluster init, then
  ``alg.train()``, then terminate) -> :class:`TrainLoop`: jit + donation,
  device feed, metrics windows, periodic checkpoint hook;
* ``local_train`` mode (``SwiftWorker.h:114-123``: skip the cluster, train
  against the local cache) -> a ``None``/single-device mesh — the same code
  path, just a trivial mesh.

Config keys honored (reference inventory, survey §2.9): ``num_iters``,
``learning_rate``, ``batch_size``, ``param_backup_period``,
``param_backup_root``, ``local_train`` — plus the resilience surface
(``docs/RESILIENCE.md``): ``param_backup_keep``, ``resume`` (``1``/``auto``),
``guardrail`` / ``guard_max_update_norm`` / ``guard_max_consecutive``, and
``chaos_spec`` / ``chaos_seed``.
"""

from __future__ import annotations

import queue
import signal
import sys
import threading
import time
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from swiftsnails_tpu.utils.config import Config
from swiftsnails_tpu.utils.metrics import MetricsLogger
from swiftsnails_tpu.utils.profiling import StepProfiler, step_annotation
from swiftsnails_tpu.telemetry.tracer import span_fn, tracer_from_config
from swiftsnails_tpu.parallel.mesh import DATA_AXIS, batch_sharding


class Trainer:
    """Pluggable training algorithm (``BaseAlgorithm`` equivalent).

    Subclasses implement:

    * :meth:`init_state`  — build the (sharded) model state pytree;
    * :meth:`batches`     — yield host batches (dicts of numpy arrays, static
      shapes; the analog of ``parse_record`` + minibatching);
    * :meth:`train_step`  — pure jit-compatible ``(state, batch, rng) ->
      (state, metrics)``;
    * :meth:`items_per_batch` — unit count for throughput metrics (words,
      examples).
    """

    name: str = "trainer"

    def __init__(self, config: Config, mesh: Optional[Mesh] = None,
                 tracer=None):
        from swiftsnails_tpu.parallel.zero import resolve_optimizer_sharding

        self.config = config
        self.mesh = mesh
        # the run's span tracer (cli._build_trainer makes it, TrainLoop adopts
        # it) or None; `self.span` is a no-op without one
        self.tracer = tracer
        self.span = span_fn(tracer)
        # optimizer_sharding: zero -> ZeRO-style update sharding of every
        # replicated optimizer plane across the data axis (parallel/zero.py)
        self.optimizer_sharding = resolve_optimizer_sharding(
            config.get_str("optimizer_sharding", "none"))

    # -- subclass API ------------------------------------------------------

    def init_state(self) -> Any:
        raise NotImplementedError

    def batches(self) -> Iterator[Dict[str, np.ndarray]]:
        raise NotImplementedError

    def train_step(self, state: Any, batch: Dict[str, jax.Array], rng: jax.Array
                   ) -> Tuple[Any, Dict[str, jax.Array]]:
        raise NotImplementedError

    def items_per_batch(self, batch: Dict[str, np.ndarray]) -> int:
        first = next(iter(batch.values()))
        return int(first.shape[0])

    # -- optional hooks ----------------------------------------------------

    def export_text(self, state: Any, path: str) -> None:
        """Final param export (ServerTerminate parity). Optional."""

    def eval_metrics(self, state: Any) -> Dict[str, float]:
        return {}

    # -- tiered-store hooks (table_tier: host; see swiftsnails_tpu/tiered) --

    def tier_spec(self) -> Optional[Dict[str, Dict]]:
        """``{table_name: {"layout": dense|packed|packed_small, "group": G}}``
        for trainers that support the host tier; ``None`` (default) means
        ``table_tier: host`` is rejected for this trainer."""
        return None

    def tier_tables(self, state: Any) -> Dict[str, Any]:
        """Extract the tierable table states from the state pytree, keyed to
        match :meth:`tier_spec`."""
        raise NotImplementedError

    def tier_with_tables(self, state: Any, tables: Dict[str, Any]) -> Any:
        """Rebuild the state pytree with (some) table states replaced."""
        raise NotImplementedError

    def tier_plan(self, batch: Dict[str, np.ndarray], root_rng: jax.Array,
                  step: np.uint32):
        """Host-side plan for one step: ``(ids, aug, remap_keys)`` where
        ``ids[name]`` is every master row id the step will touch in that
        table (hashing already applied), ``aug`` holds batch keys to
        add/replace (e.g. pre-sampled negatives — the in-jit RNG derivation
        replicated so the plan is exact, not a guess), and
        ``remap_keys[name]`` lists the batch keys to remap into cache-slot
        space. The per-step key is ``fold_in(root_rng, step)`` — derive it
        INSIDE a jitted plan fn (the step counter as a uint32 operand, like
        the step fn itself) so the plan costs one dispatch, not an eager
        threefry chain."""
        raise NotImplementedError

    def tier_warm_rows(self) -> Optional[Dict[str, np.ndarray]]:
        """Hottest-first master row ids per table for the pre-step-0 cache
        prewarm (seeded from corpus frequency ranks); ``None`` to skip."""
        return None

    def table_geometry(self) -> Optional[Dict[str, Dict]]:
        """``{table: {"layout", "group", "dim", "capacity"}}`` for the
        freshness publisher — :meth:`tier_spec`'s layout map WITHOUT the
        ``table_tier`` gate (resident runs publish too) plus the logical
        row geometry. ``None`` (default) disables delta publishing."""
        return None

    # -- hybrid-placement hook (placement: hybrid|auto; parallel/hybrid.py) --

    def placement_spec(self) -> Optional[Dict[str, Dict]]:
        """``{table_name: {"cut": K, "group": G}}`` head/tail split per table
        (names match :meth:`tier_tables`); ``None``/empty means uniform
        placement and the loop pays nothing."""
        return None

    # -- ZeRO hooks (optimizer_sharding: zero; parallel/zero.py) -----------

    def zero_planes(self, state: Any) -> Any:
        """Replicated dense-optimizer subtree of the state pytree whose
        eligible leaves ZeroManager shards across the data axis; ``None``
        (default) means this trainer carries no dense optimizer planes
        (hybrid head slots are discovered through :meth:`tier_tables`)."""
        return None

    def zero_with_planes(self, state: Any, planes: Any) -> Any:
        """Rebuild the state pytree with the optimizer subtree replaced."""
        return state


class _Prefetcher:
    """Bounded background-thread batch prefetch (``queue_with_capacity``
    parity, ``src/utils/queue.h:100-108``): the producer thread runs the
    trainer's host-side record parsing/sampling while the device computes.
    A ``None`` sentinel is the poison value; producer errors re-raise on the
    consumer side."""

    _DONE = object()

    def __init__(self, it: Iterator, depth: int = 2, span=span_fn(None)):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()
        self._exhausted = False
        self.last_wait_ns = 0  # consumer block on the last __next__
        it = iter(it)

        def produce():
            # `produce` spans are the producer's busy time (the source's own
            # parsing/sampling), `queue-full` its wait for the consumer
            try:
                while True:
                    with span("produce"):
                        item = next(it, self._DONE)
                    if item is self._DONE:
                        break
                    try:
                        self._q.put_nowait(item)
                    except queue.Full:
                        with span("queue-full"):
                            while not self._stop.is_set():
                                try:
                                    self._q.put(item, timeout=0.1)
                                    break
                                except queue.Full:
                                    continue
                    if self._stop.is_set():
                        return
            except BaseException as e:  # surfaced in __next__
                self._err = e
            finally:
                # The sentinel must never strand this thread: with depth=1 a
                # close() can drain, then our pending data put refills the
                # queue, and a blocking put here would wait forever. Keep
                # trying while live; once stopped, nobody will get() again.
                while True:
                    try:
                        self._q.put(self._DONE, timeout=0.1)
                        break
                    except queue.Full:
                        if self._stop.is_set():
                            break

        self._thread = threading.Thread(target=produce, daemon=True)
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            # idempotent end state: a retrying consumer (resilience path)
            # must re-see the error/stop instead of blocking on the drained
            # queue forever
            if self._err is not None:
                raise self._err
            raise StopIteration
        t0 = time.monotonic_ns()
        item = self._q.get()
        self.last_wait_ns = time.monotonic_ns() - t0
        if item is self._DONE:
            self._exhausted = True
            self._thread.join()
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def qsize(self) -> int:
        """Approximate queued-batch count (telemetry gauge: a persistently
        empty queue means the host pipeline is the bottleneck)."""
        return self._q.qsize()

    def set_depth(self, depth: int) -> None:
        """Grow (or shrink) the queue bound in place — the adaptive
        ``tier_prefetch_depth: auto`` control. ``queue.Queue`` guards
        ``maxsize`` with its own mutex; waking ``not_full`` lets a producer
        blocked on the old bound use the new headroom immediately."""
        q = self._q
        with q.mutex:
            q.maxsize = max(int(depth), 1)
            q.not_full.notify_all()

    def close(self):
        self._stop.set()
        # drain so the producer's pending put unblocks promptly, then reap it
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=2.0)


_STREAM_END = object()


class TrainLoop:
    """The driver: jit with state donation, device feed, metrics, checkpoints."""

    def __init__(
        self,
        trainer: Trainer,
        metrics: Optional[MetricsLogger] = None,
        checkpoint_fn: Optional[Callable[[Any, int], None]] = None,
        log_every: int = 100,
        cluster=None,
    ):
        self.trainer = trainer
        self.metrics = metrics or MetricsLogger(echo=False)
        self.log_every = log_every
        cfg = trainer.config
        self.backup_period = cfg.get_int("param_backup_period", 0)
        self.backup_root = cfg.get_str("param_backup_root", "")
        self.backup_keep = cfg.get_int("param_backup_keep", 3)
        from swiftsnails_tpu.telemetry.ledger import config_hash

        self.config_hash = config_hash(cfg.as_dict())
        # the ledger rides with any ledger_path (resilience events need it
        # even when the full telemetry stack is off); tracer/registry/black
        # box stay telemetry-gated below
        ledger_path = cfg.get_str("ledger_path", "")
        if ledger_path:
            from swiftsnails_tpu.telemetry import Ledger

            self.ledger = Ledger(ledger_path)
        else:
            self.ledger = None
        self._restored_step = None  # set by resume; protected from pruning
        self._items_seen = 0
        # cluster membership: an explicit WorkerClient wins (tests / a shared
        # in-process supervisor); `cluster_workers: N` self-hosts one — the
        # run still gets range-leased streams, exactly-once accounting, and a
        # watermark-carrying checkpoint cursor (see cluster/)
        self.cluster = cluster
        if self.cluster is None and cfg.get_int("cluster_workers", 0) > 0:
            from swiftsnails_tpu.cluster import Supervisor, WorkerClient

            sup = Supervisor.from_config(cfg, ledger=self.ledger)
            self.cluster = WorkerClient(
                sup, cfg.get_str("cluster_worker_id", "w0"))
        if checkpoint_fn is None and self.backup_root:
            from swiftsnails_tpu.framework.checkpoint import save_checkpoint

            # async periodic saves: training continues while shards write;
            # the manifest (step, config hash, CRCs, data cursor) commits
            # when the write lands, and retention prunes old generations
            from swiftsnails_tpu.resilience.retry import RetryPolicy

            ckpt_retry = RetryPolicy.from_config(cfg)

            def checkpoint_fn(state, step):
                ckpt_retry.ledger = self.ledger  # ledger binds below
                cursor = {"step": step, "items": self._items_seen}
                if self.cluster is not None:
                    # committed watermarks ride the data cursor, so resume
                    # restores exactly-once accounting across reassignment
                    cursor["cluster"] = self.cluster.cursor()
                save_checkpoint(
                    self.backup_root, state, step, wait=False,
                    cursor=cursor,
                    config_hash=self.config_hash,
                    keep=self.backup_keep, protect=self._restored_step,
                    ledger=self.ledger, tier=self.tier, retry=ckpt_retry,
                    placement=self.placement, zero=self.zero,
                )
        self.checkpoint_fn = checkpoint_fn
        self.profiler = StepProfiler(cfg)
        # resilience is opt-in per key: `guardrail: 1` arms the per-step
        # health check + rollback; a non-empty `chaos_spec` arms the fault
        # injector. Off => both stay None and the hot path pays flag checks.
        if cfg.get_bool("guardrail", False):
            from swiftsnails_tpu.resilience.guardrail import StepGuardrail

            self.guardrail = StepGuardrail(
                max_update_norm=cfg.get_float("guard_max_update_norm", 0.0),
                max_consecutive=cfg.get_int("guard_max_consecutive", 3),
            )
        else:
            self.guardrail = None
        if cfg.get_str("chaos_spec", "").strip():
            from swiftsnails_tpu.resilience.chaos import ChaosPlan

            self.chaos = ChaosPlan.from_config(cfg, ledger=self.ledger)
        else:
            self.chaos = None
        self._preempt = threading.Event()
        self._preempt_reason = None
        self.preempted = False
        self._prev_sigterm = None
        # telemetry is opt-in (`telemetry: 1` or a `trace_path`); when off,
        # tracer/registry/black-box stay None and run()'s spans are the
        # shared no-op. A trainer from cli._build_trainer brings the run's
        # tracer, with the set-up spans already in it
        self.tracer = (trainer.tracer if trainer.tracer is not None
                       else tracer_from_config(cfg))
        if self.tracer is not None:
            from swiftsnails_tpu.telemetry import (
                BlackBox, MetricRegistry, StdoutSummarySink,
            )

            sinks = [self.metrics]
            if cfg.get_bool("telemetry_stdout", False):
                sinks.append(StdoutSummarySink())
            self.registry = MetricRegistry(sinks=sinks)
            bb_steps = cfg.get_int("blackbox_steps", 32)
            if bb_steps > 0:
                self.blackbox = BlackBox(
                    capacity=bb_steps,
                    directory=cfg.get_str("blackbox_dir", "blackbox"),
                    ledger=self.ledger,
                    context={"model": trainer.name,
                             "config_hash": self.config_hash},
                )
            else:
                self.blackbox = None
            # goodput needs one compile-only audit of the step function; a
            # second lowering of the same shapes, so gateable independently
            self._want_audit = cfg.get_bool("goodput", True)
            # continuous profiling: a bounded ring of periodic metric samples
            # (`profile_cadence` steps, 0 = off) — the registry snapshot plus
            # the per-window goodput decomposition, tier breakdown, and
            # comm-audit bytes; exportable as JSONL and summarized into the
            # run record for sparklines
            self.profile_cadence = cfg.get_int("profile_cadence", 0)
            if self.profile_cadence > 0:
                from swiftsnails_tpu.telemetry.timeseries import TimeSeriesStore

                self.timeseries = TimeSeriesStore(
                    window=cfg.get_int("profile_window", 512))
            else:
                self.timeseries = None
            # drift sentinel: EWMA/CUSUM detectors over the sampled signals;
            # a confirmed drift appends one transition-edged `drift` ledger
            # event and captures an incident bundle under `incident_dir`
            if cfg.get_bool("drift_detect", False):
                from swiftsnails_tpu.telemetry.drift import DriftSentinel

                self.drift = DriftSentinel(
                    alpha=cfg.get_float("drift_ewma_alpha", 0.3),
                    k=cfg.get_float("drift_cusum_k", 1.0),
                    h=cfg.get_float("drift_cusum_h", 6.0),
                    warmup=cfg.get_int("drift_warmup", 8),
                    ledger=self.ledger,
                    context={"model": trainer.name,
                             "config_hash": self.config_hash},
                )
            else:
                self.drift = None
            self.incident_dir = cfg.get_str("incident_dir", "incidents")
        else:
            self.registry = None
            self.blackbox = None
            self._want_audit = False
            self.timeseries = None
            self.drift = None
            self.profile_cadence = 0
            self.incident_dir = ""
        self.incidents: List[str] = []
        self._incident_reasons: set = set()
        self._run_event_idx = 0  # the tracer's length when run() began
        self._profile_event_idx = 0
        self._profile_pending_loss = None
        self._audit_report = None
        # table_tier: host -> the tiered parameter store (tiered/): full-size
        # masters in host RAM, fixed-budget HBM cache planes in the state
        # pytree, per-step fault + id remap before dispatch. `device`
        # (default) keeps today's resident tables and pays nothing.
        table_tier = cfg.get_str("table_tier", "device")
        if table_tier not in ("device", "host"):
            raise ValueError(
                f"table_tier must be device|host, got {table_tier!r}")
        if table_tier == "host":
            from swiftsnails_tpu.tiered import TierManager

            self.tier = TierManager(
                trainer, registry=self.registry, tracer=self.tracer)
        else:
            self.tier = None
        # placement: hybrid|auto -> head/tail hybrid split of the sparse
        # tables (parallel/placement.py): the zipf head lives replicated, the
        # tail keeps the model-sharded collectives. Inactive (uniform, no
        # mesh, tiered, or a zero cut) => None and the loop pays nothing.
        from swiftsnails_tpu.parallel.placement import PlacementManager

        pm = PlacementManager(trainer, trainer.mesh)
        self.placement = pm if pm.active else None
        # optimizer_sharding: zero -> shard replicated optimizer planes
        # across the data axis (parallel/zero.py). Inactive (none, or no
        # mesh) => None and the loop pays nothing.
        from swiftsnails_tpu.parallel.zero import ZeroManager

        zm = ZeroManager(trainer, trainer.mesh)
        self.zero = zm if zm.active else None
        # freshness_publish: N steps + freshness_dir -> hot-row delta
        # publishing to serving subscribers (freshness/; docs/FRESHNESS.md).
        # Off (the default) => None and the hot path pays one flag check.
        self.freshness = None
        if (cfg.get_int("freshness_publish", 0) > 0
                and cfg.get_str("freshness_dir", "")):
            from swiftsnails_tpu.freshness.publisher import TrainPublisher

            fresh = TrainPublisher(
                trainer, tier=self.tier, placement=self.placement,
                ledger=self.ledger)
            self.freshness = fresh if fresh.active else None
        # tier integrity sweep cadence (steps; 0 = only at heal requests).
        # Runs on the resilient path only — like chaos/guardrail, arming it
        # costs the plain hot path nothing.
        self.tier_verify_period = cfg.get_int("tier_verify_period", 0)
        # per-step dispatch cost trimming: the batch/replicated shardings are
        # mesh properties — build them ONCE instead of per step, and fold the
        # per-step RNG derivation into the jitted step itself (the step
        # counter rides in as a uint32 array operand, so the host no longer
        # dispatches a separate fold_in op per step and nothing retraces)
        mesh = trainer.mesh
        if mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1:
            self._batch_sharding = batch_sharding(mesh)
            self._replicated = NamedSharding(mesh, P())  # scalars (progress)
        else:
            self._batch_sharding = None
            self._replicated = None

        def _step(state, batch, root_rng, step):
            rng = jax.random.fold_in(root_rng, step)
            return trainer.train_step(state, batch, rng)

        self._step_fn = jax.jit(_step, donate_argnums=(0,))
        # guardrail rollback needs the pre-step tables to survive the step:
        # instead of a per-step device copy, the guarded path runs a
        # NON-donating compile of the same step — the input buffers ARE the
        # snapshot (same 2x table memory as copy+donate, none of the copy
        # bandwidth or dispatch)
        self._step_fn_guarded = (
            jax.jit(_step) if self.guardrail is not None else None
        )

    def _device_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        if self._batch_sharding is None:
            return {k: jnp.asarray(v) for k, v in batch.items()}
        bs = self._batch_sharding
        rep = self._replicated
        data = bs.mesh.shape.get(DATA_AXIS, 1)

        def put(v):
            # batch-shard only what actually splits across the data axis;
            # scalars and step-wide entries (e.g. the tier's pre-sampled
            # negative pools, whose leading dim counts pools, not examples)
            # replicate instead
            if np.ndim(v) and np.shape(v)[0] % data == 0:
                return jax.device_put(v, bs)
            return jax.device_put(v, rep)

        return {k: put(v) for k, v in batch.items()}

    def run(self, seed: int = 0, max_steps: Optional[int] = None) -> Any:
        trainer = self.trainer
        state = trainer.init_state()
        step = 0
        skip_batches = 0
        from swiftsnails_tpu.resilience.resume import resume_mode

        mode = resume_mode(trainer.config)
        if mode != "off" and self.backup_root:
            from swiftsnails_tpu.resilience.resume import resume_state

            restored = resume_state(
                self.backup_root, state, mode=mode, ledger=self.ledger,
                config_hash=self.config_hash,
            )
            if restored is not None:
                # continue the step counter so later checkpoints advance
                # monotonically and the per-step RNG stream doesn't replay
                state, step, cursor = restored
                self._restored_step = step
                if mode == "auto":
                    # continue the data stream where the checkpoint left it:
                    # the batch generators are seed-deterministic, so
                    # skipping the consumed prefix IS the saved cursor
                    skip_batches = int(cursor.get("step", step) or 0)
                    self._items_seen = int(cursor.get("items", 0) or 0)
                    if self.cluster is not None:
                        # restore committed watermarks instead of a flat
                        # skip: the leased stream's first-writer-wins claims
                        # skip exactly the committed indices, so a run that
                        # adopted a reassigned (out-of-order) span replays
                        # bit-identically
                        self.cluster.restore(cursor.get("cluster") or {})
                        skip_batches = 0
        root_rng = jax.random.PRNGKey(seed)
        last_metrics: Dict[str, jax.Array] = {}
        total_items = 0
        tier = self.tier
        if tier is not None:
            # full-size device planes -> host masters + HBM cache planes
            # (prewarmed with the vocab's hottest rows); from here on `state`
            # carries the small cache planes until master_state() at the end
            state = tier.adopt(state)
        if self.placement is not None:
            # uniform master layout -> head/tail hybrid planes (eager,
            # value-preserving; runs AFTER resume so a uniform-layout
            # checkpoint restores transparently into a hybrid run)
            state = self.placement.adopt(state)
        if self.zero is not None:
            # replicated optimizer planes -> 1/data resident shards
            # (placement-only, values unchanged; runs AFTER placement.adopt
            # so the hybrid head's slot planes exist to shard)
            state = self.zero.adopt(state)
        fresh = self.freshness
        if fresh is not None:
            # one publisher incarnation per run, based on the resumed step;
            # under table_tier: host this also installs the flush tee (so it
            # must run AFTER tier.adopt built the tables)
            fresh.open(base_step=step)
        depth = trainer.config.get_int("prefetch_batches", 2)
        cl = self.cluster
        if cl is not None:
            # range-leased stream: indices are claimed (first-writer-wins)
            # as they're yielded and committed at the step boundary below
            src = iter(cl.leased_stream(trainer.batches))
        else:
            src = iter(trainer.batches())
        if tier is not None:
            # stage upcoming steps' plans + missing master rows on the
            # producer thread so the H2D fault traffic overlaps compute.
            # A fully-transparent tier stages nothing — keep the trainer's
            # own prefetch setting instead of forcing the staging pipeline
            src = tier.stage_stream(src, root_rng)
            if not tier.all_transparent:
                depth = tier.prefetch_depth
        tel = self.tracer
        span = span_fn(tel)
        if tel is not None:
            self._run_event_idx = self._profile_event_idx = tel.n_events()
        batches = _Prefetcher(src, depth=depth, span=span) if depth else src
        if tier is not None and isinstance(batches, _Prefetcher):
            tier.attach_prefetcher(batches)  # tier_prefetch_depth: auto
        reg = self.registry
        bb = self.blackbox
        guard = self.guardrail
        chaos = self.chaos
        resilient = (guard is not None or chaos is not None
                     or (tier is not None and self.tier_verify_period > 0))
        self._install_sigterm()
        it = iter(batches)
        if chaos is not None:
            it = chaos.wrap_stream(it)
        if resilient:
            # transient OSError (flaky filesystem, chaos TransientDataError)
            # survives under the shared retry policy; exhaustion is a durable
            # retry_exhausted ledger event before the error propagates
            from swiftsnails_tpu.resilience.retry import (
                RetryingIterator, RetryPolicy)

            policy = RetryPolicy.from_config(
                self.trainer.config, ledger=self.ledger)
            it = RetryingIterator(
                it, policy, on_error=self._on_stream_error, op="data_stream")
        if skip_batches:
            for _ in range(skip_batches):
                if next(it, _STREAM_END) is _STREAM_END:
                    break
        preempted = self._preempt.is_set
        try:
            # ONE loop body, traced or not: with telemetry off every `span`
            # is the shared no-op (nothing recorded, nothing allocated) and
            # registry / black box / time series sit behind `is not None`,
            # so the jitted step is dispatched from the same source line
            # either way and a traced run loads what a plain run compiled
            try:
                while not preempted():
                    t_step0 = time.monotonic()
                    with span("prefetch-wait", step=step):
                        batch = next(it, _STREAM_END)
                    if batch is _STREAM_END:
                        break
                    n_items = trainer.items_per_batch(batch)
                    self.profiler.on_step(step)
                    if reg is not None and isinstance(batches, _Prefetcher):
                        reg.gauge("prefetch_queue_depth").set(batches.qsize())
                    if fresh is not None:
                        # record touched rows BEFORE tier.prepare remaps the
                        # batch ids to slot space (resident/transparent path)
                        fresh.on_batch(batch, root_rng, step)
                    if chaos is not None and chaos.scheduled("slow_step", step):
                        # the injected host stall runs OUTSIDE the step span,
                        # inside its own bucketed span, so the decomposition
                        # attributes it to host_blocked_s like a real stall
                        with span("chaos-slow", step=step):
                            chaos.maybe_slow_step(step)
                    # the annotation carries the step number onto a
                    # concurrent profile_dir capture, so device work lines
                    # up with these host spans
                    with step_annotation(trainer.name, step), \
                            span(trainer.name, step=step):
                        if tier is not None:
                            # fault the rows this step touches into the cache
                            # and remap batch ids to slot space; runs BEFORE
                            # any snapshot/injection so rollback targets a
                            # slot-map-consistent state
                            with span("tier-fault", step=step):
                                state, batch = tier.prepare(
                                    state, batch, root_rng, step)
                        with span("h2d", step=step):
                            dev_batch = self._device_batch(batch)
                        if self._want_audit and self._audit_report is None:
                            # compile-only HLO audit of this exact step fn
                            # (shapes only — safe before the donated call);
                            # feeds the goodput block's FLOP/byte numerators
                            self._audit_report = self._audit_step_fn(
                                state, dev_batch, root_rng, np.uint32(step))
                        # fold_in happens inside the jitted step; the numpy
                        # scalar is an array operand (no per-value retrace)
                        with span("step", step=step):
                            if resilient:
                                state, last_metrics = self._resilient_step(
                                    state, dev_batch, root_rng, step)
                            else:
                                state, last_metrics = self._step_fn(
                                    state, dev_batch, root_rng, np.uint32(step))
                    step += 1
                    total_items += n_items
                    self._items_seen += n_items
                    if cl is not None:
                        # commit the applied batch + renew the membership
                        # lease + adopt any reassigned spans — BEFORE a
                        # checkpoint below, so the cursor sees this commit
                        cl.on_step(step)
                    if reg is not None:
                        reg.counter("steps").inc()
                        reg.counter("items").inc(n_items)
                        step_ms = (time.monotonic() - t_step0) * 1e3
                        reg.histogram("step_ms").observe(step_ms)
                        if bb is not None:
                            bb.record_step(step, step_ms=step_ms, items=n_items)
                        if (self.timeseries is not None
                                and step % self.profile_cadence == 0):
                            self._profile_sample(step, step_ms, last_metrics)
                    self.metrics.count(n_items)
                    if self.log_every and step % self.log_every == 0:
                        with span("metrics-flush", step=step):
                            host = {k: float(v) for k, v in last_metrics.items()}
                            self.metrics.flush_window(step=step, **host)
                            if reg is not None:
                                reg.flush(step=step)
                            if bb is not None:
                                bb.record_metrics(step, host)
                                if bb.nonfinite(host):
                                    bb.dump("nan-loss", tracer=tel)
                                    self._incident("nan-loss")
                    if self.backup_period and self.checkpoint_fn and step % self.backup_period == 0:
                        with span("checkpoint", step=step):
                            self.checkpoint_fn(state, step)
                    if fresh is not None:
                        fresh.maybe_publish(state, step)
                    if max_steps is not None and step >= max_steps:
                        break
            except BaseException as e:
                # the flight-recorder moment: a failing run must leave a
                # post-mortem artifact (ring of recent steps + spans) behind
                if bb is not None:
                    bb.dump("exception", exc=e, tracer=tel)
                raise
            finally:
                # `finalize` is everything run() does after its last step
                # but wait for the device (`drain`): this teardown, which
                # also runs on error/interrupt, and the end-of-run work below
                with span("finalize"):
                    self.profiler.close()  # an open capture must be finalized
                    if isinstance(batches, _Prefetcher):
                        batches.close()
                    self._uninstall_sigterm()
                    # join outstanding background checkpoint writes HERE, not
                    # only on the happy path: an async save must never be
                    # orphaned by an exception, and its write errors become
                    # ledger events, not lost
                    if self.checkpoint_fn is not None:
                        self._join_checkpoints()
            # block so throughput/final metrics are real, then final flush
            with span("drain"):
                jax.block_until_ready(jax.tree_util.tree_leaves(state))
            with span("finalize"):
                state = self._finalize(state, step, total_items, last_metrics)
        finally:
            # after the last span, so that the written trace holds them all
            if tel is not None:
                tel.close()
        return state

    def _finalize(self, state, step: int, total_items: int, last_metrics):
        """End of run(), the device drained: the preemption save, the
        managers' hand-back to the master layout, final flushes and the run
        record. Returns the state the caller gets."""
        tier, reg, bb, tel = self.tier, self.registry, self.blackbox, self.tracer
        if self._preempt.is_set():
            # preemption drain: final save + durable outage record, THEN exit
            # — the next run's `resume: auto` continues from this state
            self.preempted = True
            if self.checkpoint_fn is not None:
                try:
                    self.checkpoint_fn(state, step)
                except Exception as e:
                    self._ledger_event("cache_error", {
                        "source": "checkpoint",
                        "error": f"preemption final save failed: {e}",
                    })
            self._ledger_event("outage", {
                "probe": "preemption",
                "reason": self._preempt_reason or "SIGTERM",
                "step": step,
                "error": "run preempted; drained with a final checkpoint",
            })
        if self.freshness is not None:
            # last delta before the caller materializes/abandons the state,
            # so subscribers reach the final training watermark without
            # waiting for a full checkpoint cycle
            self.freshness.maybe_publish(state, step, force=True)
            self.freshness.close()
        if tier is not None:
            # end-of-run write-back: flush every dirty cache slot and hand
            # the caller the full-size master-backed state (same pytree type,
            # shapes, dtypes as a resident run — export/eval are unchanged)
            state = tier.master_state(state)
        if self.zero is not None:
            # 1/data shards -> replicated placement (values unchanged), so
            # end-of-run consumers see the same resident layout as an
            # unsharded run
            state = self.zero.master_state(state)
        if self.placement is not None:
            # head/tail planes -> uniform layout: callers (export, eval,
            # serving snapshots) only ever see the master layout
            state = self.placement.master_state(state)
        host = {}
        if step % max(self.log_every, 1) != 0 or not self.log_every:
            host = {k: float(v) for k, v in last_metrics.items()} if last_metrics else {}
            self.metrics.flush_window(step=step, **host)
        elif bb is not None and last_metrics:
            host = {k: float(v) for k, v in last_metrics.items()}
        if bb is not None and host:
            bb.record_metrics(step, host)
            if bb.nonfinite(host):
                bb.dump("nan-loss", tracer=tel)
                self._incident("nan-loss")
        if reg is not None:
            reg.flush(step=step, final=1)
        if tel is not None:
            self._finalize_run_record(step, total_items, host)
        if self.checkpoint_fn is not None:
            self._join_checkpoints()  # joins the preemption final save too
        return state

    # -- resilience (guardrail / chaos / preemption) ------------------------

    def _resilient_step(self, state, dev_batch, root_rng, step: int):
        """One step under the guardrail and/or the chaos plan.

        Order matters: the rollback snapshot is taken BEFORE any chaos
        injection, so the guardrail's recovery target is always clean state —
        a poisoned pulled row (pre-step fault) or a poisoned update
        (post-step fault) is detected at commit and discarded whole.
        """
        guard = self.guardrail
        chaos = self.chaos
        # with the guardrail armed the step runs WITHOUT donation, so the
        # incoming state is itself the rollback snapshot (chaos pre-step
        # poison builds new arrays and never mutates it)
        snap = state if guard is not None else None
        if chaos is not None:
            state = chaos.pre_step(state, step)
        step_fn = self._step_fn_guarded if guard is not None else self._step_fn
        new_state, metrics = step_fn(
            state, dev_batch, root_rng, np.uint32(step))
        if chaos is not None:
            new_state, metrics = chaos.post_step(new_state, metrics, step)
        if guard is not None:
            new_state, metrics, tripped, exhausted = guard.commit(
                snap, new_state, metrics)
            if tripped:
                if self.registry is not None:
                    self.registry.counter("guard_trips").inc()
                print(
                    f"guardrail: step {step} rolled back "
                    f"({guard.last_trip_reason}); trust={guard.trust:.3f}",
                    file=sys.stderr,
                )
            if exhausted:
                from swiftsnails_tpu.resilience.guardrail import GuardrailExhausted

                if self.blackbox is not None:
                    self.blackbox.dump("guardrail-giveup", tracer=self.tracer)
                raise GuardrailExhausted(
                    f"{guard.consecutive} consecutive unhealthy steps "
                    f"(last: {guard.last_trip_reason}); giving up at step {step}"
                )
        if chaos is not None:
            chaos.maybe_corrupt_checkpoint(self.backup_root, step)
            if self.tier is not None:
                chaos.maybe_flip_tier(self.tier, step)
            reason = chaos.wants_preempt(step)
            if reason is not None:
                self.request_preemption(reason)
        if (self.tier is not None and self.tier_verify_period
                and (step + 1) % self.tier_verify_period == 0):
            self._tier_integrity_sweep(new_state, step)
        return new_state, metrics

    def _tier_integrity_sweep(self, state, step: int) -> None:
        """Recompute the host masters' plane digests; on a mismatch,
        quarantine-and-rebuild from the newest verified checkpoint (the cache
        plane — which the corruption cannot reach — is re-asserted on top,
        so only units evicted since that checkpoint roll back). Failing to
        find a trustworthy checkpoint raises: silently training on a corrupt
        master is the one outcome this sweep exists to prevent."""
        bad = self.tier.verify()
        if not bad:
            return
        print(
            f"tier integrity: corrupt master plane(s) at step {step}: "
            + ", ".join(f"{t}[{', '.join(p)}]" for t, p in bad.items())
            + "; rebuilding from newest verified checkpoint",
            file=sys.stderr,
        )
        from swiftsnails_tpu.resilience.retry import RetryPolicy

        policy = RetryPolicy.from_config(self.trainer.config, ledger=self.ledger)
        ckpt_step, rebuilt = self.tier.heal(
            state, self.backup_root, corrupt=bad, retry_policy=policy)
        if self.registry is not None:
            self.registry.counter("tier_heals").inc()
        self._ledger_event("cache_error", {
            "source": "tier",
            "step": step,
            "planes": {t: list(p) for t, p in bad.items()},
            "rebuilt_from_step": ckpt_step,
            "tables": rebuilt,
        })

    def request_preemption(self, reason: str = "SIGTERM") -> None:
        """Ask the loop to drain at the next step boundary: final save,
        ledger ``outage`` record, then a normal return (``self.preempted``)."""
        self._preempt_reason = reason
        self._preempt.set()

    def _install_sigterm(self) -> None:
        """Graceful-preemption SIGTERM handler: black-box dump (the ring is
        most valuable at the moment of death) + drain request. Replaces the
        black box's own die-after-dump handler for the duration of the run;
        main-thread only (signal module restriction)."""

        def _on_term(signum, frame):
            if self.blackbox is not None:
                self.blackbox.dump("sigterm", tracer=self.tracer)
            self.request_preemption("SIGTERM")

        try:
            self._prev_sigterm = signal.signal(signal.SIGTERM, _on_term)
        except ValueError:  # not the main thread: cooperative preempt only
            self._prev_sigterm = None

    def _uninstall_sigterm(self) -> None:
        if self._prev_sigterm is not None:
            try:
                signal.signal(signal.SIGTERM, self._prev_sigterm)
            except ValueError:
                pass
            self._prev_sigterm = None

    def _ledger_event(self, kind: str, record: Dict) -> None:
        """Best-effort ledger append — resilience bookkeeping never fails
        the run."""
        if self.ledger is None:
            return
        try:
            self.ledger.append(kind, record)
        except Exception as e:
            print(f"resilience: ledger append failed: {e}", file=sys.stderr)

    def _on_stream_error(self, exc, attempt: int, recovered: bool) -> None:
        print(
            f"data stream error (attempt {attempt + 1}): {exc}"
            + ("; retrying" if recovered else "; giving up"),
            file=sys.stderr,
        )
        if self.registry is not None:
            self.registry.counter("stream_retries").inc()
        if not recovered:
            self._ledger_event("outage", {
                "probe": "data_stream",
                "error": f"{type(exc).__name__}: {exc}",
            })

    def _join_checkpoints(self) -> None:
        """Join background checkpoint writes; surface write errors as ledger
        events (they used to vanish inside the async checkpointer)."""
        from swiftsnails_tpu.framework.checkpoint import wait_for_checkpoints

        for err in wait_for_checkpoints():
            print(f"checkpoint: {err}", file=sys.stderr)
            self._ledger_event("cache_error", {
                "source": "checkpoint", "error": err,
            })

    # -- continuous profiling + drift (telemetry-only paths) ----------------

    def _profile_sample(self, step: int, step_ms: float, last_metrics) -> None:
        """One continuous-profiling sample (every ``profile_cadence`` steps):
        the registry snapshot plus the goodput decomposition of the spans
        recorded since the previous sample, the prefetch stall, and the
        comm-audit bytes — appended to the bounded ring and fed to the
        drift sentinel. Best-effort: profiling never fails the run."""
        try:
            from swiftsnails_tpu.telemetry.goodput import step_time_decomposition

            row: Dict[str, float] = {}
            if self.registry is not None:
                for k, v in self.registry.snapshot().items():
                    if isinstance(v, (int, float)):
                        row[k] = float(v)
            row["step_ms"] = float(step_ms)
            # per-window decomposition: only the spans since the last sample,
            # so the ring shows the run's shape over time, not a cumulative
            # average that hides late-run drift
            window = self.tracer.events(self._profile_event_idx)
            self._profile_event_idx += len(window)
            dec = step_time_decomposition(window)
            steps_w = dec.get("steps") or 0
            for key in ("compute_frac", "h2d_frac", "host_blocked_frac",
                        "other_frac", "unaccounted_frac"):
                if key in dec:
                    row[f"win_{key}"] = dec[key]
            if steps_w:
                row["host_blocked_ms"] = dec["host_blocked_s"] / steps_w * 1e3
                stall_us = sum(
                    float(e.get("dur_us", 0.0)) for e in window
                    if e.get("name") == "prefetch-wait")
                row["prefetch_stall_ms"] = stall_us / 1e3 / steps_w
            # the loss is read one sampling interval late: converting the
            # step's own (possibly still in-flight) array would drain the
            # async-dispatch pipeline every sample — measured ~10% of
            # words/sec on small steps vs ~0 for reading the previous
            # sample's long-since-materialized value
            pending = self._profile_pending_loss
            if last_metrics and "loss" in last_metrics:
                self._profile_pending_loss = last_metrics["loss"]
            if pending is not None:
                row["loss"] = float(pending)
            audit = self._audit_report
            if audit and "error" not in audit:
                if isinstance(audit.get("total_bytes"), (int, float)):
                    row["exchange_bytes"] = float(audit["total_bytes"])
                for scope, nbytes in (audit.get("by_scope") or {}).items():
                    row[f"comm_bytes.{scope}"] = float(nbytes)
            if "tier_cache_hit_rate" in row:
                # the drift sentinel's canonical signal name
                row["tier_hit_rate"] = row["tier_cache_hit_rate"]
            self.timeseries.sample(step, row)
            if self.drift is not None:
                edges = self.drift.events
                confirmed = self.drift.observe(step, row)
                if confirmed and self.drift.events > edges:
                    print(
                        f"drift: confirmed at step {step} on "
                        f"{', '.join(confirmed)}; capturing incident bundle",
                        file=sys.stderr,
                    )
                    self._incident("drift-" + "-".join(confirmed))
        except Exception as e:
            print(f"telemetry: profile sample failed: {e}", file=sys.stderr)

    def _incident(self, reason: str) -> Optional[str]:
        """Capture an atomic incident bundle (blackbox ring + timeseries
        window + config/env fingerprint + kept spans) under ``incident_dir``,
        once per reason per run. Armed only when continuous profiling or the
        drift sentinel is on — a bare-telemetry run leaves no dirs behind."""
        if self.timeseries is None and self.drift is None:
            return None
        if not self.incident_dir or reason in self._incident_reasons:
            return None
        self._incident_reasons.add(reason)
        try:
            from swiftsnails_tpu.telemetry.drift import build_incident_bundle

            context = {"model": self.trainer.name,
                       "config_hash": self.config_hash}
            if self.drift is not None:
                context["drift"] = self.drift.summary()
            path = build_incident_bundle(
                self.incident_dir, reason,
                blackbox=self.blackbox,
                timeseries=self.timeseries,
                tracer=self.tracer,
                context=context,
            )
            self.incidents.append(path)
            print(f"incident bundle: {path}", file=sys.stderr)
            return path
        except Exception as e:
            print(f"telemetry: incident bundle failed: {e}", file=sys.stderr)
            return None

    # -- goodput + ledger finalization (telemetry-only paths) --------------

    def _audit_step_fn(self, state, dev_batch, root_rng, step):
        """Compile-only HLO audit of the jitted step (never executes it);
        any failure costs only the goodput FLOP numbers, never the run."""
        try:
            from swiftsnails_tpu.telemetry.audit import audit_step

            return audit_step(self._step_fn, state, dev_batch, root_rng, step)
        except Exception as e:
            return {"error": f"{type(e).__name__}: {e}"}

    def _finalize_run_record(self, steps: int, items: int, final_metrics) -> None:
        """Emit the goodput block to the metrics sink and, when a
        ``ledger_path`` is configured, append the durable run record."""
        try:
            from swiftsnails_tpu.telemetry.goodput import (
                goodput_report, peaks_for,
            )
            from swiftsnails_tpu.telemetry.ledger import env_fingerprint

            devs = jax.devices()
            mesh = self.trainer.mesh
            n_chips = mesh.size if mesh is not None else 1
            audit = self._audit_report
            if audit is not None and "error" in audit:
                audit = None
            report = goodput_report(
                events=self.tracer.events(self._run_event_idx),
                audit=audit,
                steps=steps,
                items=items,
                peaks=peaks_for(devs[0].device_kind, devs[0].platform),
                n_chips=n_chips,
            )
            self.metrics.log({"goodput": report, "step": steps})
            if self.timeseries is not None:
                export = self.trainer.config.get_str("profile_export", "")
                if export:
                    self.timeseries.export_jsonl(export)
            if self.ledger is not None:
                record = {
                    "model": self.trainer.name,
                    "config_hash": self.config_hash,
                    "steps": steps,
                    "items": items,
                    "goodput": report,
                    "final_metrics": final_metrics or None,
                }
                if audit is not None and audit.get("by_scope"):
                    # per-scope comm bytes, so `ledger-report --diff` can
                    # attribute an exchange-byte delta to a named collective
                    record["comm_by_scope"] = dict(audit["by_scope"])
                if self.timeseries is not None:
                    record["timeseries"] = self.timeseries.summary()
                if self.drift is not None:
                    record["drift"] = self.drift.summary()
                if self.incidents:
                    record["incidents"] = list(self.incidents)
                wire = getattr(self.trainer, "comm_dtype", None)
                if wire:
                    # the active wire format, so `ledger-report` run lines
                    # show what a quantized run actually moved
                    record["comm_dtype"] = wire
                if self.guardrail is not None:
                    record["guardrail"] = self.guardrail.summary()
                if self.chaos is not None:
                    record["chaos"] = self.chaos.summary()
                if self.tier is not None:
                    record["tiered"] = self.tier.summary()
                placement_decision = getattr(
                    self.trainer, "placement_decision", None)
                if placement_decision:
                    # the cut decision (or the uniform-fallback reason) —
                    # rendered by `ledger-report` run lines; when the comm
                    # audit ran, pin the measured exchange bytes next to the
                    # cost model's prediction
                    pl = dict(placement_decision)
                    if audit is not None:
                        if isinstance(audit.get("total_bytes"), int):
                            pl["measured_exchange_bytes"] = audit["total_bytes"]
                        if audit.get("by_table"):
                            pl["measured_by_table"] = dict(audit["by_table"])
                    record["placement"] = pl
                if self.zero is not None and self.zero.summary():
                    # the ZeRO sharding decision: plane count, replicated vs
                    # sharded HBM bytes/replica, reduction factor
                    record["zero"] = self.zero.summary()
                if self.preempted:
                    record["preempted"] = True
                self.ledger.append(
                    "run", record, env=env_fingerprint(include_devices=True),
                )
        except Exception as e:  # observability must never fail the run
            import sys

            print(f"telemetry: run-record finalization failed: {e}",
                  file=sys.stderr)
