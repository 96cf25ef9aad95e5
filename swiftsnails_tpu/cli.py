"""CLI entry points — the reference's role binaries, collapsed TPU-style.

Reference contract (survey §2.7): per-app ``master``/``server``/``worker``
binaries taking ``-config <file>`` (``src/tools/run_master.sh``) and workers
additionally ``-data <file>`` (``run_worker.sh``), launched by Hadoop
Streaming. On TPU the three roles dissolve into one SPMD ``train`` role: the
parameter table lives sharded across the same processes that compute
(survey §7 design stance), and rendezvous is the JAX coordination service.

Usage::

    python -m swiftsnails_tpu train  -config train.conf [-data corpus.txt]
    python -m swiftsnails_tpu export -config train.conf -checkpoint ROOT -out vec.txt
    python -m swiftsnails_tpu serve  -config train.conf -checkpoint ROOT   # query REPL
    python -m swiftsnails_tpu serve  ... -replicas 4   # replica fleet behind the router
    # in the serve REPL: `subscribe <dir>` follows the trainer's live
    # hot-row delta log (freshness pipeline, docs/FRESHNESS.md);
    # `subscribe tcp://HOST:PORT` streams it over a socket instead
    # (docs/NETWORK.md) — the trainer side sets `freshness_listen`
    python -m swiftsnails_tpu net-serve --root ROOT --listen HOST:PORT
    #   one replica process serving pull/topk/score/health over TCP
    #   (the multi-host fleet's unit; spawned by net.fleet.ReplicaSpawner)
    python -m swiftsnails_tpu models
    python -m swiftsnails_tpu trace-summary TRACE_OR_JSONL   # telemetry breakdown
    python -m swiftsnails_tpu ledger-report [LEDGER.jsonl]   # run-ledger history
    python -m swiftsnails_tpu ledger-report --failures   # outage/chaos timeline
    python -m swiftsnails_tpu ledger-report --diff A B   # attribute a words/sec delta
    python -m swiftsnails_tpu supervisor-status [LEDGER.jsonl]   # membership view
    python -m swiftsnails_tpu ops [LEDGER.jsonl]   # one-screen fleet dashboard
    python -m swiftsnails_tpu worker -config ...   # alias of train (parity)

Resilience (docs/RESILIENCE.md): ``resume: auto`` continues an interrupted
run from the newest verified checkpoint (tables + data cursor); a real
SIGTERM drains with a final save and a ledger ``outage`` record instead of
dying mid-step; ``guardrail: 1`` arms the NaN/rollback step guardrail; the
fault-injection drills live in ``tools/chaos_drill.py``.

``master`` / ``server`` are accepted for parity and explain the collapse.
"""

from __future__ import annotations

import sys
from typing import List, Optional

from swiftsnails_tpu.utils.config import Config, ConfigError, global_config
from swiftsnails_tpu.utils.flags import parse_role_argv
from swiftsnails_tpu.utils.metrics import MetricsLogger


def _build_trainer(cfg: Config):
    from swiftsnails_tpu.models.registry import get_model
    from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
    from swiftsnails_tpu.telemetry.tracer import span_fn, tracer_from_config

    import jax

    from swiftsnails_tpu.data import native

    # the run's tracer starts here, where the config is first in hand, so
    # that set-up has spans too; the trainer holds it and TrainLoop adopts it
    tracer = tracer_from_config(cfg)
    with span_fn(tracer)("build-trainer"):
        # a run that wants the native producers gets them or stops here, with
        # the compiler's output, before any corpus is read
        native.use_native(cfg)
        model_name = cfg.get_str("model", "word2vec")
        trainer_cls = get_model(model_name)
        n = len(jax.devices())
        if cfg.get_bool("local_train", False) or n == 1:
            mesh = None  # reference local_train parity (SwiftWorker.h:114-123)
        else:
            model_axis = cfg.get_int("model_axis", 0)
            if model_axis <= 0:
                model_axis = next((c for c in (4, 2, 1) if n % c == 0 and n > c), 1)
            mesh = make_mesh({DATA_AXIS: n // model_axis, MODEL_AXIS: model_axis})
        return trainer_cls(cfg, mesh=mesh, tracer=tracer)


def cmd_train(argv: List[str]) -> int:
    from swiftsnails_tpu.framework.trainer import TrainLoop
    from swiftsnails_tpu.parallel.cluster import barrier, initialize_cluster

    cfg = parse_role_argv(argv)
    initialize_cluster(cfg)
    trainer = _build_trainer(cfg)
    metrics = MetricsLogger(path=cfg.get_str("metrics_path", "") or None, echo=True)
    loop = TrainLoop(trainer, metrics=metrics, log_every=cfg.get_int("log_every", 100))
    state = loop.run(seed=cfg.get_int("seed", 0))
    if loop.preempted:
        print(
            "preempted (SIGTERM): drained with a final checkpoint; "
            "restart with `resume: auto` to continue this run",
            file=sys.stderr,
        )
    barrier("end_of_training")  # MasterTerminate parity
    out = cfg.get_str("output", "")
    if out:
        trainer.export_text(state, out)
        print(f"exported parameters to {out}", file=sys.stderr)
    return 0


def cmd_export(argv: List[str]) -> int:
    from swiftsnails_tpu.framework.checkpoint import restore_checkpoint

    cfg = parse_role_argv(argv)
    trainer = _build_trainer(cfg)
    root = cfg.get_str("checkpoint")
    out = cfg.get_str("out")
    state = restore_checkpoint(root, trainer.init_state())
    trainer.export_text(state, out)
    print(f"exported {root} -> {out}", file=sys.stderr)
    return 0


def _serve_mesh(cfg: Config):
    """The serving twin of ``_build_trainer``'s mesh heuristic: query-only
    replicas shard the table the same way training did."""
    import jax

    from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh

    n = len(jax.devices())
    if cfg.get_bool("local_train", False) or n == 1:
        return None
    model_axis = cfg.get_int("model_axis", 0)
    if model_axis <= 0:
        model_axis = next((c for c in (4, 2, 1) if n % c == 0 and n > c), 1)
    return make_mesh({DATA_AXIS: n // model_axis, MODEL_AXIS: model_axis})


def cmd_serve(argv: List[str]) -> int:
    """Query-only REPL over a verified checkpoint (docs/SERVING.md).

    One request per stdin line, one JSON response per stdout line::

        pull <id> [id...]            row values
        topk <id> [k]                nearest rows to row <id> (cosine)
        score <f0> <f1> ...          CTR probability (registry models)
        stats                        latency/cache/shed snapshot
        health                       breaker / tier / version state
        ops                          one-screen dashboard (SLO / traces)
        add                          (fleet) add a replica to the ring
        drain <replica>              (fleet) drain + remove a replica
        subscribe <dir|tcp://h:p>    follow a hot-row delta log (freshness)
        freshness                    applied-seq watermark / lag / fallbacks
        quit

    ``-replicas N`` (or config ``serve_replicas``) > 1 serves through a
    :class:`~swiftsnails_tpu.serving.fleet.Fleet` — N replicas sharing the
    loaded planes behind the affinity/hedging router; the same REPL ops
    work (``Fleet`` mirrors the ``Servant`` query surface) plus elastic
    ``add``/``drain``, and ``health`` reports fleet-level liveness.

    ``subscribe <dir>`` attaches a background
    :class:`~swiftsnails_tpu.freshness.subscriber.DeltaSubscriber` polling
    the trainer's delta log (docs/FRESHNESS.md): hot-row batches apply
    behind the version-keyed cache with atomic cutover, and any gap /
    publisher restart / CRC mismatch falls back to a full
    ``reload_from_checkpoint`` of this checkpoint root. ``freshness``
    reports the applied-seq watermark, lag, and fallback count (also
    rolled into ``health``; fleets add per-replica versions).
    """
    import json

    from swiftsnails_tpu.serving import Fleet, Overloaded, Servant, Unavailable
    from swiftsnails_tpu.telemetry.ledger import Ledger

    cfg = parse_role_argv(argv)
    root = cfg.get_str("checkpoint")
    ledger_path = cfg.get_str("ledger_path", "")
    ledger = Ledger(ledger_path) if ledger_path else None
    replicas = cfg.get_int("replicas", cfg.get_int("serve_replicas", 1))
    fleet_mode = replicas > 1
    if fleet_mode:
        server_cm = Fleet.from_checkpoint(
            root, cfg, mesh=_serve_mesh(cfg), replicas=replicas,
            ledger=ledger)
    else:
        server_cm = Servant.from_checkpoint(
            root, cfg, mesh=_serve_mesh(cfg), ledger=ledger)
    subscriber = None
    delta_source = None
    with server_cm as servant:
        if fleet_mode:
            banner = (f"serving fleet of {replicas} replicas "
                      f"(one request per line; pull/topk/score/stats/"
                      "health/ops/add/drain/subscribe/freshness/quit)")
        else:
            banner = (f"serving step {servant.step} tables "
                      f"{servant.stats()['tables']} (one request per line; "
                      "pull/topk/score/stats/health/ops/subscribe/freshness/"
                      "quit)")
        print(banner, file=sys.stderr)
        for line in sys.stdin:
            toks = line.split()
            if not toks:
                continue
            op, args = toks[0], toks[1:]
            try:
                if op in ("quit", "exit"):
                    break
                elif op == "pull":
                    rows = servant.pull([int(a) for a in args])
                    out = {"rows": [[round(float(v), 6) for v in r]
                                    for r in rows]}
                elif op == "topk":
                    row = int(args[0])
                    k = int(args[1]) if len(args) > 1 else None
                    query = servant.pull([row])[0]
                    out = {"topk": servant.topk(query, k=k, exclude=(row,))}
                elif op == "score":
                    scores = servant.score([int(a) for a in args])
                    out = {"scores": [round(float(s), 6) for s in scores]}
                elif op == "stats":
                    out = servant.stats()
                elif op == "health":
                    out = servant.health()
                elif op == "ops":
                    from swiftsnails_tpu.telemetry.ops import render_ops

                    tracer = getattr(servant, "request_tracer", None)
                    anomalies = ([c.to_dict()
                                  for c in tracer.anomaly_traces(5)]
                                 if tracer is not None else None)
                    text = render_ops(servant.stats(),
                                      health=servant.health(),
                                      anomalies=anomalies)
                    print(text, file=sys.stderr)
                    out = {"ops": "printed"}
                elif op == "add" and fleet_mode:
                    out = {"added": servant.add_replica()}
                elif op == "drain" and fleet_mode:
                    out = {"drained": servant.drain(args[0])}
                elif op == "subscribe":
                    from swiftsnails_tpu.freshness.subscriber import (
                        DeltaSubscriber)

                    if subscriber is not None:
                        subscriber.stop()
                    if delta_source is not None:
                        delta_source.stop()
                        delta_source = None
                    target = args[0]
                    if target.startswith("tcp://"):
                        # socket-fed: the TCP source drives apply_batch;
                        # the subscriber never polls a local directory
                        # (docs/NETWORK.md) — base adoption, gap detection
                        # and the fallback ladder are unchanged
                        from swiftsnails_tpu.net.delta_stream import (
                            TcpDeltaSource)

                        host, _, port = target[len("tcp://"):].rpartition(":")
                        subscriber = DeltaSubscriber(
                            servant, cfg.get_str("freshness_dir", "")
                            or root + ".deltas", config=cfg,
                            checkpoint_root=root,
                            max_lag_ms=cfg.get_float(
                                "freshness_max_lag_ms", 0.0),
                            ledger=ledger)
                        delta_source = TcpDeltaSource(
                            subscriber, host, int(port), config=cfg,
                            ledger=ledger).start()
                        servant.attach_freshness(subscriber)
                        out = {"subscribed": target, "stream_open": True}
                    else:
                        subscriber = DeltaSubscriber(
                            servant, target, config=cfg,
                            checkpoint_root=root,
                            max_lag_ms=cfg.get_float(
                                "freshness_max_lag_ms", 0.0),
                            ledger=ledger)
                        found = subscriber.subscribe()
                        subscriber.start()
                        servant.attach_freshness(subscriber)
                        out = {"subscribed": target, "stream_open": found}
                elif op == "freshness":
                    if subscriber is None:
                        out = {"error": "not subscribed (use: subscribe "
                               "<dir> or subscribe tcp://HOST:PORT)"}
                    else:
                        out = subscriber.status()
                        if delta_source is not None:
                            out["source"] = delta_source.status()
                else:
                    out = {"error": f"unknown op {op!r}"}
            except Overloaded as e:
                out = {"error": f"overloaded: {e}", "shed": True}
            except Unavailable as e:
                out = {"error": f"unavailable: {e}", "shed": True}
            except Exception as e:  # noqa: BLE001 — a REPL must not die
                out = {"error": f"{type(e).__name__}: {e}"}
            print(json.dumps(out), flush=True)
        if delta_source is not None:
            delta_source.stop()
        if subscriber is not None:
            subscriber.stop()
        print(json.dumps({"final_stats": servant.stats()}), flush=True)
    return 0


def cmd_models(argv: List[str]) -> int:
    from swiftsnails_tpu.models.registry import available_models

    for name in available_models():
        print(name)
    return 0


def cmd_trace_summary(argv: List[str]) -> int:
    from swiftsnails_tpu.telemetry.summary import main as summary_main

    return summary_main(argv)


def cmd_ledger_report(argv: List[str]) -> int:
    from swiftsnails_tpu.telemetry.ledger import main as ledger_main

    return ledger_main(argv)


def cmd_ops(argv: List[str]) -> int:
    """One-screen fleet dashboard from the run ledger (docs/OBSERVABILITY.md):
    newest fleet/freshness bench blocks, SLO error budget from ``slo_burn``
    events, and the recent ``trace_anomaly`` tail with drillable trace ids."""
    from swiftsnails_tpu.telemetry.ops import main as ops_main

    return ops_main(argv)


def cmd_net_serve(argv: List[str]) -> int:
    """One replica process serving a checkpoint over TCP (docs/NETWORK.md):
    pull/topk/score/health RPCs behind the SSD1 frame codec, spawnable by
    hand here or by ``net.fleet.ReplicaSpawner``; prints one JSON ready
    line (``{"port": ..., "incarnation": ...}``) and serves until killed."""
    from swiftsnails_tpu.net.replica_server import main as replica_main

    return replica_main(argv)


def cmd_supervisor_status(argv: List[str]) -> int:
    """Replay a run ledger's membership events into the supervisor's view:
    per-worker state (alive/lost, joins, straggler flags, where reassigned
    ranges went) plus the newest exactly-once accounting verdict."""
    import os

    from swiftsnails_tpu.cluster.status import render_supervisor_status
    from swiftsnails_tpu.telemetry.ledger import DEFAULT_LEDGER, Ledger

    path = argv[0] if argv else os.environ.get("SSN_LEDGER_PATH",
                                               DEFAULT_LEDGER)
    ledger = Ledger(path)
    if not os.path.exists(ledger.path):
        print(f"supervisor-status: no ledger at {ledger.path}",
              file=sys.stderr)
        return 1
    print(render_supervisor_status(ledger))
    return 0


_ROLE_NOTE = (
    "swiftsnails_tpu has no separate {role} role: the parameter table lives\n"
    "sharded across the same TPU processes that train. Run\n"
    "  python -m swiftsnails_tpu train -config <file>\n"
    "on every host (jax.distributed handles rendezvous via master_addr)."
)


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help", "help"):
        print(__doc__)
        return 0
    cmd, rest = argv[0], argv[1:]
    if cmd in ("train", "worker", "export", "serve"):
        # the commands that compile (net-serve configures in its own main,
        # which the replica spawner also enters directly)
        from swiftsnails_tpu.utils.compile_cache import configure_compile_cache

        configure_compile_cache()
    try:
        if cmd in ("train", "worker"):
            return cmd_train(rest)
        if cmd == "export":
            return cmd_export(rest)
        if cmd == "serve":
            return cmd_serve(rest)
        if cmd == "models":
            return cmd_models(rest)
        if cmd == "trace-summary":
            return cmd_trace_summary(rest)
        if cmd == "ledger-report":
            return cmd_ledger_report(rest)
        if cmd == "supervisor-status":
            return cmd_supervisor_status(rest)
        if cmd == "ops":
            return cmd_ops(rest)
        if cmd == "net-serve":
            return cmd_net_serve(rest)
        if cmd in ("master", "server"):
            print(_ROLE_NOTE.format(role=cmd), file=sys.stderr)
            return 0
        print(
            f"unknown command {cmd!r}; try: train, export, serve, models, "
            "trace-summary, ledger-report, supervisor-status, ops, "
            "net-serve",
            file=sys.stderr,
        )
        return 2
    except ConfigError as e:
        print(f"config error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    raise SystemExit(main())
