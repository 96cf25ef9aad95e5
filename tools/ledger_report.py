#!/usr/bin/env python
"""Render the durable run ledger (RUN_LEDGER.jsonl) as a terminal report.

The ledger is the append-only record every bench run, training run, outage
event, and black-box dump writes into
(``swiftsnails_tpu/telemetry/ledger.py``). This tool renders the history —
and gates CI:

    python tools/ledger_report.py                      # full history
    python tools/ledger_report.py RUN_LEDGER.jsonl     # explicit path
    python -m swiftsnails_tpu ledger-report            # same thing

    # bench gate: exit nonzero if the newest measured run is >10% below
    # the pinned baseline (default: best earlier measured ledger record;
    # pin explicitly with --baseline VALUE or --baseline-file FILE).
    # Also gates the scaling lane's aggregate words/sec, the chaos lane's
    # recovery (unrecovered drill / resume-parity breach fails CI), the
    # tiered lane (bit-parity / round-trip failure is fatal on any
    # platform, words/sec gates per platform, and the equal-vocab
    # tiered/resident ratio has a hard 0.95x floor), and the fleet lane:
    # p99 over the SLO, 2-replica scaling under the floor, affinity not
    # beating random, or hedging not cutting p99 is fatal on any
    # platform; fleet max QPS gates per platform. The zero lane
    # (optimizer_sharding: zero) gates too: replicated-plane HBM per
    # replica must stay >=2x reduced at >=2 data shards, the dense-grad
    # reduce's audited bytes must not exceed the psum baseline, f32 loss
    # parity must hold, and a checkpoint that is not byte-identical to
    # the unsharded format fails on any platform
    python tools/ledger_report.py --check-regression 10

    # failure timeline: outage / chaos-injection / black-box / checkpoint
    # corruption events rendered next to run records
    python tools/ledger_report.py --failures

No accelerator required; jax is only imported if the ledger is missing
version fields (never initialized).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from swiftsnails_tpu.telemetry.ledger import main

if __name__ == "__main__":
    raise SystemExit(main())
