#!/usr/bin/env python
"""One-screen fleet ops dashboard reconstructed from the run ledger.

Where ``ledger-report`` renders the full append-only history, this is the
*glance* view an operator checks before paging: the
newest fleet bench block (per-replica qps/p50/p99/hit-rate, tracing
overhead), the newest freshness lane (lag p99, bit parity, gap-drill
recovery), the SLO error budget from recent ``slo_burn`` events, and the
tail of ledgered anomaly traces — each with a ``trace_id`` to drill into
with ``trace-summary``:

    python tools/ops_report.py                      # default ledger
    python tools/ops_report.py RUN_LEDGER.jsonl     # explicit path
    python -m swiftsnails_tpu ops                   # same thing

The live-fleet variant of the same screen is the ``ops`` op in the serve
REPL (``python -m swiftsnails_tpu serve``), rendered straight from
``fleet.stats()``/``health()``. No accelerator required.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from swiftsnails_tpu.telemetry.ops import main

if __name__ == "__main__":
    raise SystemExit(main())
