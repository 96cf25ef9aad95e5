"""Host-side span tracer with Chrome trace-event export.

The reference's only timeline instrumentation was glog timestamps and a
chrono ``Timer`` (SURVEY §5). This tracer answers "where did this step's
time go" on the host side: nestable spans (per-thread stacks), thread-safe
recording, and export to the Chrome/Perfetto trace-event JSON format, so a
``trace_path`` file drops straight into ``chrome://tracing`` / ui.perfetto.dev
— or into ``tools/trace_summary.py`` for a terminal breakdown.

Device-side alignment: the TrainLoop opens each step's span inside
``utils.profiling.step_annotation`` (a ``jax.profiler.StepTraceAnnotation``),
so when a ``profile_dir`` capture runs concurrently the host spans and the
XLA device timeline carry the same step numbers and line up in the combined
view.

Cost contract: a Tracer only exists when telemetry is enabled
(:func:`tracer_from_config` gives ``None`` otherwise). Code that is
instrumented either way takes its spans from :func:`span_fn`: with no tracer
every ``with span(...)`` is the one shared :data:`NO_SPAN`, which does nothing
and allocates nothing. Recording one span is one ``perf_counter_ns`` pair, one
small tuple, and one lock-guarded append.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

# event tuples: (name, ts_ns, dur_ns, tid, depth, args_or_None), "X" spans
_Event = Tuple[str, int, int, int, int, Optional[Dict]]


class _SpanCtx:
    """Reusable-shape context manager recording one complete ("X") event."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_depth")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[Dict]):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self) -> "_SpanCtx":
        tls = self._tracer._tls
        self._depth = getattr(tls, "depth", 0)
        tls.depth = self._depth + 1
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._tracer._tls.depth = self._depth
        self._tracer._record(
            (self._name, self._t0, t1 - self._t0, threading.get_ident(),
             self._depth, self._args)
        )


class _NoSpan:
    """The span of a run without telemetry: entering and leaving do nothing."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> None:
        return None


NO_SPAN = _NoSpan()


def _no_span(name: str, **args) -> _NoSpan:
    return NO_SPAN


def span_fn(tracer: Optional["Tracer"]):
    """``tracer.span``, or with no tracer a function that hands out
    :data:`NO_SPAN` - so one body of code runs traced and untraced."""
    return tracer.span if tracer is not None else _no_span


def tracer_from_config(cfg) -> Optional["Tracer"]:
    """A Tracer when the config turns telemetry on (``telemetry: 1`` or a
    ``trace_path``, where ``close`` writes the trace), else ``None``."""
    path = cfg.get_str("trace_path", "")
    if cfg.get_bool("telemetry", False) or path:
        return Tracer(path=path or None)
    return None


class Tracer:
    """Thread-safe span recorder with Chrome trace-event JSON export.

    ``path`` (optional): where :meth:`close` writes the trace. Spans nest per
    thread; concurrent threads (e.g. the prefetcher) record independently and
    render as separate tracks.
    """

    def __init__(self, path: Optional[str] = None, process_name: str = "swiftsnails_tpu"):
        self.path = path
        self.process_name = process_name
        self._events: List[_Event] = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._epoch_ns = time.perf_counter_ns()

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **args) -> _SpanCtx:
        """Open a nestable span: ``with tracer.span("h2d"): ...``"""
        return _SpanCtx(self, name, args or None)

    def _record(self, event: _Event) -> None:
        with self._lock:
            self._events.append(event)

    def n_events(self) -> int:
        """Spans recorded so far: an index for :meth:`events`. (Not
        ``__len__``: a tracer with nothing recorded yet must not be falsy.)"""
        return len(self._events)

    # -- export ------------------------------------------------------------

    def events(self, start: int = 0) -> List[Dict]:
        """The recorded spans as dicts (name, ts_us, dur_us, tid, depth,
        args), from index ``start`` on — the continuous profiler reads only
        its window this way, instead of re-converting the whole run's spans
        every sample."""
        with self._lock:
            snap = self._events[start:]
        return [
            {
                "name": name,
                "ts_us": (t0 - self._epoch_ns) / 1e3,
                "dur_us": dur / 1e3,
                "tid": tid,
                "depth": depth,
                "args": args or {},
            }
            for name, t0, dur, tid, depth, args in snap
        ]

    def chrome_trace(self) -> Dict:
        """The trace as a Chrome trace-event object (``traceEvents`` list)."""
        pid = os.getpid()
        with self._lock:
            spans = list(self._events)
        events: List[Dict] = [
            {
                "ph": "M",
                "pid": pid,
                "name": "process_name",
                "args": {"name": self.process_name},
            }
        ]
        for name, t0, dur, tid, depth, args in spans:
            ev = {
                "ph": "X",
                "pid": pid,
                "tid": tid,
                "name": name,
                "cat": "host",
                "ts": (t0 - self._epoch_ns) / 1e3,  # microseconds
                "dur": dur / 1e3,
            }
            if args:
                ev["args"] = args
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.chrome_trace(), f)

    def close(self) -> None:
        """Write the trace as it stands to ``path``. Keeps the events, so a
        later close (a second run on one trainer's tracer) writes them all."""
        if self.path:
            self.export(self.path)
