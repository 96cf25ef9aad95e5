"""tools/trace_summary.py + TrainLoop telemetry wiring, end to end:
a short traced training run must yield a Chrome-loadable trace whose
prefetch-wait/h2d/step spans the summary tool renders."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from swiftsnails_tpu.telemetry.summary import (
    load_events,
    render_events,
    summarize_events,
    summarize_file,
)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One 5-step CPU training run with trace_path + metrics_path set."""
    from test_word2vec import make_trainer

    from swiftsnails_tpu.framework.trainer import TrainLoop
    from swiftsnails_tpu.utils.metrics import MetricsLogger

    d = tmp_path_factory.mktemp("traced")
    trace_path = str(d / "trace.json")
    metrics_path = str(d / "metrics.jsonl")
    trainer = make_trainer(trace_path=trace_path)
    loop = TrainLoop(
        trainer,
        metrics=MetricsLogger(path=metrics_path),
        log_every=2,
    )
    assert loop.tracer is not None and loop.registry is not None
    state = loop.run(max_steps=5)
    loop.metrics.close()
    assert state is not None
    return trace_path, metrics_path


def test_traced_run_produces_chrome_trace(traced_run):
    trace_path, _ = traced_run
    doc = json.load(open(trace_path))
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in evs}
    assert {"prefetch-wait", "h2d", "step"} <= names, names
    assert sum(e["name"] == "step" for e in evs) == 5
    # nesting: every dispatch span sits inside its step's span (trainer name)
    outers = [e for e in evs if e["name"] == "word2vec"]
    assert outers
    for s in (e for e in evs if e["name"] == "step"):
        assert any(
            o["ts"] <= s["ts"] and s["ts"] + s["dur"] <= o["ts"] + o["dur"] + 1e-3
            for o in outers
        )
    # the producer thread's side of the queue is in the trace too
    assert "produce" in names


def test_trace_summary_renders_breakdown(traced_run):
    trace_path, _ = traced_run
    events = load_events(trace_path)
    rows = summarize_events(events)
    out = render_events(rows)
    for name in ("step", "h2d", "prefetch-wait"):
        assert name in out
    by_name = {r["name"]: r for r in rows}
    assert by_name["step"]["count"] == 5
    assert by_name["step"]["total_us"] > 0


def test_trace_summary_handles_metrics_jsonl(traced_run):
    _, metrics_path = traced_run
    out = summarize_file(metrics_path)
    assert "items_per_sec" in out
    # registry instruments flushed through the same JSONL sink
    assert "steps" in out and "prefetch_queue_depth" in out


def test_trace_summary_cli(traced_run, capsys):
    from swiftsnails_tpu.cli import main

    trace_path, metrics_path = traced_run
    assert main(["trace-summary", trace_path]) == 0
    out = capsys.readouterr().out
    assert "prefetch-wait" in out
    assert main(["trace-summary", metrics_path]) == 0
    assert "items_per_sec" in capsys.readouterr().out


def test_trace_summary_rejects_garbage(tmp_path, capsys):
    from swiftsnails_tpu.telemetry.summary import main as summary_main

    p = tmp_path / "junk.bin"
    p.write_bytes(b"\x00\x01not json")
    assert summary_main([str(p)]) == 1
    assert "neither" in capsys.readouterr().out


def test_telemetry_off_by_default():
    from test_word2vec import make_trainer

    from swiftsnails_tpu.framework.trainer import TrainLoop

    loop = TrainLoop(make_trainer(), log_every=0)
    assert loop.tracer is None and loop.registry is None
