"""Tier-1 fast subset of the chaos drill matrix (tools/chaos_drill.py).

Each drill is a deterministic end-to-end recovery scenario; the full matrix
(plus the slower preemption-resume script) runs via ``tools/chaos_drill.py``.
A drill that does not *recover* here is a
regression in the resilience stack, not flake: every fault is seeded."""

import pytest

from swiftsnails_tpu.resilience.drill import (
    FAST_DRILLS,
    drill_ckpt_walkback,
    drill_io_error,
    drill_nan_burst,
    run_drill_matrix,
)


def test_fast_drills_is_a_subset_of_the_matrix():
    from swiftsnails_tpu.resilience.drill import DRILLS

    assert set(FAST_DRILLS) <= set(DRILLS)


def test_nan_burst_recovers_with_finite_tables(tmp_path):
    res = drill_nan_burst(str(tmp_path))
    assert res["recovered"], res
    assert res["tables_finite"] and res["trips"] == 3
    assert res["steps_skipped"] == 3  # burst batches skipped, run completed


def test_io_error_retries_instead_of_dying(tmp_path):
    res = drill_io_error(str(tmp_path))
    assert res["recovered"], res
    assert res["injected"] == 2 and res["steps"] == 12


def test_ckpt_walkback_restores_newest_intact(tmp_path):
    res = drill_ckpt_walkback(str(tmp_path))
    assert res["recovered"], res
    assert res["restored_step"] < res["corrupted_step"]
    assert res["cursor"]["step"] == res["restored_step"]


def test_run_drill_matrix_fast_all_recover(tmp_path):
    results = run_drill_matrix(fast=True, workdir=str(tmp_path))
    assert set(results) == set(FAST_DRILLS)
    failed = {k: v for k, v in results.items() if not v.get("recovered")}
    assert not failed, failed


def test_chaos_drill_tool_exits_zero(tmp_path, capsys):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "chaos_drill.py")
    spec = importlib.util.spec_from_file_location("chaos_drill_tool", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    rc = tool.main(["--fast", "--workdir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "RECOVERED" in out and "UNRECOVERED" not in out


@pytest.mark.slow
def test_full_drill_matrix(tmp_path):
    results = run_drill_matrix(fast=False, workdir=str(tmp_path))
    failed = {k: v for k, v in results.items() if not v.get("recovered")}
    assert not failed, failed
