"""Resilience subsystem: chaos-spec parsing, guardrail rollback/trust/giveup,
verified-checkpoint manifests + walk-back, retention, auto-resume with the
data cursor, preemption drain, and the ledger failure views."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.framework.checkpoint import (
    CheckpointError,
    all_steps,
    intact_steps,
    prune_checkpoints,
    read_manifest,
    restore_checkpoint,
    save_checkpoint,
    wait_for_checkpoints,
)
from swiftsnails_tpu.resilience import (
    ChaosPlan,
    ChaosSpecError,
    GuardrailExhausted,
    StepGuardrail,
    TransientDataError,
    corrupt_checkpoint_dir,
    parse_chaos_spec,
    resume_state,
)
from swiftsnails_tpu.telemetry.ledger import Ledger, render_failures
from swiftsnails_tpu.utils.config import Config


def make_trainer(workdir=None, **over):
    from swiftsnails_tpu.resilience.drill import make_trainer as mk

    return mk(str(workdir), **over)


# ------------------------------------------------------------- chaos spec ---


def test_parse_chaos_spec_entries_and_ranges():
    faults = parse_chaos_spec("nan_grad@5-7, preempt@17,io_error@2")
    assert ("nan_grad", 5) in faults and ("nan_grad", 7) in faults
    assert ("preempt", 17) in faults and ("io_error", 2) in faults
    assert len(faults) == 5


@pytest.mark.parametrize("bad", ["nonsense@3", "nan_grad@", "nan_grad@7-5",
                                 "nan_grad"])
def test_parse_chaos_spec_rejects_malformed(bad):
    with pytest.raises(ChaosSpecError):
        parse_chaos_spec(bad)


def test_chaos_plan_fires_each_fault_once(tmp_path):
    ledger = Ledger(str(tmp_path / "led.jsonl"))
    plan = ChaosPlan(parse_chaos_spec("nan_grad@2"), seed=3, ledger=ledger)
    state = {"t": jnp.ones((4, 3))}
    s1, m1 = plan.post_step(state, {"loss": jnp.float32(1.0)}, 2)
    assert not np.isfinite(np.asarray(s1["t"])).all()
    s2, _ = plan.post_step(state, {"loss": jnp.float32(1.0)}, 2)
    assert np.isfinite(np.asarray(s2["t"])).all()  # fired once only
    assert ledger.latest("chaos")["fault"] == "nan_grad"
    assert plan.summary()["injected"] == 1 and not plan.summary()["unfired"]


def test_chaos_stream_raises_then_continues():
    plan = ChaosPlan(parse_chaos_spec("io_error@1"), seed=0)
    it = plan.wrap_stream(iter([10, 11, 12]))
    assert next(it) == 10
    with pytest.raises(TransientDataError):
        next(it)
    # the failed fetch did not consume the batch
    assert next(it) == 11 and next(it) == 12


# -------------------------------------------------------------- guardrail ---


def _tiny_state(val=0.0):
    return {"w": jnp.full((4, 3), val, jnp.float32)}


def test_guardrail_rolls_back_nonfinite_update():
    g = StepGuardrail(max_consecutive=3)
    snap = g.snapshot(_tiny_state(1.0))
    poisoned = {"w": snap["w"].at[0, 0].set(jnp.nan)}
    state, metrics, tripped, exhausted = g.commit(
        snap, poisoned, {"loss": jnp.float32(0.5)})
    assert tripped and not exhausted
    assert np.isfinite(np.asarray(state["w"])).all()
    assert float(metrics["guard_tripped"]) == 1.0
    assert g.trust == 0.5 and g.steps_skipped == 1


def test_guardrail_update_norm_spike_trips():
    g = StepGuardrail(max_update_norm=0.1)
    snap = g.snapshot(_tiny_state(0.0))
    spiked = {"w": snap["w"] + 100.0}
    state, _, tripped, _ = g.commit(snap, spiked, {"loss": jnp.float32(0.1)})
    assert tripped
    np.testing.assert_array_equal(np.asarray(state["w"]), 0.0)
    assert "spike" in g.last_trip_reason


def test_guardrail_trust_blends_and_recovers():
    g = StepGuardrail()
    g.trust = 0.5  # as after one trip
    snap = g.snapshot(_tiny_state(0.0))
    full = {"w": snap["w"] + 1.0}
    state, metrics, tripped, _ = g.commit(snap, full, {"loss": jnp.float32(0.1)})
    assert not tripped
    np.testing.assert_allclose(np.asarray(state["w"]), 0.5)  # half the update
    assert g.trust == 1.0  # exponential recovery doubled it back


def test_guardrail_exhaustion_flag():
    g = StepGuardrail(max_consecutive=2)
    snap = g.snapshot(_tiny_state(0.0))
    bad = {"w": snap["w"].at[0, 0].set(jnp.inf)}
    _, _, _, exhausted = g.commit(snap, bad, {})
    assert not exhausted
    _, _, _, exhausted = g.commit(snap, bad, {})
    assert exhausted and g.trips_total == 2


def test_trainloop_guardrail_giveup_raises(tmp_path):
    from swiftsnails_tpu.framework.trainer import TrainLoop

    tr = make_trainer(tmp_path, guardrail=1, guard_max_consecutive=2,
                      chaos_spec="nan_grad@1-6", chaos_seed=1)
    with pytest.raises(GuardrailExhausted):
        TrainLoop(tr, log_every=0).run(max_steps=8)


# --------------------------------------------- verified checkpoints ---------


def _save_state(tmp_path, val=2.0, step=3, **kw):
    root = str(tmp_path / "ck")
    state = {"w": jnp.full((8, 4), val, jnp.float32),
             "b": jnp.arange(6.0)}
    save_checkpoint(root, state, step, **kw)
    return root, state


def test_manifest_commits_with_crc_and_cursor(tmp_path):
    root, state = _save_state(
        tmp_path, cursor={"step": 3, "items": 99}, config_hash="abcd")
    man = read_manifest(root, 3)
    assert man["step"] == 3 and man["config_hash"] == "abcd"
    assert man["data_cursor"] == {"step": 3, "items": 99}
    assert len(man["arrays"]) == 2
    for meta in man["arrays"].values():
        assert isinstance(meta["crc"], int) and meta["algo"] in ("crc32c", "crc32")
    assert intact_steps(root) == [3]


def test_restore_verifies_and_rejects_corruption(tmp_path):
    root, state = _save_state(tmp_path)
    template = {"w": jnp.zeros((8, 4)), "b": jnp.zeros(6)}
    got = restore_checkpoint(root, template)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(state["w"]))
    corrupt_checkpoint_dir(root)
    with pytest.raises((CheckpointError, Exception)):
        restore_checkpoint(root, template)


def test_async_save_manifest_commits_on_wait(tmp_path):
    root = str(tmp_path / "ck")
    state = {"w": jnp.ones((4, 4))}
    save_checkpoint(root, state, 7, wait=False)
    errs = wait_for_checkpoints()
    assert errs == []
    assert read_manifest(root, 7) is not None


def test_resume_walks_back_past_corruption(tmp_path):
    root = str(tmp_path / "ck")
    ledger = Ledger(str(tmp_path / "led.jsonl"))
    for step, val in ((2, 1.0), (4, 2.0), (6, 3.0)):
        save_checkpoint(root, {"w": jnp.full((4, 4), val)}, step,
                        cursor={"step": step}, ledger=ledger)
    corrupt_checkpoint_dir(root)  # newest = step 6
    got = resume_state(root, {"w": jnp.zeros((4, 4))}, mode="auto",
                       ledger=ledger)
    assert got is not None
    state, step, cursor = got
    assert step == 4 and cursor["step"] == 4
    np.testing.assert_array_equal(np.asarray(state["w"]), 2.0)
    ev = ledger.latest("cache_error")
    assert ev is not None and ev["source"] == "checkpoint"


def test_retention_prunes_old_but_never_protected(tmp_path):
    root = str(tmp_path / "ck")
    for step in (1, 2, 3, 4, 5):
        save_checkpoint(root, {"w": jnp.ones((2, 2)) * step}, step)
    pruned = prune_checkpoints(root, keep=2, protect=1)
    assert set(pruned) == {2, 3}
    assert all_steps(root) == [1, 4, 5]  # protect=1 survived retention


def test_trainloop_applies_retention(tmp_path):
    from swiftsnails_tpu.framework.trainer import TrainLoop

    tr = make_trainer(tmp_path, param_backup_period=2,
                      param_backup_root=str(tmp_path / "ck"),
                      param_backup_keep=2)
    TrainLoop(tr, log_every=0).run(max_steps=11)
    wait_for_checkpoints()
    # saves at 2,4,6,8,10 -> retention keeps the newest 2 intact
    assert all_steps(str(tmp_path / "ck")) == [8, 10]


# ---------------------------------------------- preemption + auto-resume ----


def test_preemption_drains_with_final_save_and_outage_event(tmp_path):
    from swiftsnails_tpu.framework.trainer import TrainLoop

    root = str(tmp_path / "ck")
    tr = make_trainer(tmp_path, param_backup_period=4, param_backup_root=root,
                      chaos_spec="preempt@5", chaos_seed=0)
    loop = TrainLoop(tr, log_every=0)
    loop.run(max_steps=50)
    assert loop.preempted
    # drained: a final checkpoint exists past the last periodic save
    assert intact_steps(root)[0] >= 5
    led = Ledger(str(tmp_path / "LEDGER.jsonl"))
    ev = led.latest("outage")
    assert ev is not None and ev["probe"] == "preemption"


def test_auto_resume_restores_cursor_and_continues(tmp_path):
    from swiftsnails_tpu.framework.trainer import TrainLoop

    root = str(tmp_path / "ck")
    tr1 = make_trainer(tmp_path, param_backup_period=4,
                       param_backup_root=root,
                       chaos_spec="preempt@9", chaos_seed=0)
    TrainLoop(tr1, log_every=0).run(max_steps=20)

    # undisturbed control over the same deterministic stream
    tr_c = make_trainer(tmp_path)
    from swiftsnails_tpu.resilience.drill import eval_loss
    loop_c = TrainLoop(tr_c, log_every=0)
    state_c = loop_c.run(max_steps=16)

    tr2 = make_trainer(tmp_path, param_backup_period=1000,
                       param_backup_root=root, resume="auto")
    loop2 = TrainLoop(tr2, log_every=0)
    state2 = loop2.run(max_steps=16)
    assert loop2._restored_step is not None and loop2._restored_step >= 4
    # continuation, not a restart: final eval loss matches the control
    l_c, l_r = eval_loss(tr_c, state_c), eval_loss(tr2, state2)
    assert abs(l_r - l_c) / abs(l_c) < 0.05


# ----------------------------------------------------- ledger views ---------


def test_render_failures_timeline(tmp_path):
    led = Ledger(str(tmp_path / "led.jsonl"))
    led.append("chaos", {"fault": "nan_grad", "step": 5, "seed": 1})
    led.append("outage", {"probe": "preemption", "reason": "chaos", "step": 9,
                          "error": "run preempted"})
    led.append("blackbox", {"reason": "guardrail-giveup", "first_step": 1,
                            "last_step": 9, "dump_path": "/x.json"})
    led.append("cache_error", {"source": "checkpoint", "error": "crc mismatch"})
    led.append("run", {"model": "word2vec", "steps": 20,
                       "guardrail": {"trips_total": 3, "steps_skipped": 3}})
    out = render_failures(led)
    assert "CHAOS" in out and "fault=nan_grad" in out
    assert "OUTAGE" in out and "preemption" in out
    assert "BLACKBOX" in out and "guardrail-giveup" in out
    assert "CKPT/CACHE-ERROR" in out and "crc mismatch" in out
    assert "3 trips" in out


def test_ledger_report_failures_cli(tmp_path, capsys):
    """``ledger-report --failures`` renders the ledger a drill wrote."""
    from swiftsnails_tpu.cli import main
    from swiftsnails_tpu.resilience.drill import drill_io_error

    assert drill_io_error(str(tmp_path))["recovered"]
    rc = main(["ledger-report", str(tmp_path / "LEDGER.jsonl"), "--failures"])
    out = capsys.readouterr().out
    assert rc == 0 and "failure timeline" in out
    assert out.count("fault=io_error") == 2


# ------------------------------------------- resume under reassignment ---


def test_resume_under_reassignment_bit_identical(tmp_path):
    """Losing a worker mid-run, reassigning its span, checkpointing the
    cursor, and resuming into a FRESH supervisor must replay to a
    bit-identical final state: the committed-watermark snapshot pins the
    remaining set, and LeasedStream serves indices smallest-first, so the
    application order after restore is a pure function of the committed
    set (the property worker.py's module docstring promises)."""
    from swiftsnails_tpu.cluster import Supervisor, WorkerClient
    from swiftsnails_tpu.cluster.sim import make_step_fn
    from swiftsnails_tpu.cluster.worker import IndexedBatchSource

    N = 12
    trainer = make_trainer(tmp_path)
    step_fn = make_step_fn(trainer)
    root = jax.random.PRNGKey(0)

    def drain(client, state, applied, snapshot_at=None):
        source = IndexedBatchSource(trainer.batches)
        snap = snap_state = None
        while True:
            try:
                batch = client._next_batch(source)
            except StopIteration:
                break
            index = client._inflight[-1][1]
            state, _ = step_fn(state, batch, root, np.uint32(index))
            applied.append(index)
            client.on_step(len(applied))
            if snapshot_at is not None and len(applied) == snapshot_at:
                snap = client.cursor()
                # host copy BEFORE the next donated step invalidates it
                snap_state = jax.tree_util.tree_map(
                    lambda a: np.array(a), state)
        return state, snap, snap_state

    # -- leg A: worker loss + reassignment, cursor checkpoint mid-run -------
    supA = Supervisor(total_batches=N, lease_ms=1e9, grant_batches=4)
    clientA = WorkerClient(supA, "w0")
    supA.register("w1")                  # phantom peer leases [0, 4) ...
    supA.next_range("w1")
    supA.mark_dead("w1")                 # ... and dies holding it
    assert supA.workers_lost == 1 and supA.reassignments == 1
    stateA, snap, snap_state = drain(
        clientA, trainer.init_state(), appliedA := [], snapshot_at=5)
    assert supA.accountant.verify(N)["exact"]
    assert sorted(appliedA) == list(range(N))
    # the adopted span lands AFTER w0's own first grant: the run really was
    # perturbed by reassignment, not a disguised in-order control
    assert appliedA != list(range(N))

    # -- leg B: fresh supervisor restored from the cursor, replay to end ----
    supB = Supervisor(total_batches=N, lease_ms=1e9, grant_batches=4)
    supB.restore(snap)
    clientB = WorkerClient(supB, "w0")
    stateB, _, _ = drain(
        clientB, jax.tree_util.tree_map(jnp.asarray, snap_state),
        appliedB := [])
    assert supB.accountant.verify(N)["exact"]

    # replay applies exactly the post-snapshot remainder, in the same order
    assert appliedB == appliedA[5:]
    la = jax.tree_util.tree_leaves(stateA)
    lb = jax.tree_util.tree_leaves(stateB)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert np.array_equal(np.asarray(a), np.asarray(b))  # bit-identical
