"""Availability hardening (ISSUE 8): the unified retry/deadline layer, the
serving circuit breakers + degraded-mode reads, and tier integrity digests
(the serve drill itself is in ``test_drills.py``).

The bars: backoff draws stay inside the decorrelated-jitter envelope and a
wall-clock deadline pre-empts the attempt budget (all under a fake clock —
no real sleeping); an exhausted budget is a structured ``retry_exhausted``
ledger event, never a silent give-up; the breaker walks
closed -> open -> half-open -> closed with probe capping, including under
concurrent queries; a tripped pull breaker serves stale LRU rows counted
apart from every fresh counter; a direct master-plane write (bit rot) is
caught by ``HostMaster.verify()``.
"""

import os
import random
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp

from swiftsnails_tpu.resilience.retry import (
    Deadline,
    DeadlineExceeded,
    RetryBudget,
    RetryExhausted,
    RetryPolicy,
    RetryingIterator,
)
from swiftsnails_tpu.serving.breaker import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    Unavailable,
)
from swiftsnails_tpu.serving.engine import Servant
from swiftsnails_tpu.telemetry.ledger import (
    Ledger,
    render_failures,
)
from swiftsnails_tpu.utils.config import Config


class FakeClock:
    """Monotonic fake: ``sleep`` advances time, nothing really waits."""

    def __init__(self):
        self.t = 0.0
        self.slept = []

    def __call__(self) -> float:
        return self.t

    def sleep(self, s: float) -> None:
        self.slept.append(s)
        self.t += s


def _policy(clk=None, **kw):
    clk = clk or FakeClock()
    kw.setdefault("rng", random.Random(7))
    return clk, RetryPolicy(clock=clk, sleep=clk.sleep, **kw)


# ------------------------------------------------------------ retry layer --


def test_backoff_draws_stay_inside_jitter_envelope():
    _, pol = _policy(base_ms=25.0, cap_ms=100.0)
    base, cap = 0.025, 0.100
    prev = None
    for _ in range(200):
        d = pol.next_backoff_s(prev)
        hi = max(base, min(cap, (base if prev is None else prev) * 3.0))
        assert base <= d <= hi + 1e-12
        assert d <= cap + 1e-12  # the clamp actually binds
        prev = d


def test_retry_recovers_from_transient_failures():
    clk, pol = _policy(max_attempts=4, deadline_ms=60_000)
    calls = []

    def flaky():
        calls.append(clk.t)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert pol.call(flaky, op="probe") == "ok"
    assert len(calls) == 3
    assert len(clk.slept) == 2  # one backoff per failed attempt
    assert all(s >= 0.025 for s in clk.slept)


def test_non_retryable_error_propagates_immediately():
    clk, pol = _policy()
    calls = []

    def bad():
        calls.append(1)
        raise ValueError("not in retry_on")

    with pytest.raises(ValueError):
        pol.call(bad, op="probe")
    assert len(calls) == 1 and not clk.slept


def test_attempt_exhaustion_is_a_structured_ledger_event(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    clk, pol = _policy(max_attempts=3)
    pol.ledger = led

    def down():
        raise OSError("disk on fire")

    with pytest.raises(RetryExhausted) as ei:
        pol.call(down, op="ckpt_restore")
    assert ei.value.attempts == 3 and ei.value.reason == "attempts"
    assert isinstance(ei.value.__cause__, OSError)
    assert len(clk.slept) == 2  # no sleep after the final attempt
    ev = led.latest("retry_exhausted")
    assert ev["op"] == "ckpt_restore" and ev["attempts"] == 3
    assert ev["reason"] == "attempts" and "disk on fire" in ev["error"]
    assert "RETRY-EXHAUSTED op=ckpt_restore" in render_failures(led)


def test_deadline_preempts_the_attempt_budget():
    # remaining budget (50 ms) < the smallest possible backoff (base 60 ms):
    # the policy must give up on the FIRST failure with reason "deadline",
    # long before the 10-attempt budget is spent
    clk, pol = _policy(max_attempts=10, deadline_ms=50.0, base_ms=60.0)

    def down():
        raise OSError("still down")

    with pytest.raises(DeadlineExceeded) as ei:
        pol.call(down, op="flush")
    assert ei.value.reason == "deadline" and ei.value.attempts == 1
    assert not clk.slept  # never slept into a deadline it cannot make


def test_deadline_and_budget_primitives():
    clk = FakeClock()
    d = Deadline.after_ms(100.0, clock=clk)
    assert d.remaining() == pytest.approx(0.1) and not d.expired
    clk.t = 0.25
    assert d.expired and d.remaining() == 0.0
    with pytest.raises(DeadlineExceeded):
        d.check(op="op")
    b = RetryBudget(max_attempts=2)
    assert b.spend() and not b.exhausted and b.remaining == 1
    assert b.spend() and b.exhausted
    assert not b.spend()  # over budget


def test_from_config_reads_retry_keys():
    cfg = Config({
        "retry_max_attempts": "2", "retry_deadline_ms": "1234",
        "retry_base_ms": "5", "retry_cap_ms": "50",
    })
    pol = RetryPolicy.from_config(cfg)
    assert (pol.max_attempts, pol.deadline_ms) == (2, 1234.0)
    assert (pol.base_ms, pol.cap_ms) == (5.0, 50.0)


class _FlakyStream:
    def __init__(self, items, fail_every=None):
        self._it = iter(items)
        self._fail_every = fail_every
        self._n = 0

    def __iter__(self):
        return self

    def __next__(self):
        self._n += 1
        if self._fail_every and self._n % self._fail_every == 0:
            raise OSError(f"read error @{self._n}")
        return next(self._it)


def test_retrying_iterator_recovers_and_passes_stop_through():
    _, pol = _policy(max_attempts=4)
    notes = []
    it = RetryingIterator(
        _FlakyStream(range(5), fail_every=3), pol,
        on_error=lambda e, a, rec: notes.append((type(e).__name__, rec)))
    assert list(it) == [0, 1, 2, 3, 4]  # StopIteration untouched
    assert it.retried == 2
    assert notes and all(rec for _, rec in notes)


def test_retrying_iterator_exhaustion_reraises_original_error():
    _, pol = _policy(max_attempts=2)
    notes = []

    class _Dead:
        def __next__(self):
            raise OSError("permanently down")

    it = RetryingIterator(_Dead(), pol,
                          on_error=lambda e, a, rec: notes.append(rec))
    with pytest.raises(OSError, match="permanently down"):
        next(it)
    assert notes[-1] is False  # final callback reports the give-up


# -------------------------------------------------------- circuit breaker --


def test_breaker_trips_cools_down_and_recovers():
    clk = FakeClock()
    br = CircuitBreaker("pull", threshold=3, cooldown_ms=100.0, clock=clk)
    for _ in range(2):
        br.record_failure()
    assert br.state == CLOSED  # below threshold
    br.record_failure()
    assert br.state == OPEN and br.trips == 1
    assert not br.allow() and br.open_sheds == 1
    clk.t += 0.2  # cooldown elapsed -> the next request is the probe
    assert br.allow() and br.state == HALF_OPEN
    br.record_success()
    assert br.state == CLOSED and br.recoveries == 1
    assert br.last_recovery_latency_ms == pytest.approx(200.0)


def test_halfopen_probe_failure_reopens_for_another_cooldown():
    clk = FakeClock()
    br = CircuitBreaker("pull", threshold=1, cooldown_ms=100.0, clock=clk)
    br.record_failure()
    clk.t += 0.15
    assert br.allow()
    br.record_failure()  # probe found the kernel still sick
    assert br.state == OPEN and br.trips == 1  # re-open, not a new trip
    assert not br.allow()  # the new cooldown starts from the re-open
    clk.t += 0.15
    assert br.allow()
    br.record_success()
    assert br.state == CLOSED


def test_halfopen_caps_concurrent_probes():
    clk = FakeClock()
    br = CircuitBreaker("pull", threshold=1, cooldown_ms=50.0,
                        halfopen_probes=1, clock=clk)
    br.record_failure()
    clk.t += 0.1
    assert br.allow()  # the single admitted probe
    assert not br.allow()  # second concurrent request is shed
    assert br.open_sheds == 1


def test_transition_observer_sees_the_full_episode():
    clk = FakeClock()
    seen = []
    br = CircuitBreaker(
        "pull", threshold=1, cooldown_ms=50.0, clock=clk,
        on_transition=lambda name, old, new, snap: seen.append((old, new)))
    br.record_failure()
    clk.t += 0.1
    br.allow()
    br.record_success()
    assert seen == [(CLOSED, OPEN), (OPEN, HALF_OPEN), (HALF_OPEN, CLOSED)]


def test_breaker_is_consistent_under_concurrent_callers():
    br = CircuitBreaker("pull", threshold=3, cooldown_ms=1.0)
    stop = threading.Event()
    errors = []

    def hammer(seed):
        rng = random.Random(seed)
        try:
            while not stop.is_set():
                if br.allow():
                    (br.record_failure if rng.random() < 0.5
                     else br.record_success)()
        except Exception as e:  # noqa: BLE001 — the test IS the catch
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(5)
    assert not errors
    snap = br.snapshot()
    assert snap["state"] in (CLOSED, OPEN, HALF_OPEN)
    assert snap["trips"] >= 1 and snap["consecutive_failures"] >= 0


# ------------------------------------------------- degraded-mode serving ---


def test_degraded_serving_lifecycle(tmp_path):
    """The whole availability ladder on a live Servant: warmed stale rows
    survive a reload, a fault storm trips the pull breaker, degraded serves
    come from the stale LRU (counted apart from every fresh counter),
    health() degrades, and the half-open probe recovers to fresh serves."""
    ledger_path = str(tmp_path / "l.jsonl")
    rng = np.random.default_rng(0)
    t1 = rng.standard_normal((32, 4)).astype(np.float32)
    t2 = t1 + 1.0
    ids = np.arange(8, dtype=np.int32)
    with Servant({"t": t1}, batch_buckets=(8,), cache_rows=64,
                 breaker_threshold=2, breaker_cooldown_ms=50.0,
                 ledger=Ledger(ledger_path)) as sv:
        br = sv.breakers["pull"]
        np.testing.assert_array_equal(sv.pull(ids), t1[ids])  # warm the LRU
        sv.reload({"t": t2})  # version bump: warmed rows become stale
        fresh_rows = sv.registry.counter("serve.pull.rows").value

        sv.fault_hook = lambda kernel, idx: (_ for _ in ()).throw(
            OSError(f"chaos {kernel}@{idx}"))
        for n in range(4):
            got = sv.pull(ids)  # dispatch fails -> stale t1, never t2
            np.testing.assert_array_equal(got, t1[ids])
        assert br.state == OPEN and br.trips == 1
        # fresh and degraded paths never mix counters
        assert sv.registry.counter("serve.pull.rows").value == fresh_rows
        assert sv.registry.counter("serve.degraded_hits").value == 4 * len(ids)
        assert sv.health()["status"] == "degraded"

        sv.fault_hook = None
        time.sleep(0.08)  # cooldown -> next pull is the half-open probe
        np.testing.assert_array_equal(sv.pull(ids), t2[ids])  # fresh again
        assert br.state == CLOSED and br.recoveries == 1
        assert br.last_recovery_latency_ms is not None
        health = sv.health()
        assert health["status"] == "ok"
        assert health["degraded_hits"] == 4 * len(ids)
    led = Ledger(ledger_path)
    assert led.latest("degraded")["kernel"] == "pull"
    assert led.latest("breaker")["to"] == CLOSED  # the recovery transition
    rendered = render_failures(led)
    assert "BREAKER" in rendered and "DEGRADED" in rendered


def test_topk_sheds_unavailable_when_breaker_open():
    rng = np.random.default_rng(1)
    with Servant({"t": rng.standard_normal((16, 4)).astype(np.float32)},
                 batch_buckets=(4,), cache_rows=0,
                 breaker_threshold=2, breaker_cooldown_ms=10_000.0) as sv:
        sv.fault_hook = lambda kernel, idx: (_ for _ in ()).throw(
            OSError("chaos"))
        q = np.ones(4, np.float32)
        for _ in range(2):  # feed the topk breaker to its threshold
            with pytest.raises(OSError):
                sv.topk(q, k=3)
        # no stale inventory for topk: an open breaker sheds, typed
        with pytest.raises(Unavailable):
            sv.topk(q, k=3)
        assert sv.registry.counter("serve.topk.unavailable").value == 1


def test_degraded_disabled_raises_unavailable():
    rng = np.random.default_rng(2)
    t = rng.standard_normal((16, 4)).astype(np.float32)
    with Servant({"t": t}, batch_buckets=(4,), cache_rows=64,
                 breaker_threshold=1, breaker_cooldown_ms=10_000.0,
                 degraded=False) as sv:
        ids = np.arange(4, dtype=np.int32)
        sv.pull(ids)
        sv.reload({"t": t})
        sv.fault_hook = lambda kernel, idx: (_ for _ in ()).throw(
            OSError("chaos"))
        with pytest.raises(OSError):  # first failure trips (threshold 1)...
            sv.pull(ids)
        with pytest.raises(Unavailable):  # ...then strict freshness sheds
            sv.pull(ids)


def test_concurrent_queries_all_served_degraded_while_tripped():
    rng = np.random.default_rng(3)
    t = rng.standard_normal((32, 4)).astype(np.float32)
    ids = np.arange(8, dtype=np.int32)
    with Servant({"t": t}, batch_buckets=(8,), cache_rows=64,
                 breaker_threshold=3, breaker_cooldown_ms=10_000.0) as sv:
        sv.pull(ids)
        sv.reload({"t": t})
        sv.fault_hook = lambda kernel, idx: (_ for _ in ()).throw(
            OSError("chaos"))
        errors = []

        def query():
            try:
                np.testing.assert_array_equal(sv.pull(ids), t[ids])
            except Exception as e:  # noqa: BLE001 — collected for the assert
                errors.append(e)

        threads = [threading.Thread(target=query) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(10)
        assert not errors  # every caller was served (fresh or degraded)
        assert sv.breakers["pull"].state == OPEN
        assert sv.registry.counter("serve.degraded_hits").value > 0


# ---------------------------------------------------------- tier integrity --


def _master():
    from swiftsnails_tpu.parallel.store import TableState
    from swiftsnails_tpu.tiered.store import HostMaster

    rng = np.random.default_rng(0)
    return HostMaster(
        TableState(
            table=jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32)),
            slots={"m": jnp.zeros((8, 4), np.float32)}),
        "dense")


def test_scatter_keeps_digests_consistent():
    m = _master()
    assert m.checksummed and m.verify() == []
    units = np.array([1, 5])
    m.scatter(units, np.full((2, 4), 7.0, np.float32),
              {"m": np.full((2, 4), 2.0, np.float32)})
    assert m.verify() == []  # incremental digest tracked the write
    m.reload(m.state())  # wholesale reload re-seeds
    assert m.verify() == []


def test_direct_write_bypassing_scatter_is_detected():
    m = _master()
    m.table[3, 1] += 1.0  # a write that did not flow through scatter()
    assert m.verify() == ["table"]
    m.slots["m"][0, 0] = 9.0
    assert sorted(m.verify()) == ["slots/m", "table"]


def test_single_bit_flip_is_detected():
    m = _master()
    m.table.view(np.uint8).reshape(-1)[17] ^= 0x01  # the minimal corruption
    assert m.verify() == ["table"]
