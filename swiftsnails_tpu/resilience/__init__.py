"""Resilience subsystem: make every run survivable, every failure drillable.

The observability stack (PRs 1-2: tracer, ledger, black box, goodput) is the
*recording* half of production readiness; this package is the *action* half:

* :mod:`~swiftsnails_tpu.resilience.chaos` — deterministic, seeded fault
  injection (``chaos_spec`` / ``chaos_seed``): NaN/Inf updates, poisoned
  parameter rows, checkpoint bit rot, transient data-stream I/O errors,
  simulated preemption — each injection a ``chaos`` ledger event;
* :mod:`~swiftsnails_tpu.resilience.guardrail` — jit-compatible per-step
  health check with donated-buffer-safe rollback, batch skip, a halving/
  recovering trust factor, and a bounded give-up into a black-box dump
  (``guardrail``, ``guard_max_update_norm``, ``guard_max_consecutive``);
* :mod:`~swiftsnails_tpu.resilience.resume` — auto-resume from the newest
  *verified* checkpoint (manifest CRC walk-back on corruption), restoring
  the data-stream cursor so resumed loss curves continue instead of restart
  (``resume: auto``, with ``framework/checkpoint.py``);
* :mod:`~swiftsnails_tpu.resilience.drill` — the canned chaos drill matrix
  (``tools/chaos_drill.py``);
* :mod:`~swiftsnails_tpu.resilience.retry` — the unified deadline + retry
  policy (exponential backoff, decorrelated jitter, injectable clock) that
  every fallible host I/O path shares: the data stream, checkpoint
  save/restore, tier master flush/gather, Servant reload
  (``retry_max_attempts``, ``retry_deadline_ms``).

Cost contract: nothing here is imported unless a resilience config key is
set; the TrainLoop hot path pays flag checks only.
"""

from swiftsnails_tpu.resilience.chaos import (
    ChaosPlan,
    ChaosSpecError,
    TransientDataError,
    corrupt_checkpoint_dir,
    parse_chaos_spec,
)
from swiftsnails_tpu.resilience.guardrail import GuardrailExhausted, StepGuardrail
from swiftsnails_tpu.resilience.resume import resume_mode, resume_state
from swiftsnails_tpu.resilience.retry import (
    Deadline,
    DeadlineExceeded,
    RetryBudget,
    RetryExhausted,
    RetryingIterator,
    RetryPolicy,
    retry_call,
)

__all__ = [
    "ChaosPlan",
    "ChaosSpecError",
    "Deadline",
    "DeadlineExceeded",
    "GuardrailExhausted",
    "RetryBudget",
    "RetryExhausted",
    "RetryingIterator",
    "RetryPolicy",
    "StepGuardrail",
    "TransientDataError",
    "corrupt_checkpoint_dir",
    "parse_chaos_spec",
    "resume_mode",
    "resume_state",
    "retry_call",
]
