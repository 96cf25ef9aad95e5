"""Per-step health guardrail: detect a poisoned update, roll it back, recover.

The reference had no numeric-health story at all — a NaN'd gradient walked
straight into the sparse table and every later pull served it to every
worker. Since the flight-recorder PR the black box *records* that corpse;
this module prevents it: the TrainLoop snapshots the tables before the
(donated-buffer) step, checks the step's outcome with one fused jitted
reduction, and on a trip restores the snapshot so **no non-finite value ever
reaches the master tables**.

Semantics (see ``docs/RESILIENCE.md``):

* **trip conditions** — non-finite loss, non-finite update (NaN/Inf anywhere
  in the new state's float leaves shows up as a non-finite update norm), or
  an update-norm spike above ``guard_max_update_norm`` (0 disables the spike
  check; non-finiteness is always checked);
* **on trip** — roll back to the pre-step snapshot, skip the batch, halve the
  internal *trust factor*;
* **trust factor** — after a trip, subsequent clean updates are applied
  scaled (``state + trust * update``) and trust recovers exponentially
  (doubling per clean step) back to 1.0 — a burst of marginal steps re-enters
  at reduced step size instead of full speed;
* **give-up** — ``guard_max_consecutive`` consecutive trips raise
  :class:`GuardrailExhausted` (TrainLoop dumps the black box first): a
  persistently sick run must die loudly, not spin forever skipping batches.

Cost contract: when the ``guardrail`` config key is off the TrainLoop pays
one flag check per step and this module is never imported. On-path the
TrainLoop runs a NON-donating compile of the step (the input buffers are the
rollback snapshot — 2x table memory, no copy), plus one fused reduction over
the state and one host sync of its scalar result per step (the sync is what
makes "roll back before the next step" possible at all). Its cost on the
chip is not measured.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp


class GuardrailExhausted(RuntimeError):
    """``guard_max_consecutive`` consecutive unhealthy steps: giving up."""


def _is_float_leaf(x) -> bool:
    return hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)


class StepGuardrail:
    """Snapshot / health-check / rollback state machine (host-side driver,
    jit-compiled math)."""

    def __init__(
        self,
        max_update_norm: float = 0.0,
        max_consecutive: int = 3,
        min_trust: float = 0.05,
        recovery: float = 2.0,
    ):
        self.max_update_norm = float(max_update_norm)
        self.max_consecutive = max(int(max_consecutive), 1)
        self.min_trust = float(min_trust)
        self.recovery = float(recovery)
        self.trust = 1.0
        self.consecutive = 0
        self.trips_total = 0
        self.steps_skipped = 0
        self.last_update_norm: Optional[float] = None
        self.last_trip_reason: Optional[str] = None

        @jax.jit
        def _update_sq(snap, new):
            s = jnp.float32(0.0)
            for a, b in zip(jax.tree_util.tree_leaves(snap),
                            jax.tree_util.tree_leaves(new)):
                if _is_float_leaf(a):
                    d = b.astype(jnp.float32) - a.astype(jnp.float32)
                    s = s + jnp.sum(d * d)
            return s

        @jax.jit
        def _blend(snap, new, t):
            def leaf(a, b):
                if not _is_float_leaf(a):
                    return b
                af = a.astype(jnp.float32)
                return (af + t * (b.astype(jnp.float32) - af)).astype(a.dtype)

            return jax.tree_util.tree_map(leaf, snap, new)

        self._update_sq = _update_sq
        self._blend = _blend

    # -- per-step API (driven by TrainLoop._resilient_step) -----------------

    @staticmethod
    def snapshot(state: Any) -> Any:
        """Pre-step copy of the state. The step fn donates its input buffers,
        so rollback is only possible from an independent copy taken *before*
        the call — ``jnp.copy`` preserves device placement and sharding."""
        return jax.tree_util.tree_map(
            lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, state
        )

    def commit(
        self, snap: Any, new_state: Any, metrics: Dict
    ) -> Tuple[Any, Dict, bool, bool]:
        """Accept or roll back one step's outcome.

        Returns ``(state, metrics, tripped, exhausted)``. ``exhausted`` means
        the consecutive-trip budget is spent — the caller dumps the black box
        and raises :class:`GuardrailExhausted`.
        """
        norm_sq = float(self._update_sq(snap, new_state))  # host sync point
        loss = metrics.get("loss")
        loss_f = float(loss) if loss is not None else 0.0
        if math.isfinite(norm_sq) and norm_sq >= 0:
            norm = math.sqrt(norm_sq)
        else:
            norm = float("nan")
        self.last_update_norm = norm

        reason = None
        if not math.isfinite(loss_f):
            reason = f"non-finite loss ({loss_f})"
        elif not math.isfinite(norm):
            reason = "non-finite update (NaN/Inf in the new tables)"
        elif self.max_update_norm > 0 and norm > self.max_update_norm:
            reason = (
                f"update-norm spike ({norm:.4g} > "
                f"guard_max_update_norm={self.max_update_norm:.4g})"
            )

        if reason is None:
            self.consecutive = 0
            if self.trust < 1.0:
                new_state = self._blend(snap, new_state, np.float32(self.trust))
                metrics = dict(metrics)
                metrics["guard_trust"] = np.float32(self.trust)
                self.trust = min(1.0, self.trust * self.recovery)
            return new_state, metrics, False, False

        # trip: roll back, skip the batch, shrink trust
        self.last_trip_reason = reason
        self.consecutive += 1
        self.trips_total += 1
        self.steps_skipped += 1
        self.trust = max(self.trust * 0.5, self.min_trust)
        exhausted = self.consecutive >= self.max_consecutive
        trip_metrics = {
            "guard_tripped": np.float32(1.0),
            "guard_trust": np.float32(self.trust),
            "guard_consecutive": np.float32(self.consecutive),
        }
        # keep any finite metrics for the window log; drop the poisoned ones
        for k, v in metrics.items():
            try:
                fv = float(v)
            except (TypeError, ValueError):
                continue
            if math.isfinite(fv):
                trip_metrics.setdefault(k, v)
        return snap, trip_metrics, True, exhausted

    def summary(self) -> Dict:
        """Run-level accounting for the ledger's run record."""
        return {
            "trips_total": self.trips_total,
            "steps_skipped": self.steps_skipped,
            "trust": round(self.trust, 6),
            "last_update_norm": (
                round(self.last_update_norm, 6)
                if isinstance(self.last_update_norm, float)
                and math.isfinite(self.last_update_norm)
                else None
            ),
            "last_trip_reason": self.last_trip_reason,
            "max_update_norm": self.max_update_norm or None,
            "max_consecutive": self.max_consecutive,
        }
