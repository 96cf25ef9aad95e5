"""Tracing / profiling hooks.

The reference has no tracing at all (survey §5: glog timestamps and a chrono
``Timer`` only). Here: ``jax.profiler`` integration — step-scoped trace
annotations plus an on-demand Perfetto trace window, driven by two config
keys:

* ``profile_dir``   — where to write the trace (enables profiling);
* ``profile_steps`` — "start,stop" step numbers for the capture window
  (default "10,20": skips compile, captures 10 steady-state steps).

And the names the device timeline carries: :func:`step_annotation` puts the
step number on the host side of a capture, :func:`phase_scope` names what a
stretch of a jitted step is for, in every model alike, and :func:`part_scope`
what stands in front of, inside and behind a phase's kernels.
"""

from __future__ import annotations

import jax

from swiftsnails_tpu.utils.config import Config


class StepProfiler:
    """Start/stop a jax profiler trace around a configured step window."""

    def __init__(self, config: Config):
        self.trace_dir = config.get_str("profile_dir", "")
        window = config.get_str("profile_steps", "10,20")
        try:
            start_s, stop_s = window.replace(";", ",").split(",")
            self.start_step, self.stop_step = int(start_s), int(stop_s)
        except ValueError:
            raise ValueError(
                f"profile_steps must be 'start,stop', got {window!r}"
            ) from None
        if self.start_step >= self.stop_step:
            raise ValueError(
                f"profile_steps start must be < stop, got {window!r}"
            )
        self._active = False
        self._finished = False

    @property
    def enabled(self) -> bool:
        return bool(self.trace_dir)

    def on_step(self, step: int) -> None:
        if not self.enabled or self._finished:
            return
        # >= not ==: a resumed run may enter past the window start
        if not self._active and self.start_step <= step < self.stop_step:
            jax.profiler.start_trace(self.trace_dir)
            self._active = True
        elif self._active and step >= self.stop_step:
            jax.profiler.stop_trace()
            self._active = False
            self._finished = True

    def close(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False


def step_annotation(name: str, step: int) -> jax.profiler.StepTraceAnnotation:
    """Label host-side work for the profiler timeline."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


# What the operations of a jitted train step are for. A name means the same
# in every model: ``prep`` turns ids into rows, negatives, copy lists and
# merge plans; ``fused`` is Word2Vec's grouped SGNS kernel; ``pull`` and
# ``push`` read and update table rows; ``dense`` is a CTR model's forward,
# backward and dense update. A sequence model's step (``models/seqlm.py``,
# ``models/moelm.py``): ``attn`` the attention block with its projections,
# ``mlp`` a dense feed-forward (a mixture layer's shared experts too),
# ``route`` the router, its top-k, the sort by expert and the counts,
# ``experts`` the grouped products over the experts held and the combine,
# ``head`` embedding, final norm, output head and loss, ``opt`` the optimizer
# and whatever else a step changes that is no gradient, ``noise`` what turns
# a block-diffusion batch into the stack's input (the mask id where the batch
# says noised, the two copies, their positions, the loss's weights), ``kda``
# a gated delta-rule mixer (norm, projections, convolutions, gates, the
# chunked recurrence, which ``ops/gated_delta.py`` names ``phase_kda_core``
# inside it, the gated norm and the output projection), ``gdn`` the other
# gated delta-rule mixer, Gated DeltaNet's (a decay per head, keys narrower
# than values, a full-rank SiLU gate), whose recurrence stands under
# ``phase_gdn_core``. Scopes are metadata on the operations (no
# operation, no flag): a ``profile_dir`` capture shows them as the name
# scope of each device operation, and ``benchmark/lib/scopes.py`` sums
# device time by the innermost one. The prefix stays clear of the ``ssn_*``
# labels that ``telemetry/audit.py`` groups collective bytes by.
#
# Five phases of a language-model step have parts (``phase_<phase>_<part>``,
# set inside the phase's scope around the CALL, so that a ``custom_vjp``'s
# backward pass and the rematerialised forward carry it as they carry the
# phase; ``benchmark/lib/parts.py`` reads them). ``attn`` / ``in``: the
# block's norm, the projections to q, k and v with the latent norm or the
# q/k norms (per head or over the held width), rotary and the head-major transposes in front of the
# kernels. ``attn`` / ``core``: the ``flash_attention`` call alone, its three
# kernels and what it does around them in XLA; under a learned selection
# (``sa_config``) also the probability pass that gives ``L_I`` its ``p``.
# ``attn`` / ``index``: the indexer's projections, its key norm and rotary,
# and ``L_I`` with its backward kernel. ``attn`` / ``select``: the indexer's
# score kernel by query chunks, the exact top-k, the packed words and the
# walks over the live tiles. ``attn`` / ``out``: the way
# back from head-major, the sigmoid gate where there is one, ``W_o``, the
# output's norm under ``norm_order: post``, and the residual sum. ``kda`` / ``in``: the norm, the q, k and v projections with
# their causal convolutions, SiLU and the l2 norm, the rate's two products
# and ``g``, beta, the head-major transposes. ``kda`` / ``core``: the chunked
# recurrence (``ops/gated_delta.py`` sets it, forward and backward).
# ``kda`` / ``out``: the way back from head-major, the low-rank gate, the
# gated head norm, ``W_o``, the decay's mean and the residual sum. ``gdn`` /
# ``in``, ``core`` and ``out`` are the same for Gated DeltaNet: its per-head
# rate ``W_a h`` in place of the low-rank one, its full-rank gate ``silu(W_g
# h)`` in place of the low-rank sigmoid; under ``norm_order: post`` the
# mixer's output norm stands in ``out`` and the feed-forward's in ``mlp``.
# ``experts`` / ``gather``: tokens to rows, a live tile at a time.
# ``experts`` / ``products``: ``grouped_swiglu``, the grouped kernels, the
# stacked weights' casts and ``dw``. ``experts`` / ``scatter``: rows back to
# tokens under their gates. ``route`` / ``score``: the feed-forward part's
# norm, the router's product, scores, top-k, gates, the balance loss and the
# counts. ``route`` / ``plan``: the sort of the held assignments by expert
# into the row layout (inside ``phase_experts``), ``dropped`` and the live
# tiles' share.
PHASES = ("prep", "fused", "pull", "push", "dense",
          "attn", "mlp", "route", "experts", "head", "opt", "noise", "kda", "gdn")
PARTS = {"attn": ("in", "core", "out", "index", "select"),
         "kda": ("in", "core", "out"),
         "gdn": ("in", "core", "out"),
         "experts": ("gather", "products", "scatter"),
         "route": ("score", "plan")}


def phase_scope(phase: str):
    """``jax.named_scope`` of one of :data:`PHASES`: ``phase_<name>``."""
    if phase not in PHASES:
        raise ValueError(f"unknown phase {phase!r}; one of {PHASES}")
    return jax.named_scope("phase_" + phase)


def part_scope(phase: str, part: str):
    """``jax.named_scope`` of a pair of :data:`PARTS`: ``phase_<phase>_<part>``."""
    if part not in PARTS.get(phase, ()):
        raise ValueError(f"unknown part {phase!r} / {part!r}; one of {PARTS}")
    return jax.named_scope(f"phase_{phase}_{part}")
