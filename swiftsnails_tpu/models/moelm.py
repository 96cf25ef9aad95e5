"""A language model's block stack, as one chip of an expert- and
head-parallel deployment holds it: a mixer, then a feed-forward part, layer
by layer. The mixer is latent attention (MLA, DeepSeek-V3's), grouped-query
attention (Qwen3-MoE's, with a norm on every head's query and key; or
without it and without rotary, its output gated; or with the norm over the
whole projection, OLMo 2's), or a gated delta rule: with a decay per channel
(KDA, Kimi Linear's, arXiv:2510.26692) or with one per head (Gated DeltaNet,
arXiv:2412.06464); the feed-forward part is SwiGLU in the leading dense
layers and a mixture of experts after them (either may be none), of which
this chip holds ``experts_held`` routed experts from ``expert_offset`` on;
and the chip holds a slice of the vocabulary. Corpus, windows, optimizer
wiring, the one ``train_step``, the loss and its two objectives are
``models/seqlm.py``'s; this file is the stack they drive.

**The layer table.** ``mixers`` names each layer's kind of mixer. It follows
the published keys that are there: ``kv_lora_rank`` marks latent attention,
else ``num_key_value_heads`` and ``head_dim`` the grouped-query one; with
``gqa_layers`` (a list of layer numbers) the layers named run that attention
and every other layer the delta rule (``linear_attn_config.*``); with
``layer_types`` (a name a layer) ``full_attention`` is that attention and
``linear_attention`` Gated DeltaNet (``linear_*``). The
parameters of a kind are stacked on a leading axis: the feed-forward parts by
theirs (``dense``, ``moe``), and the mixers with them where the table holds
one kind (the tree of a model of one kind is what it was before the table),
by their own kind (``gqa``, ``kda``, ``gdn``) where it holds several
(:meth:`MoELMTrainer._layer_table`). ``first_k_dense_replace`` equal to
``num_hidden_layers`` is a dense stack: no router, selection bias or
``moe_*`` counter at all. ``scoring_func`` names the router's
scores; ``block_length`` the block-diffusion objective and its attention mask.

**Held heads.** ``num_attention_heads``, ``num_key_value_heads``,
``linear_attn_config.num_heads`` and ``linear_num_*_heads`` are what THIS
CHIP holds, as ``experts_held`` is: where a deployment shares a mixer's heads
out, ``W_o`` maps the held ``heads x width`` channels to ``hidden_size`` and
its output is a partial sum, which goes on as it is (no exchange is run); the
norms, the low-rank gates' first halves, the router and the shared experts
are whole. A q/k norm over the whole projection (``qk_norm: width``)
takes its mean square over the held channels: the deployment would add the
chips' sums, one ``[L]`` exchange, which is not run either.

**The residual order.** ``norm_order: pre`` (the default): per layer,
residual ``x``, each half reads ``h = RMSNorm_w(x)`` and adds its output to
``x``. ``norm_order: post`` (OLMo 2's, arXiv:2501.00656; dense layers only):
each half reads ``h = x`` itself and adds ``RMSNorm_w(its output)``, the same
gain leaf in the other place (``attn_norm`` after the mixer, ``mlp_norm``
after the feed-forward part); the final norm stays before the head. Every
norm has a learned gain and ``rms_norm_eps``:

* **MLA.** ``q = W_q h`` (heads x (nope + rope)); ``[c ; k_rope] = W_kva h``
  (``kv_lora_rank`` + rope; ``k_rope`` is one per token, shared by the heads);
  ``[k_nope_i ; v_i] = W_kvb RMSNorm_w(c)``; rotary (``rope_theta``, pairs
  (j, j + rope/2), no scaling) on ``q_rope_i`` and ``k_rope``; ``k_i = [k_nope_i ;
  k_rope]``; causal softmax of ``q_i . k_i / sqrt(nope + rope)``
  (``ops/flash_attention.py``: keys and values differ in width); ``W_o [o_i]``.
* **Grouped queries.** ``q_i = RMSNorm_qnorm(W_q h)_i`` (heads x ``head_dim``),
  ``k_j = RMSNorm_knorm(W_k h)_j``, ``v_j = (W_v h)_j`` (``num_key_value_heads``
  x ``head_dim``); rotary over the whole head on ``q`` and ``k``; softmax of
  ``q_i . k_(i // group) / sqrt(head_dim)``; ``W_o [o_i]``. No bias anywhere.
  ``qk_norm: 0`` leaves the two head norms out, ``qk_norm: width``
  norms ``W_q h`` and ``W_k h`` over their whole held width instead (gains
  as wide), ``use_rope: 0`` leaves the rotary out (MLA's too), and
  ``use_gqa_gate: 1`` multiplies ``[o_i]`` elementwise by
  ``sigmoid(W_z h)`` (``W_z [d, heads x head_dim]``) before ``W_o``.
* **Learned sparse attention** (``sa_config``: DeepSeek-V3.2's DSA on the
  grouped-query layer, every layer). ``h' = stop_gradient(h)``; an indexer of
  ``HI`` heads of width ``DI`` over one key head: ``q^I_j = rot(W_qI h')_j``,
  ``k^I = rot(LayerNorm(W_kI h'))`` (gain and bias, 1e-6), ``w = W_w h' /
  sqrt(HI)``, rotary on the first half of an indexer head; ``I[t, s] = sum_j
  w_j ReLU(q^I_j(t) . k^I(s)) / sqrt(DI)`` for ``s <= t``; ``S_t`` the
  ``topk`` largest (ties to the lower index; every ``s <= t`` where ``t <
  topk``), one for all heads. The softmax above runs over ``s`` in ``S_t``
  alone (``ops/flash_attention.selected_attention``: the selection is a
  packed bitmask and two walks over the tiles that hold a pick, made at run
  time by ``ops/sparse_attention.select`` / ``walks``, by query chunks of a
  tile, ``sa_config.q_chunk_size``). ``L_I = mean_t KL(p_t || softmax_{S_t}
  I[t, .])``, ``p_t`` the attention's probabilities summed over the heads /
  heads, held constant, joins the loss with weight 1 a layer: it reaches the
  indexer's leaves alone, and the language-model loss none of them. Under
  ``remat`` the selection and the probability pass's rows are kept for the
  backward pass (``jax.checkpoint``'s names :data:`SAVED`), not made again.
  ``rope_scaling.mrope_section`` is M-RoPE's: text positions put one index in
  its three streams, so the rotation is the 1-D rotary above.
* **KDA** (``H`` heads of width ``K`` = ``linear_attn_config.head_dim``; in
  brackets what the published keys do not state and ``benchmark/configs/``
  lists as assumed). ``c_w(u)_t = silu(sum_j w_j u_(t - taps + 1 + j))`` a
  causal depthwise convolution of ``short_conv_kernel_size`` taps, zero
  history before each row [no bias]; ``q_t = l2norm(c_q(W_q h)_t)``, ``k_t =
  l2norm(c_k(W_k h)_t)`` a head [1e-6 under the root], ``v_t = c_v(W_v h)_t``;
  ``g_t = -exp(a_log_head) * softplus(F_up F_down h_t + dt_bias)`` a channel
  [the gates' rank = ``K``, no bias but ``dt_bias``],
  ``alpha_t = exp(g_t)``; ``beta_t = beta_max * sigmoid(W_b h_t)`` a head
  (``kda_allow_neg_eigval``: ``beta_max`` 2, else 1); per head ``S_0 = 0 [K,
  K]``: ``S'_t = diag(alpha_t) S_(t-1)``; ``S_t = S'_t + beta_t k_t (v_t -
  S'_t^T k_t)^T``; ``o_t = S_t^T q_t / sqrt(K)`` [the scale]; ``y_t =
  RMSNorm_onorm(o_t)`` (over the head's ``K``) ``* sigmoid(G_up G_down h_t)``;
  ``W_o y``. State and convolution run across the documents of a packed row.
  The recurrence is computed by chunks of 64 tokens
  (``ops/gated_delta.py``, where the chunked form stands). Fresh leaves
  [assumed]: ``a_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a
  rate log-uniform in (0.001, 0.1), the taps U(-1/2, 1/2) (:func:`init_leaf`).
* **Gated DeltaNet** (``H = linear_num_value_heads`` heads, keys and queries
  ``K = linear_key_head_dim`` wide, values ``V = linear_value_head_dim``;
  ``linear_conv_kernel_dim`` taps; FLA's and Qwen3-Next's shapes). As KDA,
  but for three things: the decay is one a head, ``g_t = -exp(a_log_head) *
  softplus(W_a h_t + dt_bias_head)`` (``W_a [d, H]``), handed to the chunked
  rule once a head, which runs its per-head kernels; the state is ``[K, V]`` and ``o_t =
  S_t^T q_t / sqrt(K)``; the gate is full-rank, ``y_t = RMSNorm_onorm(o_t)``
  (over the head's ``V``) ``* silu(W_g h_t)`` (``W_g [d, H x V]``).
  ``linear_allow_neg_eigval`` doubles beta. The same fresh leaves, ``dt_bias``
  one a head.
* **Mask and positions.** Causal, rotary at ``0..L-1``. Under block diffusion
  a row is its noised copy followed by its clean copy, 2L positions, each
  rotated by its place in its own copy, under
  ``ops/flash_attention.BlockDiffusion``'s mask.
* **Feed-forward**, dense layers and shared experts (one SwiGLU as wide as
  the shared experts together): ``W_down (silu(W_gate y) * (W_up y))``, ``y``
  the half's input.
* **Routed experts.** ``s = sigmoid(W_g y)`` (``scoring_func: softmax``:
  ``softmax(W_g y)``) over all ``router_experts``,
  float32; the ``num_experts_per_tok`` largest of ``s + b`` are chosen (``b``
  the selection bias: state, no parameter); gate ``g_e = routed_scaling_factor
  * s_e / sum of the chosen s``. The layer adds ``sum over chosen e that are
  held of g_e E_e(y)`` + shared(y): what the experts on other chips would add
  is left out (the sum that normalises still runs over all that were chosen).
  Dropless: the held assignments are sorted by expert
  (``ops/grouped_matmul.plan_rows``) into a row layout with room for every
  assignment of the step, and every one of them is computed, under any skew;
  ``moe_dropped`` counts the ones that were not, and reads 0. The layout's
  tiles that hold anything are the live ones (``moe_live_tile_share`` of
  them): the moves and ``grouped_swiglu``'s kernels visit those and no
  other, so between ``rows_of_tokens`` and ``tokens_of_rows`` the rows past
  the live tiles are never written and never read, forward or backward, and
  hold whatever the memory held. The tile's height follows the load: the
  trainer picks it when it reads its shapes, from the assignments a held
  expert expects a step, ``positions * num_experts_per_tok /
  router_experts`` (``ops/grouped_matmul.tile_for``: 128, 256 or 512 rows),
  and logs it; ``moe_tile_fill_share`` is the share of the live tiles' rows
  that hold an assignment, the rest being padding the kernels multiply and
  the moves carry.
* **Balance** (``noaux_tc``): after the gradient step ``b_e += bias_update_rate
  * sign(mean(c) - c_e)``, ``c_e`` the step's tokens assigned to expert ``e``
  of that layer, all ``router_experts``; plus the sequence-wise loss
  ``aux_loss_alpha * sum_e f_e P_e``, ``f_e = E / (k L) * count_e``, ``P_e = mean_t
  s_e / sum_j s_j``, per sequence. Either is off at a rate of 0 (the counts
  are kept all the same).

Precision: parameters, gradients, optimizer state, norms, softmax, router
and loss float32, and of a delta-rule layer the convolutions, the gates'
activations, the log-decays, their running sums and exponentials, the
triangular solve and the recurrent state; the operands of every other matrix
product are rounded to ``matmul_dtype`` (bfloat16), accumulated in float32, in
the backward pass too (:func:`mm`). The layers run one after another, unrolled
(under a ``lax.scan`` over the stack the compiled step needs 4 GB more at the
published widths, and no longer fits the chip), each rematerialised in the
backward pass (``remat: 1``).

Counters, in ``after_update``'s metrics and the state: ``moe_*`` (above;
where there are mixture layers); ``attn_whole_tile_share`` (metrics only, a
constant of the trace): of the tiles
an attention kernel visits, the share the mask allows whole
(``ops/flash_attention.tile_classes``); and,
where the table has delta-rule layers, ``kda_decay_mean`` (state:
``kda_decay``) and ``gdn_decay_mean`` (state: ``gdn_decay``): the mean
``alpha`` over the step's tokens, heads and channels of that kind's layers,
the layers averaged; a gate that stopped decaying reads 1, one that
wipes the state 0; under ``sa_config`` ``sparse_live_tile_share`` (state:
``sparse_live``; it takes ``attn_whole_tile_share``'s place, which a selection
has not): the causal tiles that hold a pick, and ``sparse_indexer_kl``
(state: ``sparse_kl``): ``L_I``, the layers averaged, and
``sparse_tie_row_share`` (metrics only): the queries whose ``topk``-th score
was tied and had some of its ties left out, so that the top-k searched their
columns too (``ops/sparse_attention.sparse_top_k``), the layers averaged.
``stack``'s counts
carry each layer's packed selection (``selected``), which the train step
leaves out of the state. The parts ``attn`` /
``index`` (the indexer, ``L_I``) and ``attn`` / ``select`` (scores, top-k,
words, walks) name the new work on the device timeline
(``utils/profiling.PARTS``).

Config keys (the published names where there is one): ``hidden_size``,
``num_hidden_layers``, ``first_k_dense_replace``, ``num_attention_heads``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``
(or ``num_key_value_heads``, ``head_dim``), ``rope_theta``, ``use_rope``,
``qk_norm``, ``use_gqa_gate``, ``gqa_layers``, ``layer_types``,
``norm_order``, ``linear_attn_config.num_heads``,
``linear_attn_config.head_dim``, ``linear_attn_config.short_conv_kernel_size``,
``kda_allow_neg_eigval``, ``linear_num_key_heads``, ``linear_num_value_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``, ``linear_conv_kernel_dim``,
``linear_allow_neg_eigval``, ``sa_config.topk``, ``sa_config.indexer_num_heads``,
``sa_config.indexer_head_dim``, ``sa_config.indexer_num_kv_heads``,
``sa_config.q_chunk_size``, ``sa_config.kv_chunk_size``,
``rope_scaling.mrope_section``, ``rms_norm_eps``,
``intermediate_size``,
``moe_intermediate_size``, ``n_shared_experts``, ``num_experts_per_tok``,
``routed_scaling_factor``, ``scoring_func``, ``vocab_size``; ``router_experts`` (the router's
width: the deployment's experts), ``experts_held``, ``expert_offset``;
``bias_update_rate``, ``aux_loss_alpha``, ``init_std``, ``loss_chunks``,
``matmul_dtype``, ``remat``; and ``models/seqlm.py``'s.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from swiftsnails_tpu.models.registry import register_model
from swiftsnails_tpu.models.seqlm import SeqLMTrainer, diffusion_inputs, token_loss
from swiftsnails_tpu.ops.flash_attention import BLOCK, flash_attention, selected_attention, tile_classes
from swiftsnails_tpu.ops.gated_delta import CHUNK, gated_delta_rule
from swiftsnails_tpu.ops.grouped_matmul import (
    TILE, grouped_swiglu, plan_rows, rows_of_tokens, tile_for, tokens_of_rows)
from swiftsnails_tpu.ops.sparse_attention import indexer_loss, probability_pass, select, walks
from swiftsnails_tpu.utils.config import Config
from swiftsnails_tpu.utils.profiling import part_scope, phase_scope


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mm(x, w, dtype):
    """``x [T, K] @ w [K, N]`` float32, the operands rounded to ``dtype``."""
    return jnp.dot(x.astype(dtype), w.astype(dtype), preferred_element_type=jnp.float32)


def _mm_fwd(x, w, dtype):
    x, w = x.astype(dtype), w.astype(dtype)
    return jnp.dot(x, w, preferred_element_type=jnp.float32), (x, w)


def _mm_bwd(dtype, res, g):
    x, w = res
    g = g.astype(dtype)
    dx = jax.lax.dot_general(g, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    dw = jax.lax.dot_general(x, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return dx, dw


mm.defvjp(_mm_fwd, _mm_bwd)


def rms_norm(x, gain, eps):
    scale = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale * gain


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centred = x - mean
    return centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * gain + bias


def rotary(x, theta: float, positions=None):
    """``x [L, ..., R]`` rotated by position (``positions [L]``; ``0..L-1`` if
    none are given): pairs (j, j + R/2), angle ``pos * theta ** (-2j / R)``."""
    seq, r = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    if positions is None:
        positions = jnp.arange(seq)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    angle = angle.reshape((seq,) + (1,) * (x.ndim - 2) + (r // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


L2_EPS = 1e-6  # under the root of a head's sum of squares (KDA's q and k)
INDEX_NORM_EPS = 1e-6  # the indexer keys' LayerNorm (DeepSeek-V3.2's)
# what a layer with a selection keeps for the backward pass (``jax.checkpoint``'s names)
SAVED = ("sparse_selection", "sparse_stats")
# what a layer's mixer counts, whatever its feed-forward part: ``stack`` stacks each by its name
MIXER_COUNTS = ("kda_decay", "gdn_decay", "index_loss", "sparse_live", "sparse_ties", "selected")
DELTA = ("kda", "gdn")  # the gated delta-rule mixers: a decay per channel, one per head
# ``layer_types``' names -> the kind of mixer (None: the attention the other keys give)
LAYER_TYPES = {"full_attention": None, "linear_attention": "gdn"}


class DeltaShape(NamedTuple):
    """A delta-rule mixer as this chip holds it."""

    heads: int
    key: int  # q and k a head
    value: int  # v, the output and the gated norm a head
    taps: int  # of each causal convolution
    beta_max: float  # 2 where negative eigenvalues are allowed, else 1


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def causal_conv(u, w):
    """``u [B, L, channels]``, ``w [taps, channels]`` -> ``sum_j w_j *
    u_(t - taps + 1 + j)``, a depthwise convolution with zero history before
    each row, no bias."""
    taps, seq = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[j] * padded[:, j: j + seq] for j in range(taps))


def _one_of(cfg: Config, key: str, allowed):
    """``cfg[key]``, the first of ``allowed`` if it is not set."""
    value = cfg.get_str(key, allowed[0])
    if value not in allowed:
        raise ValueError(f"{key} must be one of {allowed}, got {value}")
    return value


def init_leaf(key, name: str, shape, std: float):
    """A fresh leaf by its name: a norm's gain 1; a delta rule's ``a_log`` the log of
    U(1, 16), its ``dt_bias`` the inverse softplus of a rate log-uniform in
    (0.001, 0.1), a convolution's taps U(-1/2, 1/2) (fan-in 4); every other
    leaf N(0, std^2)."""
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "index_k_bias":
        return jnp.zeros(shape, jnp.float32)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        rate = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return rate + jnp.log(-jnp.expm1(-rate))
    if name.startswith("conv_"):
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    return jax.random.normal(key, shape, jnp.float32) * std


# ---------------------------------------------------------- the trainer ---


@register_model("moelm")
class MoELMTrainer(SeqLMTrainer):
    name = "moelm"
    # The kernels' own; a test sets smaller ones. ``expert_tile`` is picked per trainer, by
    # ``_read_shape``, from the assignments a held expert expects a step (``grouped_matmul.tile_for``).
    attention_block, expert_tile, kda_chunk = BLOCK, TILE, CHUNK

    def _read_shape(self, cfg: Config) -> None:
        g = cfg.get_int
        self.d_model = g("hidden_size")
        self.n_layers = g("num_hidden_layers")
        self.n_dense = g("first_k_dense_replace", 1)
        self.n_heads = g("num_attention_heads")
        self.kv_rank = g("kv_lora_rank", 0)  # latent attention, if stated
        if self.kv_rank:
            self.nope, self.rope, self.v_dim = g("qk_nope_head_dim"), g("qk_rope_head_dim"), g("v_head_dim")
        else:
            self.kv_heads, self.v_dim = g("num_key_value_heads"), g("head_dim")
            if self.n_heads % self.kv_heads:
                raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        self.rope_theta = cfg.get_float("rope_theta", 10000.0)
        self.use_rope = cfg.get_bool("use_rope", True)
        self.qk_norm_width = cfg.get_str("qk_norm", "1") == "width"  # one norm over the held projection
        self.qk_norm = self.qk_norm_width or cfg.get_bool("qk_norm", True)  # else one a head, or none
        self.post_norm = _one_of(cfg, "norm_order", ("pre", "post")) == "post"
        self.attn_gate = cfg.get_bool("use_gqa_gate", False)
        self._read_table(cfg)
        self.topk = g("sa_config.topk", 0)  # learned sparse attention, if stated
        if self.topk:
            self._read_sparse(cfg)
        self.eps = cfg.get_float("rms_norm_eps", 1e-6)
        self.dense_width = g("intermediate_size", 0)
        self.init_std = cfg.get_float("init_std", 0.02)
        self.loss_chunks = g("loss_chunks", 1)
        self.matmul_dtype = jnp.dtype(cfg.get_str("matmul_dtype", "bfloat16"))
        self.remat = cfg.get_bool("remat", True)
        self.vocab_size = g("vocab_size", self.vocab_size)
        if self.mesh is not None:
            raise ValueError("moelm holds one chip's share: no mesh (local_train: 1)")
        if not 0 <= self.n_dense <= self.n_layers:
            raise ValueError("first_k_dense_replace must lie in 0..num_hidden_layers")
        self.n_moe = self.n_layers - self.n_dense
        if self.n_moe:
            if self.post_norm:
                raise ValueError("norm_order post runs dense layers only")
            self._read_mixture(cfg)

    def _read_table(self, cfg: Config) -> None:
        """The layer table (``mixers``) and each delta-rule kind's shape
        (``delta``)."""
        g = cfg.get_int
        kind = "mla" if self.kv_rank else "gqa"
        self.mixers = (kind,) * self.n_layers  # the layer table: layer -> kind of mixer
        if "gqa_layers" in cfg and "layer_types" in cfg:
            raise ValueError("gqa_layers or layer_types, not both")
        if "gqa_layers" in cfg:  # the layers named run that attention, the others the delta rule
            named = {int(i) for i in cfg.get_str("gqa_layers").strip("[]() ").split(",") if i.strip()}
            self.mixers = tuple(kind if i in named else "kda" for i in range(self.n_layers))
        if "layer_types" in cfg:  # a name a layer
            types = [t.strip(" '\"") for t in cfg.get_str("layer_types").strip("[]() ").split(",") if t.strip()]
            if len(types) != self.n_layers or not set(types) <= set(LAYER_TYPES):
                raise ValueError(f"layer_types must name each of {self.n_layers} layers one of {sorted(LAYER_TYPES)}")
            self.mixers = tuple(LAYER_TYPES[t] or kind for t in types)
        self.delta = {}
        if "kda" in self.mixers:
            width = g("linear_attn_config.head_dim")
            self.delta["kda"] = DeltaShape(
                g("linear_attn_config.num_heads"), width, width, g("linear_attn_config.short_conv_kernel_size"),
                2.0 if cfg.get_bool("kda_allow_neg_eigval", False) else 1.0)
        if "gdn" in self.mixers:
            heads = g("linear_num_value_heads")
            if g("linear_num_key_heads", heads) != heads:
                raise ValueError("Gated DeltaNet here has as many key heads as value heads")
            self.delta["gdn"] = DeltaShape(
                heads, g("linear_key_head_dim"), g("linear_value_head_dim"), g("linear_conv_kernel_dim"),
                2.0 if cfg.get_bool("linear_allow_neg_eigval", False) else 1.0)

    def _read_mixture(self, cfg: Config) -> None:
        """The mixture layers' router, experts and balance."""
        g = cfg.get_int
        self.expert_width = g("moe_intermediate_size")
        self.n_shared = g("n_shared_experts", 0)
        self.top_k = g("num_experts_per_tok")
        self.route_scale = cfg.get_float("routed_scaling_factor", 1.0)
        self.scoring = cfg.get_str("scoring_func", "sigmoid")
        self.router_experts = g("router_experts")
        self.experts_held = g("experts_held", self.router_experts)
        self.expert_offset = g("expert_offset", 0)
        self.bias_rate = cfg.get_float("bias_update_rate", 0.001)
        self.aux_alpha = cfg.get_float("aux_loss_alpha", 0.0001)
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func must be sigmoid or softmax, got {self.scoring}")
        if self.expert_offset + self.experts_held > self.router_experts:
            raise ValueError("the experts held lie outside the router's")
        expected = self._positions() * self.top_k / self.router_experts
        self.expert_tile = tile_for(expected)
        logging.getLogger(__name__).info(
            "experts: row tile %d for %.1f assignments expected a held expert a step", self.expert_tile, expected)

    def _read_sparse(self, cfg: Config) -> None:
        """``sa_config``: the indexer's heads and their width over one key
        head, the keys a query selects, and the chunk of queries (= the tile)
        the scores and the selection go by; and M-RoPE's sections, which
        text positions (the same index in all three streams) turn into one
        rotary over the head."""
        g = cfg.get_int
        self.index_heads, self.index_dim = g("sa_config.indexer_num_heads"), g("sa_config.indexer_head_dim")
        if g("sa_config.indexer_num_kv_heads", 1) != 1:
            raise ValueError("the indexer has one key head")
        chunk = g("sa_config.q_chunk_size")
        if g("sa_config.kv_chunk_size", chunk) != chunk:
            raise ValueError("sa_config's query and key chunks are one tile")
        if self.kv_rank or "gqa_layers" in cfg or "layer_types" in cfg or self.block_length:
            raise ValueError("sa_config selects keys for causal grouped-query attention in every layer")
        self.attention_block = chunk
        if "rope_scaling.mrope_section" in cfg:
            sections = [int(i) for i in cfg.get_str("rope_scaling.mrope_section").strip("[]() ").split(",")]
            if 2 * sum(sections) != self.v_dim:
                raise ValueError(f"mrope_section {sections} does not cover half of a head of {self.v_dim}")

    def _positions(self) -> int:
        """The positions a step routes: both copies under block diffusion."""
        return self.batch_size * self.seq_len * (2 if self.block_length else 1)

    # -- parameters ----------------------------------------------------------

    def _layer_table(self):
        """Per layer, where its leaves are: ((group, place in the group) of
        its mixer's, the same of its feed-forward's). The feed-forward parts
        are stacked by their kind (``dense``, ``moe``); the mixers by theirs
        where the table holds several kinds, and with the layer's
        feed-forward part where it holds one."""
        ffn = ("dense",) * self.n_dense + ("moe",) * self.n_moe
        groups = (self.mixers if len(set(self.mixers)) > 1 else ffn, ffn)
        return [tuple((g[i], g[:i].count(g[i])) for g in groups) for i in range(self.n_layers)]

    def _mixer_shapes(self, kind: str) -> Dict[str, Any]:
        d, h = self.d_model, self.n_heads
        if kind in DELTA:
            s = self.delta[kind]
            h, keys, values = s.heads, s.heads * s.key, s.heads * s.value
            leaves = {"attn_norm": (d,), "wq": (d, keys), "wk": (d, keys), "wv": (d, values),
                      "conv_q": (s.taps, keys), "conv_k": (s.taps, keys), "conv_v": (s.taps, values),
                      "a_log": (h,), "wb": (d, h), "o_norm": (s.value,), "wo": (values, d)}
            if kind == "kda":  # the rate a channel and the gate from low-rank products of rank K
                r = s.key
                return {**leaves, "f_down": (d, r), "f_up": (r, keys), "dt_bias": (keys,),
                        "g_down": (d, r), "g_up": (r, values)}
            return {**leaves, "wa": (d, h), "dt_bias": (h,), "wg": (d, values)}
        if kind == "mla":
            attn = {"wq": (d, h * (self.nope + self.rope)),
                    "wkv_a": (d, self.kv_rank + self.rope), "kv_norm": (self.kv_rank,),
                    "wkv_b": (self.kv_rank, h * (self.nope + self.v_dim))}
        else:
            hd, kv = self.v_dim, self.kv_heads
            attn = {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd)}
            if self.qk_norm:  # a head's gain, or one over the whole held projection
                attn.update({"q_norm": (h * hd if self.qk_norm_width else hd,),
                             "k_norm": (kv * hd if self.qk_norm_width else hd,)})
            if self.attn_gate:
                attn["wz"] = (d, h * hd)
            if self.topk:
                di = self.index_dim
                attn.update({"index_wq": (d, self.index_heads * di), "index_wk": (d, di),
                             "index_k_norm": (di,), "index_k_bias": (di,), "index_ww": (d, self.index_heads)})
        return {"attn_norm": (d,), **attn, "wo": (h * self.v_dim, d)}

    def param_shapes(self) -> Dict[str, Any]:
        """The parameter tree's shapes; layers of a kind are stacked on a
        leading axis (:meth:`_layer_table`). Fresh leaves: :func:`init_leaf`."""
        d = self.d_model

        def swiglu(prefix, width):
            if not width:
                return {}
            return {f"{prefix}_gate": (d, width), f"{prefix}_up": (d, width),
                    f"{prefix}_down": (width, d)}

        ffn = {"dense": {"mlp_norm": (d,), **swiglu("mlp", self.dense_width)}}
        if self.n_moe:
            e, w = self.experts_held, self.expert_width
            ffn["moe"] = {"mlp_norm": (d,), "router": (d, self.router_experts),
                          **swiglu("shared", self.n_shared * w),
                          "experts_gate": (e, d, w), "experts_up": (e, d, w), "experts_down": (e, w, d)}
        groups = {}
        for kind, (mixer, forward) in zip(self.mixers, self._layer_table()):
            for (group, place), leaves in ((mixer, self._mixer_shapes(kind)), (forward, ffn[forward[0]])):
                n, tree = groups.get(group, (0, {}))
                groups[group] = (max(n, place + 1), {**tree, **leaves})
        return {"embed": (self.vocab_size, d), "head": (d, self.vocab_size), "final_norm": (d,),
                **{group: {k: (n,) + s for k, s in tree.items()} for group, (n, tree) in groups.items()}}

    def init_state(self) -> Dict[str, Any]:
        leaves, tree = jax.tree_util.tree_flatten_with_path(
            self.param_shapes(), is_leaf=lambda s: isinstance(s, tuple))
        keys = jax.random.split(jax.random.PRNGKey(self.seed), len(leaves))
        params = tree.unflatten([init_leaf(k, path[-1].key, s, self.init_std)
                                 for (path, s), k in zip(leaves, keys)])
        return self.state_of(params)

    def state_of(self, params) -> Dict[str, Any]:
        """A fresh state around ``params``: optimizer slots, where there are
        mixture layers the selection bias at zero, and the step's counters
        (under block diffusion the positions routed are the two copies', and
        ``noised`` counts the step's masked tokens)."""
        n_moe = self.n_moe
        state = {"params": params, "opt": self.opt.init(params)}
        if n_moe:
            state.update({"router_bias": jnp.zeros((n_moe, self.router_experts), jnp.float32),
                          "counts": jnp.zeros((n_moe, self.router_experts), jnp.int32),
                          "choices": jnp.zeros((n_moe, self._positions(), self.top_k), jnp.int32),
                          "dropped": jnp.zeros((), jnp.int32)})
        if self.block_length:
            state["noised"] = jnp.zeros((), jnp.int32)
        for kind in self.delta:
            state[kind + "_decay"] = jnp.zeros((), jnp.float32)
        if self.topk:
            state.update({"sparse_kl": jnp.zeros((), jnp.float32), "sparse_live": jnp.zeros((), jnp.float32)})
        return state

    # -- layers --------------------------------------------------------------

    def _mm(self, x, w):
        return mm(x, w, self.matmul_dtype)

    def _pre(self, x, gain):
        """A half's input: ``x`` normed (``norm_order: pre``), or ``x`` itself."""
        return x if self.post_norm else rms_norm(x, gain, self.eps)

    def _post(self, out, gain):
        """What a half adds to the residual stream: its output, or, under
        ``norm_order: post``, its output normed."""
        return rms_norm(out, gain, self.eps) if self.post_norm else out

    def _latent_qkv(self, p, y, shape, spin):
        """MLA: (q, k ``[B, L, heads, nope + rope]``, v ``[B, L, heads, v]``)."""
        h, nope, rope = self.n_heads, self.nope, self.rope
        q = self._mm(y, p["wq"]).reshape(*shape, h, nope + rope)
        kva = self._mm(y, p["wkv_a"])
        c = rms_norm(kva[:, : self.kv_rank], p["kv_norm"], self.eps)
        kvb = self._mm(c, p["wkv_b"]).reshape(*shape, h, nope + self.v_dim)
        k_rope = kva[:, self.kv_rank:].reshape(*shape, 1, rope)
        q = jnp.concatenate([q[..., :nope], spin(q[..., nope:])], axis=-1)
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(spin(k_rope), (*shape, h, rope))], axis=-1)
        return q, k, kvb[..., nope:]

    def _grouped_qkv(self, p, y, shape, spin):
        """Grouped queries: (q ``[B, L, heads, head_dim]``, k, v ``[B, L,
        key/value heads, head_dim]``), the query and the key normed
        (``qk_norm: 1``) a head, or over the held width (``qk_norm: width``)."""
        def project(w, n, gain=None):
            t = self._mm(y, p[w])
            normed = gain is not None and self.qk_norm
            if normed and self.qk_norm_width:
                t = rms_norm(t, p[gain], self.eps)
            t = t.reshape(*shape, n, self.v_dim)
            return rms_norm(t, p[gain], self.eps) if normed and not self.qk_norm_width else t

        q, k = project("wq", self.n_heads, "q_norm"), project("wk", self.kv_heads, "k_norm")
        return spin(q), spin(k), project("wv", self.kv_heads)

    def _attention(self, p, x, b, positions=None):
        """``x [B * L, d]`` -> (the block's output, same shape; what a
        selection counted); ``positions [L]`` are rotary's (``0..L-1`` if
        none)."""
        seq = x.shape[0] // b
        with part_scope("attn", "in"):
            y = self._pre(x, p["attn_norm"])
            spin = jax.vmap(lambda t: rotary(t, self.rope_theta, positions)) if self.use_rope else (lambda t: t)
            qkv = self._latent_qkv if self.kv_rank else self._grouped_qkv
            fold = lambda t: t.transpose(0, 2, 1, 3).reshape(-1, seq, t.shape[-1])  # noqa: E731
            q, k, v = (fold(t) for t in qkv(p, y, (b, seq), spin))
        if self.topk:
            o, counted = self._selected(p, y, q, k, v, b, seq)
        else:
            with part_scope("attn", "core"):
                o = flash_attention(q, k, v, block=self.attention_block,
                                    dtype=self.matmul_dtype, diffusion_block=self.block_length or None)
            counted = {}
        with part_scope("attn", "out"):
            o = o.reshape(b, self.n_heads, seq, self.v_dim).transpose(0, 2, 1, 3).reshape(b * seq, -1)
            if self.attn_gate:
                o = o * jax.nn.sigmoid(self._mm(y, p["wz"]))
            return self._mm(o, p["wo"]), counted

    def _selected(self, p, y, q, k, v, b, seq):
        """The grouped-query attention over each query's selection: the
        indexer on ``stop_gradient(h)``, the top ``topk`` causal keys of each
        query, the attention kernels under that selection, and ``L_I``.
        -> (``[B * H, L, head_dim]``, {``index_loss``: ``L_I``,
        ``sparse_live``: the live tiles' share of the causal ones,
        ``sparse_ties``: the share of queries whose ties were searched,
        ``selected``: the words ``[B, L / 32, L]``})."""
        hi, di, block, dtype = self.index_heads, self.index_dim, self.attention_block, self.matmul_dtype
        hold = jax.lax.stop_gradient
        with part_scope("attn", "index"):
            h = hold(y)  # the indexer learns from L_I alone, and L_I reaches nothing else
            rope = di // 2  # rotary on the first half of an indexer head
            spin = jax.vmap(lambda t: jnp.concatenate(
                [rotary(t[..., :rope], self.rope_theta), t[..., rope:]], axis=-1))
            qi = spin(self._mm(h, p["index_wq"]).reshape(b, seq, hi, di)).transpose(0, 2, 1, 3)
            ki = layer_norm(self._mm(h, p["index_wk"]), p["index_k_norm"], p["index_k_bias"], INDEX_NORM_EPS)
            ki = spin(ki.reshape(b, seq, 1, di))[:, :, 0]
            w = self._mm(h, p["index_ww"]).reshape(b, seq, hi) * hi ** -0.5
        n = seq // block
        causal = n * (n + 1) // 2
        outs, losses, lives, ties, words = [], [], [], [], []
        for r in range(b):
            qr, kr, vr = (t[r * t.shape[0] // b:(r + 1) * t.shape[0] // b] for t in (q, k, v))
            with part_scope("attn", "select"):
                bits, live, tied = select(hold(qi[r]), hold(ki[r]), hold(w[r]), self.topk, block, dtype)
                # kept for the backward pass, not selected again there
                sel = jax.tree_util.tree_map(lambda a: checkpoint_name(a, SAVED[0]),
                                             walks(bits, live, self.n_heads // self.kv_heads))
                lives.append(jnp.sum(live) / causal)
                ties.append(tied)
                words.append(sel.bits)
            with part_scope("attn", "core"):
                o, lse = selected_attention(qr, kr, vr, sel, block=block, dtype=dtype)
                qr, kr, lse = hold(qr), hold(kr), hold(lse)
                stats = checkpoint_name(probability_pass(
                    qr, kr, lse, hold(qi[r]), hold(ki[r]), hold(w[r]), sel, block, dtype), SAVED[1])
            with part_scope("attn", "index"):
                losses.append(indexer_loss(qi[r], ki[r], w[r], qr, kr, lse, sel, stats, block, dtype, None))
            outs.append(o)
        return jnp.concatenate(outs), {"index_loss": sum(losses) / b, "sparse_live": sum(lives) / b,
                                       "sparse_ties": sum(ties) / b, "selected": jnp.stack(words)}

    def _kda(self, p, x, b, kind="kda"):
        """``x [B * L, d]`` -> (a delta-rule mixer's output, same shape; the
        mean decay ``alpha`` over its tokens, heads and channels), ``kind``
        one of :data:`DELTA`. State and convolution start at zero with each
        row and run across its documents."""
        s = self.delta[kind]
        seq, h = x.shape[0] // b, s.heads
        with part_scope(kind, "in"):
            y = self._pre(x, p["attn_norm"])
            heads = lambda t: t.reshape(b, seq, h, -1)  # noqa: E731
            conv = lambda c: heads(jax.nn.silu(causal_conv(  # noqa: E731
                self._mm(y, p["w" + c]).reshape(b, seq, -1), p["conv_" + c])))
            q, k, v = l2_norm(conv("q")), l2_norm(conv("k")), conv("v")
            if kind == "kda":  # a rate a channel
                rate = jax.nn.softplus(self._mm(self._mm(y, p["f_down"]), p["f_up"]) + p["dt_bias"])
                g = -jnp.exp(p["a_log"])[:, None] * heads(rate)
            else:  # a rate a head, ``[B, L, h, 1]``: the rule's per-head kernels
                rate = heads(jax.nn.softplus(self._mm(y, p["wa"]) + p["dt_bias"]))
                g = -jnp.exp(p["a_log"])[:, None] * rate
            beta = s.beta_max * jax.nn.sigmoid(self._mm(y, p["wb"]))
            fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, seq, -1)  # noqa: E731
            folded = fold(q), fold(k), fold(v), fold(g), fold(heads(beta))[..., 0]
        o = gated_delta_rule(*folded, chunk=self.kda_chunk, dtype=self.matmul_dtype, scope=(kind, "core"))
        with part_scope(kind, "out"):
            o = o.reshape(b, h, seq, s.value).transpose(0, 2, 1, 3).reshape(b * seq, h, s.value)
            if kind == "kda":
                gate = jax.nn.sigmoid(self._mm(self._mm(y, p["g_down"]), p["g_up"]))
            else:
                gate = jax.nn.silu(self._mm(y, p["wg"]))
            o = rms_norm(o, p["o_norm"], self.eps).reshape(b * seq, -1) * gate
            return self._mm(o, p["wo"]), jnp.mean(jnp.exp(g))

    def _mix(self, kind, p, x, b, positions=None):
        """A layer's first half: ``x`` + its mixer's output (normed under
        ``norm_order: post``), and what the mixer counted."""
        if kind in DELTA:
            with phase_scope(kind):
                out, decay = self._kda(p, x, b, kind)
                with part_scope(kind, "out"):  # the residual sum goes where ``W_o`` is
                    return x + self._post(out, p["attn_norm"]), {kind + "_decay": decay}
        with phase_scope("attn"):
            out, counted = self._attention(p, x, b, positions)
            with part_scope("attn", "out"):
                return x + self._post(out, p["attn_norm"]), counted

    def _swiglu(self, p, prefix, y):
        hidden = jax.nn.silu(self._mm(y, p[prefix + "_gate"])) * self._mm(y, p[prefix + "_up"])
        return self._mm(hidden, p[prefix + "_down"])

    def route(self, y, router, bias):
        """(choices [T, k] int32, gates [T, k], scores [T, E]): float32 at the
        highest precision, so that the choice turns on ``y`` alone."""
        score = jax.nn.sigmoid if self.scoring == "sigmoid" else jax.nn.softmax
        s = score(jnp.dot(y, router, precision=jax.lax.Precision.HIGHEST))
        _, choices = jax.lax.top_k(s + bias[None, :], self.top_k)
        chosen = jnp.take_along_axis(s, choices, axis=-1)
        gates = self.route_scale * chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20)
        return choices.astype(jnp.int32), gates, s

    def _balance(self, s, choices, b):
        """(the sequence-wise loss ``alpha * mean over sequences of sum_e f_e
        P_e``, the step's assignments per expert [E] int32)."""
        e = self.router_experts
        seq = s.shape[0] // b
        hit = jax.nn.one_hot(choices.reshape(b, seq * self.top_k), e, dtype=jnp.int32).sum(axis=1)
        if not self.aux_alpha:
            return jnp.zeros((), jnp.float32), hit.sum(axis=0)
        f = hit.astype(jnp.float32) * (e / (self.top_k * seq))
        share = (s / s.sum(axis=-1, keepdims=True)).reshape(b, seq, e).mean(axis=1)
        return self.aux_alpha * jnp.mean(jnp.sum(f * share, axis=-1)), hit.sum(axis=0)

    def _experts(self, p, y, choices, gates):
        """(the held experts' part of the layer's output, what the plan
        counted: ``dropped``, the assignments left out, 0 by construction;
        ``live_tile_share``, of the row layout's tiles the live ones;
        ``tile_fill_share``, of the live tiles' rows those that hold an
        assignment). Between the two moves every array of the row layout is a
        kernel's, written and read for the live tiles only."""
        held, tile = self.experts_held, self.expert_tile
        local = choices - self.expert_offset
        owner = jnp.where((local >= 0) & (local < held), local, held)
        with part_scope("route", "plan"):
            plan = plan_rows(owner, held, tile)
            counted = {
                "dropped": jnp.sum(owner < held, dtype=jnp.int32) - jnp.sum(plan.source < owner.size, dtype=jnp.int32),
                "live_tile_share": plan.live_tiles / plan.tile_owner.shape[0],
                "tile_fill_share": jnp.sum(plan.counts) / (plan.live_tiles * tile)}
        with part_scope("experts", "gather"):
            rows = rows_of_tokens(y, plan, tile)
        with part_scope("experts", "products"):
            out = grouped_swiglu(rows, p["experts_gate"], p["experts_up"], p["experts_down"], plan, tile,
                                 self.matmul_dtype)
        with part_scope("experts", "scatter"):
            return tokens_of_rows(out, gates, plan, tile), counted

    def _dense_layer(self, x, p, b, positions=None, kind=None):
        x, counted = self._mix(kind or self.mixers[0], p, x, b, positions)
        with phase_scope("mlp"):
            return x + self._post(self._swiglu(p, "mlp", self._pre(x, p["mlp_norm"])), p["mlp_norm"]), counted

    def _moe_layer(self, x, p, bias, b, positions=None, kind=None):
        x, counted = self._mix(kind or self.mixers[0], p, x, b, positions)
        with phase_scope("route"), part_scope("route", "score"):
            y = rms_norm(x, p["mlp_norm"], self.eps)
            choices, gates, s = self.route(y, p["router"], bias)
            aux, counts = self._balance(s, choices, b)
        with phase_scope("experts"):
            routed, planned = self._experts(p, y, choices, gates)
        with phase_scope("mlp"):
            shared = self._swiglu(p, "shared", y) if self.n_shared else 0.0
        return x + routed + shared, {**counted, **planned, "aux": aux, "counts": counts, "choices": choices}

    def stack(self, params, tokens, router_bias, positions=None):
        """(the stack's output after the last norm [B * L, d], what the
        mixture layers counted, stacked by layer (nothing in a dense stack,
        whose ``router_bias`` is None); ``kda_decay``, ``gdn_decay``,
        where the table has such layers, are theirs alone)."""
        b = tokens.shape[0]
        wrap = jax.checkpoint if self.remat else (lambda f: f)
        if self.remat and self.topk:  # the selection and the probability pass are kept, not run again
            wrap = functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.save_only_these_names(*SAVED))
        with phase_scope("head"):
            x = params["embed"][tokens.reshape(-1)]
        kinds = set(self.mixers)
        dense = {k: wrap(functools.partial(self._dense_layer, b=b, positions=positions, kind=k)) for k in kinds}
        sparse = {k: wrap(functools.partial(self._moe_layer, b=b, positions=positions, kind=k)) for k in kinds}
        seen, every = [], {}  # what the mixture layers counted; what every layer's mixer did
        for i, (kind, homes) in enumerate(zip(self.mixers, self._layer_table())):
            p = {k: v[place] for group, place in dict.fromkeys(homes) for k, v in params[group].items()}
            if i < self.n_dense:
                x, counted = dense[kind](x, p)
            else:
                x, counted = sparse[kind](x, p, router_bias[i - self.n_dense])
            for name in MIXER_COUNTS:
                if name in counted:
                    every.setdefault(name, []).append(counted.pop(name))
            if i >= self.n_dense:
                seen.append(counted)
        seen = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *seen) if seen else {}
        seen.update({name: jnp.stack(a) for name, a in every.items()})
        with phase_scope("head"):
            return rms_norm(x, params["final_norm"], self.eps), seen

    def loss_fn(self, params, batch, state):
        if self.block_length:
            return self._diffusion_loss(params, batch, state)
        tokens = batch["tokens"]
        x, seen = self.stack(params, tokens[:, :-1], state.get("router_bias"))
        with phase_scope("head"):
            ce = token_loss(x, params["head"], tokens[:, 1:].reshape(-1),
                            self.loss_chunks, self._mm)
        loss = ce + jnp.sum(seen.pop("aux")) if "aux" in seen else ce
        if "index_loss" in seen:  # L_I joins as the balance loss does; each reaches leaves the other does not
            loss = loss + jnp.sum(seen["index_loss"])
        return loss, {**seen, "ce_loss": ce}

    def _diffusion_loss(self, params, batch, state):
        """``(1 / L) sum over the noised i of CE(logits_i, tokens_i) / p`` of
        i's block, the logits at the noised copy's place i (no shift); the
        clean copy's last hidden states feed no loss."""
        tokens = batch["tokens"]
        rows, seq = tokens.shape
        ids, positions, weights = diffusion_inputs(batch, self.mask_token_id, self.block_length)
        x, seen = self.stack(params, ids, state.get("router_bias"), positions)
        with phase_scope("head"):
            x = x.reshape(rows, 2 * seq, -1)[:, :seq].reshape(rows * seq, -1)
            ce = token_loss(x, params["head"], tokens.reshape(-1), self.loss_chunks, self._mm,
                            weights=weights.reshape(-1))
        with phase_scope("noise"):
            noised = jnp.sum(batch["noised"], dtype=jnp.int32)
        loss = ce + jnp.sum(seen.pop("aux")) if "aux" in seen else ce
        return loss, {**seen, "ce_loss": ce, "noised": noised}

    def after_update(self, state, aux):
        state, metrics = dict(state), {"ce_loss": aux["ce_loss"]}
        if self.n_moe:
            state, metrics = self._after_mixture(state, aux, metrics)
        # a constant of the trace: the mask, the row's positions and the tile (a selection has none)
        if set(self.mixers) & {"mla", "gqa"} and not self.topk:
            tiles = tile_classes(self.seq_len * (2 if self.block_length else 1), self.attention_block,
                                 self.block_length or None)
            metrics["attn_whole_tile_share"] = jnp.float32(tiles["whole"] / tiles["live"])
        if self.block_length:
            state["noised"] = aux["noised"]
            metrics["diffusion_masked_share"] = aux["noised"] / (self.batch_size * self.seq_len)
        for kind in self.delta:
            state[kind + "_decay"] = metrics[kind + "_decay_mean"] = jnp.mean(aux[kind + "_decay"])
        if self.topk:
            state["sparse_kl"] = metrics["sparse_indexer_kl"] = jnp.mean(aux["index_loss"])
            state["sparse_live"] = metrics["sparse_live_tile_share"] = jnp.mean(aux["sparse_live"])
            metrics["sparse_tie_row_share"] = jnp.mean(aux["sparse_ties"])
        return state, metrics

    def _after_mixture(self, state, aux, metrics):
        """The selection bias's step and the mixture layers' counters."""
        counts, bias = aux["counts"], state["router_bias"]
        if self.bias_rate:
            with phase_scope("opt"):
                mean = jnp.mean(counts.astype(jnp.float32), axis=-1, keepdims=True)
                bias = bias + self.bias_rate * jnp.sign(mean - counts)
        lo, hi = self.expert_offset, self.expert_offset + self.experts_held
        held = counts[:, lo:hi].astype(jnp.float32)
        state = {**state, "router_bias": bias, "counts": counts, "choices": aux["choices"],
                 "dropped": jnp.sum(aux["dropped"])}
        return state, {
            **metrics,
            "moe_held_share": jnp.sum(held) / jnp.sum(counts),
            "moe_load_max_over_mean": jnp.mean(
                jnp.max(held, axis=-1) / jnp.maximum(jnp.mean(held, axis=-1), 1.0)),
            "moe_dropped": state["dropped"],
            "moe_live_tile_share": jnp.mean(aux["live_tile_share"]),
            "moe_tile_fill_share": jnp.mean(aux["tile_fill_share"]),
        }
