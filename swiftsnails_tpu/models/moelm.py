"""A mixture-of-experts language model's block stack, as one chip of an
expert-parallel deployment holds it: latent attention (MLA, DeepSeek-V3's) or
grouped-query attention with a norm on every head's query and key
(Qwen3-MoE's), SwiGLU, leading dense layers (there may be none), then
mixture-of-experts layers of which this chip holds ``experts_held`` routed
experts from ``expert_offset`` on, and a slice of the vocabulary. Corpus,
windows, optimizer wiring, the one ``train_step``, the loss and its two
objectives are ``models/seqlm.py``'s; this file is the stack they drive. A
layer's kind follows the published keys that are there: ``kv_lora_rank``
marks latent attention, else ``num_key_value_heads`` and ``head_dim`` the
grouped-query one; ``scoring_func`` the router's scores; ``block_length`` the
block-diffusion objective and its attention mask.

Per layer, residual ``x``, ``h = RMSNorm_w(x)`` (every norm has a learned
gain and ``rms_norm_eps``):

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
* **Mask and positions.** Causal, rotary at ``0..L-1``. Under block diffusion
  a row is its noised copy followed by its clean copy, 2L positions, each
  rotated by its place in its own copy, under
  ``ops/flash_attention.BlockDiffusion``'s mask.
* **Feed-forward**, dense layers and shared experts (one SwiGLU as wide as
  the shared experts together): ``W_down (silu(W_gate y) * (W_up y))``.
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
  hold whatever the memory held.
* **Balance** (``noaux_tc``): after the gradient step ``b_e += bias_update_rate
  * sign(mean(c) - c_e)``, ``c_e`` the step's tokens assigned to expert ``e``
  of that layer, all ``router_experts``; plus the sequence-wise loss
  ``aux_loss_alpha * sum_e f_e P_e``, ``f_e = E / (k L) * count_e``, ``P_e = mean_t
  s_e / sum_j s_j``, per sequence. Either is off at a rate of 0 (the counts
  are kept all the same).

Precision: parameters, gradients, optimizer state, norms, softmax, router
and loss float32; the operands of every other matrix product are rounded to
``matmul_dtype`` (bfloat16), accumulated in float32, in the backward pass
too (:func:`mm`). The parameters of a kind of layer are stacked on a leading
axis; the layers run one after another, unrolled (under a ``lax.scan`` over
the stack the compiled step needs 4 GB more at the published widths, and no
longer fits the chip), each rematerialised in the backward pass (``remat: 1``).

Config keys (the published names where there is one): ``hidden_size``,
``num_hidden_layers``, ``first_k_dense_replace``, ``num_attention_heads``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``
(or ``num_key_value_heads``, ``head_dim``), ``rope_theta``, ``rms_norm_eps``, ``intermediate_size``,
``moe_intermediate_size``, ``n_shared_experts``, ``num_experts_per_tok``,
``routed_scaling_factor``, ``scoring_func``, ``vocab_size``; ``router_experts`` (the router's
width: the deployment's experts), ``experts_held``, ``expert_offset``;
``bias_update_rate``, ``aux_loss_alpha``, ``init_std``, ``loss_chunks``,
``matmul_dtype``, ``remat``; and ``models/seqlm.py``'s.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from swiftsnails_tpu.models.registry import register_model
from swiftsnails_tpu.models.seqlm import SeqLMTrainer, diffusion_inputs, token_loss
from swiftsnails_tpu.ops.flash_attention import BLOCK, flash_attention
from swiftsnails_tpu.ops.grouped_matmul import (
    TILE, grouped_swiglu, plan_rows, rows_of_tokens, tokens_of_rows)
from swiftsnails_tpu.utils.config import Config
from swiftsnails_tpu.utils.profiling import phase_scope


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


# ---------------------------------------------------------- the trainer ---


@register_model("moelm")
class MoELMTrainer(SeqLMTrainer):
    name = "moelm"
    attention_block, expert_tile = BLOCK, TILE  # the kernels' own; a test sets smaller ones

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
        self.eps = cfg.get_float("rms_norm_eps", 1e-6)
        self.dense_width = g("intermediate_size", 0)
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
        self.init_std = cfg.get_float("init_std", 0.02)
        self.loss_chunks = g("loss_chunks", 1)
        self.matmul_dtype = jnp.dtype(cfg.get_str("matmul_dtype", "bfloat16"))
        self.remat = cfg.get_bool("remat", True)
        self.vocab_size = g("vocab_size", self.vocab_size)
        if self.mesh is not None:
            raise ValueError("moelm holds one chip's share: no mesh (local_train: 1)")
        if not 0 <= self.n_dense < self.n_layers:
            raise ValueError("first_k_dense_replace must leave a mixture layer")
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func must be sigmoid or softmax, got {self.scoring}")
        if self.expert_offset + self.experts_held > self.router_experts:
            raise ValueError("the experts held lie outside the router's")

    # -- parameters ----------------------------------------------------------

    def param_shapes(self) -> Dict[str, Any]:
        """The parameter tree's shapes; layers of a kind are stacked on a
        leading axis. Norm gains start at 1, everything else N(0, init_std)."""
        d, h = self.d_model, self.n_heads
        if self.kv_rank:
            attn = {"wq": (d, h * (self.nope + self.rope)),
                    "wkv_a": (d, self.kv_rank + self.rope), "kv_norm": (self.kv_rank,),
                    "wkv_b": (self.kv_rank, h * (self.nope + self.v_dim))}
        else:
            hd, kv = self.v_dim, self.kv_heads
            attn = {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
                    "q_norm": (hd,), "k_norm": (hd,)}
        attn = {"attn_norm": (d,), **attn, "wo": (h * self.v_dim, d), "mlp_norm": (d,)}

        def swiglu(prefix, width):
            if not width:
                return {}
            return {f"{prefix}_gate": (d, width), f"{prefix}_up": (d, width),
                    f"{prefix}_down": (width, d)}

        e, w = self.experts_held, self.expert_width
        moe = {**attn, "router": (d, self.router_experts),
               **swiglu("shared", self.n_shared * w),
               "experts_gate": (e, d, w), "experts_up": (e, d, w), "experts_down": (e, w, d)}
        stack = lambda n, tree: {k: (n,) + s for k, s in tree.items()}  # noqa: E731
        tree = {"embed": (self.vocab_size, d), "head": (d, self.vocab_size), "final_norm": (d,),
                "moe": stack(self.n_layers - self.n_dense, moe)}
        if self.n_dense:
            tree["dense"] = stack(self.n_dense, {**attn, **swiglu("mlp", self.dense_width)})
        return tree

    def init_state(self) -> Dict[str, Any]:
        leaves, tree = jax.tree_util.tree_flatten_with_path(
            self.param_shapes(), is_leaf=lambda s: isinstance(s, tuple))
        keys = jax.random.split(jax.random.PRNGKey(self.seed), len(leaves))
        params = tree.unflatten([
            jnp.ones(s, jnp.float32) if path[-1].key.endswith("norm")
            else jax.random.normal(k, s, jnp.float32) * self.init_std
            for (path, s), k in zip(leaves, keys)])
        return self.state_of(params)

    def state_of(self, params) -> Dict[str, Any]:
        """A fresh state around ``params``: optimizer slots, the selection
        bias at zero, and the step's counters (under block diffusion the
        positions routed are the two copies', and ``noised`` counts the step's
        masked tokens)."""
        n_moe = self.n_layers - self.n_dense
        positions = self.batch_size * self.seq_len * (2 if self.block_length else 1)
        state = {"params": params, "opt": self.opt.init(params),
                 "router_bias": jnp.zeros((n_moe, self.router_experts), jnp.float32),
                 "counts": jnp.zeros((n_moe, self.router_experts), jnp.int32),
                 "choices": jnp.zeros((n_moe, positions, self.top_k), jnp.int32),
                 "dropped": jnp.zeros((), jnp.int32)}
        if self.block_length:
            state["noised"] = jnp.zeros((), jnp.int32)
        return state

    # -- layers --------------------------------------------------------------

    def _mm(self, x, w):
        return mm(x, w, self.matmul_dtype)

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
        key/value heads, head_dim]``), each head's query and key normed."""
        heads = lambda w, n: self._mm(y, w).reshape(*shape, n, self.v_dim)  # noqa: E731
        q = rms_norm(heads(p["wq"], self.n_heads), p["q_norm"], self.eps)
        k = rms_norm(heads(p["wk"], self.kv_heads), p["k_norm"], self.eps)
        return spin(q), spin(k), heads(p["wv"], self.kv_heads)

    def _attention(self, p, x, b, positions=None):
        """``x [B * L, d]`` -> the block's output, same shape; ``positions
        [L]`` are rotary's (``0..L-1`` if none)."""
        seq = x.shape[0] // b
        y = rms_norm(x, p["attn_norm"], self.eps)
        spin = jax.vmap(lambda t: rotary(t, self.rope_theta, positions))
        qkv = self._latent_qkv if self.kv_rank else self._grouped_qkv
        q, k, v = qkv(p, y, (b, seq), spin)
        fold = lambda t: t.transpose(0, 2, 1, 3).reshape(-1, seq, t.shape[-1])  # noqa: E731
        o = flash_attention(fold(q), fold(k), fold(v), block=self.attention_block,
                            dtype=self.matmul_dtype, diffusion_block=self.block_length or None)
        o = o.reshape(b, self.n_heads, seq, self.v_dim).transpose(0, 2, 1, 3)
        return self._mm(o.reshape(b * seq, -1), p["wo"])

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
        """(the held experts' part of the layer's output, assignments left
        out: 0 by construction, counted all the same, the share of the row
        layout's tiles that are live). Between the two moves every array of
        the row layout is a kernel's, written and read for the live tiles only."""
        held, tile = self.experts_held, self.expert_tile
        local = choices - self.expert_offset
        owner = jnp.where((local >= 0) & (local < held), local, held)
        with phase_scope("route"):
            plan = plan_rows(owner, held, tile)
            dropped = jnp.sum(owner < held, dtype=jnp.int32) - jnp.sum(
                plan.source < owner.size, dtype=jnp.int32)
            live_share = plan.live_tiles / plan.tile_owner.shape[0]
        out = grouped_swiglu(rows_of_tokens(y, plan, tile), p["experts_gate"], p["experts_up"],
                             p["experts_down"], plan, tile, self.matmul_dtype)
        return tokens_of_rows(out, gates, plan, tile), dropped, live_share

    def _dense_layer(self, x, p, b, positions=None):
        with phase_scope("attn"):
            x = x + self._attention(p, x, b, positions)
        with phase_scope("mlp"):
            return x + self._swiglu(p, "mlp", rms_norm(x, p["mlp_norm"], self.eps))

    def _moe_layer(self, x, p, bias, b, positions=None):
        with phase_scope("attn"):
            x = x + self._attention(p, x, b, positions)
        with phase_scope("route"):
            y = rms_norm(x, p["mlp_norm"], self.eps)
            choices, gates, s = self.route(y, p["router"], bias)
            aux, counts = self._balance(s, choices, b)
        with phase_scope("experts"):
            routed, dropped, live_share = self._experts(p, y, choices, gates)
        with phase_scope("mlp"):
            shared = self._swiglu(p, "shared", y) if self.n_shared else 0.0
        return x + routed + shared, {"aux": aux, "counts": counts, "choices": choices,
                                     "dropped": dropped, "live_tile_share": live_share}

    def stack(self, params, tokens, router_bias, positions=None):
        """(the stack's output after the last norm [B * L, d], what the
        mixture layers counted, stacked by layer)."""
        b = tokens.shape[0]
        wrap = jax.checkpoint if self.remat else (lambda f: f)
        with phase_scope("head"):
            x = params["embed"][tokens.reshape(-1)]
        layer_of = lambda tree, i: {k: v[i] for k, v in tree.items()}  # noqa: E731
        dense = wrap(lambda x, p: self._dense_layer(x, p, b, positions))
        sparse = wrap(lambda x, p, bias: self._moe_layer(x, p, bias, b, positions))
        for i in range(self.n_dense):
            x = dense(x, layer_of(params["dense"], i))
        seen = []
        for i in range(self.n_layers - self.n_dense):
            x, counted = sparse(x, layer_of(params["moe"], i), router_bias[i])
            seen.append(counted)
        seen = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *seen)
        with phase_scope("head"):
            return rms_norm(x, params["final_norm"], self.eps), seen

    def loss_fn(self, params, batch, state):
        if self.block_length:
            return self._diffusion_loss(params, batch, state)
        tokens = batch["tokens"]
        x, seen = self.stack(params, tokens[:, :-1], state["router_bias"])
        with phase_scope("head"):
            ce = token_loss(x, params["head"], tokens[:, 1:].reshape(-1),
                            self.loss_chunks, self._mm)
        return ce + jnp.sum(seen.pop("aux")), {**seen, "ce_loss": ce}

    def _diffusion_loss(self, params, batch, state):
        """``(1 / L) sum over the noised i of CE(logits_i, tokens_i) / p`` of
        i's block, the logits at the noised copy's place i (no shift); the
        clean copy's last hidden states feed no loss."""
        tokens = batch["tokens"]
        rows, seq = tokens.shape
        ids, positions, weights = diffusion_inputs(batch, self.mask_token_id, self.block_length)
        x, seen = self.stack(params, ids, state["router_bias"], positions)
        with phase_scope("head"):
            x = x.reshape(rows, 2 * seq, -1)[:, :seq].reshape(rows * seq, -1)
            ce = token_loss(x, params["head"], tokens.reshape(-1), self.loss_chunks, self._mm,
                            weights=weights.reshape(-1))
        with phase_scope("noise"):
            noised = jnp.sum(batch["noised"], dtype=jnp.int32)
        return ce + jnp.sum(seen.pop("aux")), {**seen, "ce_loss": ce, "noised": noised}

    def after_update(self, state, aux):
        counts, bias = aux["counts"], state["router_bias"]
        if self.bias_rate:
            with phase_scope("opt"):
                mean = jnp.mean(counts.astype(jnp.float32), axis=-1, keepdims=True)
                bias = bias + self.bias_rate * jnp.sign(mean - counts)
        lo, hi = self.expert_offset, self.expert_offset + self.experts_held
        held = counts[:, lo:hi].astype(jnp.float32)
        state = {**state, "router_bias": bias, "counts": counts, "choices": aux["choices"],
                 "dropped": jnp.sum(aux["dropped"])}
        metrics = {
            "ce_loss": aux["ce_loss"],
            "moe_held_share": jnp.sum(held) / jnp.sum(counts),
            "moe_load_max_over_mean": jnp.mean(
                jnp.max(held, axis=-1) / jnp.maximum(jnp.mean(held, axis=-1), 1.0)),
            "moe_dropped": state["dropped"],
            "moe_live_tile_share": jnp.mean(aux["live_tile_share"]),
        }
        if self.block_length:
            state["noised"] = aux["noised"]
            metrics["diffusion_masked_share"] = aux["noised"] / (self.batch_size * self.seq_len)
        return state, metrics
