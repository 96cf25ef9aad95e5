"""Small causal transformer LM — the long-context/sequence-parallel trainer.

The reference has no sequence models (survey §5: "no attention, no notion of
sequence length"); this family exists so the framework's long-context layer
(``parallel/sequence.py`` — ring attention over a ``seq`` mesh axis, Ulysses
all-to-all) is exercised by a real trainer rather than only unit tests, and
so the mesh design (``data``/``model``/``seq`` axes, ``parallel/mesh.py``)
is demonstrably extensible beyond bag-of-features models.

Architecture: pre-norm transformer blocks; attention is dense single-device,
ring attention when the mesh has a ``seq`` axis (sequence sharded over it),
with the embedding/vocab kept replicated (vocabularies here are the sparse
tables' job). bf16-friendly; losses/softmax statistics in f32.

Config keys: ``seq_len``, ``n_layers``, ``n_heads``, ``d_model``,
``attention`` (``ring`` | ``ulysses`` | ``dense``), ``optimizer``
(``sgd`` | ``momentum`` | ``adam`` | ``adamw``), plus the usual
``learning_rate``, ``batch_size``, ``num_iters``, ``data``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, Optional

import numpy as np

import jax
import jax.numpy as jnp
import optax

from swiftsnails_tpu.framework.trainer import Trainer
from swiftsnails_tpu.models.registry import register_model
from swiftsnails_tpu.parallel.mesh import SEQ_AXIS
from swiftsnails_tpu.parallel.sequence import (
    reference_attention,
    ring_attention,
    ulysses_attention,
)
from swiftsnails_tpu.utils.config import Config


def _norm(x):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
    return (x32 * scale).astype(x.dtype)


@register_model("seqlm")
class SeqLMTrainer(Trainer):
    name = "seqlm"

    def __init__(self, config: Config, mesh=None, corpus_ids=None, vocab_size=None,
                 tracer=None):
        super().__init__(config, mesh, tracer)
        cfg = config
        self.seq_len = cfg.get_int("seq_len", 256)
        self.n_layers = cfg.get_int("n_layers", 2)
        self.n_heads = cfg.get_int("n_heads", 4)
        self.d_model = cfg.get_int("d_model", 128)
        self.attention = cfg.get_str("attention", "ring" if self._has_seq_axis() else "dense")
        self.lr = cfg.get_float("learning_rate", 3e-3)
        self.batch_size = cfg.get_int("batch_size", 8)
        self.epochs = cfg.get_int("num_iters", 1)
        self.seed = cfg.get_int("seed", 0)
        # optimizer choice, same contract as the CTR families ("sgd" default
        # = the bare SGD this trainer always ran; state carries the optax
        # slots so adam/momentum checkpoint-resume exactly)
        opt_name = cfg.get_str("optimizer", "sgd")
        opts = {
            "sgd": lambda: optax.sgd(self.lr),
            "momentum": lambda: optax.sgd(self.lr, momentum=0.9),
            "adam": lambda: optax.adam(self.lr),
            "adamw": lambda: optax.adamw(self.lr),
        }
        if opt_name not in opts:
            raise ValueError(
                f"optimizer must be one of {sorted(opts)}, got {opt_name}")
        self.opt = opts[opt_name]()
        if corpus_ids is None:
            from swiftsnails_tpu.data.text import encode_corpus

            corpus_ids, vocab = encode_corpus(
                cfg.get_str("data"), min_count=cfg.get_int("min_count", 1),
                max_vocab=cfg.get_int("max_vocab", 0) or None,
            )
            vocab_size = len(vocab)
            # multi-host contiguous corpus span (stdin-split parity); the
            # global vocab keeps token ids consistent across hosts
            if cfg.get_bool("shard_data", True):
                from swiftsnails_tpu.parallel.cluster import shard_token_stream

                corpus_ids = shard_token_stream(corpus_ids)
        self.corpus_ids = np.asarray(corpus_ids, dtype=np.int32)
        self.vocab_size = int(vocab_size)
        if self.d_model % self.n_heads:
            raise ValueError("d_model must divide by n_heads")

    def _has_seq_axis(self) -> bool:
        return self.mesh is not None and SEQ_AXIS in self.mesh.shape

    # -- model -------------------------------------------------------------

    def init_state(self) -> Dict[str, Any]:
        rng = jax.random.PRNGKey(self.seed)
        d, h = self.d_model, self.n_heads
        keys = jax.random.split(rng, 2 + 5 * self.n_layers)
        scale = d ** -0.5
        params = {
            "embed": jax.random.normal(keys[0], (self.vocab_size, d)) * 0.02,
            "pos": jax.random.normal(keys[1], (self.seq_len, d)) * 0.02,
            "blocks": [],
        }
        for i in range(self.n_layers):
            k = keys[2 + 5 * i : 7 + 5 * i]
            params["blocks"].append({
                "wqkv": jax.random.normal(k[0], (d, 3 * d)) * scale,
                "wo": jax.random.normal(k[1], (d, d)) * scale,
                "w1": jax.random.normal(k[2], (d, 4 * d)) * scale,
                "w2": jax.random.normal(k[3], (4 * d, d)) * (4 * d) ** -0.5,
            })
        state = {"params": params, "opt": self.opt.init(params)}
        if self.mesh is not None:
            # params/slots are replicated (vocab scale is the sparse tables'
            # job); commit them to the WHOLE mesh so checkpoint restore —
            # which lands on the template's shardings — and the shard_map
            # attention agree on devices
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(self.mesh, PartitionSpec())
            state = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, rep), state)
        return state

    def _attend(self, q, k, v):
        if self.attention == "dense" or self.mesh is None:
            return reference_attention(q, k, v, causal=True)
        if self.attention == "ulysses":
            return ulysses_attention(self.mesh, q, k, v, causal=True)
        return ring_attention(self.mesh, q, k, v, causal=True)

    def forward(self, params, tokens):
        b, l = tokens.shape
        h = self.n_heads
        d = self.d_model
        x = params["embed"][tokens] + params["pos"][None, :l]
        for blk in params["blocks"]:
            qkv = _norm(x) @ blk["wqkv"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, l, h, d // h)
            k = k.reshape(b, l, h, d // h)
            v = v.reshape(b, l, h, d // h)
            attn = self._attend(q, k, v).reshape(b, l, d)
            x = x + attn @ blk["wo"]
            y = _norm(x)
            x = x + jax.nn.gelu(y @ blk["w1"]) @ blk["w2"]
        logits = _norm(x) @ params["embed"].T
        return logits

    def loss_fn(self, params, tokens):
        logits = self.forward(params, tokens[:, :-1])
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -ll.mean()

    # -- trainer contract --------------------------------------------------

    def batches(self) -> Iterator[Dict[str, np.ndarray]]:
        ids = self.corpus_ids
        # +1 so each window has seq_len inputs and shifted targets
        window = self.seq_len + 1
        n_windows = len(ids) // window
        rng = np.random.default_rng(self.seed)
        for _ in range(self.epochs):
            order = rng.permutation(n_windows)
            for start in range(0, n_windows - self.batch_size + 1, self.batch_size):
                idx = order[start : start + self.batch_size]
                toks = np.stack([ids[i * window : (i + 1) * window] for i in idx])
                yield {"tokens": toks.astype(np.int32)}

    def train_step(self, state, batch, rng):
        del rng
        loss, grads = jax.value_and_grad(self.loss_fn)(
            state["params"], batch["tokens"])
        updates, opt = self.opt.update(grads, state["opt"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        return {"params": params, "opt": opt}, {"loss": loss}

    def items_per_batch(self, batch) -> int:
        return int(batch["tokens"].shape[0] * (batch["tokens"].shape[1] - 1))
