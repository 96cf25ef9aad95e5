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
``learning_rate``, ``batch_size``, ``num_iters``, ``data`` (a text corpus,
or a ``.npy`` file of token ids with ``vocab_size`` stated); ``block_length``,
``mask_token_id`` for the block-diffusion objective.

What every sequence trainer shares lives here once: the corpus and its
windows (:meth:`SeqLMTrainer.batches`), the optimizer wiring, the one
``train_step`` (gradient step, then :meth:`SeqLMTrainer.after_update` for
what a step changes that is no gradient) and the loss (:func:`token_loss`),
under either objective: the next token's, or, with ``block_length`` set, the
masked tokens' of block diffusion (:func:`draw_noise` on the host,
:func:`diffusion_inputs` in the step). ``models/moelm.py`` puts another block
stack under them.
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
from swiftsnails_tpu.utils.profiling import phase_scope


def _norm(x):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
    return (x32 * scale).astype(x.dtype)


def token_loss(hidden, head, targets, chunks: int = 1, matmul=jnp.dot, weights=None):
    """Mean cross entropy of ``hidden [T, d] @ head [d, V]`` against
    ``targets [T]``, float32, each token's term times ``weights [T]`` if
    given (the sum still over ``T``). With ``chunks`` > 1 the tokens go by in
    that many chunks, each chunk's logits recomputed in the backward pass, so
    that only ``[T / chunks, V]`` logits (and as much gradient) exist at once."""
    t = hidden.shape[0]
    if t % chunks:
        raise ValueError(f"{t} tokens do not split into {chunks} chunks")

    def chunk_loss(h, y, *w):
        logits = matmul(h, head).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ce = lse - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(ce * w[0] if w else ce)

    per_token = (targets,) if weights is None else (targets, weights)
    if chunks == 1:
        return chunk_loss(hidden, *per_token) / t
    chunk_loss = jax.checkpoint(chunk_loss)

    def body(total, xs):
        return total + chunk_loss(*xs), ()

    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        (hidden.reshape(chunks, t // chunks, -1),
         *(a.reshape(chunks, t // chunks) for a in per_token)))
    return total / t


NOISE_EPS = 1e-3  # the least masking probability of the linear schedule (LLaDA, arXiv:2502.09992)


def draw_noise(rng: np.random.Generator, tokens: np.ndarray, block: int):
    """Block diffusion's forward process for ``tokens [B, L]``, on the host:
    per block of ``block`` tokens ``t ~ U(0, 1)`` and ``p = (1 - eps) t + eps``
    (the linear schedule with ``eps`` = :data:`NOISE_EPS`; one ``t`` a block:
    arXiv:2503.09573), each token of the block masked independently with
    probability ``p``. -> ``{"noised" [B, L] bool, "p_mask" [B, L / block]}``:
    the draw is part of the batch, so a step is a function of its batch."""
    b, seq = tokens.shape
    if seq % block:
        raise ValueError(f"{seq} tokens are no whole blocks of {block}")
    p = ((1.0 - NOISE_EPS) * rng.random((b, seq // block)) + NOISE_EPS).astype(np.float32)
    return {"noised": rng.random((b, seq)) < np.repeat(p, block, axis=1), "p_mask": p}


def diffusion_inputs(batch, mask_id: int, block: int):
    """What the stack and the loss take under block diffusion: (ids ``[B,
    2L]``, the noised copy (the mask id where ``noised``) then the clean
    copy; each position's place in its own copy ``[2L]``, for rotary; the
    loss's weights ``[B, L]``, ``1 / p`` of its block where ``noised``, else 0)."""
    tokens, noised = batch["tokens"], batch["noised"]
    with phase_scope("noise"):
        ids = jnp.concatenate([jnp.where(noised, mask_id, tokens), tokens], axis=1)
        positions = jnp.tile(jnp.arange(tokens.shape[1]), 2)
        weights = noised / jnp.repeat(batch["p_mask"], block, axis=1)
    return ids, positions, weights


def make_optimizer(cfg: Config, lr: float):
    """The sequence trainers' optimizer choice, same contract as the CTR
    families ("sgd" default = bare SGD; the state carries the optax slots so
    adam/momentum checkpoint-resume exactly). ``adam_b1``, ``adam_b2``,
    ``adam_eps`` and ``weight_decay`` default to optax's own."""
    adam = {"b1": cfg.get_float("adam_b1", 0.9), "b2": cfg.get_float("adam_b2", 0.999),
            "eps": cfg.get_float("adam_eps", 1e-8)}
    opts = {
        "sgd": lambda: optax.sgd(lr),
        "momentum": lambda: optax.sgd(lr, momentum=0.9),
        "adam": lambda: optax.adam(lr, **adam),
        "adamw": lambda: optax.adamw(
            lr, weight_decay=cfg.get_float("weight_decay", 1e-4), **adam),
    }
    name = cfg.get_str("optimizer", "sgd")
    if name not in opts:
        raise ValueError(f"optimizer must be one of {sorted(opts)}, got {name}")
    return opts[name]()


@register_model("seqlm")
class SeqLMTrainer(Trainer):
    name = "seqlm"

    def __init__(self, config: Config, mesh=None, corpus_ids=None, vocab_size=None,
                 tracer=None):
        super().__init__(config, mesh, tracer)
        cfg = config
        self.seq_len = cfg.get_int("seq_len", 256)
        self.attention = cfg.get_str("attention", "ring" if self._has_seq_axis() else "dense")
        self.lr = cfg.get_float("learning_rate", 3e-3)
        self.batch_size = cfg.get_int("batch_size", 8)
        self.epochs = cfg.get_int("num_iters", 1)
        self.seed = cfg.get_int("seed", 0)
        self.opt = make_optimizer(cfg, self.lr)
        # block diffusion: a row is ``seq_len`` tokens and twice as many positions
        self.block_length = cfg.get_int("block_length", 0)
        if corpus_ids is None:
            with self.span("load-data"):
                corpus_ids, vocab_size = self._load_corpus(cfg)
        self.corpus_ids = np.asarray(corpus_ids, dtype=np.int32)
        self.vocab_size = int(vocab_size)
        self._read_shape(cfg)
        if self.block_length:
            self.mask_token_id = cfg.get_int("mask_token_id")
            if not 0 <= self.mask_token_id < self.vocab_size:
                raise ValueError(f"mask_token_id {self.mask_token_id} is no row of the vocabulary")
            if (self.corpus_ids == self.mask_token_id).any():
                raise ValueError(f"the corpus holds the mask's id {self.mask_token_id}")

    def _read_shape(self, cfg: Config) -> None:
        if self.block_length:
            raise ValueError("seqlm's own stack is causal: block diffusion runs under moelm")
        self.n_layers = cfg.get_int("n_layers", 2)
        self.n_heads = cfg.get_int("n_heads", 4)
        self.d_model = cfg.get_int("d_model", 128)
        if self.d_model % self.n_heads:
            raise ValueError("d_model must divide by n_heads")

    @staticmethod
    def _load_corpus(cfg: Config):
        """(token ids, vocabulary size): a ``.npy`` file holds the ids of a
        stated ``vocab_size``; anything else is text, whose words are the
        vocabulary."""
        path = cfg.get_str("data")
        if path.endswith(".npy"):
            ids = np.load(path)
            vocab_size = cfg.get_int("vocab_size")
            if ids.ndim != 1 or ids.min() < 0 or ids.max() >= vocab_size:
                raise ValueError(f"{path}: not a row of token ids under {vocab_size}")
        else:
            from swiftsnails_tpu.data.text import encode_corpus

            ids, vocab = encode_corpus(
                path, min_count=cfg.get_int("min_count", 1),
                max_vocab=cfg.get_int("max_vocab", 0) or None,
            )
            vocab_size = len(vocab)
        # multi-host contiguous corpus span (stdin-split parity); the
        # global vocab keeps token ids consistent across hosts
        if cfg.get_bool("shard_data", True):
            from swiftsnails_tpu.parallel.cluster import shard_token_stream

            ids = shard_token_stream(ids)
        return ids, vocab_size

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
        return self.hidden(params, tokens) @ params["embed"].T

    def hidden(self, params, tokens):
        """The stack's output after the last norm, [B, L, d]."""
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
        return _norm(x)

    def loss_fn(self, params, batch, state):
        """(loss, aux): ``aux`` is handed to :meth:`after_update`."""
        del state
        tokens = batch["tokens"]
        b, l = tokens.shape[0], tokens.shape[1] - 1
        x = self.hidden(params, tokens[:, :-1]).reshape(b * l, -1)
        return token_loss(x, params["embed"].T, tokens[:, 1:].reshape(-1)), {}

    def after_update(self, state, aux):
        """(state, metrics) once the gradient step is in ``state``: whatever a
        step changes besides (a mixture's selection bias, its counters)."""
        del aux
        return state, {}

    # -- trainer contract --------------------------------------------------

    def batches(self) -> Iterator[Dict[str, np.ndarray]]:
        ids = self.corpus_ids
        # +1 so each window has seq_len inputs and shifted targets; block
        # diffusion's targets are the inputs' own places
        window = self.seq_len + (0 if self.block_length else 1)
        n_windows = len(ids) // window
        rng = np.random.default_rng(self.seed)
        noise_rng = np.random.default_rng([self.seed, 1])  # the order stays the seed's alone
        for _ in range(self.epochs):
            order = rng.permutation(n_windows)
            for start in range(0, n_windows - self.batch_size + 1, self.batch_size):
                idx = order[start : start + self.batch_size]
                toks = np.stack([ids[i * window : (i + 1) * window] for i in idx])
                batch = {"tokens": toks.astype(np.int32)}
                if self.block_length:
                    batch.update(draw_noise(noise_rng, toks, self.block_length))
                yield batch

    def train_step(self, state, batch, rng):
        del rng
        (loss, aux), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(
            state["params"], batch, state)
        with phase_scope("opt"):
            updates, opt = self.opt.update(grads, state["opt"], state["params"])
            params = optax.apply_updates(state["params"], updates)
        state, metrics = self.after_update({**state, "params": params, "opt": opt}, aux)
        return state, {"loss": loss, **metrics}

    def items_per_batch(self, batch) -> int:
        rows, width = batch["tokens"].shape
        return int(rows * (width if self.block_length else width - 1))
