"""Shared quality probe: does a trained word2vec state know its corpus?

One implementation used by BOTH the CI gate (tests/test_path_quality.py) and
the on-chip smoke (chip_smoke.py), so the bar and the corpus cannot
drift apart. The probe corpus pairs word ``2i`` with ``2i+1`` exclusively;
a trained state should rank the partner top-1 by in-out logit
(``v_in[2i] . u_out[j]`` argmax over j). Catastrophic-regression detector:
healthy runs score 0.84-0.98 across paths and seeds, an untrained or
mis-scaled state scores ~1/vocab (the packed-init fan-in bug this gate
caught scored 0.12).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

# Fraction of pairs that must be learned for a path to pass. Measured
# envelope across step paths/seeds is 0.84-0.98; collapse is ~0.
MIN_TOP1 = 0.75

N_PAIRS = 64  # 128 words: hogwild within-block collisions stay minor

PROBE_CONFIG = {
    "dim": "16",
    "window": "1",
    "negatives": "4",
    "learning_rate": "0.3",
    "num_iters": "6",
    "batch_size": "256",
    "subsample": "0",
    "seed": "0",
    # probe-scale pool (only read by pool/fused paths)
    "pool_size": "8",
    "pool_block": "64",
}


def paired_corpus(n_pairs: int = N_PAIRS, reps: int = 4000, seed: int = 0
                  ) -> Tuple[np.ndarray, "object"]:
    """Corpus where word 2i and 2i+1 always co-occur: 'a0 b0 a3 b3 ...'."""
    from swiftsnails_tpu.data.vocab import Vocab

    rng = np.random.default_rng(seed)
    vocab_words = [f"w{i}" for i in range(2 * n_pairs)]
    seq = []
    for _ in range(reps):
        pair = rng.integers(0, n_pairs)
        seq += [2 * pair, 2 * pair + 1]
    ids = np.array(seq, dtype=np.int32)
    counts = np.bincount(ids, minlength=2 * n_pairs).astype(np.int64)
    return ids, Vocab(vocab_words, counts)


def pair_top1_hits(trainer, state) -> Tuple[int, int]:
    """(hits, n_pairs): pairs whose partner wins the in-out logit argmax."""
    import jax.numpy as jnp

    from swiftsnails_tpu.ops.rowdma import unpack_rows
    from swiftsnails_tpu.parallel.store import pull

    n_words = len(trainer.vocab)
    rows = trainer._rows(jnp.arange(n_words, dtype=jnp.int32))
    if trainer.packed:
        v = np.asarray(unpack_rows(
            state.in_table.table.at[rows].get(mode="promise_in_bounds"),
            trainer.dim))
        u = np.asarray(unpack_rows(
            state.out_table.table.at[rows].get(mode="promise_in_bounds"),
            trainer.dim))
    else:
        v = np.asarray(pull(state.in_table, rows))
        u = np.asarray(pull(state.out_table, rows))
    scores = v @ u.T
    hits = sum(
        int(np.argmax(scores[2 * p]) == 2 * p + 1) for p in range(n_words // 2)
    )
    return hits, n_words // 2


def probe_top1(path_overrides: dict) -> float:
    """Train the probe corpus under ``path_overrides`` and score it.

    Runs on whatever platform jax is using — on TPU the fused path exercises
    the REAL racy kernel (hardware hogwild), not the serialized
    interpret-mode approximation CI sees.
    """
    import jax
    import jax.numpy as jnp

    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    ids, vocab = paired_corpus()
    cfg = dict(PROBE_CONFIG)
    cfg.update(path_overrides)
    cfg["pool_size"] = PROBE_CONFIG["pool_size"]
    cfg["pool_block"] = PROBE_CONFIG["pool_block"]
    trainer = Word2VecTrainer(Config(cfg), mesh=None, corpus_ids=ids, vocab=vocab)
    state = trainer.init_state()
    step = jax.jit(trainer.train_step, donate_argnums=(0,))
    key = jax.random.PRNGKey(0)
    for i, batch in enumerate(trainer.batches()):
        dev = {k: jnp.asarray(v) for k, v in batch.items()}
        state, _ = step(state, dev, jax.random.fold_in(key, i))
    hits, n = pair_top1_hits(trainer, state)
    return hits / n
