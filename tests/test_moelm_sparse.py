"""Learned sparse attention (``models/moelm.py`` under ``sa_config``;
``ops/sparse_attention.py``; ``ops/flash_attention.py`` under a
``Selection``) against plain ``jax.numpy`` and the benchmark's float32
reference (``benchmark/models/keye.py``), small and on the CPU: the kernels
run in interpret mode, blocks of 128, matrix operands float32 where the
program is held to the reference."""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.models.moelm import MoELMTrainer, rotary
from swiftsnails_tpu.ops import flash_attention as fa
from swiftsnails_tpu.ops import sparse_attention as sa
from swiftsnails_tpu.utils.config import Config

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
BLOCK = 128
SPARSE = {"sa_config.topk": 64, "sa_config.indexer_num_heads": 4, "sa_config.indexer_head_dim": 16,
          "sa_config.indexer_num_kv_heads": 1, "sa_config.q_chunk_size": BLOCK, "sa_config.kv_chunk_size": BLOCK,
          "rope_scaling.mrope_section": "[4, 6, 6]"}
KEYS = dict(
    model="moelm", seq_len=256, batch_size=1, hidden_size=64, num_hidden_layers=2,
    first_k_dense_replace=0, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    rope_theta=10000000, rms_norm_eps=1e-6, moe_intermediate_size=32, n_shared_experts=0,
    num_experts_per_tok=3, scoring_func="softmax", router_experts=16, experts_held=4, expert_offset=4,
    vocab_size=256, optimizer="adamw", learning_rate=1e-3, adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8,
    weight_decay=0.1, bias_update_rate=0, aux_loss_alpha=0, init_std=0.1, loss_chunks=4, num_iters=2,
    matmul_dtype="float32", remat=1, **SPARSE)


@pytest.fixture(scope="module")
def keye():
    """``benchmark/models/keye.py``, the reference's home; the benchmark's
    ``lib`` it imports is not left behind for another test's tree."""
    before = {name for name in sys.modules if name == "lib" or name.startswith("lib.")}
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location("bench_models_keye_t", os.path.join(BENCH, "models", "keye.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(BENCH)
        for name in [n for n in sys.modules if (n == "lib" or n.startswith("lib.")) and n not in before]:
            del sys.modules[name]
    return mod


def _trainer(**over):
    keys = {k: v for k, v in {**KEYS, **over}.items() if v is not None}
    cfg = Config()
    for k, v in keys.items():
        cfg.set(k, str(v))
    ids = np.random.default_rng(7).integers(0, keys["vocab_size"], 4000)
    tr = MoELMTrainer(cfg, corpus_ids=ids, vocab_size=keys["vocab_size"])
    tr.expert_tile = 8
    return tr, keys


def _layer(tree, i):
    return {k: v[i] for k, v in tree.items()}


def _operands(seq=512, heads=4, kv=2, d=32, hi=4, di=16, seed=1):
    """Main and indexer operands, rounded to bfloat16 so that a product of
    them is exact in float32 whichever way it is summed."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    return (bf(jax.random.normal(ks[0], (heads, seq, d))), bf(jax.random.normal(ks[1], (kv, seq, d))),
            bf(jax.random.normal(ks[2], (kv, seq, d))), bf(jax.random.normal(ks[3], (hi, seq, di))),
            bf(jax.random.normal(ks[4], (seq, di))), jax.random.normal(ks[5], (seq, hi)) / 2)


def _index_dense(qi, ki, w):
    s = jnp.einsum("jtd,sd->jts", qi, ki, precision="highest")
    return jnp.einsum("tj,jts->ts", w, jnp.maximum(s, 0.0), precision="highest") / np.sqrt(ki.shape[-1])


# ------------------------------------------------------------ the kernels ---


def test_the_indexers_scores_are_the_formula():
    _, _, _, qi, ki, w = _operands()
    want = _index_dense(qi, ki, w)
    for c in (0, 2):
        got = sa.index_scores(qi.astype(jnp.bfloat16), ki.astype(jnp.bfloat16), w, jnp.int32(c), BLOCK, True)
        rows = slice(c * BLOCK, (c + 1) * BLOCK)
        seen = (c + 1) * BLOCK  # the chunk's causal key tiles; the rest are zeros, never fetched
        np.testing.assert_allclose(np.asarray(got[:, :seen]), np.asarray(want[rows, :seen]), rtol=1e-5, atol=1e-5)
        assert not np.asarray(got[:, seen:]).any()


def _top_k_want(scores, c, k):
    """``lax.top_k``'s choice among each row's causal keys (row ``i`` of
    chunk ``c`` is query ``c * rows + i``), as a ``[rows, L]`` bool mask."""
    rows, seq = scores.shape
    valid = np.arange(seq)[None, :] <= c * rows + np.arange(rows)[:, None]
    _, idx = jax.lax.top_k(jnp.where(valid, jnp.asarray(scores) + 0.0, -jnp.inf), min(k, seq))
    want = np.zeros((rows, seq), bool)
    want[np.arange(rows)[:, None], np.asarray(idx)] = True
    return want & valid


def _tied_scores(rows, seq, seed):
    """A tenth of the scores one value, a tenth zeros of either sign."""
    rng = np.random.default_rng(seed)
    scores = rng.normal(size=(rows, seq)).astype(np.float32)
    scores[rng.random((rows, seq)) < 0.1] = 0.75
    scores[rng.random((rows, seq)) < 0.1] = 0.0
    scores[rng.random((rows, seq)) < 0.05] = -0.0
    return scores


@pytest.mark.parametrize("k", [1, 7, 64, 600])
def test_the_selection_is_lax_top_k_with_its_ties(k):
    """The top-k kernel by query chunks (the first, one inside, the last):
    ties go to the lower index; rows with ``k`` or fewer causal keys keep
    them all (``k`` 7 and 64 put both kinds of row in one block of 32, 600
    every row of every chunk); the key tiles after the chunk are not read
    (they hold noise here, where the score kernel writes zeros) and their
    words are zeros."""
    seq = 512
    for c in (0, 1, 3):
        scores = _tied_scores(BLOCK, seq, 10 * k + c)
        words, tied = sa.sparse_top_k(jnp.asarray(scores), jnp.int32(c), k, True)
        got = np.asarray(fa.unpack_bits(words))
        want = _top_k_want(scores, c, k)
        np.testing.assert_array_equal(got, want, err_msg=f"chunk {c}")
        t = c * BLOCK + np.arange(BLOCK)
        np.testing.assert_array_equal(got.sum(axis=1), np.minimum(t + 1, k))
        assert 0 <= int(tied) <= int((t >= k).sum())


@pytest.mark.parametrize("k", [100, 257])
def test_the_top_k_kernel_where_a_block_mixes_few_and_many_keys(k):
    """A ``k`` that is no multiple of 32: in the block of 32 rows where the
    rows reach ``k + 1`` causal keys, some keep all theirs and the rest run
    the counting passes; a row's ties, where some must be left out, are
    searched by column (and counted), the others not."""
    seq, c = 512, (k + 1) // BLOCK
    rng = np.random.default_rng(k)
    scores = (rng.integers(-4, 5, (BLOCK, seq)) / 2).astype(np.float32)  # nine values: most k-th ones tied
    scores[rng.random((BLOCK, seq)) < 0.05] = -0.0
    words, tied = sa.sparse_top_k(jnp.asarray(scores), jnp.int32(c), k, True)
    np.testing.assert_array_equal(np.asarray(fa.unpack_bits(words)), _top_k_want(scores, c, k))
    # the rows whose k-th value is tied with ties left out: as lax.top_k leaves some out
    t = c * BLOCK + np.arange(BLOCK)
    kth = np.sort(np.where(np.arange(seq)[None, :] <= t[:, None], scores + 0.0, -np.inf), axis=1)[:, -k]
    ties = (scores + 0.0 == kth[:, None]) & (np.arange(seq)[None, :] <= t[:, None])
    above = ((scores + 0.0 > kth[:, None]) & (np.arange(seq)[None, :] <= t[:, None])).sum(axis=1)
    assert int(tied) == int(((t >= k) & (k - above < ties.sum(axis=1))).sum()) > 0


def test_packed_words_and_the_walks():
    rng = np.random.default_rng(3)
    keep = np.tril(rng.random((512, 512)) < 0.02)
    keep[:BLOCK] = False  # the first query tile selects nothing of its own: its tile is opened and closed
    keep[BLOCK:2 * BLOCK, :BLOCK] = True
    words = sa.pack_bits(jnp.asarray(keep))
    assert words.shape == (16, 512) and words.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(fa.unpack_bits(words)), keep)
    n = 4
    live = keep.reshape(n, BLOCK, n, BLOCK).any(axis=(1, 3))
    sel = sa.walks(words, jnp.asarray(live), 2)
    q, k = np.asarray(sel.by_query), np.asarray(sel.by_key)
    assert q.shape == (6, 10) and k.shape == (6, 20)
    sparse = q[fa._KIND] == fa._SPARSE
    assert {(a, b) for a, b in zip(q[fa._QI][sparse], q[fa._KJ][sparse])} == set(zip(*np.nonzero(live)))
    assert q[fa._FIRST].sum() == q[fa._LAST].sum() == n  # every query tile opens and closes once
    assert (q[fa._KIND][q[fa._FIRST] == 0] != fa._SPARSE).sum() == q.shape[1] - sparse.sum() - 1  # the lone one
    assert k[fa._FIRST].sum() == k[fa._LAST].sum() == n  # so does every key tile, the empty ones by a skip step
    # every causal pair selected: the causal walks, step for step
    full = sa.walks(sa.pack_bits(jnp.tril(jnp.ones((512, 512), bool))), jnp.tril(jnp.ones((n, n), bool)), 2)
    rows = [fa._QI, fa._KJ, fa._FIRST, fa._LAST, fa._HEAD]
    np.testing.assert_array_equal(np.asarray(full.by_query)[rows], fa._steps(fa.Causal(), n)[rows])
    np.testing.assert_array_equal(np.asarray(full.by_key)[rows], fa._steps(fa.Causal(), n, 2)[rows])


def test_select_against_a_dense_top_k_with_planted_ties():
    """The score kernel and the top-k kernel by chunks of 512 over 2,048
    keys (topk 256): with small whole-number operands every score is exact
    whichever way it is summed and most rows' 256th value is tied, the
    words and live tiles are a dense ``lax.top_k`` mask's, packed; with
    random operands no row's ties are searched."""
    seq, block, topk = 2048, 512, 256
    rng = np.random.default_rng(43)
    qi = jnp.asarray(rng.integers(-2, 3, (4, seq, 16)), jnp.float32)
    ki = jnp.asarray(rng.integers(-2, 3, (seq, 16)), jnp.float32)
    w = jnp.asarray(rng.integers(-2, 3, (seq, 4)) / 4, jnp.float32)
    words, live, share = sa.select(qi, ki, w, topk, block)
    causal = np.tril(np.ones((seq, seq), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, _index_dense(qi, ki, w) + 0.0, -jnp.inf), topk)
    keep = np.zeros((seq, seq), bool)
    keep[np.arange(seq)[:, None], np.asarray(idx)] = True
    want = sa.pack_bits(jnp.asarray(keep & causal))
    np.testing.assert_array_equal(np.asarray(words), np.asarray(want))
    n = seq // block
    np.testing.assert_array_equal(np.asarray(live), (keep & causal).reshape(n, block, n, block).any(axis=(1, 3)))
    assert 0 < float(share) <= 1 - topk / seq
    _, _, _, qi, ki, w = _operands(seq=seq, hi=16)  # a score is exactly 0 where all 16 heads' ReLU are
    assert float(sa.select(qi, ki, w, topk, block)[2]) == 0.0


@pytest.fixture(scope="module")
def selected():
    q, k, v, qi, ki, w = _operands()
    words, live, _ = sa.select(qi, ki, w, 64, BLOCK)
    sel = sa.walks(words, live, 2)
    return (q, k, v, qi, ki, w), sel, np.asarray(fa.unpack_bits(words))


def test_the_three_kernels_and_the_pass_against_a_dense_mask(selected):
    (q, k, v, qi, ki, w), sel, keep = selected
    causal = np.tril(np.ones(keep.shape, bool))
    want_keep = np.asarray(jax.lax.top_k(jnp.where(causal, _index_dense(qi, ki, w) + 0.0, -jnp.inf), 64)[1])
    dense_keep = np.zeros_like(keep)
    dense_keep[np.arange(keep.shape[0])[:, None], want_keep] = True
    np.testing.assert_array_equal(keep, dense_keep & causal)

    def dense(q, k, v):
        kk, vv = jnp.repeat(k, 2, axis=0), jnp.repeat(v, 2, axis=0)
        s = jnp.einsum("htd,hsd->hts", q, kk, precision="highest") / np.sqrt(q.shape[-1])
        s = jnp.where(keep[None], s, -jnp.inf)
        return jnp.einsum("hts,hsd->htd", jax.nn.softmax(s, axis=-1), vv, precision="highest"), \
            jax.nn.logsumexp(s, axis=-1)

    weights = jnp.cos(jnp.arange(q.shape[-1]))
    o, lse = fa.selected_attention(q, k, v, sel, block=BLOCK, dtype=jnp.float32)
    o_want, lse_want = dense(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_want), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_want), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(fa.selected_attention(*a, sel, block=BLOCK, dtype=jnp.float32)[0]
                                      * weights), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a)[0] * weights), (0, 1, 2))(q, k, v)
    for g, h in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(h), rtol=1e-4, atol=1e-4)
    # L_I and its gradients in the indexer's three operands
    p = jnp.mean(jax.nn.softmax(jnp.where(keep[None], jnp.einsum(
        "htd,hsd->hts", q, jnp.repeat(k, 2, axis=0), precision="highest") / np.sqrt(q.shape[-1]), -jnp.inf), -1), 0)

    def loss_dense(qi, ki, w):
        log_q = jax.nn.log_softmax(jnp.where(keep, _index_dense(qi, ki, w), -jnp.inf), axis=-1)
        return jnp.mean(jnp.sum(jnp.where(keep, p * (jnp.log(jnp.where(keep, p, 1.0)) - log_q), 0.0), axis=-1))

    stats = sa.probability_pass(q, k, lse, qi, ki, w, sel, BLOCK, jnp.float32)
    mine = lambda qi, ki, w: sa.indexer_loss(qi, ki, w, q, k, lse, sel, stats, BLOCK, jnp.float32, None)  # noqa: E731
    a, ga = jax.value_and_grad(mine, (0, 1, 2))(qi, ki, w)
    b, gb = jax.value_and_grad(loss_dense, (0, 1, 2))(qi, ki, w)
    assert float(a) == pytest.approx(float(b), rel=1e-5) and float(a) > 0
    for g, h in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(g), np.asarray(h), rtol=1e-4, atol=1e-6)


def test_index_flops_count_the_causal_and_the_selected_pairs():
    got = sa.index_flops(512, 4, 16, 8, 32, pairs=1000.0)
    assert got == {"scores": 512 * 513 / 2 * 2 * 4 * 16, "pass": 1000 * (2 * 8 * 32 + 2 * 4 * 16),
                   "bwd": 1000 * (2 * 8 * 32 + 3 * 2 * 4 * 16)}


# -------------------------------------------------------------- the layer ---


def test_topk_over_the_length_is_the_causal_grouped_query_layer_bit_for_bit():
    """Every causal key selected: the same kernels walk the same tiles in the
    same order, so output and every gradient of the attention's leaves are
    the causal layer's exactly (the indexer's leaves are not its)."""
    sparse, _ = _trainer(**{"sa_config.topk": 256, "matmul_dtype": "bfloat16"})
    plain, _ = _trainer(**{k: None for k in SPARSE}, matmul_dtype="bfloat16")
    plain.attention_block = BLOCK
    p = _layer(sparse.init_state()["params"]["moe"], 0)
    x = jax.random.normal(jax.random.PRNGKey(3), (256, 64))
    leaves = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo")

    def run(tr, p, x):
        return jnp.sum(jnp.sin(tr._attention(p, x, 1)[0]))

    got = jax.jit(jax.value_and_grad(lambda p, x: run(sparse, p, x), (0, 1)))(p, x)
    want = jax.jit(jax.value_and_grad(lambda p, x: run(plain, p, x), (0, 1)))({k: p[k] for k in leaves}, x)
    assert float(got[0]) == float(want[0])
    np.testing.assert_array_equal(np.asarray(got[1][1]), np.asarray(want[1][1]))
    assert float(jnp.abs(got[1][1]).max()) > 0
    for k in leaves:
        np.testing.assert_array_equal(np.asarray(got[1][0][k]), np.asarray(want[1][0][k]), err_msg=k)


def test_mrope_on_text_positions_is_one_d_rotary(keye):
    _, keys = _trainer()
    math = keye.reference_math(keys)
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 4, 32))
    text = jnp.tile(jnp.arange(256)[None], (3, 1))
    np.testing.assert_allclose(np.asarray(math.mrope(x, text)), np.asarray(rotary(x, 1e7)), rtol=1e-5, atol=1e-5)
    image = text.at[1].set(text[1] // 7)  # a height stream that differs moves its section only
    moved = np.abs(np.asarray(math.mrope(x, image) - math.mrope(x, text))).max(axis=(0, 1))
    assert moved[:4].max() == 0 and moved[4:10].max() > 0 and moved[10:16].max() == 0


def test_the_two_losses_reach_their_own_leaves():
    tr, _ = _trainer()
    state = tr.init_state()
    batch = {k: jnp.asarray(v) for k, v in next(iter(tr.batches())).items()}
    index = ("index_wq", "index_wk", "index_k_norm", "index_k_bias", "index_ww")

    def part(which):
        def loss(params):
            total, seen = tr.loss_fn(params, batch, state)
            return jnp.sum(seen["index_loss"]) if which == "index" else total - jnp.sum(seen["index_loss"])
        return jax.jit(jax.grad(loss))(state["params"])

    lm, li = part("lm"), part("index")
    for k, g in li["moe"].items():
        assert (float(jnp.abs(g).max()) > 0) == (k in index), k  # L_I moves the indexer alone
    assert not any(float(jnp.abs(g).max()) for k, g in li.items() if k != "moe")
    for k in index:
        assert float(jnp.abs(lm["moe"][k]).max()) == 0, k  # and the language-model loss none of it


def test_the_selection_is_kept_for_the_backward_pass(capsys):
    """Under ``remat`` a layer keeps its selection (the words and the two
    walks) and the probability pass's rows for the backward pass, so that
    neither the score kernel, the top-k nor the pass runs again there; and
    nothing of ``[L, L]`` is kept."""
    from swiftsnails_tpu.models import moelm

    tr, _ = _trainer(matmul_dtype="bfloat16")
    p = _layer(tr.init_state()["params"]["moe"], 0)
    x = jax.random.normal(jax.random.PRNGKey(3), (256, 64))

    def layer(x, p):
        out, seen = tr._moe_layer(x, p, jnp.zeros(16), 1)
        return jnp.sum(out) + seen["index_loss"]

    jax.ad_checkpoint.print_saved_residuals(
        jax.checkpoint(layer, policy=jax.checkpoint_policies.save_only_these_names(*moelm.SAVED)), x, p)
    saved = capsys.readouterr().out.splitlines()
    mine = sorted(line.split(" ")[0] for line in saved if "_selected" in line)  # what the layer computed
    assert mine == ["f32[256,128]", "i32[6,3]", "i32[6,6]", "i32[8,256]"], saved  # stats, walks, words
    assert sum("sparse_selection" in line for line in saved) == 3, saved
    assert not any(line.startswith("f32[256,256]") for line in saved), saved


# -------------------------------------------------------- the whole model ---


def test_train_steps_match_reference(keye):
    """Loss (the next token's plus the layers' L_I), every leaf's first
    gradient (from AdamW's first moment) and every leaf's change after three
    AdamW steps, and the selections, against the float32 reference."""
    tr, keys = _trainer()
    state = tr.init_state()
    w = keye._flatten(state["params"])
    assert {k: tuple(v.shape) for k, v in w.items()} == keye.shapes(keys)
    it = iter(tr.batches())
    batches = [next(it) for _ in range(3)]
    ref = keye.keye_reference(dict(w), batches, keys)
    step = jax.jit(tr.train_step)
    start, losses = state["params"], []
    for i, b in enumerate(batches):
        state, m = step(state, {k: jnp.asarray(v) for k, v in b.items()}, jax.random.PRNGKey(0))
        losses.append(float(m["loss"]))
        if i == 0:
            grad1 = {k: float(jnp.sum(v * v)) / (1 - 0.9) ** 2
                     for k, v in keye._flatten(state["opt"][0].mu).items()}
        assert int(m["moe_dropped"]) == 0 and "attn_whole_tile_share" not in m
        assert float(m["sparse_live_tile_share"]) == 1.0  # at random weights every causal tile holds a pick
        assert 0 < float(m["sparse_indexer_kl"]) < 5
    np.testing.assert_allclose(losses, ref["loss"], rtol=2e-5)
    for k, want in ref["grad1"].items():
        assert grad1[k] == pytest.approx(want, rel=2e-3, abs=1e-12), k
    change = {k: float(jnp.sum((v - keye._flatten(start)[k]) ** 2))
              for k, v in keye._flatten(state["params"]).items()}
    for k, want in ref["change"].items():
        assert change[k] == pytest.approx(want[-1], rel=5e-3), k
    selected = keye.program_selection(tr, start, batches[0]["tokens"])  # the step keeps no selection
    assert "selected" not in state and keye.select_disagree_share(selected, ref["selected"]) == 0.0
    picks = fa.unpack_bits(jnp.asarray(selected[0, 0]))
    assert (np.asarray(picks).sum(axis=1) == np.minimum(np.arange(256) + 1, 64)).all()


@pytest.fixture(scope="module")
def sound(keye):
    """(weights, one batch, the keys, the sound reference's first step)."""
    tr, keys = _trainer()
    w = keye._flatten(tr.init_state()["params"])
    batches = [next(iter(tr.batches()))]
    return w, batches, keys, keye.keye_reference(dict(w), batches, keys)


@pytest.mark.parametrize("part", ["control", "dense", "topk_half", "no_relu", "no_index_loss", "losses_mixed"])
def test_control_and_faults_read_far_from_the_reference(keye, sound, part):
    """The bfloat16 control and the faults this model adds move the first
    loss or a leaf's first gradient far past the 2e-5 and 2e-3 the program is
    held to above."""
    w, batches, keys, sound = sound
    other = keye.keye_reference(dict(w), batches, keys, **(
        {"precision": "bfloat16"} if part == "control" else {"fault": part}))
    loss_gap = abs(other["loss"][0] - sound["loss"][0]) / sound["loss"][0]
    grad_gap = max(abs(np.sqrt(other["grad1"][k]) - np.sqrt(v)) / np.sqrt(v)
                   for k, v in sound["grad1"].items() if v > 0)
    assert loss_gap > 2e-4 or grad_gap > 2e-2, (loss_gap, grad_gap)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(keye):
    """The routed parts that the eight chips compute, 16 of 128 experts each
    in the deployment (here 2 of 16), are the whole mixture as the reference
    has it."""
    whole, keys = _trainer(experts_held=16, expert_offset=0)
    math = keye.reference_math(keys)
    p = _layer(whole.init_state()["params"]["moe"], 0)
    y = jax.random.normal(jax.random.PRNGKey(5), (256, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = math.mixture(p, y)
    choices, gates, _ = whole.route(y, p["router"], jnp.zeros(16))
    total = 0.0
    for share in range(8):
        tr, _ = _trainer(experts_held=2, expert_offset=2 * share)
        mine = {k: (v[2 * share: 2 * share + 2] if k.startswith("experts_") else v) for k, v in p.items()}
        routed, planned = tr._experts(mine, y, choices, gates)
        assert int(planned["dropped"]) == 0
        total = total + routed
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_the_configuration_is_read_as_published():
    tr, _ = _trainer()
    assert (tr.topk, tr.index_heads, tr.index_dim, tr.attention_block) == (64, 4, 16, BLOCK)
    shapes = tr.param_shapes()["moe"]
    assert shapes["index_wq"] == (2, 64, 64) and shapes["index_wk"] == (2, 64, 16)
    assert shapes["index_ww"] == (2, 64, 4) and shapes["index_k_bias"] == (2, 16)
    for bad, match in (({"sa_config.indexer_num_kv_heads": 2}, "one key head"),
                       ({"sa_config.kv_chunk_size": 256}, "one tile"),
                       ({"rope_scaling.mrope_section": "[4, 6, 4]"}, "half of a head"),
                       ({"block_length": 4, "mask_token_id": 255}, "causal grouped-query")):
        with pytest.raises(ValueError, match=match):
            _trainer(**bad)
