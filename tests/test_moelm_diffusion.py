"""The block stack's second kind of layer and second objective
(``models/moelm.py`` with grouped-query attention, a softmax router and no
shared expert; ``models/seqlm.py``'s block-diffusion loss) and the attention
kernels under the block-diffusion mask, against the benchmark's plain float32
reference (``benchmark/models/sdar.py``), small and on the CPU: the kernels
run in interpret mode, matrix operands stay float32 so that the two agree
closely. ``tests/test_moelm.py`` holds the latent-attention kind to its own
reference the same way."""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.framework.trainer import TrainLoop
from swiftsnails_tpu.models.moelm import MoELMTrainer, rotary
from swiftsnails_tpu.models.seqlm import SeqLMTrainer, diffusion_inputs, draw_noise, token_loss
from swiftsnails_tpu.ops.flash_attention import attention_flops, flash_attention
from swiftsnails_tpu.utils.config import Config
from swiftsnails_tpu.utils.metrics import MetricsLogger

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")

KEYS = dict(
    model="moelm", seq_len=32, batch_size=2, hidden_size=32, num_hidden_layers=2,
    first_k_dense_replace=0, num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    rope_theta=1000000, rms_norm_eps=1e-6, moe_intermediate_size=16, n_shared_experts=0,
    num_experts_per_tok=3, scoring_func="softmax", router_experts=16, experts_held=4,
    expert_offset=4, vocab_size=64, block_length=4, mask_token_id=63,
    optimizer="adamw", learning_rate=1e-3, adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8,
    weight_decay=0.1, bias_update_rate=0, aux_loss_alpha=0, init_std=0.05, loss_chunks=4,
    num_iters=2, matmul_dtype="float32", remat=1)


@pytest.fixture(scope="module")
def sdar():
    """``benchmark/models/sdar.py``, the reference's home."""
    sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_models_sdar_t", os.path.join(BENCH, "models", "sdar.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trainer(**over):
    cfg = Config()
    for k, v in {**KEYS, **over}.items():
        cfg.set(k, str(v))
    ids = np.random.default_rng(7).integers(0, 63, 3000)  # never the mask's id
    tr = MoELMTrainer(cfg, corpus_ids=ids, vocab_size=cfg.get_int("vocab_size"))
    tr.attention_block, tr.expert_tile = 16, 8  # several blocks and tiles at this size
    return tr, {**KEYS, **over}


def _layer(tree, i):
    return {k: v[i] for k, v in tree.items()}


def _x(tr, positions, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (positions, tr.d_model))


# ------------------------------------------------------------ the kernels ---


def _dense_attention(q, k, v, keep):
    group = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(q.shape[-1])
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1), v)


@pytest.mark.parametrize("mask,heads,kv_heads", [
    ("diffusion", 16, 2), ("diffusion", 2, 2), ("causal", 16, 2)])
def test_flash_attention_under_a_mask_and_over_grouped_heads(sdar, mask, heads, kv_heads):
    """Forward and all three gradients against a dense masked softmax, at a
    tile smaller than L (B 4, tile 16, L 64: all three regions have whole,
    partial and skipped tiles) and with 8 query heads to a key/value head:
    ``dk`` and ``dv`` are summed over the group inside the kernel."""
    seq, block, tile, dk, dv = 64, 4, 16, 24, 16
    n = 2 * seq if mask == "diffusion" else seq
    keep = sdar.allowed_pairs(seq, block) if mask == "diffusion" else np.tril(np.ones((n, n), bool))
    assert keep.sum() == (seq * (seq + block) if mask == "diffusion" else n * (n + 1) // 2)
    if mask == "diffusion":  # whole, partial and dead tiles in every region
        tiles = keep.reshape(n // tile, tile, n // tile, tile).sum(axis=(1, 3))
        assert {0, tile * block, tile * tile} <= set(tiles.ravel()) and (tiles > 0).sum() == 4 + 10 + 10
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = jax.random.normal(ks[0], (heads, n, dk)), jax.random.normal(ks[1], (kv_heads, n, dk))
    v, w = jax.random.normal(ks[2], (kv_heads, n, dv)), jax.random.normal(ks[3], (heads, n, dv))
    fast = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, block=tile, dtype=jnp.float32, diffusion_block=block if mask == "diffusion" else None)
    plain = lambda q, k, v: _dense_attention(q, k, v, keep)  # noqa: E731
    np.testing.assert_allclose(np.asarray(fast(q, k, v)), np.asarray(plain(q, k, v)), rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(fast(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):  # sums of up to 8 x 128 float32 products in another order
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4)


def test_a_tile_holds_whole_blocks_and_a_copy_whole_tiles():
    q = jnp.zeros((2, 48, 8))
    with pytest.raises(ValueError):  # a copy of 24 is no multiple of the tile 16
        flash_attention(q, q, q, block=16, diffusion_block=4)
    with pytest.raises(ValueError):  # a tile of 8 holds no whole blocks of 3
        flash_attention(q[:, :32], q[:, :32], q[:, :32], block=8, diffusion_block=3)
    with pytest.raises(ValueError):  # 3 query heads over 2 key/value heads
        flash_attention(jnp.zeros((3, 32, 8)), q[:, :32], q[:, :32], block=16)


def test_attention_flops_count_the_pairs_a_mask_allows():
    got = attention_flops(8192, 32, 128, 128, diffusion_block=4)  # two copies of 4,096
    pairs = 32 * 4096 * (4096 + 4)
    assert got == {"fwd": 2 * pairs * 256, "dq": 2 * pairs * 384, "dkv": 2 * pairs * 512}
    causal = 16 * 8192 * 8193 / 2  # unchanged, with or without the new argument
    assert attention_flops(8192, 16, 192, 128) == attention_flops(8192, 16, 192, 128, None) == {
        "fwd": 2 * causal * 320, "dq": 2 * causal * 512, "dkv": 2 * causal * 640}


# ------------------------------------------------------------- the layers ---


def test_grouped_query_layer_with_head_norms_matches_reference(sdar):
    tr, keys = _trainer()
    math = sdar.reference_math(keys)
    p = dict(_layer(tr.init_state()["params"]["moe"], 1))
    p["q_norm"], p["k_norm"] = jnp.linspace(0.5, 1.5, 8), jnp.linspace(1.4, 0.6, 8)  # gains that show
    rows, seq = tr.batch_size, tr.seq_len
    x = _x(tr, rows * 2 * seq)
    positions = jnp.tile(jnp.arange(seq), 2)
    got = jax.jit(lambda p, x: tr._attention(p, x, rows, positions))(p, x).reshape(rows, 2 * seq, -1)
    keep = jnp.asarray(sdar.allowed_pairs(seq, 4))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda x: math.attention(p, x, positions, keep)))(x.reshape(got.shape))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
    # both copies carry the positions 0..L-1: a copy rotated alone is the same
    spun = rotary(x[: 2 * seq, None, :8], 1e6, positions)
    np.testing.assert_allclose(np.asarray(spun[seq:]), np.asarray(rotary(x[seq: 2 * seq, None, :8], 1e6)),
                               rtol=1e-6, atol=1e-6)


def test_softmax_routed_layer_matches_reference(sdar):
    tr, keys = _trainer(batch_size=1)
    math = sdar.reference_math(keys)
    p = _layer(tr.init_state()["params"]["moe"], 0)
    seq = tr.seq_len
    x = _x(tr, 2 * seq)
    positions = jnp.tile(jnp.arange(seq), 2)
    got, seen = jax.jit(lambda x, p: tr._moe_layer(x, p, jnp.zeros(16), 1, positions))(x, p)
    keep = jnp.asarray(sdar.allowed_pairs(seq, 4))

    @jax.jit
    def reference(x, p):
        x1 = x + math.attention(p, x, positions, keep)
        return (x1,) + math.mixture(p, math.norm(x1, p["mlp_norm"]))

    with jax.default_matmul_precision("highest"):
        x1, out, choices = reference(x, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x1 + out), rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.sort(seen["choices"], axis=-1), np.sort(choices, axis=-1))
    assert float(seen["aux"]) == 0.0  # no balance loss: the counts are kept all the same
    assert int(seen["dropped"]) == 0 and int(seen["counts"].sum()) == 2 * seq * tr.top_k
    _, gates, s = tr.route(math.norm(x, p["mlp_norm"]), p["router"], jnp.zeros(16))
    np.testing.assert_allclose(np.asarray(s.sum(axis=-1)), 1.0, rtol=1e-5)  # a softmax over all 16
    np.testing.assert_allclose(np.asarray(gates.sum(axis=-1)), 1.0, rtol=1e-5)  # renormalised, no scale


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(sdar):
    """The routed parts that the eight chips of a deployment compute are the
    whole layer as the reference has it (no shared expert to count once; the
    residual and attention are every chip's alike and stay outside)."""
    whole, keys = _trainer(batch_size=1, experts_held=16, expert_offset=0)
    math = sdar.reference_math(keys)
    p = _layer(whole.init_state()["params"]["moe"], 0)
    y = _x(whole, 2 * whole.seq_len)
    with jax.default_matmul_precision("highest"):
        want, _ = math.mixture(p, y)
    choices, gates, _ = whole.route(y, p["router"], jnp.zeros(16))
    total = 0.0
    for share in range(8):
        tr, _ = _trainer(batch_size=1, experts_held=2, expert_offset=2 * share)
        mine = {k: (v[2 * share: 2 * share + 2] if k.startswith("experts_") else v)
                for k, v in p.items()}
        routed, dropped, _ = tr._experts(mine, y, choices, gates)
        assert int(dropped) == 0
        total = total + routed
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_dropless_when_every_choice_of_every_position_is_held():
    tr, _ = _trainer(batch_size=1)
    p = _layer(tr.init_state()["params"]["moe"], 0)
    y = _x(tr, 2 * tr.seq_len)
    choices = jnp.tile(jnp.asarray([[4, 6, 7]], jnp.int32), (y.shape[0], 1))  # 4..7 are held
    gates = jnp.tile(jnp.asarray([[0.5, 0.3, 0.2]]), (y.shape[0], 1))
    out, dropped, _ = tr._experts(p, y, choices, gates)
    want = sum(g * (jax.nn.silu(y @ p["experts_gate"][e]) * (y @ p["experts_up"][e])) @ p["experts_down"][e]
               for g, e in ((0.5, 0), (0.3, 2), (0.2, 3)))
    assert int(dropped) == 0
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------- the objective ---


def test_noise_draw_follows_p_mask_and_the_batch_carries_it():
    tr, _ = _trainer(seq_len=128)
    it = iter(tr.batches())
    first = next(it)
    assert set(first) == {"tokens", "noised", "p_mask"}
    assert first["tokens"].shape == first["noised"].shape == (2, 128) and first["p_mask"].shape == (2, 32)
    assert first["noised"].dtype == bool and tr.items_per_batch(first) == 2 * 128  # L a row, no shift
    second = next(it)
    assert not np.array_equal(first["noised"], second["noised"])  # a fresh draw every step
    again = next(iter(tr.batches()))
    assert all(np.array_equal(first[k], again[k]) for k in first)  # from the trainer's seed
    drawn = draw_noise(np.random.default_rng(0), np.zeros((64, 4096), np.int32), 4)
    p = drawn["p_mask"]
    assert 1e-3 <= p.min() and p.max() <= 1.0 and abs(p.mean() - 0.5005) < 0.005
    per_block = drawn["noised"].reshape(64, 1024, 4).mean(axis=-1)
    for lo, hi in ((0.0, 0.25), (0.25, 0.5), (0.5, 0.75), (0.75, 1.0)):  # the masked share follows p
        mine = (p >= lo) & (p < hi)
        assert abs(per_block[mine].mean() - p[mine].mean()) < 0.01
    assert abs(drawn["noised"].mean() - 0.5) < 0.01
    plain, _ = _trainer(block_length=0, first_k_dense_replace=0)
    assert set(next(iter(plain.batches()))) == {"tokens"}
    assert plain.items_per_batch(next(iter(plain.batches()))) == 2 * 32  # L + 1 a row, shifted
    with pytest.raises(ValueError):  # seqlm's own stack is causal
        cfg = Config()
        cfg.set("block_length", "4")
        SeqLMTrainer(cfg, corpus_ids=np.arange(100), vocab_size=100)
    with pytest.raises(ValueError):  # the mask's id is in the feed
        _trainer(mask_token_id=5)


def test_diffusion_inputs_and_the_weighted_loss():
    tokens = jnp.asarray([[5, 6, 7, 8, 9, 10, 11, 12]], jnp.int32)
    noised = jnp.asarray([[True, False, False, True, False, False, False, False]])
    p_mask = jnp.asarray([[0.5, 0.25]], jnp.float32)
    ids, positions, weights = diffusion_inputs(
        {"tokens": tokens, "noised": noised, "p_mask": p_mask}, mask_id=63, block=4)
    np.testing.assert_array_equal(ids, [[63, 6, 7, 63, 9, 10, 11, 12, 5, 6, 7, 8, 9, 10, 11, 12]])
    np.testing.assert_array_equal(positions, [0, 1, 2, 3, 4, 5, 6, 7] * 2)
    np.testing.assert_allclose(weights, [[2.0, 0, 0, 2.0, 0, 0, 0, 0]])
    hidden = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    head = jax.random.normal(jax.random.PRNGKey(2), (6, 64))
    logits = hidden @ head
    ce = jax.nn.logsumexp(logits, axis=-1) - logits[jnp.arange(8), tokens[0]]
    want = float(jnp.sum(ce * weights[0]) / 8)
    for chunks in (1, 4):
        got = token_loss(hidden, head, tokens[0], chunks, weights=weights[0])
        assert float(got) == pytest.approx(want, rel=1e-5)
    assert float(token_loss(hidden, head, tokens[0], 2)) == pytest.approx(float(ce.mean()), rel=1e-5)


def test_train_steps_under_the_diffusion_loss_match_reference(sdar):
    """Loss, every leaf's first gradient (from AdamW's first moment) and every
    leaf's change after three AdamW steps, the reference handed the batch's
    own noise draw."""
    tr, keys = _trainer()
    state = tr.init_state()
    w = sdar._flatten(state["params"])
    assert {k: tuple(v.shape) for k, v in w.items()} == sdar.shapes(keys)
    it = iter(tr.batches())
    batches = [next(it) for _ in range(3)]
    ref = sdar.sdar_reference(w, batches, keys)
    step = jax.jit(tr.train_step)
    start, losses = state["params"], []
    for i, b in enumerate(batches):
        state, m = step(state, {k: jnp.asarray(v) for k, v in b.items()}, jax.random.PRNGKey(0))
        losses.append(float(m["loss"]))
        if i == 0:
            grad1 = {k: float(jnp.sum(v * v)) / (1 - 0.9) ** 2
                     for k, v in sdar._flatten(state["opt"][0].mu).items()}
        assert int(m["moe_dropped"]) == 0
        assert float(m["diffusion_masked_share"]) == pytest.approx(b["noised"].mean())
        assert int(state["noised"]) == b["noised"].sum()
    np.testing.assert_allclose(losses, ref["loss"], rtol=2e-5)
    for k, want in ref["grad1"].items():
        assert grad1[k] == pytest.approx(want, rel=2e-3, abs=1e-12), k
    change = {k: float(jnp.sum((v - sdar._flatten(start)[k]) ** 2))
              for k, v in sdar._flatten(state["params"]).items()}
    for k, want in ref["change"].items():
        assert change[k] == pytest.approx(want[-1], rel=5e-3), k
    assert float(jnp.abs(state["router_bias"]).max()) == 0.0  # no bias step at a rate of 0
    assert sdar.disagree_share([np.asarray(state["choices"])], ref["choices"][-1:]) == 0.0


@pytest.mark.parametrize("part", ["control", "causal_noised", "noised_past", "no_p_weight",
                                  "no_qk_norm", "fifteen_experts", "busiest_expert_out", "half_batch",
                                  "state_unchanged"])
def test_control_and_faults_read_far_from_the_reference(sdar, part):
    """The bfloat16 control, and every fault the reference can plant, moves
    the first loss or the first gradient by far more than the 2e-5 and 2e-3
    the program is held to above."""
    tr, keys = _trainer()
    w = sdar._flatten(tr.init_state()["params"])
    batches = [next(iter(tr.batches()))]
    sound = sdar.sdar_reference(dict(w), batches, keys)
    other = sdar.sdar_reference(dict(w), batches, keys, **(
        {"precision": "bfloat16"} if part == "control" else {"fault": part}))
    loss_gap = abs(other["loss"][0] - sound["loss"][0]) / sound["loss"][0]
    grad_gap = max(abs(np.sqrt(other["grad1"][k]) - np.sqrt(v)) / np.sqrt(v)
                   for k, v in sound["grad1"].items() if v > 0)
    change_gap = max(abs(np.sqrt(other["change"][k][0]) - np.sqrt(v[0])) / np.sqrt(v[0])
                     for k, v in sound["change"].items())
    assert loss_gap > 2e-4 or grad_gap > 2e-2 or change_gap > 0.5, (loss_gap, grad_gap, change_gap)


def test_bfloat16_operands_stay_close_and_the_step_is_scoped():
    exact, _ = _trainer()
    rounded, _ = _trainer(matmul_dtype="bfloat16")
    state = exact.init_state()
    batch = {k: jnp.asarray(v) for k, v in next(iter(exact.batches())).items()}
    want, _ = jax.jit(exact.loss_fn)(state["params"], batch, state)
    got, _ = jax.jit(rounded.loss_fn)(state["params"], batch, state)
    assert float(got) == pytest.approx(float(want), rel=2e-3) and float(got) != float(want)
    text = jax.jit(rounded.train_step).lower(state, batch, jax.random.PRNGKey(0)).as_text(debug_info=True)
    for phase in ("noise", "attn", "route", "experts", "head", "opt"):
        assert f"phase_{phase}" in text, phase
    assert "phase_mlp" not in text  # no dense layer, no shared expert


def test_runs_under_train_loop_from_a_file_of_ids(tmp_path):
    path = str(tmp_path / "ids.npy")
    np.save(path, np.random.default_rng(0).integers(0, 63, 2000).astype(np.int32))
    cfg = Config()
    for k, v in {**KEYS, "data": path, "shard_data": 0, "num_iters": 1}.items():
        cfg.set(k, str(v))
    tr = MoELMTrainer(cfg)
    assert tr.vocab_size == 64 and "dense" not in tr.param_shapes()
    state = TrainLoop(tr, metrics=MetricsLogger(echo=False), log_every=0).run(max_steps=3)
    assert int(state["dropped"]) == 0 and 0 < int(state["noised"]) < 2 * 32
    assert state["choices"].shape == (2, 2 * 2 * 32, 3)  # both copies are routed
