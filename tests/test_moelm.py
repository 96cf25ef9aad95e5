"""The mixture-of-experts block stack (``models/moelm.py``) and its kernels
against the benchmark's plain float32 reference
(``benchmark/models/moonlight.py``), small and on the CPU: the kernels run in
interpret mode, matrix operands stay float32 so that the two agree closely."""

import functools
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.framework.trainer import TrainLoop
from swiftsnails_tpu.models.moelm import MoELMTrainer
from swiftsnails_tpu.ops.flash_attention import attention_flops, flash_attention
from swiftsnails_tpu.ops.grouped_matmul import (
    grouped_matmul, grouped_swiglu, plan_rows, rows_for, rows_of_tokens, tokens_of_rows)
from swiftsnails_tpu.parallel.mesh import SEQ_AXIS, make_mesh
from swiftsnails_tpu.parallel.sequence import reference_attention, ring_attention
from swiftsnails_tpu.utils.config import Config
from swiftsnails_tpu.utils.metrics import MetricsLogger

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")

KEYS = dict(
    model="moelm", seq_len=32, batch_size=2, hidden_size=32, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=12, rope_theta=50000, rms_norm_eps=1e-5, intermediate_size=64,
    moe_intermediate_size=16, n_shared_experts=2, num_experts_per_tok=3,
    routed_scaling_factor=2.446, router_experts=16, experts_held=4, expert_offset=4,
    vocab_size=64, optimizer="adamw", learning_rate=1e-3, adam_b1=0.9, adam_b2=0.95,
    adam_eps=1e-8, weight_decay=0.1, bias_update_rate=0.001, aux_loss_alpha=0.0001,
    init_std=0.05, loss_chunks=4, num_iters=2,
    matmul_dtype="float32", remat=1)


@pytest.fixture(scope="module")
def moonlight():
    """``benchmark/models/moonlight.py``, the reference's home."""
    sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_models_moonlight_t", os.path.join(BENCH, "models", "moonlight.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trainer(**over):
    cfg = Config()
    for k, v in {**KEYS, **over}.items():
        cfg.set(k, str(v))
    ids = np.random.default_rng(7).integers(0, int(cfg.get_int("vocab_size")), 3000)
    tr = MoELMTrainer(cfg, corpus_ids=ids, vocab_size=cfg.get_int("vocab_size"))
    tr.attention_block, tr.expert_tile = 16, 8  # several blocks and tiles at this size
    return tr, {**KEYS, **over}


def _layer(tree, i):
    return {k: v[i] for k, v in tree.items()}


def _x(tr, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (tr.batch_size * tr.seq_len, tr.d_model))


def test_mla_layer_matches_reference(moonlight):
    tr, keys = _trainer()
    math = moonlight.reference_math(keys)
    p = _layer(tr.init_state()["params"]["moe"], 1)
    x = _x(tr)
    got = jax.jit(lambda p, x: tr._attention(p, x, tr.batch_size))(p, x)
    got = got.reshape(tr.batch_size, tr.seq_len, -1)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(math.attention, (None, 0)))(p, x.reshape(got.shape))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_routed_and_shared_layer_matches_reference(moonlight):
    tr, keys = _trainer(batch_size=1)
    math = moonlight.reference_math(keys)
    p = _layer(tr.init_state()["params"]["moe"], 0)
    bias = jnp.linspace(-0.05, 0.05, tr.router_experts)
    x = _x(tr)
    got, seen = jax.jit(lambda x, p, bias: tr._moe_layer(x, p, bias, 1))(x, p, bias)

    @jax.jit
    def reference(x, p, bias):
        x1 = x + math.attention(p, x)
        return (x1,) + math.mixture(p, bias, math.norm(x1, p["mlp_norm"]))

    with jax.default_matmul_precision("highest"):
        x1, out, balance, choices = reference(x, p, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x1 + out), rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.sort(seen["choices"], axis=-1), np.sort(choices, axis=-1))
    assert float(seen["aux"]) == pytest.approx(float(balance), rel=1e-5)
    assert int(seen["dropped"]) == 0 and int(seen["counts"].sum()) == tr.seq_len * tr.top_k


def test_train_steps_match_reference(moonlight):
    """Loss, every leaf's first gradient (from AdamW's first moment) and every
    leaf's change after three AdamW + bias steps."""
    tr, keys = _trainer()
    state = tr.init_state()
    w = moonlight._flatten(state["params"])
    it = iter(tr.batches())
    batches = [next(it) for _ in range(3)]
    ref = moonlight.moonlight_reference(w, batches, keys)
    step = jax.jit(tr.train_step)
    start, losses = state["params"], []
    for i, b in enumerate(batches):
        state, m = step(state, {"tokens": jnp.asarray(b["tokens"])}, jax.random.PRNGKey(0))
        losses.append(float(m["loss"]))
        if i == 0:
            grad1 = {k: float(jnp.sum(v * v)) / (1 - 0.9) ** 2
                     for k, v in moonlight._flatten(state["opt"][0].mu).items()}
        assert int(m["moe_dropped"]) == 0 and 0 < float(m["moe_live_tile_share"]) <= 1
        assert float(m["attn_whole_tile_share"]) == pytest.approx(1 / 3)  # two causal tiles a side: 1 of 3 visited
    np.testing.assert_allclose(losses, ref["loss"], rtol=2e-5)
    for k, want in ref["grad1"].items():
        assert grad1[k] == pytest.approx(want, rel=2e-3, abs=1e-12), k
    change = {k: float(jnp.sum((v - moonlight._flatten(start)[k]) ** 2))
              for k, v in moonlight._flatten(state["params"]).items()}
    change["router_bias"] = float(jnp.sum(state["router_bias"] ** 2))
    for k, want in ref["change"].items():
        assert change[k] == pytest.approx(want[-1], rel=5e-3), k
    assert moonlight.disagree_share([np.asarray(state["choices"])], ref["choices"][-1:]) == 0.0


def test_eight_shares_add_up_to_the_uncut_layer(moonlight):
    """The routed parts that the eight chips of a deployment compute, plus
    the shared experts once, are the whole layer as the reference has it."""
    whole, keys = _trainer(batch_size=1, router_experts=16, experts_held=16, expert_offset=0)
    math = moonlight.reference_math(keys)
    p = _layer(whole.init_state()["params"]["moe"], 0)
    bias = jnp.zeros(16)
    y = _x(whole)
    with jax.default_matmul_precision("highest"):
        want, _, _ = math.mixture(p, bias, y)
        shared = math.swiglu(p, "shared", y)
    choices, gates, _ = whole.route(y, p["router"], bias)
    total = shared
    for share in range(8):
        tr, _ = _trainer(batch_size=1, experts_held=2, expert_offset=2 * share)
        mine = {k: (v[2 * share: 2 * share + 2] if k.startswith("experts_") else v)
                for k, v in p.items()}
        routed, planned = tr._experts(mine, y, choices, gates)
        assert int(planned["dropped"]) == 0 and 0 < float(planned["live_tile_share"]) <= 1
        total = total + routed
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_dropless_when_every_token_goes_to_one_held_expert():
    tr, _ = _trainer(batch_size=1, seq_len=64)
    p = _layer(tr.init_state()["params"]["moe"], 0)
    y = _x(tr)
    tokens = y.shape[0]
    choices = jnp.tile(jnp.asarray([[5, 0, 15]], jnp.int32), (tokens, 1))  # only 5 is held (4..7)
    gates = jnp.full((tokens, 3), 0.5)
    out, planned = tr._experts(p, y, choices, gates)
    # the one expert's 64 rows in 8 tiles of 8, a tile of padding for each of the other three
    assert float(planned["live_tile_share"]) == pytest.approx(11 / (rows_for(tokens * 3, 4, 8) // 8))
    assert float(planned["tile_fill_share"]) == pytest.approx(8 / 11)
    e = 5 - tr.expert_offset
    want = 0.5 * (jax.nn.silu(y @ p["experts_gate"][e]) * (y @ p["experts_up"][e])) @ p["experts_down"][e]
    assert int(planned["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-5)
    none = plan_rows(jnp.full((tokens, 3), 4, jnp.int32), 4, 8)  # nothing held: a tile of padding an expert
    assert int(none.live_tiles) == 4 and int((none.source < tokens * 3).sum()) == 0
    plan = plan_rows(jnp.full((tokens, 3), 1, jnp.int32), 4, 8)  # and all of them, three times over
    assert int(plan.counts[1]) == tokens * 3 and int(plan.live_tiles) == tokens * 3 // 8 + 3
    assert int((plan.source < tokens * 3).sum()) == tokens * 3 <= rows_for(tokens * 3, 4, 8)


@pytest.mark.parametrize("d", [5, 256])
def test_rows_and_tokens_are_each_others_transpose(d):
    """The moves loop over the live tiles, forward and backward, and leave
    the rows past them alone (unwritten: whatever the buffer held); their
    vjps are exact. A width of whole lanes is added to the tokens as slabs."""
    rng = np.random.default_rng(0)
    owner = jnp.asarray(rng.integers(0, 6, (20, 2)).clip(max=4), jnp.int32)
    plan = plan_rows(owner, 4, 8)
    rows = rows_for(40, 4, 8)
    row_of = np.full(40, rows)  # the row of each assignment, from the plan's other direction
    row_of[np.asarray(plan.source)[np.asarray(plan.source) < 40]] = np.flatnonzero(np.asarray(plan.source) < 40)
    y = jnp.asarray(rng.normal(size=(20, d)), jnp.float32)
    gates = jnp.asarray(rng.normal(size=(20, 2)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(rows, d)), jnp.float32)

    def fast(y, gates):
        return jnp.sum(tokens_of_rows(rows_of_tokens(y, plan, 8) * wr, gates, plan, 8) ** 2)

    def plain(y, gates):
        held = owner < 4
        each = jnp.repeat(y, 2, axis=0) * wr[np.minimum(row_of, rows - 1)]
        return jnp.sum(jnp.einsum("tk,tkd->td", gates * held, each.reshape(20, 2, d)) ** 2)

    assert float(fast(y, gates)) == pytest.approx(float(plain(y, gates)), rel=1e-5)
    for a, b in zip(jax.grad(fast, (0, 1))(y, gates), jax.grad(plain, (0, 1))(y, gates)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
    # every held assignment has a row, each in a live tile, and a row's token is its assignment's
    np.testing.assert_array_equal(row_of < rows, np.asarray(owner).reshape(-1) < 4)
    assert row_of[row_of < rows].max() < int(plan.live_tiles) * 8
    np.testing.assert_array_equal(np.asarray(plan.token), np.minimum(np.asarray(plan.source) // 2, 20))
    moved = np.asarray(rows_of_tokens(y, plan, 8))[: int(plan.live_tiles) * 8]
    padding = np.asarray(plan.token)[: len(moved)] == 20
    assert padding.any() and not moved[padding].any()  # padding inside a live tile is zeros
    np.testing.assert_array_equal(moved[~padding], np.asarray(y)[np.asarray(plan.token)[: len(moved)][~padding]])


@pytest.mark.parametrize("case", ["spread", "one_expert", "none_held"])
def test_grouped_matmul_and_its_gradients(case):
    rng = np.random.default_rng(1)
    e, tile, k, n, a = 4, 16, 32, 48, 100
    owner = {"spread": rng.integers(0, e + 3, a).clip(max=e), "one_expert": np.full(a, 2),
             "none_held": np.full(a, e)}[case].astype(np.int32)
    plan = plan_rows(jnp.asarray(owner)[:, None], e, tile)
    rows = rows_for(a, e, tile)
    row_of = np.full(a, rows - 1)  # an assignment that is not held reads a row of padding: zeros
    row_of[np.asarray(plan.source)[np.asarray(plan.source) < a]] = np.flatnonzero(np.asarray(plan.source) < a)
    x, w, g = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((a, k), (e, k, n), (a, n)))

    def fast(x, w):
        xr = jnp.where((plan.source < a)[:, None], x[jnp.minimum(plan.source, a - 1)], 0)
        y = grouped_matmul(xr, w, plan, tile=tile, dtype=jnp.float32)
        return jnp.sum(y[row_of] * g)

    def plain(x, w):
        return jnp.sum(jnp.einsum("ak,akn->an", x, w[np.minimum(owner, e - 1)]) * (owner < e)[:, None] * g)

    assert float(fast(x, w)) == pytest.approx(float(plain(x, w)), rel=1e-5, abs=1e-5)
    for got, want in zip(jax.grad(fast, (0, 1))(x, w), jax.grad(plain, (0, 1))(x, w)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("case", ["spread", "one_expert", "none_held", "bfloat16", "nan_past_live"])
def test_grouped_swiglu_and_its_gradients(case):
    """The fused feed-forward of the held experts against the plain ``einsum``
    form and against the three public products with SwiGLU between them:
    value and the gradients of the rows and the three weights. Nothing reads
    the rows past the live tiles: filled with NaN in every input (the rows
    and, in the backward pass, the result's cotangent) they change nothing."""
    rng = np.random.default_rng(2)
    e, tile, k, n, a = 4, 16, 32, 48, 100
    owner = {"one_expert": np.full(a, 2), "none_held": np.full(a, e)}.get(
        case, rng.integers(0, e + 3, a).clip(max=e)).astype(np.int32)
    dtype = jnp.bfloat16 if case == "bfloat16" else jnp.float32
    plan = plan_rows(jnp.asarray(owner)[:, None], e, tile)
    rows = rows_for(a, e, tile)
    live = (jnp.arange(rows) < plan.live_tiles * tile)[:, None]
    assert rows > int(plan.live_tiles) * tile  # there are rows past the live tiles
    held = np.asarray(plan.source) < a
    row_of = np.zeros(a, np.int64)  # an assignment that is not held reads any row: masked below
    row_of[np.asarray(plan.source)[held]] = np.flatnonzero(held)
    shapes = ((a, k), (e, k, n), (e, k, n), (e, n, k), (a, k))
    x, wg, wu, wd, g = (jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32) for s in shapes)
    mask = jnp.asarray(owner < e)[:, None]

    @jax.custom_vjp
    def poison(y):  # the cotangent's rows past the live tiles become NaN
        return y

    poison.defvjp(lambda y: (y, None), lambda _, ct: (jnp.where(live, ct, jnp.nan),))

    def on_rows(feed_forward, nan=False):
        def loss(x, wg, wu, wd):
            xr = jnp.where((plan.source < a)[:, None], x[jnp.minimum(plan.source, a - 1)], 0)
            if nan:
                xr = jnp.where(live, xr, jnp.nan)
            y = feed_forward(xr, wg, wu, wd)
            y = jnp.where(live, poison(y) if nan else y, 0)
            return jnp.sum(jnp.where(mask, y[row_of], 0) * g)
        return loss

    def fused(dt):
        return lambda xr, *w: grouped_swiglu(xr, *w, plan, tile=tile, dtype=dt)

    def composed(dt):
        gm = functools.partial(grouped_matmul, plan=plan, tile=tile, dtype=dt)
        return lambda xr, wg, wu, wd: gm(jax.nn.silu(gm(xr, wg)) * gm(xr, wu), wd)

    def plain(x, wg, wu, wd):
        own = np.minimum(owner, e - 1)
        hidden = jax.nn.silu(jnp.einsum("ak,akn->an", x, wg[own])) * jnp.einsum("ak,akn->an", x, wu[own])
        return jnp.sum(jnp.where(mask, jnp.einsum("an,ank->ak", hidden, wd[own]), 0) * g)

    def both(f):
        value, grads = jax.value_and_grad(f, (0, 1, 2, 3))(x, wg, wu, wd)
        return [np.asarray(value)] + [np.asarray(t) for t in grads]

    got = both(on_rows(fused(dtype), nan=case == "nan_past_live"))
    assert all(np.isfinite(t).all() for t in got)
    # the same rounding points as the three products composed: equal but for the order of sums
    for have, want in zip(got, both(on_rows(composed(dtype)))):
        np.testing.assert_allclose(have, want, rtol=1e-5, atol=1e-5)
    # and against the plain form: float32 exactly that, bfloat16 operands a rounding away
    close = dict(rtol=1e-4, atol=1e-4) if dtype == jnp.float32 else dict(rtol=0.1, atol=0.05)
    for have, want in zip(got, both(plain)):
        np.testing.assert_allclose(have, want, **close)
    if case == "none_held":
        assert not any(t.any() for t in got)


@pytest.mark.parametrize("kernel", ["flash", "ring"])
def test_blockwise_attention_at_unequal_key_and_value_widths(kernel):
    """192-wide keys and 128-wide values in miniature: the running output
    takes the values' width, forward and backward."""
    b, seq, h, dk, dv = 2, 64, 2, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(kk, (b, seq, h, dk)) for kk in ks[:2])
    v, w = (jax.random.normal(kk, (b, seq, h, dv)) for kk in ks[2:])
    if kernel == "ring":
        mesh = make_mesh({SEQ_AXIS: 8})
        attend = lambda q, k, v: ring_attention(mesh, q, k, v, causal=True)  # noqa: E731
    else:
        fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, seq, -1)  # noqa: E731
        attend = lambda q, k, v: flash_attention(  # noqa: E731
            fold(q), fold(k), fold(v), block=16, dtype=jnp.float32
        ).reshape(b, h, seq, dv).transpose(0, 2, 1, 3)
    want = reference_attention(q, k, v, causal=True)
    assert want.shape == (b, seq, h, dv)
    np.testing.assert_allclose(np.asarray(attend(q, k, v)), np.asarray(want), rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(attend(*a) * w), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(reference_attention(*a, causal=True) * w), (0, 1, 2))(q, k, v)
    for x, y in zip(got, ref):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=5e-3, atol=5e-4)


def test_attention_flops_count_the_causal_pairs():
    got = attention_flops(8192, 16, 192, 128)
    pairs = 16 * 8192 * 8193 / 2
    assert got == {"fwd": 2 * pairs * 320, "dq": 2 * pairs * 512, "dkv": 2 * pairs * 640}


def test_bfloat16_operands_stay_close_and_the_step_is_scoped():
    """The cell's precision (bfloat16 operands) moves the loss by rounding
    only; every operation of the compiled step carries a phase."""
    exact, _ = _trainer()
    rounded, _ = _trainer(matmul_dtype="bfloat16")
    state = exact.init_state()
    tokens = jnp.asarray(next(iter(exact.batches()))["tokens"])
    want, _ = jax.jit(exact.loss_fn)(state["params"], {"tokens": tokens}, state)
    got, _ = jax.jit(rounded.loss_fn)(state["params"], {"tokens": tokens}, state)
    assert float(got) == pytest.approx(float(want), rel=2e-3) and float(got) != float(want)
    text = jax.jit(rounded.train_step).lower(
        state, {"tokens": tokens}, jax.random.PRNGKey(0)).as_text(debug_info=True)
    for phase in ("attn", "mlp", "route", "experts", "head", "opt"):
        assert f"phase_{phase}" in text, phase


def test_runs_under_train_loop_from_a_file_of_ids(tmp_path):
    """``data: x.npy`` + ``vocab_size``: the entry path reads token ids; the
    loop drives the trainer like every other family."""
    path = str(tmp_path / "ids.npy")
    np.save(path, np.random.default_rng(0).integers(0, 64, 2000).astype(np.int32))
    cfg = Config()
    for k, v in {**KEYS, "data": path, "shard_data": 0, "num_iters": 1}.items():
        cfg.set(k, str(v))
    tr = MoELMTrainer(cfg)
    assert tr.vocab_size == 64 and len(tr.corpus_ids) == 2000
    state = TrainLoop(tr, metrics=MetricsLogger(echo=False), log_every=0).run(max_steps=3)
    assert int(state["dropped"]) == 0 and float(jnp.abs(state["router_bias"]).max()) > 0
    with pytest.raises(ValueError):
        MoELMTrainer(cfg, mesh=make_mesh({SEQ_AXIS: 8}))
