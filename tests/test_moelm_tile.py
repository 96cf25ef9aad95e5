"""The expert layout's row tile follows the load (``ops/grouped_matmul.tile_for``,
picked by ``MoELMTrainer._read_shape``): the rule at the benchmark's three
cells' shapes, the fused feed-forward at the tiles the rule picks from, and a
small trainer whose step does not depend on the tile."""

import functools
import glob
import json
import logging
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.models.moelm import MoELMTrainer
from swiftsnails_tpu.ops.grouped_matmul import (
    TILE, TILES, grouped_matmul, grouped_swiglu, plan_rows, rows_for, tile_for)
from swiftsnails_tpu.utils.config import Config

KEYS = dict(  # tests/test_moelm.py's small model
    model="moelm", seq_len=32, batch_size=2, hidden_size=32, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=12, rope_theta=50000, rms_norm_eps=1e-5, intermediate_size=64,
    moe_intermediate_size=16, n_shared_experts=2, num_experts_per_tok=3,
    routed_scaling_factor=2.446, router_experts=16, experts_held=4, expert_offset=4,
    vocab_size=64, optimizer="adamw", learning_rate=1e-3, bias_update_rate=0.001, aux_loss_alpha=0.0001,
    init_std=0.05, loss_chunks=4, num_iters=2, matmul_dtype="float32", remat=1)
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "configs")


def _trainer(keys, **over):
    cfg = Config()
    for k, v in {**keys, **over}.items():
        cfg.set(k, str(v))
    ids = np.random.default_rng(7).integers(0, 60, 3000)
    return MoELMTrainer(cfg, corpus_ids=ids, vocab_size=cfg.get_int("vocab_size"))


def _cell_keys(config):
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        return json.load(f).get("keys", {})


def test_every_language_model_cell_is_in_the_rules_table():
    configs = [os.path.basename(p)[:-5] for p in glob.glob(os.path.join(CONFIGS, "*.json"))]
    cells = {c for c in configs if _cell_keys(c).get("model") == "moelm"}
    mixture = {c for c in cells
               if int(_cell_keys(c)["first_k_dense_replace"]) < int(_cell_keys(c)["num_hidden_layers"])}
    assert mixture == {"solar-open2-250b", "sdar-30b-a3b", "moonlight-16b-a3b", "keye-vl-2.0-30b-a3b"}
    assert cells - mixture == {"olmo-hybrid-7b"}  # a dense stack: no expert layout, no tile to pick


@pytest.mark.parametrize("config,over,expected,tile", [
    ("solar-open2-250b", {}, 2048 * 8 / 320, 128),
    ("sdar-30b-a3b", {}, 8192 * 8 / 128, 512),  # both copies of the 4,096 tokens are routed
    ("moonlight-16b-a3b", {}, 8192 * 6 / 64, 512),
    ("keye-vl-2.0-30b-a3b", {}, 16384 * 8 / 128, 512),
    # what the deployment's five head groups, a sequence each, would hand one of Solar's chips
    ("solar-open2-250b", {"batch_size": 5}, 256.0, 512),
], ids=["solar", "sdar", "moonlight", "keye", "solar-at-a-deployments-load"])
def test_the_tile_follows_the_load_a_held_expert_expects(caplog, config, over, expected, tile):
    """The trainer reads the tile from its shapes: the assignments a held
    expert expects a step, and nothing that names a model."""
    with caplog.at_level(logging.INFO, logger="swiftsnails_tpu.models.moelm"):
        tr = _trainer(_cell_keys(config), **over)
    assert tr.expert_tile == tile == tile_for(expected)
    said = [r.getMessage() for r in caplog.records if "row tile" in r.getMessage()]
    assert said == [f"experts: row tile {tile} for {expected:.1f} assignments expected a held expert a step"]
    assert MoELMTrainer.expert_tile == TILE  # the class keeps the kernels' own: a test sets smaller ones


def test_the_rule_is_the_smallest_tile_that_holds_twice_the_expected_load():
    assert TILES == (128, 256, 512) and TILE == 512
    assert [tile_for(x) for x in (0.0, 51.2, 64.0, 64.1, 128.0, 128.1, 256.0, 512.0, 4096.0)] == [
        128, 128, 128, 256, 256, 512, 512, 512, 512]
    assert rows_for(2048 * 8, 8, 128) == 17408 and rows_for(2048 * 8, 8) == 20480


@pytest.mark.parametrize("tile", [128, 256])
def test_grouped_swiglu_at_the_small_tiles(tile):
    """The tiles the rule adds, in interpret mode: an expert nobody chose
    (a tile of padding, a zero weight gradient), one that needs three tiles,
    one that fills a tile exactly and one far under a tile; value and the
    four gradients against the three public products with SwiGLU between
    them and against a plain loop over the experts."""
    rng = np.random.default_rng(tile)
    e, k, n = 4, 32, 48
    counts = [0, 2 * tile + 44, tile, 37]
    owner = np.concatenate([np.full(c, i) for i, c in enumerate(counts)] + [np.full(29, e)]).astype(np.int32)
    rng.shuffle(owner)
    a = owner.size
    plan = plan_rows(jnp.asarray(owner)[:, None], e, tile)
    assert np.asarray(plan.counts).tolist() == counts and int(plan.live_tiles) == 1 + 3 + 1 + 1
    assert np.asarray(plan.tile_owner)[:6].tolist() == [0, 1, 1, 1, 2, 3]
    rows = rows_for(a, e, tile)
    live = (jnp.arange(rows) < plan.live_tiles * tile)[:, None]
    held = np.asarray(plan.source) < a
    row_of = np.zeros(a, np.int64)  # an assignment that is not held reads any row: masked below
    row_of[np.asarray(plan.source)[held]] = np.flatnonzero(held)
    shapes = ((a, k), (e, k, n), (e, k, n), (e, n, k), (a, k))
    x, wg, wu, wd, g = (jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32) for s in shapes)
    mask = jnp.asarray(owner < e)[:, None]

    def on_rows(feed_forward):
        def loss(x, wg, wu, wd):
            xr = jnp.where((plan.source < a)[:, None], x[jnp.minimum(plan.source, a - 1)], 0)
            y = jnp.where(live, feed_forward(xr, wg, wu, wd), 0)
            return jnp.sum(jnp.where(mask, y[row_of], 0) * g)
        return loss

    def fused(xr, *w):
        return grouped_swiglu(xr, *w, plan, tile=tile, dtype=jnp.float32)

    def composed(xr, wg, wu, wd):
        gm = functools.partial(grouped_matmul, plan=plan, tile=tile, dtype=jnp.float32)
        return gm(jax.nn.silu(gm(xr, wg)) * gm(xr, wu), wd)

    def loop(x, wg, wu, wd):
        y = jnp.zeros_like(x)
        for i in range(e):
            mine = jnp.asarray(owner == i)[:, None]
            y = y + jnp.where(mine, (jax.nn.silu(x @ wg[i]) * (x @ wu[i])) @ wd[i], 0)
        return jnp.sum(y * g)

    def both(f):
        value, grads = jax.value_and_grad(f, (0, 1, 2, 3))(x, wg, wu, wd)
        return [np.asarray(value)] + [np.asarray(t) for t in grads]

    got = both(on_rows(fused))
    for have, want in zip(got, both(on_rows(composed))):
        np.testing.assert_allclose(have, want, rtol=1e-5, atol=1e-5)
    for have, want in zip(got, both(loop)):
        np.testing.assert_allclose(have, want, rtol=1e-4, atol=1e-4)
    for dw in got[2:]:  # the expert nobody chose: its gradient is written, as zeros
        assert not dw[0].any() and dw[1].any() and dw[2].any() and dw[3].any()


def test_a_step_does_not_depend_on_the_tile():
    """Loss and every leaf's gradient at two tiles agree to float32's
    rounding (the order of ``dw``'s sum over an expert's tiles is all that
    differs), and ``moe_tile_fill_share`` is what the step's counts say of
    each tile: held assignments over the live tiles' rows."""
    tr = _trainer(KEYS)
    tr.attention_block = 16
    state = tr.init_state()
    batch = {"tokens": jnp.asarray(next(iter(tr.batches()))["tokens"])}
    lo, hi = tr.expert_offset, tr.expert_offset + tr.experts_held
    seen = {}
    for tile in (8, 16):
        tr.expert_tile = tile
        (loss, aux), grads = jax.jit(jax.value_and_grad(tr.loss_fn, has_aux=True))(state["params"], batch, state)
        _, metrics = tr.after_update(state, aux)
        held = np.asarray(aux["counts"])[:, lo:hi]
        want = np.mean(held.sum(axis=1) / (np.maximum(1, -(-held // tile)).sum(axis=1) * tile))
        assert float(metrics["moe_tile_fill_share"]) == pytest.approx(float(want), rel=1e-6)
        assert 0 < float(metrics["moe_tile_fill_share"]) <= 1 and int(metrics["moe_dropped"]) == 0
        seen[tile] = (float(loss), grads, float(metrics["moe_tile_fill_share"]))
    assert seen[8][0] == pytest.approx(seen[16][0], rel=1e-6)
    assert seen[8][2] > seen[16][2]  # the smaller tile carries less padding
    for a, b in zip(jax.tree_util.tree_leaves(seen[8][1]), jax.tree_util.tree_leaves(seen[16][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
