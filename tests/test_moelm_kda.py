"""The block stack's layer table and its third kind of mixer
(``models/moelm.py``: a gated delta-rule layer with its convolutions, gates
and gated head norm; a gated grouped-query softmax layer without rotary;
heads held as the chip's share) against the benchmark's plain float32
reference (``benchmark/models/solar.py``: the recurrence a token at a time),
small and on the CPU: the kernels run in interpret mode, matrix operands stay
float32 so that the two agree closely. And that the two models the stack ran
before the table are what they were, by values recorded from the parent."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.framework.trainer import TrainLoop
from swiftsnails_tpu.models.moelm import MoELMTrainer
from swiftsnails_tpu.utils.config import Config
from swiftsnails_tpu.utils.metrics import MetricsLogger

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

KEYS = {
    "model": "moelm", "seq_len": 24, "batch_size": 2, "hidden_size": 64, "num_hidden_layers": 4,
    "first_k_dense_replace": 0, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "use_rope": 0, "qk_norm": 0, "use_gqa_gate": 1, "gqa_layers": "[0]",
    "linear_attn_config.num_heads": 4, "linear_attn_config.head_dim": 16,
    "linear_attn_config.short_conv_kernel_size": 4, "kda_allow_neg_eigval": 1, "rms_norm_eps": 1e-5, "moe_intermediate_size": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 3, "routed_scaling_factor": 1, "router_experts": 16, "experts_held": 4,
    "expert_offset": 4, "vocab_size": 64, "optimizer": "adamw", "learning_rate": 1e-3, "adam_b1": 0.9,
    "adam_b2": 0.95, "adam_eps": 1e-8, "weight_decay": 0.1, "bias_update_rate": 0.001,
    "aux_loss_alpha": 0.0001, "init_std": 0.05, "loss_chunks": 4, "num_iters": 2,
    "matmul_dtype": "float32", "remat": 1}


@pytest.fixture(scope="module")
def solar():
    """``benchmark/models/solar.py``, the reference's home."""
    sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_models_solar_t", os.path.join(BENCH, "models", "solar.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trainer(keys=KEYS, **over):
    cfg = Config()
    for k, v in {**keys, **over}.items():
        cfg.set(k, str(v))
    ids = np.random.default_rng(7).integers(0, 63, 3000)
    tr = MoELMTrainer(cfg, corpus_ids=ids, vocab_size=cfg.get_int("vocab_size"))
    tr.attention_block, tr.expert_tile, tr.kda_chunk = 8, 8, 8  # several blocks, tiles and chunks at this size
    return tr, {**keys, **over, "kda_chunk": 8}  # the reference's state_dropped needs the chunk


def _layer(tree, i):
    return {k: v[i] for k, v in tree.items()}


def _x(tr, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (tr.batch_size * tr.seq_len, tr.d_model))


def _rows(tr, fn, x):
    """``fn`` of one sequence over every row of ``x [B * L, d]``."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.vmap(fn))(x.reshape(tr.batch_size, tr.seq_len, -1)).reshape(x.shape)


# --------------------------------------------------------- the layer table ---


def test_layer_table_and_parameter_tree(solar):
    tr, keys = _trainer()
    assert tr.mixers == ("gqa", "kda", "kda", "kda")
    assert tr._layer_table() == [(("gqa", 0), ("moe", 0)), (("kda", 0), ("moe", 1)),
                                 (("kda", 1), ("moe", 2)), (("kda", 2), ("moe", 3))]
    mine = {k: tuple(v) for k, v in solar._flatten(tr.param_shapes()).items()}
    assert mine == solar.shapes(keys)
    assert mine["gqa.wz"] == (1, 64, 64) and "gqa.q_norm" not in mine  # gated, no q/k norm
    assert mine["kda.conv_k"] == (3, 4, 64) and mine["kda.a_log"] == (3, 4) and mine["kda.wb"] == (3, 64, 4)
    assert mine["moe.router"] == (4, 64, 16) and "moe.wq" not in mine  # the feed-forward parts over all layers
    state = tr.init_state()
    kda = state["params"]["kda"]
    assert float(kda["o_norm"].min()) == float(kda["attn_norm"].max()) == 1.0
    assert 0.0 <= float(kda["a_log"].min()) and float(kda["a_log"].max()) <= np.log(16.0)
    rate = jax.nn.softplus(kda["dt_bias"])
    assert 1e-3 * 0.999 <= float(rate.min()) and float(rate.max()) <= 0.1 * 1.001
    assert float(jnp.abs(kda["conv_q"]).max()) <= 0.5 and float(jnp.abs(kda["conv_q"]).mean()) > 0.2
    assert float(state["kda_decay"]) == 0.0 and set(state) >= {"router_bias", "counts", "choices", "dropped"}
    two, _ = _trainer(num_hidden_layers=8, gqa_layers="[0, 4]")  # two periods: the table goes on
    assert two.mixers == ("gqa", "kda", "kda", "kda") * 2 and two._layer_table()[5] == (("kda", 3), ("moe", 5))
    assert two.param_shapes()["gqa"]["wq"] == (2, 64, 64) and two.param_shapes()["kda"]["wq"] == (6, 64, 64)


def test_gated_softmax_layer_without_rotary_matches_reference(solar):
    tr, keys = _trainer()
    math = solar.reference_math(keys)
    p = _layer(tr.init_state()["params"]["gqa"], 0)
    x = _x(tr)
    got = jax.jit(lambda p, x: tr._attention(p, x, tr.batch_size))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_rows(tr, lambda r: math.attention(p, r), x)),
                               rtol=2e-4, atol=2e-5)
    ungated = solar.reference_math(keys, fault="no_gqa_gate")
    gap = jnp.abs(got - _rows(tr, lambda r: ungated.attention(p, r), x)).max()
    assert float(gap) > 0.1 * float(jnp.abs(got).max())  # the gate is there
    # no rotary: a row's output does not turn on where the row starts
    late = jax.jit(lambda p, x: tr._attention(p, x, tr.batch_size, jnp.arange(tr.seq_len) + 100))(p, x)
    np.testing.assert_array_equal(np.asarray(late), np.asarray(got))


def test_delta_rule_layer_matches_reference(solar):
    tr, keys = _trainer()
    math = solar.reference_math(keys)
    p = dict(_layer(tr.init_state()["params"]["kda"], 1))
    p["o_norm"] = jnp.linspace(0.5, 1.5, 16)  # a gain that shows
    x = _x(tr)
    got, decay = jax.jit(lambda p, x: tr._kda(p, x, tr.batch_size))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_rows(tr, lambda r: math.kda(p, r), x)),
                               rtol=2e-4, atol=2e-5)
    assert 0.5 < float(decay) < 1.0
    for fault in ("no_decay", "beta_not_doubled", "no_conv", "no_kda_gate", "state_dropped"):
        other = _rows(tr, lambda r: solar.reference_math(keys, fault=fault).kda(p, r), x)
        assert float(jnp.abs(got - other).max()) > 0.02 * float(jnp.abs(got).max()), fault
    # the convolution and the state start at zero with each row: a row alone gives the same
    alone, _ = jax.jit(lambda p, x: tr._kda(p, x, 1))(p, x[tr.seq_len:])
    np.testing.assert_allclose(np.asarray(alone), np.asarray(got[tr.seq_len:]), rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------- the step ---


def test_train_steps_match_reference(solar):
    """The cell's five numbers at a small size: three losses, every leaf's
    first gradient (from AdamW's first moment) and every leaf's change after
    three AdamW steps with the selection bias's."""
    tr, keys = _trainer()
    state = tr.init_state()
    w = solar._flatten(state["params"])
    it = iter(tr.batches())
    batches = [next(it) for _ in range(3)]
    ref = solar.solar_reference(dict(w), batches, keys)
    step = jax.jit(tr.train_step)
    start, losses = state["params"], []
    for i, b in enumerate(batches):
        state, m = step(state, {k: jnp.asarray(v) for k, v in b.items()}, jax.random.PRNGKey(0))
        losses.append(float(m["loss"]))
        if i == 0:
            grad1 = {k: float(jnp.sum(v * v)) / (1 - 0.9) ** 2
                     for k, v in solar._flatten(state["opt"][0].mu).items()}
        assert int(m["moe_dropped"]) == 0
        assert 0.5 < float(m["kda_decay_mean"]) < 1.0 and float(state["kda_decay"]) == float(m["kda_decay_mean"])
        assert float(m["attn_whole_tile_share"]) == 0.5  # the softmax layer's three causal tiles a side: 3 of 6
    np.testing.assert_allclose(losses, ref["loss"], rtol=2e-5)
    assert set(grad1) == set(ref["grad1"]) == set(solar.shapes(keys))
    for k, want in ref["grad1"].items():
        assert grad1[k] == pytest.approx(want, rel=2e-3, abs=1e-12), k
    change = {k: float(jnp.sum((v - solar._flatten(start)[k]) ** 2))
              for k, v in solar._flatten(state["params"]).items()}
    for k, want in ref["change"].items():
        if k != "router_bias":
            assert change[k] == pytest.approx(want[-1], rel=5e-3), k
    assert float(jnp.sum(state["router_bias"] ** 2)) == pytest.approx(ref["change"]["router_bias"][-1], rel=1e-5)
    assert solar.disagree_share([np.asarray(state["choices"])], ref["choices"][-1:]) == 0.0


@pytest.mark.parametrize("part", ["control", "half_batch", "state_unchanged", "no_decay", "beta_not_doubled",
                                  "no_conv", "no_kda_gate", "no_gqa_gate", "state_dropped"])
def test_control_and_faults_read_far_from_the_reference(solar, part):
    """The bfloat16 control, and every fault the reference can plant, moves
    the first loss or the first gradient by far more than the 2e-5 and 2e-3
    the program is held to above."""
    tr, keys = _trainer()
    w = solar._flatten(tr.init_state()["params"])
    batches = [next(iter(tr.batches()))]
    sound = solar.solar_reference(dict(w), batches, keys)
    other = solar.solar_reference(dict(w), batches, keys, **(
        {"precision": "bfloat16"} if part == "control" else {"fault": part}))
    loss_gap = abs(other["loss"][0] - sound["loss"][0]) / sound["loss"][0]
    grad_gap = max(abs(np.sqrt(other["grad1"][k]) - np.sqrt(v)) / np.sqrt(v)
                   for k, v in sound["grad1"].items() if v > 0)
    change_gap = max(abs(np.sqrt(other["change"][k][0]) - np.sqrt(v[0])) / np.sqrt(v[0])
                     for k, v in sound["change"].items() if v[0] > 0)
    assert loss_gap > 2e-4 or grad_gap > 2e-2 or change_gap > 0.5, (loss_gap, grad_gap, change_gap)


def test_bfloat16_operands_stay_close_and_the_step_is_scoped(tmp_path):
    exact, _ = _trainer()
    rounded, _ = _trainer(matmul_dtype="bfloat16")
    state = exact.init_state()
    batch = {k: jnp.asarray(v) for k, v in next(iter(exact.batches())).items()}
    want, _ = jax.jit(exact.loss_fn)(state["params"], batch, state)
    got, _ = jax.jit(rounded.loss_fn)(state["params"], batch, state)
    assert float(got) == pytest.approx(float(want), rel=2e-3) and float(got) != float(want)
    text = jax.jit(rounded.train_step).lower(state, batch, jax.random.PRNGKey(0)).as_text(debug_info=True)
    for phase in ("kda", "kda_core", "attn", "route", "experts", "mlp", "head", "opt"):
        assert f"phase_{phase}" in text, phase
    path = str(tmp_path / "ids.npy")
    np.save(path, np.random.default_rng(0).integers(0, 64, 2000).astype(np.int32))
    cfg = Config()
    for k, v in {**KEYS, "data": path, "shard_data": 0, "num_iters": 1}.items():
        cfg.set(k, str(v))
    tr = MoELMTrainer(cfg)
    tr.attention_block, tr.expert_tile, tr.kda_chunk = 8, 8, 8
    state = TrainLoop(tr, metrics=MetricsLogger(echo=False), log_every=0).run(max_steps=3)
    assert int(state["dropped"]) == 0 and 0.5 < float(state["kda_decay"]) < 1.0


# ------------------------------------------- the share ties to the model ---


def _head_share(kind, p, share, heads, width):
    """Layer ``p`` of 4 heads (2 key/value heads) as the chip that holds
    ``heads`` of them from ``share * heads`` on holds it: slices by head of
    what is per head, what every chip computes alike as it is."""
    cols = slice(share * heads * width, (share + 1) * heads * width)
    if kind == "gqa":
        kv = slice(share * width, (share + 1) * width)  # one key/value head to two query heads
        return {**p, "wq": p["wq"][:, cols], "wz": p["wz"][:, cols], "wk": p["wk"][:, kv],
                "wv": p["wv"][:, kv], "wo": p["wo"][cols]}
    mine = {k: p[k][:, cols] for k in ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "f_up", "g_up")}
    held = slice(share * heads, (share + 1) * heads)
    return {**p, **mine, "a_log": p["a_log"][held], "dt_bias": p["dt_bias"][cols], "wb": p["wb"][:, held],
            "wo": p["wo"][cols]}


@pytest.mark.parametrize("kind", ["gqa", "kda"])
def test_the_shares_of_the_heads_add_up_to_the_whole_mixer(solar, kind):
    """What the chips of a head group compute (here two, two heads each; the
    norm and the gates' first halves alike in each) are partial sums of the
    whole mixer's output as the reference has it."""
    whole, keys = _trainer()
    math = solar.reference_math(keys)
    p = _layer(whole.init_state()["params"][kind], 0)
    x = _x(whole)
    want = _rows(whole, lambda r: (math.attention if kind == "gqa" else math.kda)(p, r), x)
    half, _ = _trainer(num_attention_heads=2, num_key_value_heads=1, **{"linear_attn_config.num_heads": 2})
    assert half.param_shapes()[kind]["wo"] == (1 if kind == "gqa" else 3, 32, 64)
    mix = (lambda p, x: half._attention(p, x, half.batch_size)) if kind == "gqa" else (
        lambda p, x: half._kda(p, x, half.batch_size)[0])
    parts = [jax.jit(mix)(_head_share(kind, p, share, 2, 16), x) for share in range(2)]
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_the_shares_of_the_experts_and_the_shared_expert_once_add_up_to_the_uncut_layer(solar):
    whole, keys = _trainer(batch_size=1, experts_held=16, expert_offset=0)
    math = solar.reference_math(keys)
    p = _layer(whole.init_state()["params"]["moe"], 2)
    y = _x(whole)
    bias = jnp.linspace(-0.02, 0.02, 16)
    with jax.default_matmul_precision("highest"):
        want, _, _ = math.mixture(p, bias, y)
    choices, gates, _ = whole.route(y, p["router"], bias)
    total = whole._swiglu(p, "shared", y)  # every chip computes it: counted once
    for share in range(4):
        tr, _ = _trainer(batch_size=1, experts_held=4, expert_offset=4 * share)
        mine = {k: (v[4 * share: 4 * share + 4] if k.startswith("experts_") else v) for k, v in p.items()}
        routed, planned = tr._experts(mine, y, choices, gates)
        assert int(planned["dropped"]) == 0
        total = total + routed
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_parameters_held_and_of_the_whole_model_from_the_configurations_keys(solar):
    with open(os.path.join(BENCH, "configs", "solar-open2-250b.json")) as f:
        config = json.load(f)
    keys, published = config["keys"], config["published"]
    assert solar.parameters_held(keys) == 840_871_320  # 13.45 GB at 16 B
    whole = {**keys, "num_hidden_layers": published["num_hidden_layers"],
             "num_attention_heads": published["num_attention_heads"],
             "num_key_value_heads": published["num_key_value_heads"],
             "linear_attn_config.num_heads": published["linear_attn_config"]["num_heads"],
             "experts_held": published["n_routed_experts"], "vocab_size": published["vocab_size"]}
    assert solar._dims(whole)["kinds"].count("gqa") == 12 and solar._dims(whole)["kinds"].count("kda") == 36
    assert solar.parameters_held(whole) == 250_287_794_944  # the 250B of its name
    all_heads = {**keys, "num_attention_heads": 64, "num_key_value_heads": 8, "linear_attn_config.num_heads": 64}
    assert solar.parameters_held(all_heads) == 1_295_086_144  # 20.72 GB: why the heads are shared out too
    tr, _ = _trainer(keys, seq_len=64, matmul_dtype="float32")
    mine = {k: tuple(v) for k, v in solar._flatten(tr.param_shapes()).items()}
    assert mine == solar.shapes(keys)  # the program's tree at the published widths


# ------------------------------- the models the stack ran before the table ---

MOONLIGHT = dict(
    model="moelm", seq_len=32, batch_size=2, hidden_size=32, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=12, rope_theta=50000, rms_norm_eps=1e-5, intermediate_size=64,
    moe_intermediate_size=16, n_shared_experts=2, num_experts_per_tok=3,
    routed_scaling_factor=2.446, router_experts=16, experts_held=4, expert_offset=4,
    vocab_size=64, optimizer="adamw", learning_rate=1e-3, adam_b1=0.9, adam_b2=0.95,
    adam_eps=1e-8, weight_decay=0.1, bias_update_rate=0.001, aux_loss_alpha=0.0001,
    init_std=0.05, loss_chunks=4, num_iters=2, matmul_dtype="float32", remat=1, seed=36)
SDAR = dict(
    model="moelm", seq_len=32, batch_size=2, hidden_size=32, num_hidden_layers=2,
    first_k_dense_replace=0, num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    rope_theta=1000000, rms_norm_eps=1e-6, moe_intermediate_size=16, n_shared_experts=0,
    num_experts_per_tok=3, scoring_func="softmax", router_experts=16, experts_held=4,
    expert_offset=4, vocab_size=64, block_length=4, mask_token_id=63,
    optimizer="adamw", learning_rate=1e-3, adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8,
    weight_decay=0.1, bias_update_rate=0, aux_loss_alpha=0, init_std=0.05, loss_chunks=4,
    num_iters=2, matmul_dtype="float32", remat=1, seed=36)
_ATTN_MLA = {"attn_norm": (32,), "kv_norm": (16,), "wkv_a": (32, 24), "wkv_b": (16, 56), "wo": (24, 32),
             "wq": (32, 48), "mlp_norm": (32,)}
_EXPERTS = {"experts_down": (4, 16, 32), "experts_gate": (4, 32, 16), "experts_up": (4, 32, 16),
            "router": (32, 16)}
# recorded at the parent (d6c4c60, before the layer table; my CPU run, PR 36): the tree and, from
# ``init_state`` at seed 36 over the corpus of ``_trainer``, the three first steps' losses
BEFORE = {
    "moonlight": (MOONLIGHT, {
        "embed": (64, 32), "final_norm": (32,), "head": (32, 64),
        "dense": {k: (1,) + s for k, s in {**_ATTN_MLA, "mlp_down": (64, 32), "mlp_gate": (32, 64),
                                           "mlp_up": (32, 64)}.items()},
        "moe": {k: (2,) + s for k, s in {**_ATTN_MLA, **_EXPERTS, "shared_down": (32, 32),
                                         "shared_gate": (32, 32), "shared_up": (32, 32)}.items()}},
        ["choices", "counts", "dropped", "opt", "params", "router_bias"],
        [4.209514617919922, 4.175540447235107, 4.257844924926758]),
    "sdar": (SDAR, {
        "embed": (64, 32), "final_norm": (32,), "head": (32, 64),
        "moe": {k: (2,) + s for k, s in {**_EXPERTS, "attn_norm": (32,), "mlp_norm": (32,), "q_norm": (8,),
                                         "k_norm": (8,), "wq": (32, 64), "wk": (32, 16), "wv": (32, 16),
                                         "wo": (64, 32)}.items()}},
        ["choices", "counts", "dropped", "noised", "opt", "params", "router_bias"],
        [2.5519604682922363, 3.1433181762695312, 5.316507339477539]),
}


@pytest.mark.parametrize("model", sorted(BEFORE))
def test_the_models_before_the_table_are_what_they_were(model):
    keys, tree, state_keys, losses = BEFORE[model]
    tr, _ = _trainer(keys)
    tr.attention_block, tr.expert_tile = 16, 8
    assert len(set(tr.mixers)) == 1  # one kind: its leaves stay with the layer's feed-forward part
    assert tr.param_shapes() == tree
    state = tr.init_state()
    assert sorted(state) == state_keys
    step, it, got = jax.jit(tr.train_step), iter(tr.batches()), []
    for _ in range(3):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, m = step(state, batch, jax.random.PRNGKey(0))
        got.append(float(m["loss"]))
        assert "kda_decay_mean" not in m
    np.testing.assert_allclose(got, losses, rtol=1e-6)
