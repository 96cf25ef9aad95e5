"""A dense stack with OLMo 2's residual order and the second kind of delta
rule (``models/moelm.py``: Gated DeltaNet, a decay per head, keys narrower
than values, a SiLU gate; the softmax layer's q/k norm over the whole
projection; no mixture layer at all) against the benchmark's plain float32
reference (``benchmark/models/olmo_hybrid.py``: the recurrence a token at a
time), small and on the CPU: the kernels run in interpret mode, matrix
operands stay float32 so that the two agree closely."""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.framework.trainer import TrainLoop
from swiftsnails_tpu.models import moelm
from swiftsnails_tpu.models.moelm import MoELMTrainer
from swiftsnails_tpu.ops import gated_delta
from swiftsnails_tpu.ops.gated_delta import gated_delta_rule, recurrence
from swiftsnails_tpu.utils.config import Config, ConfigError
from swiftsnails_tpu.utils.metrics import MetricsLogger

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
PERIOD = ["linear_attention"] * 3 + ["full_attention"]

KEYS = {
    "model": "moelm", "seq_len": 32, "batch_size": 2, "hidden_size": 64, "num_hidden_layers": 4,
    "first_k_dense_replace": 4, "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 16,
    "use_rope": 0, "qk_norm": "width", "use_gqa_gate": 0, "norm_order": "post",
    "layer_types": PERIOD, "linear_num_key_heads": 2, "linear_num_value_heads": 2, "linear_key_head_dim": 16,
    "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": 1, "rms_norm_eps": 1e-6,
    "intermediate_size": 48, "vocab_size": 64, "optimizer": "adamw", "learning_rate": 1e-3, "adam_b1": 0.9,
    "adam_b2": 0.95, "adam_eps": 1e-8, "weight_decay": 0.1, "init_std": 0.05, "loss_chunks": 4, "num_iters": 2,
    "matmul_dtype": "float32", "remat": 1}


@pytest.fixture(scope="module")
def olmo():
    """``benchmark/models/olmo_hybrid.py``, the reference's home."""
    sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_models_olmo_t", os.path.join(BENCH, "models", "olmo_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trainer(keys=KEYS, **over):
    cfg = Config()
    for k, v in {**keys, **over}.items():
        cfg.set(k, str(v))
    ids = np.random.default_rng(7).integers(0, 63, 3000)
    tr = MoELMTrainer(cfg, corpus_ids=ids, vocab_size=cfg.get_int("vocab_size"))
    tr.attention_block, tr.kda_chunk = 16, 16  # two blocks and two chunks a row at this size
    return tr, {**keys, **over, "kda_chunk": 16}  # the reference's state_dropped needs the chunk


def _layer(tree, i):
    return {k: v[i] for k, v in tree.items()}


def _x(tr, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (tr.batch_size * tr.seq_len, tr.d_model))


def _rows(tr, fn, x):
    """``fn`` of one sequence over every row of ``x [B * L, d]``."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.vmap(fn))(x.reshape(tr.batch_size, tr.seq_len, -1)).reshape(x.shape)


# --------------------------------------------------------- the layer table ---


def test_layer_table_and_a_dense_stack_without_any_mixture_state(olmo):
    tr, keys = _trainer()
    assert tr.mixers == ("gdn", "gdn", "gdn", "gqa") and tr.n_moe == 0 and tr.post_norm
    assert tr._layer_table() == [(("gdn", 0), ("dense", 0)), (("gdn", 1), ("dense", 1)),
                                 (("gdn", 2), ("dense", 2)), (("gqa", 0), ("dense", 3))]
    mine = {k: tuple(v) for k, v in olmo._flatten(tr.param_shapes()).items()}
    assert mine == olmo.shapes(keys)
    assert mine["gdn.wq"] == (3, 64, 32) and mine["gdn.wv"] == (3, 64, 64) and mine["gdn.wg"] == (3, 64, 64)
    assert mine["gdn.a_log"] == mine["gdn.dt_bias"] == (3, 2) and mine["gdn.o_norm"] == (3, 32)
    assert mine["gqa.q_norm"] == mine["gqa.k_norm"] == (1, 32)  # over the held width, not a head's 16
    assert not any(k.startswith("moe.") for k in mine) and "gdn.f_down" not in mine
    state = tr.init_state()
    assert sorted(state) == ["gdn_decay", "opt", "params"]  # no router_bias, counts, choices or dropped
    gdn = state["params"]["gdn"]
    rate = jax.nn.softplus(gdn["dt_bias"])
    assert 1e-3 * 0.999 <= float(rate.min()) and float(rate.max()) <= 0.1 * 1.001
    assert 0.0 <= float(gdn["a_log"].min()) and float(gdn["a_log"].max()) <= np.log(16.0)
    two, _ = _trainer(num_hidden_layers=8, first_k_dense_replace=8, layer_types=PERIOD * 2)
    assert two.mixers == ("gdn", "gdn", "gdn", "gqa") * 2 and two.param_shapes()["gdn"]["wq"][0] == 6


@pytest.mark.parametrize("over,match", [
    ({"layer_types": PERIOD[:3]}, "layer_types must name each"),
    ({"layer_types": PERIOD[:3] + ["sliding_attention"]}, "layer_types must name each"),
    ({"gqa_layers": "[3]"}, "not both"),
    ({"norm_order": "sandwich"}, "norm_order must be one of"),
    ({"qk_norm": "group"}, "not a bool"),
    ({"linear_num_key_heads": 1}, "as many key heads"),
    ({"first_k_dense_replace": 5}, "first_k_dense_replace must lie"),
    ({"first_k_dense_replace": 3, "moe_intermediate_size": 16, "num_experts_per_tok": 2, "router_experts": 4},
     "dense layers only"),
])
def test_what_the_table_refuses(over, match):
    with pytest.raises((ValueError, ConfigError), match=match):
        _trainer(**over)


# ------------------------------------------------------------ the layers ---


@pytest.mark.parametrize("order", ["post", "pre"])
def test_gated_deltanet_layer_matches_reference(olmo, order):
    tr, keys = _trainer(norm_order=order)
    math = olmo.reference_math(keys)
    p = dict(_layer(tr.init_state()["params"]["gdn"], 1))
    p["o_norm"] = jnp.linspace(0.5, 1.5, 32)  # a gain that shows
    p["attn_norm"] = jnp.linspace(0.5, 1.5, 64)
    x = _x(tr)
    got, decay = jax.jit(lambda p, x: tr._kda(p, x, tr.batch_size, "gdn"))(p, x)
    want = _rows(tr, lambda r: math.gdn(p, r), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
    assert 0.5 < float(decay) < 1.0
    for fault in ("no_decay", "beta_not_doubled", "no_conv", "sigmoid_gate", "state_dropped", "pre_norm"):
        if fault == "pre_norm" and order == "pre":
            continue
        other = _rows(tr, lambda r: olmo.reference_math(keys, fault=fault).gdn(p, r), x)
        assert float(jnp.abs(got - other).max()) > 0.02 * float(jnp.abs(got).max()), fault
    # the convolution and the state start at zero with each row: a row alone gives the same
    alone, _ = jax.jit(lambda p, x: tr._kda(p, x, 1, "gdn"))(p, x[tr.seq_len:])
    np.testing.assert_allclose(np.asarray(alone), np.asarray(got[tr.seq_len:]), rtol=1e-5, atol=1e-6)


def test_the_mixer_hands_the_rule_a_decay_a_head(olmo, monkeypatch):
    """Gated DeltaNet's mixer gives ``gated_delta_rule`` one decay a head
    (``[B * h, L, 1]``, no broadcast over the key channels), so the per-head
    kernels run; the layer still matches the reference, and the mean decay
    is the one the decay on every channel reads."""
    tr, keys = _trainer()
    p = dict(_layer(tr.init_state()["params"]["gdn"], 2))
    x = _x(tr, seed=5)
    handed = []

    def rule(q, k, v, g, beta, **kw):
        handed.append((q, g))
        return gated_delta_rule(q, k, v, g, beta, **kw)

    monkeypatch.setattr(moelm, "gated_delta_rule", rule)
    got, decay = tr._kda(p, x, tr.batch_size, "gdn")
    (q, g), = handed
    assert g.shape == q.shape[:2] + (1,) and q.shape == (tr.batch_size * 2, tr.seq_len, 16)
    assert gated_delta._form(g) is gated_delta._HEAD
    want = _rows(tr, lambda r: olmo.reference_math(keys).gdn(p, r), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
    spread = np.mean(np.exp(np.broadcast_to(np.asarray(g, np.float64), q.shape)))  # on every channel, as before
    assert float(decay) == pytest.approx(spread, rel=1e-5) and 0.5 < float(decay) < 1.0  # a float32 sum's rounding


def test_softmax_layer_with_whole_width_norms_matches_reference(olmo):
    tr, keys = _trainer()
    math = olmo.reference_math(keys)
    p = dict(_layer(tr.init_state()["params"]["gqa"], 0))
    p["q_norm"], p["k_norm"] = jnp.linspace(0.5, 1.5, 32), jnp.linspace(1.5, 0.5, 32)
    x = _x(tr)
    got = jax.jit(lambda p, x: tr._attention(p, x, tr.batch_size)[0])(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_rows(tr, lambda r: math.attention(p, r), x)),
                               rtol=2e-4, atol=2e-5)
    per_head = olmo.reference_math(keys, fault="qk_norm_per_head")
    gap = jnp.abs(got - _rows(tr, lambda r: per_head.attention(p, r), x)).max()
    assert float(gap) > 0.01 * float(jnp.abs(got).max())  # a head's norm is another norm
    # no rotary: a row's output does not turn on where the row starts
    late = jax.jit(lambda p, x: tr._attention(p, x, tr.batch_size, jnp.arange(tr.seq_len) + 100)[0])(p, x)
    np.testing.assert_array_equal(np.asarray(late), np.asarray(got))


def test_the_residual_order_puts_each_norm_after_its_half(olmo):
    """Under ``post`` a layer is ``h = x + N(mix(x))``, ``h + N(ffn(h))``:
    the layer of the program against the reference's, both orders."""
    for order in ("post", "pre"):
        tr, keys = _trainer(norm_order=order)
        math = olmo.reference_math(keys)
        params = tr.init_state()["params"]
        p = {**_layer(params["gdn"], 0), **_layer(params["dense"], 0)}
        p["attn_norm"], p["mlp_norm"] = jnp.linspace(0.5, 1.5, 64), jnp.linspace(1.5, 0.5, 64)
        x = _x(tr)
        got, counted = jax.jit(lambda p, x: tr._dense_layer(x, p, tr.batch_size, kind="gdn"))(p, x)
        want = _rows(tr, lambda r: math.layer("gdn", p, r), x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
        assert set(counted) == {"gdn_decay"}


# ------------------------------------------------------------- the step ---


@pytest.mark.parametrize("order", ["post", "pre"])
def test_train_steps_match_reference(olmo, order):
    """The cell's five numbers at a small size: three losses, every leaf's
    first gradient (from AdamW's first moment) and every leaf's change after
    three AdamW steps."""
    tr, keys = _trainer(norm_order=order)
    state = tr.init_state()
    w = olmo._flatten(state["params"])
    it = iter(tr.batches())
    batches = [next(it) for _ in range(3)]
    ref = olmo.olmo_reference(dict(w), batches, keys)
    step = jax.jit(tr.train_step)
    start, losses = state["params"], []
    for i, b in enumerate(batches):
        state, m = step(state, {k: jnp.asarray(v) for k, v in b.items()}, jax.random.PRNGKey(0))
        losses.append(float(m["loss"]))
        if i == 0:
            grad1 = {k: float(jnp.sum(v * v)) / (1 - 0.9) ** 2
                     for k, v in olmo._flatten(state["opt"][0].mu).items()}
        assert not [k for k in m if k.startswith("moe_")] and "kda_decay_mean" not in m
        assert 0.5 < float(m["gdn_decay_mean"]) < 1.0 and float(state["gdn_decay"]) == float(m["gdn_decay_mean"])
        assert float(m["attn_whole_tile_share"]) == pytest.approx(1 / 3)  # the softmax layer's two causal tiles a side: 1 of 3
    np.testing.assert_allclose(losses, ref["loss"], rtol=2e-5)
    assert set(grad1) == set(ref["grad1"]) == set(olmo.shapes(keys))
    for k, want in ref["grad1"].items():
        assert grad1[k] == pytest.approx(want, rel=2e-3, abs=1e-12), k
    change = {k: float(jnp.sum((v - olmo._flatten(start)[k]) ** 2))
              for k, v in olmo._flatten(state["params"]).items()}
    for k, want in ref["change"].items():
        assert change[k] == pytest.approx(want[-1], rel=5e-3), k


@pytest.mark.parametrize("part", ["control", "half_batch", "state_unchanged", "pre_norm", "qk_norm_per_head",
                                  "sigmoid_gate", "no_decay", "beta_not_doubled", "no_conv", "state_dropped"])
def test_control_and_faults_read_far_from_the_reference(olmo, part):
    """The bfloat16 control, and every fault the reference can plant, moves
    the first loss or the first gradient by far more than the 2e-5 and 2e-3
    the program is held to above."""
    tr, keys = _trainer()
    w = olmo._flatten(tr.init_state()["params"])
    batches = [next(iter(tr.batches()))]
    sound = olmo.olmo_reference(dict(w), batches, keys)
    other = olmo.olmo_reference(dict(w), batches, keys, **(
        {"precision": "bfloat16"} if part == "control" else {"fault": part}))
    loss_gap = abs(other["loss"][0] - sound["loss"][0]) / sound["loss"][0]
    grad_gap = max(abs(np.sqrt(other["grad1"][k]) - np.sqrt(v)) / np.sqrt(v)
                   for k, v in sound["grad1"].items() if v > 0)
    change_gap = max(abs(np.sqrt(other["change"][k][0]) - np.sqrt(v[0])) / np.sqrt(v[0])
                     for k, v in sound["change"].items() if v[0] > 0)
    assert loss_gap > 2e-4 or grad_gap > 2e-2 or change_gap > 0.5, (loss_gap, grad_gap, change_gap)


def test_the_loop_runs_a_dense_stack_and_the_step_is_scoped(tmp_path):
    rounded, _ = _trainer(matmul_dtype="bfloat16")
    state = rounded.init_state()
    batch = {k: jnp.asarray(v) for k, v in next(iter(rounded.batches())).items()}
    text = jax.jit(rounded.train_step).lower(state, batch, jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("gdn", "gdn_in", "gdn_core", "gdn_out", "attn", "attn_in", "attn_core", "mlp", "head", "opt"):
        assert f"phase_{scope}" in text, scope
    assert "phase_kda" not in text and "phase_route" not in text and "phase_experts" not in text
    path = str(tmp_path / "ids.npy")
    np.save(path, np.random.default_rng(0).integers(0, 64, 2000).astype(np.int32))
    cfg = Config()
    for k, v in {**KEYS, "data": path, "shard_data": 0, "num_iters": 1}.items():
        cfg.set(k, str(v))
    tr = MoELMTrainer(cfg)
    tr.attention_block, tr.kda_chunk = 16, 16
    state = TrainLoop(tr, metrics=MetricsLogger(echo=False), log_every=0).run(max_steps=3)
    assert 0.5 < float(state["gdn_decay"]) < 1.0 and "router_bias" not in state


# ------------------------------------------------------- the kernels' path ---


@pytest.mark.parametrize("seq,chunk", [(32, 16), (128, 64)], ids=["two-chunks", "chunk-64"])
def test_chunked_rule_with_a_decay_a_head_and_keys_narrower_than_values(seq, chunk):
    """``gated_delta_rule`` (interpret mode) with ``g`` equal across a
    head's key channels (the per-channel kernels on a decay a head), K 16
    and V 32, against the recurrence, forward and the gradients of all five
    inputs, ``g``'s summed over the channels as a broadcast sums it."""
    heads, width, value = 3, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(jax.random.normal(ks[0], (heads, seq, width))), unit(jax.random.normal(ks[1], (heads, seq, width)))
    v = jax.random.normal(ks[2], (heads, seq, value))
    per_head = -jnp.exp(jax.random.uniform(ks[3], (heads, seq), minval=np.log(1e-3), maxval=np.log(3.0)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (heads, seq)))
    w = jax.random.normal(ks[5], (heads, seq, value))
    spread = lambda g: jnp.broadcast_to(g[..., None], (heads, seq, width))  # noqa: E731
    fast = lambda q, k, v, g, b: gated_delta_rule(q, k, v, spread(g), b, chunk=chunk, dtype=jnp.float32)  # noqa: E731
    slow = lambda q, k, v, g, b: recurrence(q, k, v, spread(g), b)  # noqa: E731
    args = (q, k, v, per_head, beta)
    np.testing.assert_allclose(np.asarray(jax.jit(fast)(*args)), np.asarray(jax.jit(slow)(*args)), rtol=0, atol=1e-4)
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args)  # noqa: E731
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads(fast), grads(slow)):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=2e-4, err_msg=name)


# ------------------------------------------- the share ties to the model ---


def _head_share(kind, p, share, olmo_dims):
    """Layer ``p`` of 2 heads as the chip that holds head ``share`` holds it:
    slices by head of what is per head, what every chip computes alike as it
    is."""
    cols = lambda width: slice(share * width, (share + 1) * width)  # noqa: E731
    if kind == "gqa":
        hd = olmo_dims["hd"]
        return {**p, **{k: p[k][:, cols(hd)] for k in ("wq", "wk", "wv")}, "wo": p["wo"][cols(hd)],
                "q_norm": p["q_norm"][cols(hd)], "k_norm": p["k_norm"][cols(hd)]}
    kw, vw = olmo_dims["key"], olmo_dims["value"]
    mine = {k: p[k][:, cols(kw)] for k in ("wq", "wk", "conv_q", "conv_k")}
    mine.update({k: p[k][:, cols(vw)] for k in ("wv", "conv_v", "wg")})
    mine.update({k: p[k][:, cols(1)] for k in ("wa", "wb")})
    return {**p, **mine, "a_log": p["a_log"][cols(1)], "dt_bias": p["dt_bias"][cols(1)], "wo": p["wo"][cols(vw)]}


@pytest.mark.parametrize("kind", ["gdn", "gqa"])
def test_the_shares_of_the_heads_add_up_to_the_whole_mixer(olmo, kind):
    """What the two chips of a pair compute, a head each: for the delta rule
    (its norms are a head's) partial sums of the whole mixer's output; for
    the softmax layer the same once each share's q/k norm takes the whole
    width's mean square, the statistic the pair would exchange. The
    program's share is the reference's, whose statistic is the held width's."""
    whole, keys = _trainer()
    math = olmo.reference_math(keys)
    p = _layer(whole.init_state()["params"][kind], 0)
    x = _x(whole)
    mixer = math.gdn if kind == "gdn" else math.attention
    want = _rows(whole, lambda r: mixer(p, r), x)
    one = {"num_attention_heads": 1, "num_key_value_heads": 1, "linear_num_key_heads": 1, "linear_num_value_heads": 1}
    half, half_keys = _trainer(**one)
    half_math = olmo.reference_math(half_keys)
    shares = [_head_share(kind, p, s, math.dims) for s in range(2)]
    if kind == "gdn":
        mix = jax.jit(lambda p, x: half._kda(p, x, half.batch_size, "gdn")[0])
        parts = [mix(share, x) for share in shares]
        assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-3
        np.testing.assert_allclose(np.asarray(parts[0] + parts[1]), np.asarray(want), rtol=2e-4, atol=2e-5)
        return

    def whole_width(r):  # the pair's sum: the mean square of the whole projection, for q and for k
        return tuple(jnp.mean((r @ p[w]) ** 2, axis=-1, keepdims=True) for w in ("wq", "wk"))

    parts = [_rows(whole, lambda r: half_math.attention(share, r, whole_width(r)), x) for share in shares]
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]), np.asarray(want), rtol=2e-4, atol=2e-5)
    mine = jax.jit(lambda p, x: half._attention(p, x, half.batch_size)[0])(shares[0], x)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(_rows(whole, lambda r: half_math.attention(shares[0], r), x)),
                               rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(mine - parts[0]).max()) > 1e-4  # the held width's statistic is not the pair's
