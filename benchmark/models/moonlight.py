"""Moonlight-16B-A3B (``model_type: deepseek_v3``: latent attention, 64 routed
and 2 shared experts) as one chip of eight holds it, for the benchmark: its
feed, its weights in the program's layout, what is read from the program's
state, the plain reference, and the operations the step and its kernels need.
``lib/jobs.py`` loads this file by the configuration's ``model``. Only
``Adapter`` touches the program (``swiftsnails_tpu/models/moelm.py``).

The layer equations are in that file's head and, executably, in
:func:`moonlight_reference` below: plain ``jax.numpy``, float32 at
``highest``, no kernel, no sort (a loop over the experts held, with a mask),
dense attention in query blocks so that the scores fit.
"""

import functools
import os
import types

import numpy as np

from lib import gen

# ----------------------------------------------- operations and shapes ---


def _dims(keys):
    g = lambda k, d=None: int(keys.get(k, d))  # noqa: E731
    return {
        "d": g("hidden_size"), "layers": g("num_hidden_layers"), "n_dense": g("first_k_dense_replace", 1),
        "heads": g("num_attention_heads"), "rank": g("kv_lora_rank"), "nope": g("qk_nope_head_dim"),
        "rope": g("qk_rope_head_dim"), "vd": g("v_head_dim"), "dense_w": g("intermediate_size"),
        "expert_w": g("moe_intermediate_size"), "shared": g("n_shared_experts", 0),
        "top_k": g("num_experts_per_tok"), "router": g("router_experts"),
        "held": g("experts_held"), "offset": g("expert_offset", 0), "vocab": g("vocab_size"),
        "seq": g("seq_len"), "batch": g("batch_size", 1), "remat": g("remat", 1),
    }


def shapes(keys) -> dict:
    """{leaf name: shape}; layers of a kind are stacked on a leading axis, as
    the program holds them (``MoELMTrainer.param_shapes``, flattened with
    dots)."""
    m = _dims(keys)
    d, h = m["d"], m["heads"]
    attn = {"attn_norm": (d,), "wq": (d, h * (m["nope"] + m["rope"])),
            "wkv_a": (d, m["rank"] + m["rope"]), "kv_norm": (m["rank"],),
            "wkv_b": (m["rank"], h * (m["nope"] + m["vd"])), "wo": (h * m["vd"], d),
            "mlp_norm": (d,)}

    def swiglu(prefix, width):
        return {f"{prefix}_gate": (d, width), f"{prefix}_up": (d, width), f"{prefix}_down": (width, d)}

    e, w = m["held"], m["expert_w"]
    moe = {**attn, "router": (d, m["router"]), **swiglu("shared", m["shared"] * w),
           "experts_gate": (e, d, w), "experts_up": (e, d, w), "experts_down": (e, w, d)}
    out = {"embed": (m["vocab"], d), "head": (d, m["vocab"]), "final_norm": (d,)}
    out.update({"dense." + k: (m["n_dense"],) + s
                for k, s in {**attn, **swiglu("mlp", m["dense_w"])}.items()})
    out.update({"moe." + k: (m["layers"] - m["n_dense"],) + s for k, s in moe.items()})
    return dict(sorted(out.items()))


def parameters_held(keys) -> int:
    return int(sum(np.prod(s) for s in shapes(keys).values()))


def matrix_parameters_per_token(keys) -> float:
    """Matrix parameters a token's forward pass multiplies by: attention's
    five, the dense layers' feed-forward, per mixture layer the router, the
    shared experts and the routed experts held that the token is expected to
    reach (``top_k * held / router``), and the head (not the embedding: a
    row is read, not multiplied)."""
    m = _dims(keys)
    d, h = m["d"], m["heads"]
    attn = (d * h * (m["nope"] + m["rope"]) + d * (m["rank"] + m["rope"])
            + m["rank"] * h * (m["nope"] + m["vd"]) + h * m["vd"] * d)
    n_moe = m["layers"] - m["n_dense"]
    expert = 3 * d * m["expert_w"]
    return (m["layers"] * attn + m["n_dense"] * 3 * d * m["dense_w"]
            + n_moe * (d * m["router"] + m["shared"] * expert
                       + m["top_k"] * m["held"] / m["router"] * expert)
            + d * m["vocab"])


def attention_flops_per_token(keys) -> float:
    """Causal scores and weighted values, forward, all layers: a token at
    position p meets p + 1 keys, (L + 1) / 2 on average."""
    m = _dims(keys)
    return 2.0 * m["layers"] * m["heads"] * (m["nope"] + m["rope"] + m["vd"]) * (m["seq"] + 1) / 2


def flops_per_item(keys) -> float:
    """Per token with a target: forward and backward (2 + 4 a parameter, and
    three times the forward attention product). Rematerialised operations
    are not counted."""
    return 6.0 * matrix_parameters_per_token(keys) + 3.0 * attention_flops_per_token(keys)


def attention_kernel_flops_per_step(keys) -> float:
    """What the attention kernels' calls of one step need
    (``ops/flash_attention.attention_flops``'s count, restated): per layer
    the forward call, again where the layer is rematerialised, the dq call
    and the dkv call."""
    m = _dims(keys)
    dk, dv = m["nope"] + m["rope"], m["vd"]
    pairs = m["batch"] * m["heads"] * m["seq"] * (m["seq"] + 1) / 2
    fwd, dq, dkv = 2.0 * pairs * (dk + dv), 2.0 * pairs * (2 * dk + dv), 2.0 * pairs * (2 * dk + 2 * dv)
    return m["layers"] * (fwd * (2 if m["remat"] else 1) + dq + dkv)


def experts_kernel_flops(keys, held_assignments: float) -> float:
    """What the grouped products need for ``held_assignments`` (token, expert)
    pairs, whatever steps and mixture layers they are summed over: three
    products an expert, each forward (again where rematerialised), dx and
    dw; padding rows are not counted."""
    m = _dims(keys)
    one = 2.0 * m["d"] * m["expert_w"]
    return held_assignments * 3 * one * ((2 if m["remat"] else 1) + 2)


KERNELS = {"attn": [r"flash_attention_(fwd|dq|dkv)"], "experts": [r"grouped_matmul"]}
WARM_STEPS = 3  # ``jobs/train.py``'s: the window's steps follow them


def window_counts(run) -> np.ndarray:
    """``[steps, mixture layers, router_experts]``: what each step dispatched
    in the window assigned to every expert. The job hands the adapter the
    state of the warm steps only, so the window's steps are run once more:
    the same trainer, the same weights and feed from the seed, the program's
    own loop and compiled step (from the cache), every step's ``counts``
    kept. The warm steps come by again on the way and have to count what
    they counted the first time, or the run was not the one replayed."""
    if "window_counts" in run.extra:
        return run.extra["window_counts"]
    import jax

    from swiftsnails_tpu.framework.trainer import TrainLoop
    from swiftsnails_tpu.utils.metrics import MetricsLogger

    adapter = run.extra["adapter"]
    trainer, seen = adapter.trainer, []
    trainer.init_state = adapter.state
    loop = TrainLoop(trainer, metrics=MetricsLogger(echo=False), log_every=0)
    inner = loop._step_fn

    def counting(state, batch, rng, step):
        out, metrics = inner(state, batch, rng, step)
        seen.append(out["counts"])
        return out, metrics

    loop._step_fn = counting
    try:
        loop.run(seed=run.seed & 0x7FFFFFFF, max_steps=WARM_STEPS + int(run.counters["steps"]))
    finally:
        del trainer.init_state
    counts = np.asarray(jax.device_get(seen))
    if not np.array_equal(counts[:WARM_STEPS], adapter.warm_counts):
        raise RuntimeError("the replayed warm steps count other assignments than the run's did")
    run.extra["window_counts"] = counts[WARM_STEPS:]
    return run.extra["window_counts"]


def kernel_roofline_pct(run, which: str, flops_of_steps):
    """A kernel family's share of the chip's peak over its own device time,
    over the WHOLE measured window: from its opening (the device idle) to
    ``TrainLoop.run``'s return (drained) every dispatched step runs on the
    device exactly once (``lib/scopes.py`` reads the phases the same way),
    so the kernels' device seconds there belong to exactly the program's
    ``step`` spans of the window, and ``flops_of_steps(steps)`` says what
    those steps need."""
    import re

    from lib import peaks, spans

    profile = run.extra.get("profile")
    if run.trace is None or profile is None or profile.window is None:
        return None
    steps = spans.steps_in(run, run.t0, run.t1)
    pats = [re.compile(p) for p in KERNELS[which]]
    w0 = profile.window[0]
    w1 = w0 + round(run.window_s * 1e9)
    planes = profile.neutral["device"].values()
    ns = sum(max(0, min(s + d, w1) - max(s, w0)) for ops in planes for name, s, d in ops
             if any(p.search(name) for p in pats))
    if not steps or ns <= 0:
        return None
    least = flops_of_steps(steps) / peaks.peaks_for(run.device["kind"])["bf16_flops_per_s"]
    return peaks.share_pct(least, ns / len(planes) / 1e9, f"kernel.{which}_roofline")


# --------------------------------------------------------------- the feed ---


def token_ids(tokens: int, vocab: int, exponent: float, doc_median: float, doc_sigma: float,
              seed: int) -> np.ndarray:
    """``tokens`` ids: documents of log-normal length packed end to end, each
    ending in id 0 (the slice's separator); a document's other tokens are
    ranks 1..vocab-1 under a power law, rank r the id r."""
    rng = np.random.default_rng(seed)
    ids = 1 + gen.zipf_ranks(rng, vocab - 1, exponent, tokens)
    ends = np.cumsum(np.maximum(2, rng.lognormal(np.log(doc_median), doc_sigma,
                                                 size=8 + int(4 * tokens / doc_median)).astype(np.int64)))
    ids[ends[ends <= tokens] - 1] = 0
    return ids.astype(np.int32)


# ----------------------------------------------------------------- weights ---


def _leaf(seed, index, shape, std: float, gain: bool):
    import jax
    import jax.numpy as jnp

    if gain:
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), index)
    return jax.random.normal(key, shape, jnp.float32) * std


@functools.lru_cache(maxsize=None)
def _jitted():
    """(a leaf from the seed; the squared distance of an array from that
    leaf): seed and leaf number are operands, so leaves of one shape share
    one compiled program."""
    import jax
    import jax.numpy as jnp

    def change(v, seed, index, std, gain):
        return jnp.sum((v - _leaf(seed, index, v.shape, std, gain)) ** 2)

    return (jax.jit(_leaf, static_argnums=(2, 3, 4)), jax.jit(change, static_argnums=(3, 4)))


def make_weights(keys, seed: int, std: float) -> dict:
    """{leaf: float32 array} from the seed: leaf i (in name order) is
    N(0, std^2) from ``fold_in(PRNGKey(seed), i)``, a norm's gain is ones.
    One jitted call a leaf, the seed an operand: nothing but the leaf in
    hand is alive beside what the caller keeps."""
    make, _ = _jitted()
    s = np.uint32(seed & 0xFFFFFFFF)
    return {name: make(s, np.uint32(i), shape, std, name.endswith("norm"))
            for i, (name, shape) in enumerate(shapes(keys).items())}


def _nest(flat: dict) -> dict:
    out = {}
    for name, v in flat.items():
        group, _, leaf = name.rpartition(".")
        (out.setdefault(group, {}) if group else out)[leaf] = v
    return out


def _flatten(tree: dict) -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update({f"{k}.{kk}": vv for kk, vv in v.items()})
        else:
            out[k] = v
    return dict(sorted(out.items()))


# ---------------------------------------------------------- reference ---

FAULTS = ("half_batch", "state_unchanged", "five_experts", "no_route_scale", "no_shared",
          "no_rotary", "capacity_drop", "no_bias_step")


def reference_math(hp, precision="float32", fault=None):
    """The model's arithmetic as plain functions of one sequence, for
    :func:`moonlight_reference` and for the tests that hold the program's
    layers to it one at a time: ``norm``, ``attention(p, x)``, ``swiglu(p,
    prefix, y)``, ``mixture(p, bias, y)`` -> (output, balance loss, choices),
    ``loss_of(params, bias, tokens)`` -> (loss, choices), ``store``."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    m = _dims(hp)
    bf16 = precision == "bfloat16"
    store = (lambda a: jax.lax.reduce_precision(a, 8, 7)) if bf16 else (lambda a: a)
    eps, theta = float(hp["rms_norm_eps"]), float(hp["rope_theta"])
    scale = 1.0 if fault == "no_route_scale" else float(hp["routed_scaling_factor"])
    top_k = m["top_k"] - (1 if fault == "five_experts" else 0)
    alpha = float(hp["aux_loss_alpha"])
    h, nope, rope, vd, rank = m["heads"], m["nope"], m["rope"], m["vd"], m["rank"]
    n_moe = m["layers"] - m["n_dense"]
    q_block = 1024

    def norm(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain

    def rotate(x):  # [L, ..., rope]: pairs (j, j + rope/2)
        if fault == "no_rotary":
            return x
        half = rope // 2
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / rope)
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :]
        ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                                b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)

    def attention(p, x):  # one sequence [L, d]
        seq = x.shape[0]
        y = norm(x, p["attn_norm"])
        q = (y @ p["wq"]).reshape(seq, h, nope + rope)
        kva = y @ p["wkv_a"]
        kvb = (norm(kva[:, :rank], p["kv_norm"]) @ p["wkv_b"]).reshape(seq, h, nope + vd)
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], axis=-1)
        k_rope = rotate(kva[:, rank:].reshape(seq, 1, rope))
        k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_rope, (seq, h, rope))], axis=-1)
        v = kvb[..., nope:]

        block = min(q_block, seq)  # the scores of one block of queries at a time

        @jax.checkpoint
        def attend(args):
            qb, first = args
            s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(nope + rope)
            keep = (first + jnp.arange(block))[:, None] >= jnp.arange(seq)[None, :]
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1), v)

        out = jax.lax.map(attend, (q.reshape(seq // block, block, h, nope + rope),
                                   jnp.arange(0, seq, block)))
        return out.reshape(seq, h * vd) @ p["wo"]

    def swiglu(p, prefix, y):
        return (jax.nn.silu(y @ p[prefix + "_gate"]) * (y @ p[prefix + "_up"])) @ p[prefix + "_down"]

    def mixture(p, bias, y):  # one sequence [L, d] -> (output, balance loss, choices)
        seq = y.shape[0]
        s = jax.nn.sigmoid(y @ p["router"])
        _, choices = jax.lax.top_k(s + bias[None, :], top_k)
        chosen = jnp.take_along_axis(s, choices, axis=-1)
        gates = scale * chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20)
        hit = jax.nn.one_hot(choices, m["router"], dtype=jnp.float32)  # [L, k, E]
        f = jax.lax.stop_gradient(hit.sum(axis=(0, 1))) * (m["router"] / (top_k * seq))
        balance = alpha * jnp.sum(f * jnp.mean(s / s.sum(axis=-1, keepdims=True), axis=0))
        if fault == "capacity_drop":
            cap = -(-seq * top_k // m["router"])
            flat = hit.reshape(seq * top_k, -1)
            nth = jnp.sum((jnp.cumsum(flat, axis=0) - 1) * flat, axis=-1)  # its place in its expert's queue
            hit = hit * (nth < cap).reshape(seq, top_k, 1)
        gate_of = jnp.einsum("tk,tke->te", gates, hit)  # each token's gate for every expert
        def add_expert(out, expert):  # every held expert over every token, masked by its gate
            w_gate, w_up, w_down, gate = expert
            return out + gate[:, None] * ((jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down), ()

        out, _ = jax.lax.scan(add_expert, jnp.zeros_like(y), (
            p["experts_gate"], p["experts_up"], p["experts_down"],
            gate_of[:, m["offset"]: m["offset"] + m["held"]].T))
        if m["shared"] and fault != "no_shared":
            out = out + swiglu(p, "shared", y)
        return out, balance, choices

    def layer_of(tree, i):
        return {k: v[i] for k, v in tree.items()}

    def loss_of(params, bias, tokens):  # tokens [B, L + 1]
        tree = _nest(params)
        total, balance, picks = 0.0, 0.0, []
        for row in tokens:
            x = store(params["embed"][row[:-1]])
            seen = []
            for i in range(m["n_dense"]):
                @jax.checkpoint
                def dense(x, p):
                    x = store(x + attention(p, x))
                    return store(x + swiglu(p, "mlp", norm(x, p["mlp_norm"])))
                x = dense(x, layer_of(tree["dense"], i))
            for i in range(n_moe):
                @jax.checkpoint
                def sparse(x, p, b):
                    x = store(x + attention(p, x))
                    out, bal, ch = mixture(p, b, norm(x, p["mlp_norm"]))
                    return store(x + out), bal, ch
                x, bal, ch = sparse(x, layer_of(tree["moe"], i), bias[i])
                balance = balance + bal
                seen.append(ch)
            logits = norm(x, params["final_norm"]) @ params["head"]
            ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, row[1:, None], axis=-1)[:, 0]
            total = total + jnp.sum(ce)
            picks.append(jnp.stack(seen))  # [layers, L, k]
        count = tokens.shape[0] * (tokens.shape[1] - 1)
        return total / count + balance / tokens.shape[0], jnp.concatenate(picks, axis=1)

    return types.SimpleNamespace(norm=norm, attention=attention, swiglu=swiglu, mixture=mixture,
                                 loss_of=loss_of, store=store, dims=m)


def moonlight_reference(w, batches, hp, precision="float32", fault=None):
    """Three (``len(batches)``) training steps from the weights ``w`` ({leaf:
    array}, layers stacked; the dict is emptied): loss (next-token cross entropy over the slice +
    the sequence-wise balance loss), gradients, AdamW, then the selection
    bias's step. ``hp``: the configuration's ``keys`` and ``keys_reference``.

    ``precision`` "bfloat16" is the control: the residual stream and the
    weights are stored in bfloat16 and products run at the default precision.
    ``fault``: "half_batch" trains on the first half of every sequence;
    "state_unchanged" applies no update; "five_experts" chooses one expert
    fewer; "no_route_scale", "no_shared", "no_rotary" leave that part out;
    "capacity_drop" drops the assignments past each expert's fair share
    (capacity factor 1); "no_bias_step" leaves the selection bias at 0.

    Returns {"loss": [...], "grad1": {leaf: sumsq of the first gradient},
    "change": {leaf: [sumsq of the change since the start, after each step]},
    "choices": [per step, [mixture layers, tokens, k] expert ids]}.
    """
    import jax
    import jax.numpy as jnp

    math = reference_math(hp, precision, fault)
    m, store, loss_of = math.dims, math.store, math.loss_of
    bf16 = precision == "bfloat16"
    n_moe = m["layers"] - m["n_dense"]
    bias_rate = float(hp["bias_update_rate"])
    lr, b1, b2 = float(hp["learning_rate"]), float(hp["adam_b1"]), float(hp["adam_b2"])
    adam_eps, decay = float(hp["adam_eps"]), float(hp["weight_decay"])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mom, var, bias, tokens, t):
        (loss, choices), g = jax.value_and_grad(loss_of, has_aux=True)(params, bias, tokens)
        g2 = {k: jnp.sum(v * v) for k, v in g.items()}
        if fault == "state_unchanged":
            return params, mom, var, bias, loss, g2, choices
        mom = {k: b1 * mom[k] + (1 - b1) * g[k] for k in g}
        var = {k: b2 * var[k] + (1 - b2) * g[k] * g[k] for k in g}
        params = {k: store(params[k] - lr * (
            (mom[k] / (1 - b1 ** t)) / (jnp.sqrt(var[k] / (1 - b2 ** t)) + adam_eps)
            + decay * params[k])) for k in g}
        if fault != "no_bias_step":
            counts = jax.nn.one_hot(choices, m["router"], dtype=jnp.float32).sum(axis=(1, 2))
            bias = bias + bias_rate * jnp.sign(counts.mean(axis=-1, keepdims=True) - counts)
        return params, mom, var, bias, loss, g2, choices

    @jax.jit
    def sumsq(a, b):
        return jnp.sum((a - b) ** 2)

    with jax.default_matmul_precision("default" if bf16 else "highest"):
        # ``w`` is emptied leaf by leaf (the control's rounded copy must not
        # stand beside the original), and the start stays on the host: the
        # step's three trees and its gradients are all the device holds
        # beside the activations
        params = {k: store(w.pop(k)) for k in list(w)}
        start = {k: np.asarray(v) for k, v in params.items()}
        mom = {k: jnp.zeros_like(v) for k, v in params.items()}
        var = {k: jnp.zeros_like(v) for k, v in params.items()}
        bias = jnp.zeros((n_moe, m["router"]), jnp.float32)
        out = {"loss": [], "grad1": {}, "change": {k: [] for k in list(start) + ["router_bias"]},
               "choices": []}
        for i, bt in enumerate(batches):
            tokens = np.asarray(bt["tokens"])
            if fault == "half_batch":
                tokens = tokens[:, : (tokens.shape[1] - 1) // 2 + 1]
            params, mom, var, bias, loss, g2, choices = step(
                params, mom, var, bias, jnp.asarray(tokens), jnp.float32(i + 1))
            if i == 0:
                out["grad1"] = {k: float(v) for k, v in jax.device_get(g2).items()}
            out["loss"].append(float(loss))
            out["choices"].append(np.asarray(choices))
            for k in start:
                out["change"][k].append(float(sumsq(params[k], start[k])))
            out["change"]["router_bias"].append(float(jnp.sum(bias * bias)))
    return out


def disagree_share(program_choices, reference_choices) -> float:
    """The share of a token's choices on which program and reference differ:
    1 - (experts both chose) / (the most either chose), averaged over tokens
    and mixture layers, the largest over the steps. Both: [mixture layers,
    tokens, k] per step (k may differ: a program that chooses five of six
    reads a sixth)."""
    worst = 0.0
    for p, r in zip(program_choices, reference_choices):
        p, r = np.asarray(p), np.asarray(r)
        both = (p[..., :, None] == r[..., None, :]).any(axis=-1).sum(axis=-1)
        worst = max(worst, 1.0 - float(both.mean()) / max(p.shape[-1], r.shape[-1]))
    return worst


# ------------------------------------------------------------ adapter ---


class Adapter:
    """The program's state is ``{"params": nested by kind of layer, "opt":
    optax.adamw's, "router_bias", "counts", "choices", "dropped"}``; the
    benchmark's leaves are the same arrays under dotted names."""

    def __init__(self, run, trainer):
        self.run, self.trainer = run, trainer
        self.keys = {**run.config["keys"], **run.mix.get("keys", {})}
        mine = {k: tuple(v) for k, v in _flatten(trainer.param_shapes()).items()}
        if mine != shapes(self.keys):
            raise ValueError("the program's parameter tree is not the benchmark's")
        # the weights come from the configuration's ``init.seed``, the same in
        # every run (which experts a sequence's tokens flock to at random
        # weights is drawn with them: PERF.md section 7); the feed and the
        # loop's order come from --seed
        self.weights_seed = int(run.config["init"]["seed"])
        self._program_choices = self._reference_choices = None

    @staticmethod
    def dataset(run, work_dir: str) -> str:
        feed = {**run.config["feed"], **run.mix.get("feed", {})}
        keys = {**run.config["keys"], **run.mix.get("keys", {})}
        ids = token_ids(int(feed["tokens"]), int(keys["vocab_size"]), float(feed["zipf_exponent"]),
                        float(feed["doc_median_tokens"]), float(feed["doc_sigma"]), run.seed)
        path = os.path.join(work_dir, "tokens.npy")
        np.save(path, ids)
        return path

    def _hp(self):
        return {**self.keys, **self.run.config["keys_reference"]}

    def _weights(self):
        return make_weights(self.keys, self.weights_seed, float(self.run.config["init"]["std"]))

    def state(self):
        return self.trainer.state_of(_nest(self._weights()))

    def readings(self):
        """Per warm step: each leaf's change since the start (the weights
        made again, a leaf at a time), the sum of squares of AdamW's
        first moment (after one step (1 - b1) times the first gradient), the
        step's choices, counts and dropped assignments."""
        import jax
        import jax.numpy as jnp

        names = shapes(self.keys)
        std = float(self.run.config["init"]["std"])

        @jax.jit
        def sumsq(a):
            return jnp.sum(a * a)

        _, change_of = _jitted()
        seed = np.uint32(self.weights_seed & 0xFFFFFFFF)

        def read(state, _run_seed):
            params = _flatten(state["params"])
            moment = _flatten(state["opt"][0].mu)
            change = {k: change_of(params[k], seed, np.uint32(i), std, k.endswith("norm"))
                      for i, k in enumerate(names)}
            change["router_bias"] = sumsq(state["router_bias"])
            return {"change": change, "moment": {k: sumsq(moment[k]) for k in names},
                    "choices": state["choices"], "counts": state["counts"],
                    "dropped": state["dropped"]}

        return read

    def program_grad1(self, reads):
        """The first gradient as AdamW got it, from its first moment; and
        what the warm steps counted, kept for the readers of the program's
        counters (``benchmark/metrics/moe.*.py``)."""
        m = _dims(self.keys)
        counts = np.asarray([r["counts"] for r in reads], np.float64)  # [steps, layers, E]
        held = counts[:, :, m["offset"]: m["offset"] + m["held"]]
        self.run.counters["moe"] = {
            "held_share_pct": 100.0 * held.sum() / counts.sum(),
            "load_max_over_mean": float(np.mean(held.max(axis=-1) / np.maximum(held.mean(axis=-1), 1))),
            "dropped": int(sum(int(r["dropped"]) for r in reads)),
        }
        self.warm_counts = counts.astype(np.int64)
        self._program_choices = [np.asarray(r.pop("choices")) for r in reads]
        for r in reads:
            r.pop("counts"), r.pop("dropped")
        b1 = float(self._hp()["adam_b1"])
        return {k: float(v) / (1 - b1) ** 2 for k, v in reads[0]["moment"].items()}

    def reference(self, batches, precision="float32", fault=None):
        ref = moonlight_reference(self._weights(), batches, self._hp(),
                                  precision=precision, fault=fault)
        choices = ref.pop("choices")
        if precision == "float32" and fault is None:
            self._reference_choices = choices
        return ref

    def extra_numbers(self, batches):
        """``route_disagree_share``: bfloat16 operands upstream of a router
        flip choices whose scores nearly tie; anything else moves far more."""
        if self._reference_choices is None:
            self.reference(batches)
        return {"route_disagree_share": disagree_share(self._program_choices, self._reference_choices)}

    def reference_variants(self):
        return [()]

    def parts(self):
        """For ``control.py``: every fault the reference can plant, put in the
        program's place; each has to come out not correct."""
        return {f: {"fault": f} for f in FAULTS if f != "half_batch"}

    def extra_faults(self):
        return {}
