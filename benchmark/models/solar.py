"""Solar-Open2-250B (``model_type: solar_open2``: of every four layers three
mix tokens by a gated delta rule with a decay per channel (Kimi Delta
Attention, arXiv:2510.26692) and one by grouped-query softmax attention
without rotary, its output gated; every layer a mixture of 320 routed experts,
8 a token, and one shared) as one chip of forty holds it, for the benchmark:
its feed, its weights in the program's layout, what is read from the
program's state, the plain reference, and the operations the step and its
kernels need. ``lib/jobs.py`` loads this file by the configuration's
``model``. Only ``Adapter`` touches the program
(``swiftsnails_tpu/models/moelm.py``). What is no model's own comes from
``moonlight.py`` beside it: the kernels' share of their roofline, the window's
counts, the feed's generator, the routers' disagreement.

The equations are :func:`reference_math`'s, executably: plain ``jax.numpy``,
float32 at ``highest``, no kernel, no sort, no chunk and no solve: the delta
rule runs a token at a time (``S'_t = diag(alpha_t) S_(t-1)``; ``S_t = S'_t +
beta_t k_t (v_t - S'_t^T k_t)^T``; ``o_t = S_t^T q_t / sqrt(128)``), the
convolution is four shifted sums, the softmax is dense and causal a head, the
experts held are a loop with a mask. The heads, experts and vocabulary rows
are the share the configuration states, here as in the program.
"""

import functools
import types

import numpy as np

from lib import jobs

moonlight = jobs.load_model("moonlight")
kernel_roofline_pct, window_counts = moonlight.kernel_roofline_pct, moonlight.window_counts
disagree_share = moonlight.disagree_share
_flatten = moonlight._flatten  # the program's tree -> dotted leaves

# ----------------------------------------------- operations and shapes ---

CHUNK = 64  # the tokens of a chunk in the program's recurrence (a test's small trainer says ``kda_chunk``)


def _dims(keys):
    g = lambda k, d=None: int(keys.get(k, d))  # noqa: E731
    layers = g("num_hidden_layers")
    named = keys["gqa_layers"]
    if isinstance(named, str):  # as the program's own config holds it
        named = [int(i) for i in named.strip("[]() ").split(",") if i.strip()]
    kinds = tuple("gqa" if i in named else "kda" for i in range(layers))
    return {
        "d": g("hidden_size"), "layers": layers, "kinds": kinds, "heads": g("num_attention_heads"),
        "kv": g("num_key_value_heads"), "hd": g("head_dim"),
        "kda_heads": g("linear_attn_config.num_heads"), "kda_hd": g("linear_attn_config.head_dim"),
        "taps": g("linear_attn_config.short_conv_kernel_size"), "rank": g("linear_attn_config.head_dim"),
        "chunk": g("kda_chunk", CHUNK), "expert_w": g("moe_intermediate_size"), "shared": g("n_shared_experts", 0),
        "top_k": g("num_experts_per_tok"), "router": g("router_experts"), "held": g("experts_held"),
        "offset": g("expert_offset", 0), "vocab": g("vocab_size"), "seq": g("seq_len"),
        "batch": g("batch_size", 1), "remat": g("remat", 1),
    }


def shapes(keys) -> dict:
    """{leaf name: shape}; the mixers of a kind are stacked on a leading
    axis, the feed-forward parts over all layers, as the program holds them
    (``MoELMTrainer.param_shapes``, flattened with dots)."""
    m = _dims(keys)
    d, h, kv, hd, e, w = m["d"], m["heads"], m["kv"], m["hd"], m["held"], m["expert_w"]
    kh, khd, r = m["kda_heads"], m["kda_hd"], m["rank"]
    gqa = {"attn_norm": (d,), "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
           "wz": (d, h * hd), "wo": (h * hd, d)}
    kda = {"attn_norm": (d,), "wq": (d, kh * khd), "wk": (d, kh * khd), "wv": (d, kh * khd),
           "conv_q": (m["taps"], kh * khd), "conv_k": (m["taps"], kh * khd), "conv_v": (m["taps"], kh * khd),
           "f_down": (d, r), "f_up": (r, kh * khd), "a_log": (kh,), "dt_bias": (kh * khd,),
           "wb": (d, kh), "g_down": (d, r), "g_up": (r, kh * khd), "o_norm": (khd,), "wo": (kh * khd, d)}
    moe = {"mlp_norm": (d,), "router": (d, m["router"]), "shared_gate": (d, m["shared"] * w),
           "shared_up": (d, m["shared"] * w), "shared_down": (m["shared"] * w, d),
           "experts_gate": (e, d, w), "experts_up": (e, d, w), "experts_down": (e, w, d)}
    out = {"embed": (m["vocab"], d), "head": (d, m["vocab"]), "final_norm": (d,)}
    for group, tree, n in (("gqa", gqa, m["kinds"].count("gqa")), ("kda", kda, m["kinds"].count("kda")),
                           ("moe", moe, m["layers"])):
        out.update({f"{group}.{k}": (n,) + s for k, s in tree.items()})
    return dict(sorted(out.items()))


def parameters_held(keys) -> int:
    return int(sum(np.prod(s) for s in shapes(keys).values()))


def matrix_parameters_per_token(keys) -> float:
    """Matrix parameters a token's forward pass multiplies by: a softmax
    layer's five (q, k, v, the gate, o), a delta-rule layer's nine (q, k, v,
    o, the two low-rank gates' four, beta; the convolutions' 12 taps a channel
    are no matrix), per layer the router, the shared expert and the routed
    experts held that the token is expected to reach (``top_k * held /
    router``), and the head (not the embedding: a row is read)."""
    m = _dims(keys)
    d = m["d"]
    gqa = 3 * d * m["heads"] * m["hd"] + 2 * d * m["kv"] * m["hd"]
    wide = m["kda_heads"] * m["kda_hd"]
    kda = 4 * d * wide + 2 * d * m["rank"] + 2 * m["rank"] * wide + d * m["kda_heads"]
    expert = 3 * d * m["expert_w"]
    return (m["kinds"].count("gqa") * gqa + m["kinds"].count("kda") * kda
            + m["layers"] * (d * m["router"] + m["shared"] * expert
                             + m["top_k"] * m["held"] / m["router"] * expert)
            + d * m["vocab"])


def attention_flops_per_token(keys) -> float:
    """Causal scores and weighted values, forward, the softmax layers: a
    token at position p meets p + 1 keys, (L + 1) / 2 on average."""
    m = _dims(keys)
    return 2.0 * m["kinds"].count("gqa") * m["heads"] * 2 * m["hd"] * (m["seq"] + 1) / 2


def kda_flops_per_token(keys) -> float:
    """The chunked delta rule's matrix products, forward, the delta-rule
    layers, per token (``ops/gated_delta.gated_delta_flops``' count,
    restated): per head three products with the ``[K, V]`` state and, over
    the chunk's ``C`` tokens, the two pairwise sums, the solve's two results
    and the outputs' sum: ``2 (3 K V + C (3 K + 2 V))``, triangles as squares."""
    m = _dims(keys)
    width = m["kda_hd"]
    return 2.0 * m["kinds"].count("kda") * m["kda_heads"] * (3 * width * width + m["chunk"] * 5 * width)


def flops_per_item(keys) -> float:
    """Per token with a target: forward and backward (2 + 4 a parameter, and
    three times the forward attention and delta-rule products).
    Rematerialised operations are not counted."""
    return (6.0 * matrix_parameters_per_token(keys)
            + 3.0 * (attention_flops_per_token(keys) + kda_flops_per_token(keys)))


def attention_kernel_flops_per_step(keys) -> float:
    """What the attention kernels' calls of one step need
    (``ops/flash_attention.attention_flops``'s count, restated): per softmax
    layer the forward call, again where the layer is rematerialised, the dq
    call and the dkv call."""
    m = _dims(keys)
    dk = dv = m["hd"]
    pairs = m["batch"] * m["heads"] * m["seq"] * (m["seq"] + 1) / 2
    fwd, dq, dkv = 2.0 * pairs * (dk + dv), 2.0 * pairs * (2 * dk + dv), 2.0 * pairs * (2 * dk + 2 * dv)
    return m["kinds"].count("gqa") * (fwd * (2 if m["remat"] else 1) + dq + dkv)


def kda_kernel_flops_per_step(keys) -> float:
    """What the chunked recurrence's calls of one step need: per delta-rule
    layer the forward, again where the layer is rematerialised, and the
    backward, which computes the chunks again and then two products for each
    of theirs (three forwards)."""
    m = _dims(keys)
    fwd = m["batch"] * m["seq"] * kda_flops_per_token(keys)
    return fwd * ((2 if m["remat"] else 1) + 3)


def experts_kernel_flops(keys, held_assignments: float) -> float:
    """What the grouped products need for ``held_assignments`` (token, expert)
    pairs, whatever steps and layers they are summed over: three products an
    expert, each forward (again where rematerialised), dx and dw; padding rows
    are not counted."""
    m = _dims(keys)
    return held_assignments * 3 * 2.0 * m["d"] * m["expert_w"] * ((2 if m["remat"] else 1) + 2)


# ----------------------------------------------------------------- weights ---


def _leaf(seed, index, shape, std: float, law: str):
    """Leaf ``index`` from the seed under its law (``models/moelm.init_leaf``'s
    laws, from ``fold_in(PRNGKey(seed), index)``)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.PRNGKey(seed), index)
    if law == "ones":
        return jnp.ones(shape, jnp.float32)
    if law == "a_log":  # -exp(a_log) in (-16, -1)
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if law == "dt_bias":  # softplus(dt_bias) log-uniform in (0.001, 0.1)
        rate = jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
        return rate + jnp.log(-jnp.expm1(-rate))
    if law == "taps":
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    return jax.random.normal(key, shape, jnp.float32) * std


def law_of(name: str) -> str:
    leaf = name.rsplit(".", 1)[-1]
    if leaf.endswith("norm"):
        return "ones"
    return leaf if leaf in ("a_log", "dt_bias") else "taps" if leaf.startswith("conv_") else "normal"


@functools.lru_cache(maxsize=None)
def _jitted():
    """(a leaf from the seed; the squared distance of an array from that
    leaf): seed and leaf number are operands, so leaves of one shape and law
    share one compiled program."""
    import jax
    import jax.numpy as jnp

    def change(v, seed, index, std, law):
        return jnp.sum((v - _leaf(seed, index, v.shape, std, law)) ** 2)

    return (jax.jit(_leaf, static_argnums=(2, 3, 4)), jax.jit(change, static_argnums=(3, 4)))


def make_weights(keys, seed: int, std: float) -> dict:
    """{leaf: float32 array} from the seed, a jitted call a leaf, leaf i in
    name order."""
    make, _ = _jitted()
    s = np.uint32(seed & 0xFFFFFFFF)
    return {name: make(s, np.uint32(i), shape, std, law_of(name))
            for i, (name, shape) in enumerate(shapes(keys).items())}


# ---------------------------------------------------------- reference ---

FAULTS = ("half_batch", "state_unchanged", "no_decay", "beta_not_doubled", "no_conv", "no_kda_gate",
          "no_gqa_gate", "state_dropped")
L2_EPS = 1e-6


def reference_math(hp, precision="float32", fault=None):
    """The model's arithmetic as plain functions of one sequence, for
    :func:`solar_reference` and for the tests that hold the program's layers
    to it one at a time: ``norm``, ``attention(p, x)``, ``kda(p, x)``,
    ``swiglu(p, prefix, y)``, ``mixture(p, bias, y)`` -> (output, balance
    loss, choices), ``loss_of(params, bias, tokens)`` -> (loss, choices),
    ``store``."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    m = _dims(hp)
    bf16 = precision == "bfloat16"
    store = (lambda a: jax.lax.reduce_precision(a, 8, 7)) if bf16 else (lambda a: a)
    eps = float(hp["rms_norm_eps"])
    scale, alpha_aux = float(hp.get("routed_scaling_factor", 1.0)), float(hp["aux_loss_alpha"])
    h, kv, hd, top_k = m["heads"], m["kv"], m["hd"], m["top_k"]
    kh, khd, taps = m["kda_heads"], m["kda_hd"], m["taps"]
    beta_max = 2.0 if int(hp.get("kda_allow_neg_eigval", 0)) and fault != "beta_not_doubled" else 1.0
    block = 64  # tokens whose states the backward pass makes again at a time: no part of the arithmetic

    def norm(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain

    def attention(p, x):  # one sequence [L, d]: no rotary, no q/k norm, the output gated
        seq = x.shape[0]
        y = norm(x, p["attn_norm"])
        q, k = (y @ p["wq"]).reshape(seq, h, hd), (y @ p["wk"]).reshape(seq, kv, hd)
        v = (y @ p["wv"]).reshape(seq, kv, hd)
        keep = jnp.tril(jnp.ones((seq, seq), bool))

        @jax.checkpoint
        def head(i):  # the scores of one query head at a time
            s = q[:, i] @ k[:, i // (h // kv)].T / np.sqrt(hd)
            return jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1) @ v[:, i // (h // kv)]

        out = jax.lax.map(head, jnp.arange(h)).transpose(1, 0, 2).reshape(seq, h * hd)
        if fault != "no_gqa_gate":
            out = out * jax.nn.sigmoid(y @ p["wz"])
        return out @ p["wo"]

    def conv(u, w):  # silu of four shifted sums: u [L, channels], w [taps, channels]
        if fault == "no_conv":
            return jax.nn.silu(u)
        seq = u.shape[0]
        shifted = lambda by: jnp.concatenate([jnp.zeros_like(u[:by]), u[: seq - by]])  # noqa: E731
        return jax.nn.silu(sum(w[j] * shifted(taps - 1 - j) for j in range(taps)))

    def kda(p, x):  # one sequence [L, d], the recurrence a token at a time
        seq = x.shape[0]
        y = norm(x, p["attn_norm"])
        heads = lambda t: t.reshape(seq, kh, khd)  # noqa: E731
        l2 = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)  # noqa: E731
        q, k = l2(heads(conv(y @ p["wq"], p["conv_q"]))), l2(heads(conv(y @ p["wk"], p["conv_k"])))
        v = heads(conv(y @ p["wv"], p["conv_v"]))
        g = -jnp.exp(p["a_log"])[None, :, None] * heads(jax.nn.softplus((y @ p["f_down"]) @ p["f_up"] + p["dt_bias"]))
        alpha = jnp.ones_like(g) if fault == "no_decay" else jnp.exp(g)
        beta = beta_max * jax.nn.sigmoid(y @ p["wb"])  # [L, heads]
        fresh = (jnp.arange(seq) % m["chunk"] == 0) if fault == "state_dropped" else jnp.zeros(seq, bool)

        def token(s, t):  # s [heads, K, V]
            q_t, k_t, v_t, a_t, b_t, drop = t
            s = a_t[:, :, None] * jnp.where(drop, 0.0, s)
            s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t) / np.sqrt(khd)

        tokens = (q, k, v, alpha, beta, fresh)
        if seq % block == 0:  # keep a state a block, not one a token, for the backward pass
            blocks = jax.tree_util.tree_map(lambda a: a.reshape(seq // block, block, *a.shape[1:]), tokens)
            _, o = jax.lax.scan(jax.checkpoint(lambda s, b: jax.lax.scan(token, s, b)),
                                jnp.zeros((kh, khd, khd), jnp.float32), blocks)
            o = o.reshape(seq, kh, khd)
        else:
            _, o = jax.lax.scan(token, jnp.zeros((kh, khd, khd), jnp.float32), tokens)
        o = norm(o, p["o_norm"]).reshape(seq, kh * khd)
        if fault != "no_kda_gate":
            o = o * jax.nn.sigmoid((y @ p["g_down"]) @ p["g_up"])
        return o @ p["wo"]

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
        balance = alpha_aux * jnp.sum(f * jnp.mean(s / s.sum(axis=-1, keepdims=True), axis=0))
        gate_of = jnp.einsum("tk,tke->te", gates, hit)  # each token's gate for every expert

        @jax.checkpoint
        def expert(w_gate, w_up, w_down, gate):  # one held expert over every token, masked by its gate
            return gate[:, None] * ((jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down)

        out, _ = jax.lax.scan(lambda out, e: (out + expert(*e), ()), jnp.zeros_like(y), (
            p["experts_gate"], p["experts_up"], p["experts_down"],
            gate_of[:, m["offset"]: m["offset"] + m["held"]].T))
        if m["shared"]:
            out = out + swiglu(p, "shared", y)
        return out, balance, choices

    def leaves_of(params, i):  # layer i's leaves: its mixer's, then its feed-forward's
        kind = m["kinds"][i]
        place = m["kinds"][:i].count(kind)
        take = lambda group, at: {k.split(".")[1]: v for k, v in params.items()  # noqa: E731
                                  if k.startswith(group + ".") and k.endswith(f".{at}")}
        return {**take(kind, place), **take("moe", i)}

    def loss_of(params, bias, tokens):  # a leaf a layer (``by_layer``); tokens [B, L + 1]
        total, balance, picks = 0.0, 0.0, []
        for row in tokens:
            x = store(params["embed"][row[:-1]])
            seen = []
            for i, kind in enumerate(m["kinds"]):
                @jax.checkpoint
                def layer(x, p, b, mix=kda if kind == "kda" else attention):
                    x = store(x + mix(p, x))
                    out, bal, ch = mixture(p, b, norm(x, p["mlp_norm"]))
                    return store(x + out), bal, ch
                x, bal, ch = layer(x, leaves_of(params, i), bias[i])
                balance = balance + bal
                seen.append(ch)
            logits = norm(x, params["final_norm"]) @ params["head"]
            ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, row[1:, None], axis=-1)[:, 0]
            total = total + jnp.sum(ce)
            picks.append(jnp.stack(seen))  # [layers, L, k]
        count = tokens.shape[0] * (tokens.shape[1] - 1)
        return total / count + balance / tokens.shape[0], jnp.concatenate(picks, axis=1)

    return types.SimpleNamespace(norm=norm, attention=attention, kda=kda, swiglu=swiglu, mixture=mixture,
                                 loss_of=loss_of, store=store, dims=m)


GROUPS = ("gqa.", "kda.", "moe.")


def by_layer(w: dict) -> dict:
    """``{"kda.wq": [layers, ...]}`` -> ``{"kda.wq.0": ..., "kda.wq.1": ...}``,
    the other leaves as they are; ``w`` is emptied. The reference holds a
    leaf a layer: a slice of a stacked leaf inside the differentiated step is
    a copy of it, and its gradient a padded sum."""
    out = {}
    for k in list(w):
        v = w.pop(k)
        out.update({f"{k}.{i}": v[i] for i in range(v.shape[0])} if k.startswith(GROUPS) else {k: v})
    return out


def _stacked(sumsq: dict) -> dict:
    """A sum of squares a stacked leaf from one a layer's leaf."""
    out = {}
    for k, v in sumsq.items():
        name = k.rsplit(".", 1)[0] if k.startswith(GROUPS) else k
        out[name] = out.get(name, 0.0) + float(v)
    return out


def reference_step(hp, math, fault=None):
    """The jitted reference step ``(params, mom, var, bias, tokens, t) ->
    (params, mom, var, bias, loss, each leaf's squared gradient, choices)``:
    loss and gradients by ``jax.grad``, AdamW written out, then the selection
    bias's step."""
    import jax
    import jax.numpy as jnp

    m, store, loss_of = math.dims, math.store, math.loss_of
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
        counts = jax.nn.one_hot(choices, m["router"], dtype=jnp.float32).sum(axis=(1, 2))
        bias = bias + bias_rate * jnp.sign(counts.mean(axis=-1, keepdims=True) - counts)
        return params, mom, var, bias, loss, g2, choices

    return step


def solar_reference(w, batches, hp, precision="float32", fault=None):
    """Three (``len(batches)``) training steps from the weights ``w`` ({leaf:
    array}, layers stacked; the dict is emptied): loss (next-token cross
    entropy over the slice + the sequence-wise balance loss), gradients,
    AdamW, then the selection bias's step. ``hp``: the configuration's
    ``keys`` and ``keys_reference``.

    ``precision`` "bfloat16" is the control: the residual stream and the
    weights are stored in bfloat16 and products run at the default precision.
    ``fault``: "half_batch" trains on the first half of every sequence;
    "state_unchanged" applies no update; "no_decay" takes ``alpha = 1``;
    "beta_not_doubled" ``beta = sigmoid`` alone; "no_conv" leaves the
    convolutions out (their SiLU stays); "no_kda_gate" and "no_gqa_gate" the
    output gate of that mixer; "state_dropped" starts every chunk of 64
    tokens (the program's) from ``S = 0``.

    Returns {"loss": [...], "grad1": {leaf: sumsq of the first gradient},
    "change": {leaf: [sumsq of the change since the start, after each step]},
    "choices": [per step, [layers, tokens, k] expert ids]}.
    """
    import jax
    import jax.numpy as jnp

    math = reference_math(hp, precision, fault)
    m, store = math.dims, math.store
    bf16 = precision == "bfloat16"
    step = reference_step(hp, math, fault)

    @jax.jit
    def sumsq(a, b):
        return jnp.sum((a - b) ** 2)

    with jax.default_matmul_precision("default" if bf16 else "highest"):
        # ``w`` is emptied leaf by leaf (the control's rounded copy must not
        # stand beside the original), and the start stays on the host
        params = {k: store(v) for k, v in by_layer(w).items()}
        start = {k: np.asarray(v) for k, v in params.items()}
        mom = {k: jnp.zeros_like(v) for k, v in params.items()}
        var = {k: jnp.zeros_like(v) for k, v in params.items()}
        bias = jnp.zeros((m["layers"], m["router"]), jnp.float32)
        names = list(_stacked(dict.fromkeys(start, 0.0)))
        out = {"loss": [], "grad1": {}, "change": {k: [] for k in names + ["router_bias"]}, "choices": []}
        for i, bt in enumerate(batches):
            tokens = np.asarray(bt["tokens"])
            if fault == "half_batch":
                tokens = tokens[:, : (tokens.shape[1] - 1) // 2 + 1]
            params, mom, var, bias, loss, g2, choices = step(
                params, mom, var, bias, jnp.asarray(tokens), jnp.float32(i + 1))
            if i == 0:
                out["grad1"] = _stacked(jax.device_get(g2))
            out["loss"].append(float(loss))
            out["choices"].append(np.asarray(choices))
            for k, v in _stacked({k: sumsq(params[k], start[k]) for k in start}).items():
                out["change"][k].append(v)
            out["change"]["router_bias"].append(float(jnp.sum(bias * bias)))
    return out


# ------------------------------------------------------------ adapter ---


class Adapter(moonlight.Adapter):
    """The program's state is ``{"params": {"embed", "head", "final_norm",
    "gqa", "kda": the mixers stacked by kind, "moe": the feed-forward parts
    stacked over all layers}, "opt": optax.adamw's, "router_bias", "counts",
    "choices", "dropped", "kda_decay"}``; the benchmark's leaves are the same
    arrays under dotted names. ``dataset``, ``state``, ``extra_numbers``
    (``route_disagree_share``), ``reference_variants`` and ``extra_faults``
    are ``moonlight.Adapter``'s."""

    def __init__(self, run, trainer):
        self.run, self.trainer = run, trainer
        self.keys = {**run.config["keys"], **run.mix.get("keys", {})}
        mine = {k: tuple(v) for k, v in _flatten(trainer.param_shapes()).items()}
        if mine != shapes(self.keys):
            raise ValueError("the program's parameter tree is not the benchmark's")
        # the weights come from the configuration's ``init.seed``, the same in
        # every run (the routed load is drawn with them: PERF.md section 7);
        # the feed and the loop's order come from --seed
        self.weights_seed = int(run.config["init"]["seed"])
        self._program_choices = self._reference_choices = None

    def _weights(self):
        return make_weights(self.keys, self.weights_seed, float(self.run.config["init"]["std"]))

    def readings(self):
        """Per warm step: each leaf's change since the start (the weights
        made again, a leaf at a time), the sum of squares of AdamW's first
        moment (after one step (1 - b1) times the first gradient), the step's
        choices, counts, dropped assignments and mean decay."""
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
            change = {k: change_of(params[k], seed, np.uint32(i), std, law_of(k))
                      for i, k in enumerate(names)}
            change["router_bias"] = sumsq(state["router_bias"])
            return {"change": change, "moment": {k: sumsq(moment[k]) for k in names},
                    "choices": state["choices"], "counts": state["counts"],
                    "dropped": state["dropped"], "kda_decay": state["kda_decay"]}

        return read

    def program_grad1(self, reads):
        """The first gradient as AdamW got it, from its first moment; and
        what the warm steps counted, kept for the readers of the program's
        counters (``benchmark/metrics/moe.*.py``, ``kda.decay_mean.py``)."""
        m = _dims(self.keys)
        counts = np.asarray([r.pop("counts") for r in reads], np.float64)  # [steps, layers, E]
        held = counts[:, :, m["offset"]: m["offset"] + m["held"]]
        self.run.counters["moe"] = {
            "held_share_pct": 100.0 * held.sum() / counts.sum(),
            "load_max_over_mean": float(np.mean(held.max(axis=-1) / np.maximum(held.mean(axis=-1), 1))),
            "dropped": int(sum(int(r.pop("dropped")) for r in reads)),
        }
        self.run.counters["kda"] = {"decay_mean": float(np.mean([float(r.pop("kda_decay")) for r in reads]))}
        self.warm_counts = counts.astype(np.int64)
        self._program_choices = [np.asarray(r.pop("choices")) for r in reads]
        b1 = float(self._hp()["adam_b1"])
        return {k: float(v) / (1 - b1) ** 2 for k, v in reads[0]["moment"].items()}

    def reference(self, batches, precision="float32", fault=None):
        ref = solar_reference(self._weights(), batches, self._hp(), precision=precision, fault=fault)
        choices = ref.pop("choices")
        if precision == "float32" and fault is None:
            self._reference_choices = choices
        return ref

    def parts(self):
        """For ``control.py``: every fault the reference can plant, put in the
        program's place; each has to come out not correct."""
        return {f: {"fault": f} for f in FAULTS if f != "half_batch"}
