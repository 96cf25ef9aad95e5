"""Olmo-Hybrid-7B (``model_type: olmo_hybrid``: of every four layers three mix
tokens by Gated DeltaNet, a delta rule with a decay per head (arXiv:2412.06464),
keys 96 wide and values 192, and one by full softmax attention without
rotary, its query and key normed over the whole projection; every layer a
dense SwiGLU; OLMo 2's norms after each half, arXiv:2501.00656) as one chip
of a two-way tensor-parallel layer holds it, for the benchmark: its feed, its
weights in the program's layout, what is read from the program's state, the
plain reference, and the operations the step and its kernels need.
``lib/jobs.py`` loads this file by the configuration's ``model``. Only
``Adapter`` touches the program (``swiftsnails_tpu/models/moelm.py``). What
is no model's own comes from the files beside it: the feed's generator and
the attention kernels' share of their roofline (``moonlight.py``), the
weights' laws by leaf name (``solar.py``).

The equations are :func:`reference_math`'s, executably: plain ``jax.numpy``,
float32 at ``highest``, no kernel, no chunk and no solve: the delta rule runs
a token at a time (``S'_t = alpha_t S_(t-1)`` with one ``alpha`` a head;
``S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T``; ``o_t = S_t^T q_t /
sqrt(96)``), the convolution is four shifted sums, the softmax is dense and
causal a head. The heads and vocabulary rows are the share the configuration
states, here as in the program.
"""

import functools
import types

import numpy as np

from lib import jobs

moonlight = jobs.load_model("moonlight")
solar = jobs.load_model("solar")
kernel_roofline_pct = moonlight.kernel_roofline_pct
_flatten, _nest = moonlight._flatten, moonlight._nest  # the program's tree <-> dotted leaves
law_of, _jitted = solar.law_of, solar._jitted  # a leaf's law by its name; the jitted leaf maker

# ----------------------------------------------- operations and shapes ---

CHUNK = 64  # the tokens of a chunk in the program's recurrence (a test's small trainer says ``kda_chunk``)


def _dims(keys):
    g = lambda k, d=None: int(keys.get(k, d))  # noqa: E731
    layers = g("num_hidden_layers")
    named = keys["layer_types"]
    if isinstance(named, str):  # as the program's own config holds it
        named = [t.strip(" '\"") for t in named.strip("[]() ").split(",") if t.strip()]
    kinds = tuple({"full_attention": "gqa", "linear_attention": "gdn"}[t] for t in named[:layers])
    return {
        "d": g("hidden_size"), "layers": layers, "kinds": kinds, "heads": g("num_attention_heads"),
        "kv": g("num_key_value_heads"), "hd": g("head_dim"), "lin_heads": g("linear_num_value_heads"),
        "key": g("linear_key_head_dim"), "value": g("linear_value_head_dim"), "taps": g("linear_conv_kernel_dim"),
        "chunk": g("kda_chunk", CHUNK), "ffn": g("intermediate_size"), "vocab": g("vocab_size"),
        "seq": g("seq_len"), "batch": g("batch_size", 1), "remat": g("remat", 1),
    }


def shapes(keys) -> dict:
    """{leaf name: shape}; the mixers of a kind are stacked on a leading
    axis, the feed-forward parts over all layers, as the program holds them
    (``MoELMTrainer.param_shapes``, flattened with dots)."""
    m = _dims(keys)
    d, h, kv, hd = m["d"], m["heads"], m["kv"], m["hd"]
    lh, keys_w, values_w = m["lin_heads"], m["lin_heads"] * m["key"], m["lin_heads"] * m["value"]
    gqa = {"attn_norm": (d,), "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
           "q_norm": (h * hd,), "k_norm": (kv * hd,), "wo": (h * hd, d)}
    gdn = {"attn_norm": (d,), "wq": (d, keys_w), "wk": (d, keys_w), "wv": (d, values_w),
           "conv_q": (m["taps"], keys_w), "conv_k": (m["taps"], keys_w), "conv_v": (m["taps"], values_w),
           "wa": (d, lh), "a_log": (lh,), "dt_bias": (lh,), "wb": (d, lh), "wg": (d, values_w),
           "o_norm": (m["value"],), "wo": (values_w, d)}
    dense = {"mlp_norm": (d,), "mlp_gate": (d, m["ffn"]), "mlp_up": (d, m["ffn"]), "mlp_down": (m["ffn"], d)}
    out = {"embed": (m["vocab"], d), "head": (d, m["vocab"]), "final_norm": (d,)}
    for group, tree, n in (("gqa", gqa, m["kinds"].count("gqa")), ("gdn", gdn, m["kinds"].count("gdn")),
                           ("dense", dense, m["layers"])):
        out.update({f"{group}.{k}": (n,) + s for k, s in tree.items()})
    return dict(sorted(out.items()))


def parameters_held(keys) -> int:
    return int(sum(np.prod(s) for s in shapes(keys).values()))


def matrix_parameters_per_token(keys) -> float:
    """Matrix parameters a token's forward pass multiplies by: a softmax
    layer's four (q, k, v, o), a delta-rule layer's seven (q, k, v, the
    gate, o, the rate's and beta's; the convolutions' taps are no matrix),
    every layer's SwiGLU, and the head (not the embedding: a row is read)."""
    m = _dims(keys)
    d = m["d"]
    gqa = 2 * d * m["heads"] * m["hd"] + 2 * d * m["kv"] * m["hd"]
    keys_w, values_w = m["lin_heads"] * m["key"], m["lin_heads"] * m["value"]
    gdn = 2 * d * keys_w + 3 * d * values_w + 2 * d * m["lin_heads"]
    return (m["kinds"].count("gqa") * gqa + m["kinds"].count("gdn") * gdn
            + m["layers"] * 3 * d * m["ffn"] + d * m["vocab"])


def attention_flops_per_token(keys) -> float:
    """Causal scores and weighted values, forward, the softmax layers: a
    token at position p meets p + 1 keys, (L + 1) / 2 on average."""
    m = _dims(keys)
    return 2.0 * m["kinds"].count("gqa") * m["heads"] * 2 * m["hd"] * (m["seq"] + 1) / 2


def gdn_flops_per_token(keys) -> float:
    """The chunked delta rule's matrix products, forward, the delta-rule
    layers, per token (``ops/gated_delta.gated_delta_flops``' count,
    restated): per head three products with the ``[K, V]`` state and, over
    the chunk's ``C`` tokens, the two pairwise sums, the solve's two results
    and the outputs' sum: ``2 (3 K V + C (3 K + 2 V))``, triangles as squares."""
    m = _dims(keys)
    k, v = m["key"], m["value"]
    return 2.0 * m["kinds"].count("gdn") * m["lin_heads"] * (3 * k * v + m["chunk"] * (3 * k + 2 * v))


def flops_per_item(keys) -> float:
    """Per token with a target: forward and backward (2 + 4 a parameter, and
    three times the forward attention and delta-rule products).
    Rematerialised operations are not counted."""
    return (6.0 * matrix_parameters_per_token(keys)
            + 3.0 * (attention_flops_per_token(keys) + gdn_flops_per_token(keys)))


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


def gdn_kernel_flops_per_step(keys) -> float:
    """What the chunked recurrence's calls of one step need: per delta-rule
    layer the forward, again where the layer is rematerialised, and the
    backward, which computes the chunks again and then two products for each
    of theirs (three forwards)."""
    m = _dims(keys)
    fwd = m["batch"] * m["seq"] * gdn_flops_per_token(keys)
    return fwd * ((2 if m["remat"] else 1) + 3)


# ----------------------------------------------------------------- weights ---


def make_weights(keys, seed: int, std: float) -> dict:
    """{leaf: float32 array} from the seed, a jitted call a leaf, leaf i in
    name order, each under its law (``solar.law_of``)."""
    make, _ = _jitted()
    s = np.uint32(seed & 0xFFFFFFFF)
    return {name: make(s, np.uint32(i), shape, std, law_of(name))
            for i, (name, shape) in enumerate(shapes(keys).items())}


# ---------------------------------------------------------- reference ---

FAULTS = ("half_batch", "state_unchanged", "pre_norm", "qk_norm_per_head", "sigmoid_gate", "no_decay",
          "beta_not_doubled", "no_conv", "state_dropped")
L2_EPS = 1e-6


def reference_math(hp, precision="float32", fault=None):
    """The model's arithmetic as plain functions of one sequence, for
    :func:`olmo_reference` and for the tests that hold the program's layers
    to it one at a time: ``norm``, ``attention(p, x, mean_squares=None)``,
    ``gdn(p, x)``, ``swiglu(p, y)``, ``layer(kind, p, x)``,
    ``loss_of(params, tokens)``, ``store``. ``mean_squares`` (a pair ``[L,
    1]``), if given, stands for the q/k norm's over the held width: what a
    share of the heads would take from the deployment's exchange."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    m = _dims(hp)
    bf16 = precision == "bfloat16"
    store = (lambda a: jax.lax.reduce_precision(a, 8, 7)) if bf16 else (lambda a: a)
    eps = float(hp["rms_norm_eps"])
    h, kv, hd = m["heads"], m["kv"], m["hd"]
    lh, kw, vw, taps = m["lin_heads"], m["key"], m["value"], m["taps"]
    beta_max = 2.0 if int(hp.get("linear_allow_neg_eigval", 0)) and fault != "beta_not_doubled" else 1.0
    post = fault != "pre_norm" and hp.get("norm_order", "pre") == "post"
    block = 64  # tokens whose states the backward pass makes again at a time: no part of the arithmetic

    def norm(x, gain, mean_square=None):
        if mean_square is None:
            mean_square = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(mean_square + eps) * gain

    def qk_norm(t, gain, n, mean_square):  # over the held width, or a head at a time (the fault)
        if fault == "qk_norm_per_head":
            seq = t.shape[0]
            return norm(t.reshape(seq, n, hd), 1.0).reshape(seq, n * hd) * gain
        return norm(t, gain, mean_square)

    def attention(p, x, mean_squares=None):  # one sequence [L, d]: no rotary, the q/k norm over the width
        seq = x.shape[0]
        y = x if post else norm(x, p["attn_norm"])
        ms_q, ms_k = mean_squares or (None, None)
        q = qk_norm(y @ p["wq"], p["q_norm"], h, ms_q).reshape(seq, h, hd)
        k = qk_norm(y @ p["wk"], p["k_norm"], kv, ms_k).reshape(seq, kv, hd)
        v = (y @ p["wv"]).reshape(seq, kv, hd)
        keep = jnp.tril(jnp.ones((seq, seq), bool))

        @jax.checkpoint
        def head(i):  # the scores of one query head at a time
            s = q[:, i] @ k[:, i // (h // kv)].T / np.sqrt(hd)
            return jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1) @ v[:, i // (h // kv)]

        return jax.lax.map(head, jnp.arange(h)).transpose(1, 0, 2).reshape(seq, h * hd) @ p["wo"]

    def conv(u, w):  # silu of four shifted sums: u [L, channels], w [taps, channels]
        if fault == "no_conv":
            return jax.nn.silu(u)
        seq = u.shape[0]
        shifted = lambda by: jnp.concatenate([jnp.zeros_like(u[:by]), u[: seq - by]])  # noqa: E731
        return jax.nn.silu(sum(w[j] * shifted(taps - 1 - j) for j in range(taps)))

    def gdn(p, x):  # one sequence [L, d], the recurrence a token at a time
        seq = x.shape[0]
        y = x if post else norm(x, p["attn_norm"])
        l2 = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)  # noqa: E731
        q = l2(conv(y @ p["wq"], p["conv_q"]).reshape(seq, lh, kw))
        k = l2(conv(y @ p["wk"], p["conv_k"]).reshape(seq, lh, kw))
        v = conv(y @ p["wv"], p["conv_v"]).reshape(seq, lh, vw)
        g = -jnp.exp(p["a_log"])[None, :] * jax.nn.softplus(y @ p["wa"] + p["dt_bias"])  # [L, heads]
        alpha = jnp.ones_like(g) if fault == "no_decay" else jnp.exp(g)
        beta = beta_max * jax.nn.sigmoid(y @ p["wb"])  # [L, heads]
        fresh = (jnp.arange(seq) % m["chunk"] == 0) if fault == "state_dropped" else jnp.zeros(seq, bool)

        def token(s, t):  # s [heads, K, V]
            q_t, k_t, v_t, a_t, b_t, drop = t
            s = a_t[:, None, None] * jnp.where(drop, 0.0, s)
            s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t) / np.sqrt(kw)

        tokens = (q, k, v, alpha, beta, fresh)
        start = jnp.zeros((lh, kw, vw), jnp.float32)
        if seq % block == 0:  # keep a state a block, not one a token, for the backward pass
            blocks = jax.tree_util.tree_map(lambda a: a.reshape(seq // block, block, *a.shape[1:]), tokens)
            _, o = jax.lax.scan(jax.checkpoint(lambda s, b: jax.lax.scan(token, s, b)), start, blocks)
            o = o.reshape(seq, lh, vw)
        else:
            _, o = jax.lax.scan(token, start, tokens)
        gate = (jax.nn.sigmoid if fault == "sigmoid_gate" else jax.nn.silu)(y @ p["wg"])
        return (norm(o, p["o_norm"]).reshape(seq, lh * vw) * gate) @ p["wo"]

    def swiglu(p, y):
        return (jax.nn.silu(y @ p["mlp_gate"]) * (y @ p["mlp_up"])) @ p["mlp_down"]

    def layer(kind, p, x):  # the two halves, each's norm where the residual order puts it
        mix = gdn if kind == "gdn" else attention
        if post:
            x = store(x + norm(mix(p, x), p["attn_norm"]))
            return store(x + norm(swiglu(p, x), p["mlp_norm"]))
        x = store(x + mix(p, x))
        return store(x + swiglu(p, norm(x, p["mlp_norm"])))

    def leaves_of(params, i):  # layer i's leaves: its mixer's, then its feed-forward's
        kind = m["kinds"][i]
        place = m["kinds"][:i].count(kind)
        take = lambda group, at: {k.split(".")[1]: v for k, v in params.items()  # noqa: E731
                                  if k.startswith(group + ".") and k.endswith(f".{at}")}
        return {**take(kind, place), **take("dense", i)}

    def loss_of(params, tokens):  # a leaf a layer (``by_layer``); tokens [B, L + 1]
        total = 0.0
        for row in tokens:
            x = store(params["embed"][row[:-1]])
            for i, kind in enumerate(m["kinds"]):
                x = jax.checkpoint(functools.partial(layer, kind))(leaves_of(params, i), x)
            logits = norm(x, params["final_norm"]) @ params["head"]
            ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, row[1:, None], axis=-1)[:, 0]
            total = total + jnp.sum(ce)
        return total / (tokens.shape[0] * (tokens.shape[1] - 1))

    return types.SimpleNamespace(norm=norm, attention=attention, gdn=gdn, swiglu=swiglu, layer=layer,
                                 loss_of=loss_of, store=store, dims=m)


GROUPS = ("gqa.", "gdn.", "dense.")


def by_layer(w: dict) -> dict:
    """``{"gdn.wq": [layers, ...]}`` -> ``{"gdn.wq.0": ..., "gdn.wq.1": ...}``,
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
    """The jitted reference step ``(params, mom, var, tokens, t) -> (params,
    mom, var, loss, each leaf's squared gradient)``: loss and gradients by
    ``jax.grad``, AdamW written out."""
    import jax
    import jax.numpy as jnp

    store, loss_of = math.store, math.loss_of
    lr, b1, b2 = float(hp["learning_rate"]), float(hp["adam_b1"]), float(hp["adam_b2"])
    adam_eps, decay = float(hp["adam_eps"]), float(hp["weight_decay"])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mom, var, tokens, t):
        loss, g = jax.value_and_grad(loss_of)(params, tokens)
        g2 = {k: jnp.sum(v * v) for k, v in g.items()}
        if fault == "state_unchanged":
            return params, mom, var, loss, g2
        mom = {k: b1 * mom[k] + (1 - b1) * g[k] for k in g}
        var = {k: b2 * var[k] + (1 - b2) * g[k] * g[k] for k in g}
        params = {k: store(params[k] - lr * (
            (mom[k] / (1 - b1 ** t)) / (jnp.sqrt(var[k] / (1 - b2 ** t)) + adam_eps)
            + decay * params[k])) for k in g}
        return params, mom, var, loss, g2

    return step


def olmo_reference(w, batches, hp, precision="float32", fault=None):
    """Three (``len(batches)``) training steps from the weights ``w`` ({leaf:
    array}, layers stacked; the dict is emptied): next-token cross entropy
    over the slice, gradients, AdamW. ``hp``: the configuration's ``keys``
    and ``keys_reference``.

    ``precision`` "bfloat16" is the control: the residual stream and the
    weights are stored in bfloat16 and products run at the default precision.
    ``fault``: "half_batch" trains on the first half of every sequence;
    "state_unchanged" applies no update; "pre_norm" puts each half's norm
    before it (the residual order of every other model here); "qk_norm_per_head"
    norms the query and the key a head; "sigmoid_gate" gates the delta rule's
    output by a sigmoid; "no_decay" takes ``alpha = 1``; "beta_not_doubled"
    ``beta = sigmoid`` alone; "no_conv" leaves the convolutions out (their
    SiLU stays); "state_dropped" starts every chunk of 64 tokens (the
    program's) from ``S = 0``.

    Returns {"loss": [...], "grad1": {leaf: sumsq of the first gradient},
    "change": {leaf: [sumsq of the change since the start, after each step]}}.
    """
    import jax
    import jax.numpy as jnp

    math = reference_math(hp, precision, fault)
    store = math.store
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
        out = {"loss": [], "grad1": {}, "change": {k: [] for k in _stacked(dict.fromkeys(start, 0.0))}}
        for i, bt in enumerate(batches):
            tokens = np.asarray(bt["tokens"])
            if fault == "half_batch":
                tokens = tokens[:, : (tokens.shape[1] - 1) // 2 + 1]
            params, mom, var, loss, g2 = step(params, mom, var, jnp.asarray(tokens), jnp.float32(i + 1))
            if i == 0:
                out["grad1"] = _stacked(jax.device_get(g2))
            out["loss"].append(float(loss))
            for k, v in _stacked({k: sumsq(params[k], start[k]) for k in start}).items():
                out["change"][k].append(v)
    return out


# ------------------------------------------------------------ adapter ---


class Adapter(moonlight.Adapter):
    """The program's state is ``{"params": {"embed", "head", "final_norm",
    "gqa", "gdn": the mixers stacked by kind, "dense": the feed-forward parts
    over all layers}, "opt": optax.adamw's, "gdn_decay"}``: a dense stack
    has no router, selection bias or counts. The benchmark's leaves are the
    same arrays under dotted names. ``dataset``, ``state`` and
    ``reference_variants`` are ``moonlight.Adapter``'s."""

    def __init__(self, run, trainer):
        self.run, self.trainer = run, trainer
        self.keys = {**run.config["keys"], **run.mix.get("keys", {})}
        mine = {k: tuple(v) for k, v in _flatten(trainer.param_shapes()).items()}
        if mine != shapes(self.keys):
            raise ValueError("the program's parameter tree is not the benchmark's")
        # the weights come from the configuration's ``init.seed``, the same in
        # every run; the feed and the loop's order come from --seed
        self.weights_seed = int(run.config["init"]["seed"])

    def _weights(self):
        return make_weights(self.keys, self.weights_seed, float(self.run.config["init"]["std"]))

    def readings(self):
        """Per warm step: each leaf's change since the start (the weights
        made again, a leaf at a time), the sum of squares of AdamW's first
        moment (after one step (1 - b1) times the first gradient) and the
        step's mean decay."""
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
            return {"change": change, "moment": {k: sumsq(moment[k]) for k in names},
                    "gdn_decay": state["gdn_decay"]}

        return read

    def program_grad1(self, reads):
        """The first gradient as AdamW got it, from its first moment; and the
        warm steps' mean decay, kept for ``benchmark/metrics/gdn.decay_mean.py``
        (no ``moe`` counters: there are no experts)."""
        self.run.counters["gdn"] = {"decay_mean": float(np.mean([float(r.pop("gdn_decay")) for r in reads]))}
        b1 = float(self._hp()["adam_b1"])
        return {k: float(v) / (1 - b1) ** 2 for k, v in reads[0]["moment"].items()}

    def reference(self, batches, precision="float32", fault=None):
        return olmo_reference(self._weights(), batches, self._hp(), precision=precision, fault=fault)

    def extra_numbers(self, batches):
        return {}

    def parts(self):
        """For ``control.py``: every fault the reference can plant, put in the
        program's place; each has to come out not correct."""
        return {f: {"fault": f} for f in FAULTS if f != "half_batch"}
