"""SDAR-30B-A3B-Chat (``model_type: sdar_moe``: Qwen3-MoE's layer, 32 query
heads over 4 key/value heads with a norm on every head's query and key, a
softmax router over 128 experts, 8 a token, none shared) trained by block
diffusion, as one chip of eight holds it, for the benchmark: its feed, its
weights in the program's layout, what is read from the program's state, the
plain reference, and the operations the step and its kernels need.
``lib/jobs.py`` loads this file by the configuration's ``model``. Only
``Adapter`` touches the program (``swiftsnails_tpu/models/moelm.py``). What
is no model's own comes from ``moonlight.py`` beside it: the kernels' share of
their roofline, the window's counts, the feed's generator, the leaf-by-leaf
weights, the routers' disagreement.

The equations are :func:`reference_math`'s, executably: plain ``jax.numpy``,
float32 at ``highest``, no kernel, no sort (a loop over the experts held,
with a mask), the attention mask a dense boolean matrix built from the three
rules, the scores of one head at a time so that ``[2L, 2L]`` fits.

Block diffusion (Arriola et al., arXiv:2503.09573) runs the layers on the
noised copy of a sequence (positions ``0..L-1``; the mask id where the batch
says ``noised``) and on the clean copy (``L..2L-1``) together, every position
rotated by its place in its own copy. With ``b = (pos mod L) // B``: a noised
query sees the noised keys of block ``b`` and the clean keys of the blocks
before ``b``; a clean query the clean keys of the blocks up to ``b``; nothing
else. The loss is ``(1 / L) sum over the noised i of CE(logits_i, tokens_i) /
p`` of i's block, the logits at the noised copy's place i.
"""

import functools
import os
import types

import numpy as np

from lib import jobs

moonlight = jobs.load_model("moonlight")
kernel_roofline_pct, window_counts = moonlight.kernel_roofline_pct, moonlight.window_counts
token_ids, disagree_share, KERNELS = moonlight.token_ids, moonlight.disagree_share, moonlight.KERNELS
_flatten = moonlight._flatten  # the program's tree -> dotted leaves

# ----------------------------------------------- operations and shapes ---


def _dims(keys):
    g = lambda k, d=None: int(keys.get(k, d))  # noqa: E731
    return {
        "d": g("hidden_size"), "layers": g("num_hidden_layers"), "heads": g("num_attention_heads"),
        "kv": g("num_key_value_heads"), "hd": g("head_dim"), "expert_w": g("moe_intermediate_size"),
        "top_k": g("num_experts_per_tok"), "router": g("router_experts"), "held": g("experts_held"),
        "offset": g("expert_offset", 0), "vocab": g("vocab_size"), "seq": g("seq_len"),
        "batch": g("batch_size", 1), "remat": g("remat", 1), "block": g("block_length"),
        "mask_id": g("mask_token_id"),
    }


LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "mlp_norm", "router",
                "experts_gate", "experts_up", "experts_down")


def shapes(keys) -> dict:
    """{leaf name: shape}; the layers are stacked on a leading axis, as the
    program holds them (``MoELMTrainer.param_shapes``, flattened with dots)."""
    m = _dims(keys)
    d, h, kv, hd, e, w = m["d"], m["heads"], m["kv"], m["hd"], m["held"], m["expert_w"]
    layer = ((d,), (d, h * hd), (d, kv * hd), (d, kv * hd), (hd,), (hd,), (h * hd, d), (d,),
             (d, m["router"]), (e, d, w), (e, d, w), (e, w, d))
    out = {"embed": (m["vocab"], d), "head": (d, m["vocab"]), "final_norm": (d,)}
    out.update({"moe." + k: (m["layers"],) + s for k, s in zip(LAYER_LEAVES, layer)})
    return dict(sorted(out.items()))


def parameters_held(keys) -> int:
    return int(sum(np.prod(s) for s in shapes(keys).values()))


def matrix_parameters_per_position(keys) -> float:
    """Matrix parameters a position's forward pass through ONE layer
    multiplies by: the four attention projections, the router, and the
    routed experts held that it is expected to reach (``top_k * held /
    router``)."""
    m = _dims(keys)
    attn = 2 * m["d"] * m["heads"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]
    return attn + m["d"] * m["router"] + m["top_k"] * m["held"] / m["router"] * 3 * m["d"] * m["expert_w"]


def attention_flops_per_token(keys) -> float:
    """Scores and weighted values, forward, all layers, per clean token: the
    mask allows ``L (L + B)`` pairs a head over the two copies, ``L + B`` a
    token."""
    m = _dims(keys)
    return 2.0 * m["layers"] * m["heads"] * 2 * m["hd"] * (m["seq"] + m["block"])


def flops_per_item(keys) -> float:
    """Per clean token (a step's items are its L clean tokens): each token
    costs TWO positions through the layers (its noised and its clean copy)
    and ONE through the head (the noised copy's; the clean copy's last hidden
    states feed no loss; the embedding reads a row and multiplies nothing).
    Forward and backward: 2 + 4 a parameter, and three times the forward
    attention product. Rematerialised operations are not counted."""
    m = _dims(keys)
    matrices = 2 * m["layers"] * matrix_parameters_per_position(keys) + m["d"] * m["vocab"]
    return 6.0 * matrices + 3.0 * attention_flops_per_token(keys)


def attention_kernel_flops_per_step(keys) -> float:
    """What the attention kernels' calls of one step need
    (``ops/flash_attention.attention_flops``'s count under the block-diffusion
    mask, restated): ``H L (L + B)`` pairs a layer; per layer the forward
    call, again where the layer is rematerialised, the dq call and the dkv
    call."""
    m = _dims(keys)
    dk = dv = m["hd"]
    pairs = m["batch"] * m["heads"] * m["seq"] * (m["seq"] + m["block"])
    fwd, dq, dkv = 2.0 * pairs * (dk + dv), 2.0 * pairs * (2 * dk + dv), 2.0 * pairs * (2 * dk + 2 * dv)
    return m["layers"] * (fwd * (2 if m["remat"] else 1) + dq + dkv)


def experts_kernel_flops(keys, held_assignments: float) -> float:
    """What the grouped products need for ``held_assignments`` (position,
    expert) pairs, whatever steps and layers they are summed over: three
    products an expert, each forward (again where rematerialised), dx and dw;
    padding rows are not counted."""
    m = _dims(keys)
    return held_assignments * 3 * 2.0 * m["d"] * m["expert_w"] * ((2 if m["remat"] else 1) + 2)


# ----------------------------------------------------------------- weights ---


def make_weights(keys, seed: int, std: float) -> dict:
    """{leaf: float32 array} from the seed, a jitted call a leaf
    (``moonlight.make_weights``'s law over this model's leaves): leaf i (in
    name order) is N(0, std^2) from ``fold_in(PRNGKey(seed), i)``, a norm's
    gain is ones."""
    make, _ = moonlight._jitted()
    s = np.uint32(seed & 0xFFFFFFFF)
    return {name: make(s, np.uint32(i), shape, std, name.endswith("norm"))
            for i, (name, shape) in enumerate(shapes(keys).items())}


# ---------------------------------------------------------- reference ---

FAULTS = ("half_batch", "state_unchanged", "causal_noised", "noised_past", "no_p_weight",
          "no_qk_norm", "fifteen_experts", "busiest_expert_out")


def allowed_pairs(seq: int, block: int, fault=None) -> np.ndarray:
    """``[2L, 2L]`` bool, query by key, from the three rules. ``fault``
    "causal_noised": the noised-noised region causal instead of by block;
    "noised_past": noised queries read the NOISED copy's earlier blocks
    instead of the clean copy's."""
    pos = np.arange(2 * seq)
    noised, place = pos < seq, pos % seq
    blk = place // block
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = blk[:, None], blk[None, :]
    own = (place[None, :] <= place[:, None]) if fault == "causal_noised" else (qb == kb)
    earlier = kn if fault == "noised_past" else ~kn
    return (qn & kn & own) | (qn & earlier & (kb < qb)) | (~qn & ~kn & (kb <= qb))


def reference_math(hp, precision="float32", fault=None):
    """The model's arithmetic as plain functions of one sequence, for
    :func:`sdar_reference` and for the tests that hold the program's layers
    to it one at a time: ``norm``, ``attention(p, x, positions, keep)``,
    ``mixture(p, y)`` -> (output, choices), ``loss_of(params, batch)`` ->
    (loss, choices), ``store``."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    m = _dims(hp)
    bf16 = precision == "bfloat16"
    store = (lambda a: jax.lax.reduce_precision(a, 8, 7)) if bf16 else (lambda a: a)
    eps, theta = float(hp["rms_norm_eps"]), float(hp["rope_theta"])
    h, kv, hd, top_k, block = m["heads"], m["kv"], m["hd"], m["top_k"], m["block"]
    held = m["held"]

    def norm(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain

    def rotate(x, positions):  # [P, heads, hd]: pairs (j, j + hd/2) of the whole head
        half = hd // 2
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / hd)
        ang = (positions.astype(jnp.float32)[:, None] * freq[None, :])[:, None, :]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                                b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)

    def attention(p, x, positions, keep):  # one sequence's positions [P, d]; keep [P, P]
        n = x.shape[0]
        y = norm(x, p["attn_norm"])
        q, k = (y @ p["wq"]).reshape(n, h, hd), (y @ p["wk"]).reshape(n, kv, hd)
        v = (y @ p["wv"]).reshape(n, kv, hd)
        if fault != "no_qk_norm":
            q, k = norm(q, p["q_norm"]), norm(k, p["k_norm"])
        q, k = rotate(q, positions), rotate(k, positions)

        @jax.checkpoint
        def head(i):  # the scores of one query head at a time
            s = q[:, i] @ k[:, i // (h // kv)].T / np.sqrt(hd)
            return jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1) @ v[:, i // (h // kv)]

        out = jax.lax.map(head, jnp.arange(h))  # [h, P, hd]
        return out.transpose(1, 0, 2).reshape(n, h * hd) @ p["wo"]

    def mixture(p, y):  # [P, d] -> (the held experts' part of the output, choices [P, k])
        s = jax.nn.softmax(y @ p["router"], axis=-1)
        _, choices = jax.lax.top_k(s, top_k)
        chosen = jnp.take_along_axis(s, choices, axis=-1)
        gates = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20)
        hit = jax.nn.one_hot(choices, m["router"], dtype=jnp.float32)  # [P, k, E]
        gate_of = jnp.einsum("tk,tke->te", gates, hit)  # each position's gate for every expert

        @jax.checkpoint
        def expert(w_gate, w_up, w_down, gate):  # one held expert over every position, masked by its gate
            return gate[:, None] * ((jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down)

        mine = gate_of[:, m["offset"]: m["offset"] + held]
        if fault == "fifteen_experts":  # the last held expert is left out
            mine = mine * (jnp.arange(held) != held - 1)
        if fault == "busiest_expert_out":  # the held expert most positions chose
            mine = mine * (jnp.arange(held) != jnp.argmax((mine > 0).sum(axis=0)))
        # the sum is linear in its terms and a term is recomputed in the
        # backward pass: nothing of [experts, P, d] is kept
        out, _ = jax.lax.scan(lambda out, e: (out + expert(*e), ()), jnp.zeros_like(y), (
            p["experts_gate"], p["experts_up"], p["experts_down"], mine.T))
        return out, choices

    def loss_of(params, batch):  # a leaf a layer (``by_layer``); tokens, noised [B, L]; p_mask [B, L / block]
        tokens = batch["tokens"]
        seq = tokens.shape[1]
        keep = jnp.asarray(allowed_pairs(seq, block, fault))
        positions = jnp.tile(jnp.arange(seq), 2)
        total, picks = 0.0, []
        for row, noised, p_mask in zip(tokens, batch["noised"], batch["p_mask"]):
            ids = jnp.concatenate([jnp.where(noised, m["mask_id"], row), row])
            x = store(params["embed"][ids])
            seen = []
            for i in range(m["layers"]):
                @jax.checkpoint
                def layer(x, p):
                    x = store(x + attention(p, x, positions, keep))
                    out, ch = mixture(p, norm(x, p["mlp_norm"]))
                    return store(x + out), ch
                x, ch = layer(x, {k: params[f"moe.{k}.{i}"] for k in LAYER_LEAVES})
                seen.append(ch)
            logits = norm(x[:seq], params["final_norm"]) @ params["head"]
            ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, row[:, None], axis=-1)[:, 0]
            weight = noised if fault == "no_p_weight" else noised / jnp.repeat(p_mask, block)
            total = total + jnp.sum(ce * weight)
            picks.append(jnp.stack(seen))  # [layers, 2L, k]
        return total / tokens.size, jnp.concatenate(picks, axis=1)

    return types.SimpleNamespace(norm=norm, attention=attention, mixture=mixture,
                                 loss_of=loss_of, store=store, dims=m)


def by_layer(w: dict) -> dict:
    """``{"moe.wq": [layers, ...]}`` -> ``{"moe.wq.0": ..., "moe.wq.1": ...}``,
    the other leaves as they are; ``w`` is emptied. The reference holds a
    leaf a layer: a slice of a stacked leaf inside the differentiated step is
    a copy of it, and its gradient a padded sum, 2 GB more than the chip has."""
    out = {}
    for k in list(w):
        v = w.pop(k)
        out.update({f"{k}.{i}": v[i] for i in range(v.shape[0])} if k.startswith("moe.") else {k: v})
    return out


def _stacked(sumsq: dict) -> dict:
    """A sum of squares a stacked leaf from one a layer's leaf."""
    out = {}
    for k, v in sumsq.items():
        name = k.rsplit(".", 1)[0] if k.startswith("moe.") else k
        out[name] = out.get(name, 0.0) + float(v)
    return out


def reference_step(hp, math, fault=None):
    """The jitted reference step ``(params, mom, var, batch, t) -> (params,
    mom, var, loss, each leaf's squared gradient, choices)``: loss and
    gradients by ``jax.grad``, AdamW written out."""
    import jax
    import jax.numpy as jnp

    store, loss_of = math.store, math.loss_of
    lr, b1, b2 = float(hp["learning_rate"]), float(hp["adam_b1"]), float(hp["adam_b2"])
    adam_eps, decay = float(hp["adam_eps"]), float(hp["weight_decay"])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mom, var, batch, t):
        (loss, choices), g = jax.value_and_grad(loss_of, has_aux=True)(params, batch)
        g2 = {k: jnp.sum(v * v) for k, v in g.items()}
        if fault == "state_unchanged":
            return params, mom, var, loss, g2, choices
        mom = {k: b1 * mom[k] + (1 - b1) * g[k] for k in g}
        var = {k: b2 * var[k] + (1 - b2) * g[k] * g[k] for k in g}
        params = {k: store(params[k] - lr * (
            (mom[k] / (1 - b1 ** t)) / (jnp.sqrt(var[k] / (1 - b2 ** t)) + adam_eps)
            + decay * params[k])) for k in g}
        return params, mom, var, loss, g2, choices

    return step


def sdar_reference(w, batches, hp, precision="float32", fault=None):
    """Three (``len(batches)``) training steps from the weights ``w`` ({leaf:
    array}, layers stacked; the dict is emptied): the masked-token loss of
    block diffusion on the batch's OWN noise draw, gradients, AdamW written
    out. ``hp``: the configuration's ``keys`` and ``keys_reference``.

    ``precision`` "bfloat16" is the control: the residual stream and the
    weights are stored in bfloat16 and products run at the default precision.
    ``fault``: "half_batch" trains on the first half of every sequence;
    "state_unchanged" applies no update; "causal_noised" masks the
    noised-noised region causally instead of by block; "noised_past" lets
    noised queries read the noised copy's past instead of the clean copy's;
    "no_p_weight" leaves the 1 / p weight out; "no_qk_norm" the norm of the
    heads' queries and keys; "fifteen_experts" holds the first fifteen of
    the sixteen experts (at this draw of the weights the last one gets next to
    no position, so only the weights' change shows it: PERF.md section 6);
    "busiest_expert_out" leaves out, in every layer, the held expert most of
    the step's positions chose (three fifths of the held assignments here).

    Returns {"loss": [...], "grad1": {leaf: sumsq of the first gradient},
    "change": {leaf: [sumsq of the change since the start, after each step]},
    "choices": [per step, [layers, positions, k] expert ids]}.
    """
    import jax
    import jax.numpy as jnp

    math = reference_math(hp, precision, fault)
    store = math.store
    bf16 = precision == "bfloat16"
    block = math.dims["block"]
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
        out = {"loss": [], "grad1": {}, "change": {k: [] for k in _stacked(dict.fromkeys(start, 0.0))},
               "choices": []}
        for i, bt in enumerate(batches):
            bt = {k: np.asarray(bt[k]) for k in ("tokens", "noised", "p_mask")}
            if fault == "half_batch":
                half = bt["tokens"].shape[1] // 2
                bt = {"tokens": bt["tokens"][:, :half], "noised": bt["noised"][:, :half],
                      "p_mask": bt["p_mask"][:, : half // block]}
            params, mom, var, loss, g2, choices = step(
                params, mom, var, {k: jnp.asarray(v) for k, v in bt.items()}, jnp.float32(i + 1))
            if i == 0:
                out["grad1"] = _stacked(jax.device_get(g2))
            out["loss"].append(float(loss))
            out["choices"].append(np.asarray(choices))
            for k, v in _stacked({k: sumsq(params[k], start[k]) for k in start}).items():
                out["change"][k].append(v)
    return out


# ------------------------------------------------------------ adapter ---


class Adapter(moonlight.Adapter):
    """The program's state is ``{"params": {"embed", "head", "final_norm",
    "moe": the layers stacked}, "opt": optax.adamw's, "router_bias" (zeros: no
    bias step), "counts", "choices", "dropped", "noised"}``; the benchmark's
    leaves are the same arrays under dotted names. ``state``,
    ``extra_numbers`` (``route_disagree_share``), ``reference_variants`` and
    ``extra_faults`` are ``moonlight.Adapter``'s."""

    def __init__(self, run, trainer):
        self.run, self.trainer = run, trainer
        self.keys = {**run.config["keys"], **run.mix.get("keys", {})}
        mine = {k: tuple(v) for k, v in _flatten(trainer.param_shapes()).items()}
        if mine != shapes(self.keys):
            raise ValueError("the program's parameter tree is not the benchmark's")
        # the weights come from the configuration's ``init.seed``, the same in
        # every run (the routed load is drawn with them: PERF.md section 7);
        # the feed, the noise and the loop's order come from --seed
        self.weights_seed = int(run.config["init"]["seed"])
        self._program_choices = self._reference_choices = None

    @staticmethod
    def dataset(run, work_dir: str) -> str:
        """Ids ``0..mask_token_id - 1``: the mask's id is never drawn."""
        feed = {**run.config["feed"], **run.mix.get("feed", {})}
        keys = {**run.config["keys"], **run.mix.get("keys", {})}
        ids = token_ids(int(feed["tokens"]), int(keys["mask_token_id"]), float(feed["zipf_exponent"]),
                        float(feed["doc_median_tokens"]), float(feed["doc_sigma"]), run.seed)
        path = os.path.join(work_dir, "tokens.npy")
        np.save(path, ids)
        return path

    def _weights(self):
        return make_weights(self.keys, self.weights_seed, float(self.run.config["init"]["std"]))

    def readings(self):
        """Per warm step: each leaf's change since the start (the weights
        made again, a leaf at a time), the sum of squares of AdamW's first
        moment (after one step (1 - b1) times the first gradient), the step's
        choices, counts, dropped assignments and noised tokens."""
        import jax
        import jax.numpy as jnp

        names = shapes(self.keys)
        std = float(self.run.config["init"]["std"])

        @jax.jit
        def sumsq(a):
            return jnp.sum(a * a)

        _, change_of = moonlight._jitted()
        seed = np.uint32(self.weights_seed & 0xFFFFFFFF)

        def read(state, _run_seed):
            params = _flatten(state["params"])
            moment = _flatten(state["opt"][0].mu)
            change = {k: change_of(params[k], seed, np.uint32(i), std, k.endswith("norm"))
                      for i, k in enumerate(names)}
            return {"change": change, "moment": {k: sumsq(moment[k]) for k in names},
                    "choices": state["choices"], "counts": state["counts"],
                    "dropped": state["dropped"], "noised": state["noised"]}

        return read

    def program_grad1(self, reads):
        """The first gradient as AdamW got it, from its first moment; and
        what the warm steps counted, kept for the readers of the program's
        counters (``benchmark/metrics/moe.*.py``, ``diffusion.masked_share.py``)."""
        m = _dims(self.keys)
        counts = np.asarray([r.pop("counts") for r in reads], np.float64)  # [steps, layers, E]
        held = counts[:, :, m["offset"]: m["offset"] + m["held"]]
        self.run.counters["moe"] = {
            "held_share_pct": 100.0 * held.sum() / counts.sum(),
            "load_max_over_mean": float(np.mean(held.max(axis=-1) / np.maximum(held.mean(axis=-1), 1))),
            "dropped": int(sum(int(r.pop("dropped")) for r in reads)),
        }
        self.run.counters["diffusion"] = {"masked_share_pct": 100.0 * sum(
            int(r.pop("noised")) for r in reads) / (len(reads) * m["batch"] * m["seq"])}
        self.warm_counts = counts.astype(np.int64)
        self._program_choices = [np.asarray(r.pop("choices")) for r in reads]
        b1 = float(self._hp()["adam_b1"])
        return {k: float(v) / (1 - b1) ** 2 for k, v in reads[0]["moment"].items()}

    def reference(self, batches, precision="float32", fault=None):
        ref = sdar_reference(self._weights(), batches, self._hp(), precision=precision, fault=fault)
        choices = ref.pop("choices")
        if precision == "float32" and fault is None:
            self._reference_choices = choices
        return ref

    def parts(self):
        """For ``control.py``: every fault the reference can plant, put in the
        program's place; each has to come out not correct."""
        return {f: {"fault": f} for f in FAULTS if f != "half_batch"}
