"""Wide & Deep with AdaGrad on a hashed table, for the benchmark: its feed,
its weights in the program's layout, what is read from the program's state,
the plain reference, and the operations and bytes the algorithm needs.
``lib/jobs.py`` loads this file by the configuration's ``model``.

A model with no deep side (``embed_dim`` 0 and no ``hidden_dims``: logistic
regression over the same hashed table) is the same arithmetic with the MLP
left out, and a file of its own beside this one can take everything from
here. Only ``Adapter`` touches the program.
"""

import os

import numpy as np

from lib import gen, weights
from lib.refmath import row_of_key

# ----------------------------------------------- operations and bytes ---


def _hidden(keys):
    return [int(x) for x in str(keys.get("hidden_dims", "")).replace(";", ",").split(",") if x]


def _dims(keys):
    f, k = int(keys["num_fields"]), int(keys.get("embed_dim", 0))
    return ([f * k] + _hidden(keys) + [1]) if k else []


def flops_per_item(keys) -> float:
    """Per example: MLP forward and backward (2 + 4 flops per weight: the
    input gradient of the first layer feeds the embeddings), the wide sum and
    embedding gather sums, and AdaGrad (square, add, rsqrt, multiply,
    subtract: 6 per element) on the touched rows and, amortised over the
    batch, the dense side."""
    f, k = int(keys["num_fields"]), int(keys.get("embed_dim", 0))
    dims = _dims(keys)
    n_weights = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    n_biases = sum(dims[1:])
    batch = int(keys["batch_size"])
    return (6.0 * n_weights + 2.0 * n_biases + 2.0 * f + 6.0 * f * (1 + k)
            + 6.0 * (n_weights + n_biases) / batch)


def bytes_per_item(keys, row_bytes: int) -> float:
    """Table bytes an example's update must read and write: one stored row
    (parameters and accumulator) per field, in and out."""
    return 2.0 * row_bytes * int(keys["num_fields"])


def layout(config: dict):
    keys = config["keys"]
    dim = 1 + int(keys.get("embed_dim", 0))
    group, stride = weights.small_rows(dim)
    init = {**config["init"], "table": {**config["init"]["table"], "stride": stride, "dim": dim}}
    leaves = [("table", (-(-int(keys["capacity"]) // group), weights.LANES))]
    dims = _dims(keys)
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        leaves += [(f"w{i}", (a, b)), (f"b{i}", (b,))]
    leaves.append(("bias", ()))
    return weights.layout_of(leaves, init)


# ---------------------------------------------------------- reference ---


def widedeep_reference(w, batches, hp, precision="float32", fault=None):
    """Wide & Deep with AdaGrad on the hashed table and on the dense side.

    logit = bias + sum_f w[row_f] + MLP(concat_f e[row_f]) with ReLU between
    layers; loss = mean binary cross-entropy on logits. Table: the gradients
    of one step's rows merge by row first, then ``acc += g^2; row -= lr g /
    sqrt(acc + eps)`` with acc starting at 0. Dense: the same rule with acc
    starting at ``dense_acc0`` and ``dense_eps`` (optax.adagrad's defaults).

    Only the rows the batches touch are held (the rest of the table does not
    move), in arrays of a size fixed by the batches' shapes, so that every
    seed runs the same compiled step. ``precision`` "bfloat16" stores rows
    and dense leaves in bfloat16 and multiplies at the default precision;
    ``fault`` "half_batch" leaves out the second half of every step's rows;
    "state_unchanged" computes every step's loss and gradient and applies
    no update.
    Returns {"loss": [...], "grad1": {"table": sumsq of the first step's
    merged gradient}, "change": {leaf: [sumsq after each step]}}.
    """
    import jax
    import jax.numpy as jnp

    bf16 = precision == "bfloat16"
    # reduce_precision, not a cast there and back: inside a jitted step XLA may
    # drop such a pair of converts as excess precision, and with it the control
    store = (lambda a: jax.lax.reduce_precision(a, 8, 7)) if bf16 else (lambda a: a)
    capacity = int(hp["capacity"])
    lr, eps = float(hp["learning_rate"]), float(hp["table_eps"])
    d_lr = float(hp.get("dense_learning_rate", lr))
    d_eps, d_acc0 = float(hp["dense_eps"]), float(hp["dense_acc0"])
    n_layers = sum(1 for k in w if k.startswith("w"))

    feats = [np.asarray(bt["feats"]) for bt in batches]
    labels = [np.asarray(bt["labels"], np.float32) for bt in batches]
    if fault == "half_batch":
        feats = [f[: len(f) // 2] for f in feats]
        labels = [y[: len(y) // 2] for y in labels]
    rows = [row_of_key(np.maximum(f, 0), capacity) for f in feats]
    uniq = np.unique(np.concatenate([r.reshape(-1) for r in rows]))
    idx = [np.searchsorted(uniq, r).astype(np.int32) for r in rows]
    held = sum(f.size for f in feats)  # as many as if no row came twice
    uniq = np.concatenate([uniq, np.zeros(held - len(uniq), uniq.dtype)])

    def forward(pulled, dense, mask):
        logit = dense["bias"] + jnp.where(mask, pulled[..., 0], 0).sum(axis=1)
        if n_layers:
            x = jnp.where(mask[..., None], pulled[..., 1:], 0).reshape(mask.shape[0], -1)
            for i in range(n_layers):
                x = x @ dense[f"w{i}"] + dense[f"b{i}"]
                if i < n_layers - 1:
                    x = jax.nn.relu(x)
            logit = logit + x[..., 0]
        return logit

    def loss_of(pulled, dense, mask, y):
        z = forward(pulled, dense, mask)
        return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))

    @jax.jit
    def step(param, acc, dense, dacc, f, y, ix):
        mask = f >= 0
        loss, (dp, dd) = jax.value_and_grad(loss_of, argnums=(0, 1))(param[ix], dense, mask, y)
        dp = jnp.where(mask[..., None], dp, 0)
        g = jax.ops.segment_sum(dp.reshape(-1, dp.shape[-1]), ix.reshape(-1),
                                num_segments=param.shape[0])
        if fault == "state_unchanged":
            return param, acc, dense, dacc, loss, jnp.sum(g * g)
        acc = acc + g * g
        param = store(param - lr * g * jax.lax.rsqrt(acc + eps))
        dacc = {k: dacc[k] + dd[k] * dd[k] for k in dense}
        dense = {k: store(dense[k] - d_lr * dd[k] * jax.lax.rsqrt(dacc[k] + d_eps)) for k in dense}
        return param, acc, dense, dacc, loss, jnp.sum(g * g)

    @jax.jit
    def sumsq(now, start):
        return jax.tree_util.tree_map(lambda a, b: jnp.sum((a - b) ** 2), now, start)

    with jax.default_matmul_precision("default" if bf16 else "highest"):
        dim = int(hp.get("embed_dim", 0)) + 1
        start = {"table": store(weights.table_rows(w["table"], uniq, dim)),
                 **{k: store(v) for k, v in w.items() if k != "table"}}
        param, acc = start["table"], jnp.zeros_like(start["table"])
        dense = {k: v for k, v in start.items() if k != "table"}
        dacc = {k: jnp.full_like(v, d_acc0) for k, v in dense.items()}
        out = {"loss": [], "grad1": {}, "change": {k: [] for k in start}}
        for i, (f, y, ix) in enumerate(zip(feats, labels, idx)):
            param, acc, dense, dacc, loss, g2 = step(
                param, acc, dense, dacc, jnp.asarray(f), jnp.asarray(y), jnp.asarray(ix))
            if i == 0:
                out["grad1"] = {"table": float(g2)}
            out["loss"].append(float(loss))
            for k, v in jax.device_get(sumsq({"table": param, **dense}, start)).items():
                out["change"][k].append(float(v))
    return out


# ------------------------------------------------------------ adapter ---


class Adapter:
    """The small-row plane: [T, 2, 128] tiles, sublane 0 the parameters of
    ``group`` rows side by side, sublane 1 their AdaGrad accumulators; the
    dense side an optax AdaGrad."""

    def __init__(self, run, trainer):
        self.run, self.trainer = run, trainer
        self.layout = layout(run.config)

    @staticmethod
    def dataset(run, work_dir: str) -> str:
        feed = {**run.config["feed"], **run.mix.get("feed", {})}
        labels, ids = gen.ctr_examples(
            int(feed["examples"]), feed["field_cardinalities"],
            float(feed["zipf_exponent"]), run.seed)
        if ids.shape[1] != int(run.config["keys"]["num_fields"]):
            raise ValueError("the feed's fields are not the configuration's")
        path = os.path.join(work_dir, "examples.txt")
        gen.write_ctr(path, labels, ids)
        return path

    def state(self):
        import jax
        import jax.numpy as jnp

        from swiftsnails_tpu.models.sparse_base import CTRState
        from swiftsnails_tpu.parallel.store import PackedTableState, small_group

        tr = self.trainer
        if not tr.packed:
            raise ValueError("the adapter knows the packed small-row layout only")
        dim = tr.table_dim
        g = small_group(dim)
        if (g, 128 // g) != weights.small_rows(dim):
            raise ValueError("the program's small-row geometry is not the benchmark's")

        @jax.jit
        def tiles(seed):  # [T, 2, 128], made block by block, the logical table never whole
            t = weights.map_blocks(seed, self.layout, "table",
                                   lambda p: jnp.stack([p, jnp.zeros_like(p)], axis=1))
            return t.reshape((-1,) + t.shape[2:])

        table = PackedTableState(table=tiles(np.uint32(self.run.seed & 0xFFFFFFFF)), slots={})
        dense = dict(weights.make_weights(
            self.layout, self.run.seed, only=[n for n, _, _ in self.layout if n != "table"]))
        return CTRState(table=table, dense=dense, opt=tr.dense_opt.init(dense))

    def readings(self):
        import jax
        import jax.numpy as jnp

        names = tuple(n for n, _, _ in self.layout if n != "table")

        def read(state, seed):
            t = state.table.table  # pad lanes hold zeros in both, so tiles compare as rows
            change = {"table": jnp.sum(weights.map_blocks(
                seed, self.layout, "table", lambda w0, tb: jnp.sum((tb[:, 0, :] - w0) ** 2), t))}
            grad = {"table": jnp.sum(t[:, 1, :])}
            w0 = weights._make(seed, self.layout, names)
            for k, v in state.dense.items():
                change[k] = jnp.sum((v - w0[k]) ** 2)
            return {"change": change, "grad": grad}

        return jax.jit(read)

    def _hp(self):
        return {**self.run.config["keys"], **self.run.config["keys_reference"]}

    def reference(self, batches, precision="float32", fault=None):
        w = weights.make_weights(self.layout, self.run.seed)
        ref = widedeep_reference(w, batches, self._hp(), precision=precision, fault=fault)
        first = {k: v[0] for k, v in ref["change"].items()}
        ref["grad1"] = {"table": ref["grad1"]["table"], **self._dense_grad1(first)}
        return ref

    def _dense_grad1(self, change1):
        """A dense leaf's first gradient from its first change: the dense
        accumulator starts at ``dense_acc0`` = 0.1, where float32 cannot hold
        a g^2 of 1e-9, so the state after one step says it through the
        parameters: change = lr g / sqrt(acc0 + g^2 + eps), g^2 << acc0."""
        hp = self._hp()
        lr = float(hp.get("dense_learning_rate", hp["learning_rate"]))
        return {k: v * float(hp["dense_acc0"]) / lr ** 2 for k, v in change1.items() if k != "table"}

    def program_grad1(self, reads):
        # the table's accumulators start at 0: after one step they hold g^2
        return {"table": reads[0]["grad"]["table"], **self._dense_grad1(reads[0]["change"])}

    def extra_numbers(self, batches):
        return {}

    def reference_variants(self):
        return [()]  # one stated behaviour: merged gradients, in step order

    def parts(self):
        """For ``control.py``: a step that returns its state unchanged, in the
        reference put in the program's place (what the later losses read
        then; the two norms read 0 and 1 by definition)."""
        return {"state_unchanged": {"fault": "state_unchanged"}}

    def extra_faults(self):
        return {}
