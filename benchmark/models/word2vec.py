"""Word2Vec (skip-gram with pooled negative sampling) for the benchmark: its
feed, its weights in the program's layout, what is read from the program's
state, the plain reference, and the operations and bytes the algorithm needs.
``lib/jobs.py`` loads this file by the configuration's ``model``.

Only ``Adapter`` touches the program; the reference and the arithmetic
import nothing of it.
"""

import collections
import os

import numpy as np

from lib import gen, weights
from lib.refmath import assign_last, log_sigmoid, sigmoid, sumsq, to_bf16

# ----------------------------------------------- operations and bytes ---


def flops_per_item(keys) -> float:
    """Per center word. Real pairs per center: window + 1 (dynamic window
    b ~ U(1, window), 2 E[b]). Per pair: the dot and the two gradient rows,
    3 x 2 dim. Per center against the pool: logits, dv and dp, 3 x 2 dim
    pool_size. Row updates (axpy, 2 dim) for the center, its contexts and its
    share of the pool rows."""
    d, w = int(keys["dim"]), int(keys["window"])
    pn, pc = int(keys["pool_size"]), int(keys["centers_per_block"])
    pairs = w + 1
    return 6.0 * d * pairs + 6.0 * d * pn + 2.0 * d * (1 + pairs + pn / pc)


def bytes_per_item(keys, row_bytes: int) -> float:
    """Table bytes a center word's update must read and write: its own row,
    its window + 1 context rows, its share of the pool's rows, each once in
    and once out, at the stored row size."""
    w, pn, pc = int(keys["window"]), int(keys["pool_size"]), int(keys["centers_per_block"])
    return 2.0 * row_bytes * (1 + (w + 1) + pn / pc)


def layout(config: dict):
    keys = config["keys"]
    shape = (int(keys["capacity"]), int(keys["dim"]))
    return weights.layout_of([("in_table", shape), ("out_table", shape)], config["init"])


# ---------------------------------------------------------- reference ---


def sgns_reference(in_rows, batches, hp, pools, precision="float32", fault=None, depth=1,
                   operands="float32"):
    """Skip-gram with pooled negative sampling, asynchronous SGD in blocks
    (hogwild with bounded staleness, later write wins).

    A step is ``len(centers) / batch_size`` substeps in order. A substep is
    blocks of ``centers_per_block`` centers that share ``pool_size`` negatives.
    Block i reads its rows (center, real contexts, pool) from the tables as
    blocks up to i-1-``depth`` left them: with ``depth`` 1, what a two-deep
    pipeline does (block i+1's rows are fetched before block i writes); 0 is
    sequential, 2 a three-deep pipeline. The configuration states the bound
    (``staleness_blocks_max``) and what the program does today
    (``staleness_blocks``). A block computes

        pos = u.v per real context, neg = v.p per pool row
        g_pos = (sigmoid(pos) - 1) / B,  g_neg = (negatives/pool) sigmoid(neg) n_real / B
        dv = sum g_pos u + g_neg p;  du = g_pos v;  dp = g_neg^T v;  B = n (window + 1)

    and writes v - lr dv, u - lr du, p - lr dp: centers, then contexts in
    slot order (context position major), then pool rows; of a row written
    twice in a block the later write stays. The output table starts at zero.

    ``operands`` says how the three contractions with the pool (v.p, g_neg p
    and g_neg^T v) multiply: "float32" exactly, "bfloat16" with both operands
    rounded to bfloat16 first and the products summed exactly, which is what
    a matrix unit does with float32 operands at its default precision. The
    configuration states which are legal (``contraction_operands_allowed``)
    and what the program does today (``contraction_operands``).

    ``in_rows(ids)`` gives the input table's starting rows (float32); only
    the rows the batches touch are held. ``pools[step][substep]`` is
    [blocks, pool_size] row ids: the negatives are the step's random draw and
    are given, as the batches are (``Adapter.pools`` says where they come
    from). ``fault`` "half_batch" leaves out the second half of every
    substep's rows and takes the mean over the rest.

    Returns {"loss": [per step], "change": {leaf: [sumsq after each step]}}.
    """
    bf16 = precision == "bfloat16"
    store = to_bf16 if bf16 else (lambda a: a.astype(np.float32))
    if operands == "bfloat16":
        mxu = lambda a: to_bf16(a.astype(np.float32)).astype(np.float64)  # noqa: E731
    elif operands == "float32":
        mxu = lambda a: a  # noqa: E731
    else:
        raise ValueError(f"unknown contraction operands {operands!r}")
    lr, window = float(hp["learning_rate"]), int(hp["window"])
    b, pn = int(hp["batch_size"]), int(hp["pool_size"])
    lam = float(hp["negatives"]) / pn
    cs = [np.asarray(bt["centers"]).astype(np.int64) for bt in batches]
    xs = [np.asarray(bt["contexts"]).astype(np.int64) for bt in batches]
    ps = [[np.asarray(p).astype(np.int64) for p in step] for step in pools]
    touched = np.unique(np.concatenate(
        [c for c in cs] + [x[x >= 0] for x in xs] + [p.reshape(-1) for st in ps for p in st]))
    local = lambda ids: np.searchsorted(touched, ids)  # noqa: E731  (row id -> held row)
    in_t = store(np.asarray(in_rows(touched), np.float32))
    start_in = in_t.copy()
    out_t = np.zeros_like(in_t)
    losses, change = [], {"in_table": [], "out_table": []}

    for step, (centers, ctxs) in enumerate(zip(cs, xs)):
        t = max(len(centers) // b, 1)
        sub = len(centers) // t
        sub_losses = []
        for s in range(t):
            c_s = local(centers[s * sub:(s + 1) * sub])
            x_raw = ctxs[s * sub:(s + 1) * sub]
            x_s = np.where(x_raw >= 0, local(np.maximum(x_raw, 0)), -1)
            pool_s = local(ps[step][s])
            nb = len(pool_s)
            if fault == "half_batch":
                c_s, x_s, nb = c_s[: sub // 2], x_s[: sub // 2], nb // 2
            n = len(c_s)
            blk = n // nb
            inv_b = 1.0 / (n * (window + 1))

            def read(i):
                c = c_s[i * blk:(i + 1) * blk]
                x = x_s[i * blk:(i + 1) * blk]  # [blk, CW]
                return (c, x, pool_s[i], in_t[c].astype(np.float64),
                        out_t[np.maximum(x, 0)].astype(np.float64),
                        out_t[pool_s[i]].astype(np.float64))

            loss = 0.0
            fetched, to_read = collections.deque(), 0
            for i in range(nb):
                while to_read <= min(i + depth, nb - 1):  # fetched before block i writes
                    fetched.append(read(to_read))
                    to_read += 1
                c, x, pool, vv, uu, pv = fetched.popleft()
                mask = (x >= 0).astype(np.float64)  # [blk, CW]
                uu = uu * mask[:, :, None]
                n_real = mask.sum(axis=1)  # [blk]
                pos = (uu * vv[:, None, :]).sum(axis=-1)
                neg = mxu(vv) @ mxu(pv).T  # [blk, pn]
                g_pos = (sigmoid(pos) - 1.0) * inv_b * mask
                g_neg = (lam * inv_b) * sigmoid(neg) * n_real[:, None]
                dv = (g_pos[:, :, None] * uu).sum(axis=1) + mxu(g_neg) @ mxu(pv)
                du = g_pos[:, :, None] * vv[:, None, :]
                dp = mxu(g_neg).T @ mxu(vv)
                loss += -(np.sum(log_sigmoid(pos) * mask)
                          + lam * np.sum(log_sigmoid(-neg) * n_real[:, None])) * inv_b
                assign_last(in_t, c, store(vv - lr * dv))
                # slot order: context position major, then center
                flat = x.T.reshape(-1)
                new_u = store(np.transpose(uu - lr * du, (1, 0, 2)).reshape(len(flat), -1))
                real = flat >= 0
                assign_last(out_t, flat[real], new_u[real])
                assign_last(out_t, pool, store(pv - lr * dp))
            sub_losses.append(loss)
        losses.append(float(np.mean(sub_losses)))
        change["in_table"].append(sumsq(in_t, start_in))
        change["out_table"].append(sumsq(out_t, None))
    return {"loss": losses, "change": change}


RANK_EDGES = [0] + [2 ** i for i in range(40)]


def negatives_z(word_ids, counts, power: float) -> float:
    """How far a sample of negatives lies from unigram^``power``: the words
    (as the corpus numbers them: counts fall with the number) fall into bins [0,1), [1,2), [2,4), ... by
    their number, and the widest gap between a bin's share of the sample and
    its share of ``counts ** power`` is given in standard deviations of that
    bin's count."""
    p = np.asarray(counts, np.float64) ** power
    p /= p.sum()
    edges = np.array([e for e in RANK_EDGES if e < len(p)] + [len(p)])
    expect = np.add.reduceat(p, edges[:-1])
    got = np.histogram(np.asarray(word_ids), bins=edges)[0]
    n = len(word_ids)
    return float(np.max(np.abs(got - n * expect) / np.sqrt(n * expect * (1 - expect) + 1e-30)))


# ------------------------------------------------------------ adapter ---


def _counts(run):
    feed = {**run.config["feed"], **run.mix.get("feed", {})}
    vocab = int(run.config["keys"]["capacity"])
    return gen.corpus_counts(vocab, int(feed["zipf_tokens"]), float(feed["zipf_exponent"]))


class Adapter:
    """Benchmark weights into the program's packed state, and the program's
    state back into sums over logical rows."""

    def __init__(self, run, trainer):
        self.run, self.trainer = run, trainer
        self.layout = layout(run.config)

    @staticmethod
    def dataset(run, work_dir: str) -> str:
        ids = gen.corpus_ids(_counts(run), run.seed)
        path = os.path.join(work_dir, "corpus.txt")
        gen.write_corpus(path, ids)
        return path

    def state(self):
        import jax

        from swiftsnails_tpu.models.word2vec import W2VState
        from swiftsnails_tpu.ops.rowdma import pack_rows
        from swiftsnails_tpu.parallel.store import PackedTableState

        tr = self.trainer
        if not tr.packed:
            raise ValueError("the adapter knows the packed layout only")

        @jax.jit
        def packed(seed):
            out = {}
            for name, _, _ in self.layout:
                t = weights.map_blocks(
                    seed, self.layout, name, lambda a: pack_rows(a).astype(tr.table_dtype))
                out[name] = t.reshape((-1,) + t.shape[2:])
            return out

        def table(t):
            slots = tr.access.init_slots((t.shape[0], t.shape[1] * t.shape[2]), t.dtype)
            return PackedTableState(
                table=t, slots={k: v.reshape(t.shape) for k, v in slots.items()})

        p = packed(np.uint32(self.run.seed & 0xFFFFFFFF))
        return W2VState(in_table=table(p["in_table"]), out_table=table(p["out_table"]))

    def readings(self):
        """jitted (state, seed) -> {"change": {leaf: sumsq of (now - init)}},
        block by block: the start is made again from the seed and never held."""
        import jax
        import jax.numpy as jnp

        dim = self.trainer.dim

        def read(state, seed):
            now = {"in_table": state.in_table.table, "out_table": state.out_table.table}
            out = {}
            for k, t in now.items():
                def part(start, tiles, dtype=t.dtype):
                    logical = tiles.reshape(tiles.shape[0], -1)[:, :dim].astype(jnp.float32)
                    return jnp.sum((logical - start.astype(dtype).astype(jnp.float32)) ** 2)

                out[k] = jnp.sum(weights.map_blocks(seed, self.layout, k, part, t))
            return {"change": out}

        return jax.jit(read)

    def pools(self, batches):
        """Each warm step's negatives, drawn again through the program's own
        sampler with the step's keys. They are the step's random input, as
        the batch is, but are made inside the jitted step and are no output
        of it, so the harness has to know how: ``TrainLoop`` folds the step
        number into ``PRNGKey(seed)``, ``train_step`` splits that key over
        the substeps, and ``_substep_grouped`` draws ``[blocks, pool_size]``
        rows from ``neg_alias`` with each. A step that drew them otherwise
        would leave the reference with other negatives than it used, and
        ``in_table``'s norm 0.18-0.55 apart (my chip run, PR 25); what the
        sampler and its table give is held by ``negatives_dist_z``."""
        import jax

        from swiftsnails_tpu.data.sampler import alias_sample

        tr = self.trainer
        root = jax.random.PRNGKey(self.run.seed & 0x7FFFFFFF)
        out = []
        for step, bt in enumerate(batches):
            rng = jax.random.fold_in(root, step)
            n = len(bt["centers"])
            t = max(n // tr.batch_size, 1)
            blocks = (n // t) // tr._effective_pc(n // t)
            keys = jax.random.split(rng, t) if t > 1 else [rng]
            out.append([np.asarray(tr._rows(alias_sample(tr.neg_alias, k, (blocks, tr.pool_size))))
                        for k in keys])
        return out

    def sample_negatives(self, n: int):
        """``n`` draws of the program's sampler from its table, as the
        benchmark's word numbers (the program's vocabulary only says which
        word it put where)."""
        import jax

        from swiftsnails_tpu.data.sampler import alias_sample

        tr = self.trainer
        key = jax.random.fold_in(jax.random.PRNGKey(self.run.seed & 0x7FFFFFFF), 0x5A17)
        drawn = np.asarray(alias_sample(tr.neg_alias, key, (n,)))
        word_of = np.fromiter((int(w[1:]) for w in tr.vocab.words), np.int64, len(tr.vocab.words))
        return word_of[drawn]

    def extra_numbers(self, batches):
        """``negatives_dist_z``: the program's sampler against the
        configuration's unigram^power over the corpus's own counts."""
        ns = self.run.config["negatives_check"]
        words = self.sample_negatives(int(ns["draws"]))
        return {"negatives_dist_z": negatives_z(words, _counts(self.run), float(ns["power"]))}

    def reference_variants(self):
        """What the configuration states, first as the program does it today
        (``staleness_blocks``, ``contraction_operands``), then the other
        legal operands and the other legal depths up to
        ``staleness_blocks_max``: ``in_table``'s early change is a
        second-order quantity (the output table starts at zero) carried by
        few hot rows, and reads up to 2.4 apart between depths and up to 0.02
        between operands (PERF.md), so no one limit holds them all; a result
        has to agree with one of them."""
        cfg = self.run.config
        mine = int(cfg["staleness_blocks"])
        depths = [mine] + [d for d in range(int(cfg["staleness_blocks_max"]) + 1) if d != mine]
        ops = [cfg["contraction_operands"]] + [
            o for o in cfg["contraction_operands_allowed"] if o != cfg["contraction_operands"]]
        return [(("depth", d), ("operands", o)) for d in depths for o in ops]

    def parts(self):
        """For ``control.py``: the reference as the other legal variants have
        it, put in the program's place (they have to pass): each other depth
        at today's operands, and each other operand at today's depth."""
        first = dict(self.reference_variants()[0])
        out = {}
        for v in map(dict, self.reference_variants()[1:]):
            if v["operands"] == first["operands"]:
                out[f"depth{v['depth']}"] = v
            elif v["depth"] == first["depth"]:
                out[f"operands_{v['operands']}"] = v
        return out

    def extra_faults(self):
        """For ``control.py``: ``negatives_dist_z`` of samplers that draw from
        another distribution than the configuration states (the benchmark's
        own draw, as many as a run's)."""
        ns, counts = self.run.config["negatives_check"], _counts(self.run)
        rng = np.random.default_rng(self.run.seed)
        out = {}
        for name, power in (("unigram_1.0", 1.0), ("unigram_0.6", 0.6), ("uniform", 0.0)):
            p = counts.astype(np.float64) ** power
            words = rng.choice(len(p), size=int(ns["draws"]), p=p / p.sum())
            out["sampler_" + name] = {
                "negatives_dist_z": negatives_z(words, counts, float(ns["power"]))}
        return out

    def reference(self, batches, precision="float32", fault=None, depth=None, operands=None):
        import jax.numpy as jnp

        w = weights.make_weights(self.layout, self.run.seed, only=("in_table",))

        def in_rows(ids, bucket=1 << 17):
            # gathered in a size that few seeds change, so that the gather is compiled once
            padded = np.concatenate([ids, np.zeros(-len(ids) % bucket, ids.dtype)])
            return np.asarray(jnp.take(w["in_table"], jnp.asarray(padded), axis=0))[: len(ids)]

        depth = int(self.run.config["staleness_blocks"]) if depth is None else depth
        operands = self.run.config["contraction_operands"] if operands is None else operands
        ref = sgns_reference(
            in_rows, batches, self.run.config["keys"], self.pools(batches),
            precision=precision, fault=fault, depth=depth, operands=operands)
        lr2 = float(self.run.config["keys"]["learning_rate"]) ** 2
        # plain SGD keeps no state: the first gradient, as the optimizer got
        # it, is the first step's change over the learning rate
        ref["grad1"] = {k: v[0] / lr2 for k, v in ref["change"].items()}
        return ref

    def program_grad1(self, reads):
        lr2 = float(self.run.config["keys"]["learning_rate"]) ** 2
        return {k: v / lr2 for k, v in reads[0]["change"].items()}
