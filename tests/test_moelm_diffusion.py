"""The block stack's second kind of layer and second objective
(``models/moelm.py`` with grouped-query attention, a softmax router and no
shared expert; ``models/seqlm.py``'s block-diffusion loss) and the attention
kernels under the block-diffusion mask, against the benchmark's plain float32
reference (``benchmark/models/sdar.py``), small and on the CPU: the kernels
run in interpret mode, matrix operands stay float32 so that the two agree
closely. ``tests/test_moelm.py`` holds the latent-attention kind to its own
reference the same way."""

import dataclasses
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.framework.trainer import TrainLoop
from swiftsnails_tpu.models.moelm import MoELMTrainer, rotary
from swiftsnails_tpu.models.seqlm import SeqLMTrainer, diffusion_inputs, draw_noise, token_loss
from swiftsnails_tpu.ops import flash_attention as fa
from swiftsnails_tpu.ops.flash_attention import attention_flops, flash_attention, tile_classes
from swiftsnails_tpu.utils.config import Config
from swiftsnails_tpu.utils.metrics import MetricsLogger

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")

KEYS = dict(
    model="moelm", seq_len=32, batch_size=2, hidden_size=32, num_hidden_layers=2,
    first_k_dense_replace=0, num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    rope_theta=1000000, rms_norm_eps=1e-6, moe_intermediate_size=16, n_shared_experts=0,
    num_experts_per_tok=3, scoring_func="softmax", router_experts=16, experts_held=4,
    expert_offset=4, vocab_size=64, block_length=4, mask_token_id=63,
    optimizer="adamw", learning_rate=1e-3, adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8,
    weight_decay=0.1, bias_update_rate=0, aux_loss_alpha=0, init_std=0.05, loss_chunks=4,
    num_iters=2, matmul_dtype="float32", remat=1)


@pytest.fixture(scope="module")
def sdar():
    """``benchmark/models/sdar.py``, the reference's home."""
    sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_models_sdar_t", os.path.join(BENCH, "models", "sdar.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trainer(**over):
    cfg = Config()
    for k, v in {**KEYS, **over}.items():
        cfg.set(k, str(v))
    ids = np.random.default_rng(7).integers(0, 63, 3000)  # never the mask's id
    tr = MoELMTrainer(cfg, corpus_ids=ids, vocab_size=cfg.get_int("vocab_size"))
    tr.attention_block, tr.expert_tile = 16, 8  # several blocks and tiles at this size
    return tr, {**KEYS, **over}


def _layer(tree, i):
    return {k: v[i] for k, v in tree.items()}


def _x(tr, positions, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (positions, tr.d_model))


# ------------------------------------------------------------ the kernels ---


def _dense_attention(q, k, v, keep):
    group = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(q.shape[-1])
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1), v)


@pytest.mark.parametrize("mask,heads,kv_heads", [
    ("diffusion", 16, 2), ("diffusion", 2, 2), ("causal", 16, 2)])
def test_flash_attention_under_a_mask_and_over_grouped_heads(sdar, mask, heads, kv_heads):
    """Forward and all three gradients against a dense masked softmax, at a
    tile smaller than L (B 4, tile 16, L 64: all three regions have whole,
    partial and skipped tiles) and with 8 query heads to a key/value head:
    ``dk`` and ``dv`` are summed over the group inside the kernel."""
    seq, block, tile, dk, dv = 64, 4, 16, 24, 16
    n = 2 * seq if mask == "diffusion" else seq
    keep = sdar.allowed_pairs(seq, block) if mask == "diffusion" else np.tril(np.ones((n, n), bool))
    assert keep.sum() == (seq * (seq + block) if mask == "diffusion" else n * (n + 1) // 2)
    if mask == "diffusion":  # whole, partial and dead tiles in every region
        tiles = keep.reshape(n // tile, tile, n // tile, tile).sum(axis=(1, 3))
        assert {0, tile * block, tile * tile} <= set(tiles.ravel()) and (tiles > 0).sum() == 4 + 10 + 10
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = jax.random.normal(ks[0], (heads, n, dk)), jax.random.normal(ks[1], (kv_heads, n, dk))
    v, w = jax.random.normal(ks[2], (kv_heads, n, dv)), jax.random.normal(ks[3], (heads, n, dv))
    fast = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, block=tile, dtype=jnp.float32, diffusion_block=block if mask == "diffusion" else None)
    plain = lambda q, k, v: _dense_attention(q, k, v, keep)  # noqa: E731
    np.testing.assert_allclose(np.asarray(fast(q, k, v)), np.asarray(plain(q, k, v)), rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(fast(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):  # sums of up to 8 x 128 float32 products in another order
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4)


def _one_piece(mask):
    """``mask`` with every visited tile taken as one piece under its mask:
    the kernels as they were before a tile had a class."""

    @dataclasses.dataclass(frozen=True)
    class OnePiece(type(mask)):
        def classes(self, qi, kj, n):
            return {"whole": True}

    return OnePiece(**dataclasses.asdict(mask))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("mask,heads,kv_heads,dk,dv", [
    (fa.Causal(), 2, 2, 24, 16), (fa.Causal(), 8, 1, 16, 16),
    (fa.BlockDiffusion(4), 2, 2, 16, 16), (fa.BlockDiffusion(4), 8, 1, 16, 8)],
    ids=["causal-unequal-widths", "causal-8-to-1", "diffusion-1-to-1", "diffusion-8-to-1"])
def test_a_cut_tile_by_its_pieces_is_the_whole_tile_bit_for_bit(mask, heads, kv_heads, dk, dv, dtype):
    """The forward output, ``dq``, ``dk`` and ``dv`` of the kernels as shipped
    (a tile the mask cuts computes only its sub-tiles that hold a pair)
    against the same kernels under a mask whose every tile is one piece,
    three tiles a side (a copy) and four sub-tiles a tile's side, so that
    whole, lower, diagonal and dead steps all occur. What a piece leaves out
    is exact zeros in the whole tile's sums; on the chip the comparison is
    made at the cells' widths against the parent's kernels (PERF.md, PR 37)."""
    tile, copies = 16, 2 if isinstance(mask, fa.BlockDiffusion) else 1
    seq = 3 * tile * copies
    assert fa._parts(mask, tile, True) == 4 and fa._parts(mask, tile, False) == 1  # [4, 4] is no whole lanes
    classes = tile_classes(seq, tile, getattr(mask, "block_length", None))
    assert min(classes.values()) > 0, classes
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k = jax.random.normal(ks[0], (heads, seq, dk)), jax.random.normal(ks[1], (kv_heads, seq, dk))
    v, w = jax.random.normal(ks[2], (kv_heads, seq, dv)), jax.random.normal(ks[3], (heads, seq, dv))

    def results(mask):
        o, vjp = jax.vjp(lambda q, k, v: fa._attend(q, k, v, tile, jnp.dtype(dtype), True, mask), q, k, v)
        return [np.asarray(t) for t in (o, *vjp(w))]

    for name, got, want in zip(("o", "dq", "dk", "dv"), results(mask), results(_one_piece(mask))):
        assert np.isfinite(got).all() and np.array_equal(got, want), name


def _walks(mask, n):
    """Every step of the masks' two rectangular walks, dead ones too, as
    plain numbers: ``(qi, j, kj, live)`` of ``key_tile`` and ``(kj, i, qi,
    live)`` of ``query_tile``."""
    qi, j = np.meshgrid(np.arange(n), np.arange(mask.key_steps(n)), indexing="ij")
    by_query = zip(qi.ravel(), j.ravel(), *(np.broadcast_to(t, qi.shape).ravel() for t in mask.key_tile(qi, j, n)))
    kj, i = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    by_key = zip(kj.ravel(), i.ravel(), *(np.broadcast_to(t, kj.shape).ravel() for t in mask.query_tile(kj, i, n)))
    return [tuple(int(x) for x in s) for s in by_query], [tuple(int(x) for x in s) for s in by_key]


def _edges(steps, row):
    """Where the tile named in ``row`` changes along a step table: (whether a
    step is the first of its tile, whether it is the last)."""
    run, last = [int(x) for x in steps[row]], steps.shape[1] - 1
    return ([int(t == 0 or run[t] != run[t - 1]) for t in range(last + 1)],
            [int(t == last or run[t] != run[t + 1]) for t in range(last + 1)])


@pytest.mark.parametrize("per_copy,tile,block_length", [
    (1, 8, None), (2, 4, None), (3, 8, None), (5, 4, None),
    (1, 4, 4), (2, 4, 2), (3, 8, 2), (4, 8, 1), (3, 16, 4), (2, 32, 8), (2, 16, 8)],
    ids=lambda x: str(x))
def test_a_tile_s_class_says_where_its_mask_keeps_a_pair(sdar, per_copy, tile, block_length):
    """Over every step of the masks' walks: a visited tile has exactly one
    class; ``whole`` says that ``keep`` over the tile is all true (where a
    tile holds several blocks); the pieces of its class hold every pair
    ``keep`` allows and lie inside the tile, each sub-tile at most once; the
    live tiles' ``keep`` are the dense mask and the tiles never visited hold
    no pair; every row's first live tile holds a pair it may see (the
    module's standing promise) and a cut tile of several blocks at least one.
    The step tables hold the live steps in the walks' order and no dead one,
    with the first and the last step of every accumulated tile marked, under
    grouped heads too; :func:`tile_classes` counts what the walk counts."""
    mask = fa._mask_of(block_length)
    n = per_copy * (2 if block_length else 1)
    seq, full = n * tile, ((0, tile), (0, tile))
    parts = fa._parts(mask, tile, True)
    assert parts == (4 if tile % (4 * mask.unit) == 0 else 1)
    dense = sdar.allowed_pairs(seq // 2, block_length) if block_length else np.tril(np.ones((seq, seq), bool))
    by_query, by_key = _walks(mask, n)
    one_block_diagonal = {(q, k) for q in range(n) for k in range(n)
                          if tile == (block_length or 1) and q % per_copy == k % per_copy}
    seen, counts, table = np.zeros_like(dense), {"live": 0, "whole": 0, "cut": 0, "dead": 0}, []
    for qi, j, kj, live in by_query:
        if not live:
            counts["dead"] += 1
            continue
        keep = np.asarray(mask.keep(jnp.int32(qi), jnp.int32(kj), n, tile, *full))
        kinds = [kind for kind, here in mask.classes(qi, kj, n).items() if here]
        assert len(kinds) == 1 and kinds[0] in fa._KINDS, (qi, kj, kinds)
        whole = kinds == ["whole"]
        # (a tile of ONE block is full on the diagonal, or empty where only earlier blocks
        # count, and has a cut tile's class either way)
        assert whole == (keep.all() and (qi, kj) not in one_block_diagonal), (qi, kj)
        assert keep.any() or (qi, kj) in one_block_diagonal, (qi, kj)
        covered = np.zeros_like(keep)
        for rows, cols in fa._pieces(kinds[0], tile, parts):
            part = np.asarray(mask.keep(jnp.int32(qi), jnp.int32(kj), n, tile, rows, cols))
            at = (slice(rows[0], rows[0] + rows[1]), slice(cols[0], cols[0] + cols[1]))
            assert part.shape == keep[at].shape and np.array_equal(part, keep[at]) and not covered[at].any()
            covered[at] = True
        assert not (keep & ~covered).any(), (qi, kj, kinds)
        assert (len(fa._pieces(kinds[0], tile, parts)) > 1) == (kinds == ["diagonal"] and parts > 1)
        if not any(row[0] == qi for row in table):
            assert keep.any(axis=1).all(), (qi, kj)  # the running maximum is finite from the first step on
        assert not seen[qi * tile:(qi + 1) * tile, kj * tile:(kj + 1) * tile].any()  # a tile is visited once
        seen[qi * tile:(qi + 1) * tile, kj * tile:(kj + 1) * tile] = keep
        counts["live"] += 1
        counts["whole" if whole else "cut"] += 1
        table.append((qi, kj, fa._KINDS.index(kinds[0])))
    assert np.array_equal(seen, dense)
    assert counts == tile_classes(seq, tile, block_length)
    steps = fa._steps(mask, n)
    assert steps.dtype == np.int32 and [tuple(c) for c in steps[:3].T] == table and not steps[fa._HEAD].any()
    assert _edges(steps, fa._QI) == ([int(f) for f in steps[fa._FIRST]], [int(f) for f in steps[fa._LAST]])
    # the key side: a key tile's live steps once a query head of the group, in the walk's order
    kinds_of = {(qi, kj): kind for qi, kj, kind in table}
    want = [(qi, kj, kinds_of[qi, kj], head) for kj in range(n) for head in range(3)
            for k, _, qi, live in by_key if live and k == kj]
    steps = fa._steps(mask, n, group=3)
    assert [tuple(c) for c in steps[[fa._QI, fa._KJ, fa._KIND, fa._HEAD]].T] == want
    assert {(qi, kj) for qi, kj, *_ in want} == set(kinds_of) and len(want) == 3 * len(table)
    assert steps[fa._FIRST].sum() == steps[fa._LAST].sum() == n
    assert _edges(steps, fa._KJ) == ([int(f) for f in steps[fa._FIRST]], [int(f) for f in steps[fa._LAST]])


@pytest.mark.parametrize("seq,block_length,live,whole", [
    (8192, None, 136, 120), (8192, 4, 80, 56), (2048, None, 10, 6)], ids=["moonlight", "sdar", "solar"])
def test_tile_classes_at_the_cells_shapes(seq, block_length, live, whole):
    got = tile_classes(seq, diffusion_block=block_length)  # the kernels' own tile of 512
    steps = {None: (seq // 512) ** 2, 4: 16 * 9}[block_length]
    assert got == {"live": live, "whole": whole, "cut": live - whole, "dead": steps - live}


def test_a_tile_holds_whole_blocks_and_a_copy_whole_tiles():
    q = jnp.zeros((2, 48, 8))
    with pytest.raises(ValueError):  # a copy of 24 is no multiple of the tile 16
        flash_attention(q, q, q, block=16, diffusion_block=4)
    with pytest.raises(ValueError):  # a tile of 8 holds no whole blocks of 3
        flash_attention(q[:, :32], q[:, :32], q[:, :32], block=8, diffusion_block=3)
    with pytest.raises(ValueError):  # 3 query heads over 2 key/value heads
        flash_attention(jnp.zeros((3, 32, 8)), q[:, :32], q[:, :32], block=16)


def test_attention_flops_count_the_pairs_a_mask_allows():
    got = attention_flops(8192, 32, 128, 128, diffusion_block=4)  # two copies of 4,096
    pairs = 32 * 4096 * (4096 + 4)
    assert got == {"fwd": 2 * pairs * 256, "dq": 2 * pairs * 384, "dkv": 2 * pairs * 512}
    causal = 16 * 8192 * 8193 / 2  # unchanged, with or without the new argument
    assert attention_flops(8192, 16, 192, 128) == attention_flops(8192, 16, 192, 128, None) == {
        "fwd": 2 * causal * 320, "dq": 2 * causal * 512, "dkv": 2 * causal * 640}


# ------------------------------------------------------------- the layers ---


def test_grouped_query_layer_with_head_norms_matches_reference(sdar):
    tr, keys = _trainer()
    math = sdar.reference_math(keys)
    p = dict(_layer(tr.init_state()["params"]["moe"], 1))
    p["q_norm"], p["k_norm"] = jnp.linspace(0.5, 1.5, 8), jnp.linspace(1.4, 0.6, 8)  # gains that show
    rows, seq = tr.batch_size, tr.seq_len
    x = _x(tr, rows * 2 * seq)
    positions = jnp.tile(jnp.arange(seq), 2)
    got = jax.jit(lambda p, x: tr._attention(p, x, rows, positions))(p, x).reshape(rows, 2 * seq, -1)
    keep = jnp.asarray(sdar.allowed_pairs(seq, 4))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda x: math.attention(p, x, positions, keep)))(x.reshape(got.shape))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
    # both copies carry the positions 0..L-1: a copy rotated alone is the same
    spun = rotary(x[: 2 * seq, None, :8], 1e6, positions)
    np.testing.assert_allclose(np.asarray(spun[seq:]), np.asarray(rotary(x[seq: 2 * seq, None, :8], 1e6)),
                               rtol=1e-6, atol=1e-6)


def test_softmax_routed_layer_matches_reference(sdar):
    tr, keys = _trainer(batch_size=1)
    math = sdar.reference_math(keys)
    p = _layer(tr.init_state()["params"]["moe"], 0)
    seq = tr.seq_len
    x = _x(tr, 2 * seq)
    positions = jnp.tile(jnp.arange(seq), 2)
    got, seen = jax.jit(lambda x, p: tr._moe_layer(x, p, jnp.zeros(16), 1, positions))(x, p)
    keep = jnp.asarray(sdar.allowed_pairs(seq, 4))

    @jax.jit
    def reference(x, p):
        x1 = x + math.attention(p, x, positions, keep)
        return (x1,) + math.mixture(p, math.norm(x1, p["mlp_norm"]))

    with jax.default_matmul_precision("highest"):
        x1, out, choices = reference(x, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x1 + out), rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.sort(seen["choices"], axis=-1), np.sort(choices, axis=-1))
    assert float(seen["aux"]) == 0.0  # no balance loss: the counts are kept all the same
    assert int(seen["dropped"]) == 0 and int(seen["counts"].sum()) == 2 * seq * tr.top_k
    _, gates, s = tr.route(math.norm(x, p["mlp_norm"]), p["router"], jnp.zeros(16))
    np.testing.assert_allclose(np.asarray(s.sum(axis=-1)), 1.0, rtol=1e-5)  # a softmax over all 16
    np.testing.assert_allclose(np.asarray(gates.sum(axis=-1)), 1.0, rtol=1e-5)  # renormalised, no scale


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(sdar):
    """The routed parts that the eight chips of a deployment compute are the
    whole layer as the reference has it (no shared expert to count once; the
    residual and attention are every chip's alike and stay outside)."""
    whole, keys = _trainer(batch_size=1, experts_held=16, expert_offset=0)
    math = sdar.reference_math(keys)
    p = _layer(whole.init_state()["params"]["moe"], 0)
    y = _x(whole, 2 * whole.seq_len)
    with jax.default_matmul_precision("highest"):
        want, _ = math.mixture(p, y)
    choices, gates, _ = whole.route(y, p["router"], jnp.zeros(16))
    total = 0.0
    for share in range(8):
        tr, _ = _trainer(batch_size=1, experts_held=2, expert_offset=2 * share)
        mine = {k: (v[2 * share: 2 * share + 2] if k.startswith("experts_") else v)
                for k, v in p.items()}
        routed, planned = tr._experts(mine, y, choices, gates)
        assert int(planned["dropped"]) == 0
        total = total + routed
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_dropless_when_every_choice_of_every_position_is_held():
    tr, _ = _trainer(batch_size=1)
    p = _layer(tr.init_state()["params"]["moe"], 0)
    y = _x(tr, 2 * tr.seq_len)
    choices = jnp.tile(jnp.asarray([[4, 6, 7]], jnp.int32), (y.shape[0], 1))  # 4..7 are held
    gates = jnp.tile(jnp.asarray([[0.5, 0.3, 0.2]]), (y.shape[0], 1))
    out, planned = tr._experts(p, y, choices, gates)
    want = sum(g * (jax.nn.silu(y @ p["experts_gate"][e]) * (y @ p["experts_up"][e])) @ p["experts_down"][e]
               for g, e in ((0.5, 0), (0.3, 2), (0.2, 3)))
    assert int(planned["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------- the objective ---


def test_noise_draw_follows_p_mask_and_the_batch_carries_it():
    tr, _ = _trainer(seq_len=128)
    it = iter(tr.batches())
    first = next(it)
    assert set(first) == {"tokens", "noised", "p_mask"}
    assert first["tokens"].shape == first["noised"].shape == (2, 128) and first["p_mask"].shape == (2, 32)
    assert first["noised"].dtype == bool and tr.items_per_batch(first) == 2 * 128  # L a row, no shift
    second = next(it)
    assert not np.array_equal(first["noised"], second["noised"])  # a fresh draw every step
    again = next(iter(tr.batches()))
    assert all(np.array_equal(first[k], again[k]) for k in first)  # from the trainer's seed
    drawn = draw_noise(np.random.default_rng(0), np.zeros((64, 4096), np.int32), 4)
    p = drawn["p_mask"]
    assert 1e-3 <= p.min() and p.max() <= 1.0 and abs(p.mean() - 0.5005) < 0.005
    per_block = drawn["noised"].reshape(64, 1024, 4).mean(axis=-1)
    for lo, hi in ((0.0, 0.25), (0.25, 0.5), (0.5, 0.75), (0.75, 1.0)):  # the masked share follows p
        mine = (p >= lo) & (p < hi)
        assert abs(per_block[mine].mean() - p[mine].mean()) < 0.01
    assert abs(drawn["noised"].mean() - 0.5) < 0.01
    plain, _ = _trainer(block_length=0, first_k_dense_replace=0)
    assert set(next(iter(plain.batches()))) == {"tokens"}
    assert plain.items_per_batch(next(iter(plain.batches()))) == 2 * 32  # L + 1 a row, shifted
    with pytest.raises(ValueError):  # seqlm's own stack is causal
        cfg = Config()
        cfg.set("block_length", "4")
        SeqLMTrainer(cfg, corpus_ids=np.arange(100), vocab_size=100)
    with pytest.raises(ValueError):  # the mask's id is in the feed
        _trainer(mask_token_id=5)


def test_diffusion_inputs_and_the_weighted_loss():
    tokens = jnp.asarray([[5, 6, 7, 8, 9, 10, 11, 12]], jnp.int32)
    noised = jnp.asarray([[True, False, False, True, False, False, False, False]])
    p_mask = jnp.asarray([[0.5, 0.25]], jnp.float32)
    ids, positions, weights = diffusion_inputs(
        {"tokens": tokens, "noised": noised, "p_mask": p_mask}, mask_id=63, block=4)
    np.testing.assert_array_equal(ids, [[63, 6, 7, 63, 9, 10, 11, 12, 5, 6, 7, 8, 9, 10, 11, 12]])
    np.testing.assert_array_equal(positions, [0, 1, 2, 3, 4, 5, 6, 7] * 2)
    np.testing.assert_allclose(weights, [[2.0, 0, 0, 2.0, 0, 0, 0, 0]])
    hidden = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    head = jax.random.normal(jax.random.PRNGKey(2), (6, 64))
    logits = hidden @ head
    ce = jax.nn.logsumexp(logits, axis=-1) - logits[jnp.arange(8), tokens[0]]
    want = float(jnp.sum(ce * weights[0]) / 8)
    for chunks in (1, 4):
        got = token_loss(hidden, head, tokens[0], chunks, weights=weights[0])
        assert float(got) == pytest.approx(want, rel=1e-5)
    assert float(token_loss(hidden, head, tokens[0], 2)) == pytest.approx(float(ce.mean()), rel=1e-5)


def test_train_steps_under_the_diffusion_loss_match_reference(sdar):
    """Loss, every leaf's first gradient (from AdamW's first moment) and every
    leaf's change after three AdamW steps, the reference handed the batch's
    own noise draw."""
    tr, keys = _trainer()
    state = tr.init_state()
    w = sdar._flatten(state["params"])
    assert {k: tuple(v.shape) for k, v in w.items()} == sdar.shapes(keys)
    it = iter(tr.batches())
    batches = [next(it) for _ in range(3)]
    ref = sdar.sdar_reference(w, batches, keys)
    step = jax.jit(tr.train_step)
    start, losses = state["params"], []
    for i, b in enumerate(batches):
        state, m = step(state, {k: jnp.asarray(v) for k, v in b.items()}, jax.random.PRNGKey(0))
        losses.append(float(m["loss"]))
        if i == 0:
            grad1 = {k: float(jnp.sum(v * v)) / (1 - 0.9) ** 2
                     for k, v in sdar._flatten(state["opt"][0].mu).items()}
        assert int(m["moe_dropped"]) == 0
        assert float(m["diffusion_masked_share"]) == pytest.approx(b["noised"].mean())
        assert int(state["noised"]) == b["noised"].sum()
        assert float(m["attn_whole_tile_share"]) == 0.25  # two tiles a copy: 2 of the 8 visited hold earlier blocks only
    np.testing.assert_allclose(losses, ref["loss"], rtol=2e-5)
    for k, want in ref["grad1"].items():
        assert grad1[k] == pytest.approx(want, rel=2e-3, abs=1e-12), k
    change = {k: float(jnp.sum((v - sdar._flatten(start)[k]) ** 2))
              for k, v in sdar._flatten(state["params"]).items()}
    for k, want in ref["change"].items():
        assert change[k] == pytest.approx(want[-1], rel=5e-3), k
    assert float(jnp.abs(state["router_bias"]).max()) == 0.0  # no bias step at a rate of 0
    assert sdar.disagree_share([np.asarray(state["choices"])], ref["choices"][-1:]) == 0.0


@pytest.mark.parametrize("part", ["control", "causal_noised", "noised_past", "no_p_weight",
                                  "no_qk_norm", "fifteen_experts", "busiest_expert_out", "half_batch",
                                  "state_unchanged"])
def test_control_and_faults_read_far_from_the_reference(sdar, part):
    """The bfloat16 control, and every fault the reference can plant, moves
    the first loss or the first gradient by far more than the 2e-5 and 2e-3
    the program is held to above."""
    tr, keys = _trainer()
    w = sdar._flatten(tr.init_state()["params"])
    batches = [next(iter(tr.batches()))]
    sound = sdar.sdar_reference(dict(w), batches, keys)
    other = sdar.sdar_reference(dict(w), batches, keys, **(
        {"precision": "bfloat16"} if part == "control" else {"fault": part}))
    loss_gap = abs(other["loss"][0] - sound["loss"][0]) / sound["loss"][0]
    grad_gap = max(abs(np.sqrt(other["grad1"][k]) - np.sqrt(v)) / np.sqrt(v)
                   for k, v in sound["grad1"].items() if v > 0)
    change_gap = max(abs(np.sqrt(other["change"][k][0]) - np.sqrt(v[0])) / np.sqrt(v[0])
                     for k, v in sound["change"].items())
    assert loss_gap > 2e-4 or grad_gap > 2e-2 or change_gap > 0.5, (loss_gap, grad_gap, change_gap)


def test_bfloat16_operands_stay_close_and_the_step_is_scoped():
    exact, _ = _trainer()
    rounded, _ = _trainer(matmul_dtype="bfloat16")
    state = exact.init_state()
    batch = {k: jnp.asarray(v) for k, v in next(iter(exact.batches())).items()}
    want, _ = jax.jit(exact.loss_fn)(state["params"], batch, state)
    got, _ = jax.jit(rounded.loss_fn)(state["params"], batch, state)
    assert float(got) == pytest.approx(float(want), rel=2e-3) and float(got) != float(want)
    text = jax.jit(rounded.train_step).lower(state, batch, jax.random.PRNGKey(0)).as_text(debug_info=True)
    for phase in ("noise", "attn", "route", "experts", "head", "opt"):
        assert f"phase_{phase}" in text, phase
    assert "phase_mlp" not in text  # no dense layer, no shared expert


def test_runs_under_train_loop_from_a_file_of_ids(tmp_path):
    path = str(tmp_path / "ids.npy")
    np.save(path, np.random.default_rng(0).integers(0, 63, 2000).astype(np.int32))
    cfg = Config()
    for k, v in {**KEYS, "data": path, "shard_data": 0, "num_iters": 1}.items():
        cfg.set(k, str(v))
    tr = MoELMTrainer(cfg)
    assert tr.vocab_size == 64 and "dense" not in tr.param_shapes()
    state = TrainLoop(tr, metrics=MetricsLogger(echo=False), log_every=0).run(max_steps=3)
    assert int(state["dropped"]) == 0 and 0 < int(state["noised"]) < 2 * 32
    assert state["choices"].shape == (2, 2 * 2 * 32, 3)  # both copies are routed
