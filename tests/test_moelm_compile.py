"""The kernels of the mixture-of-experts step (at Moonlight-16B-A3B's and at
SDAR-30B-A3B-Chat's published widths), the chunked delta rule at
Solar-Open2-250B's and Olmo-Hybrid-7B's, and the table plane's gather (at the Wide&Deep cell's
shapes) compile for a TPU v5e that is described and not attached: what
interpret mode cannot show (tile alignment, VMEM, transposed products in
Mosaic). Compile-only: nothing runs, and no time or result comes of it. The
topology is described inside a fixture, in this file alone (one process may
load the TPU's library)."""

import functools

import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.ops import flash_attention as fa
from swiftsnails_tpu.ops import grouped_matmul as gm

SEQ, HIDDEN = 8192, 2048
# (query heads, key/value heads, key width, value width, block-diffusion block, experts' width, held, a token)
MOONLIGHT = (16, 16, 192, 128, None, 1408, 8, 6)  # qk_nope 128 + qk_rope 64; v_head_dim 128; causal
SDAR = (32, 4, 128, 128, 4, 768, 16, 8)  # 8 query heads to a key/value head; 4,096 tokens in two copies
SOLAR_SOFTMAX = (8, 1, 128, 128, None)  # Solar-Open2-250B's softmax layer as its cell holds it: 8 heads to 1, causal
# the expert layers: (experts' width, held, a token, the router's experts, hidden, positions a step)
EXPERTS = {"moonlight": MOONLIGHT[5:] + (64, HIDDEN, SEQ), "sdar": SDAR[5:] + (128, HIDDEN, SEQ),
           "solar": (1280, 8, 8, 320, 4096, 2048)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    """A described-chip compile is written to the persistent cache and
    cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("widths,seq", [(MOONLIGHT, SEQ), (SDAR, SEQ), (SOLAR_SOFTMAX, 2048)],
                         ids=["moonlight-causal", "sdar-block-diffusion", "solar-causal-8-to-1"])
def test_attention_kernels_compile_at_published_widths(one_chip, no_cache, widths, seq):
    """Each kernel over its grid of live steps (the step table in scalar
    memory; under block diffusion with the second body, a block-diagonal
    tile's ``[128, 128]`` pieces) at the three cells' widths and tiles a
    side: 16, 8 a copy, 4."""
    heads, kv_heads, dk, dv, diffusion_block = widths[:5]

    def loss(q, k, v, w):
        return jnp.sum(fa.flash_attention(q, k, v, block=512, interpret=False,
                                          diffusion_block=diffusion_block) * w)

    f32 = jnp.float32
    compiled = _compiled(jax.grad(loss, (0, 1, 2)), one_chip,
                         ((heads, seq, dk), f32), ((kv_heads, seq, dk), f32),
                         ((kv_heads, seq, dv), f32), ((heads, seq, dv), f32))
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv"):
        assert name in text and "tpu_custom_call" in text, name
    # scores never leave VMEM, and keys and values are never copied out to the
    # query heads: nothing the size of [heads, L, L] is allocated
    assert compiled.memory_analysis().temp_size_in_bytes < heads * seq * seq * 4 / 4
    assert compiled.out_info[1].shape == (kv_heads, seq, dk)  # dk summed over the group in the kernel


@pytest.mark.parametrize("fused", [False, True], ids=["products", "fused"])
@pytest.mark.parametrize("cell", ["moonlight", "sdar", "solar"])
def test_grouped_products_compile_at_published_widths(one_chip, no_cache, monkeypatch, cell, fused):
    """At the tile the rule gives each cell's load: 512 rows for Moonlight
    and SDAR, 128 for Solar (a grid that ends at the live tiles, ``dw``'s
    wider cut)."""
    monkeypatch.setattr(gm, "on_tpu", lambda: True)  # the backend here is the CPU; the moves' buffers are kernels' too
    EXPERT_WIDTH, HELD, TOP_K, ROUTED, hidden, seq = EXPERTS[cell]
    tile = gm.tile_for(seq * TOP_K / ROUTED)
    assert tile == (128 if cell == "solar" else gm.TILE)
    assignments = seq * TOP_K  # every position could choose all of its experts among those held
    rows = gm.rows_for(assignments, HELD, tile)
    assert rows == (assignments // tile + HELD) * tile

    def loss(feed_forward, y, w_gate, w_up, w_down, gates, owner):  # the moves ride along
        plan = gm.plan_rows(owner, HELD, tile)
        out = feed_forward(gm.rows_of_tokens(y, plan, tile), w_gate, w_up, w_down, plan, tile)
        return jnp.sum(gm.tokens_of_rows(out, gates, plan, tile))

    def products(rows, w_gate, w_up, w_down, plan, tile):
        mm = functools.partial(gm.grouped_matmul, plan=plan, tile=tile, interpret=False)
        return mm(jax.nn.silu(mm(rows, w_gate)) * mm(rows, w_up), w_down)

    def compiled(feed_forward):
        f32, wide = jnp.float32, (HELD, hidden, EXPERT_WIDTH)
        return _compiled(jax.grad(functools.partial(loss, feed_forward), (0, 1, 2, 3, 4)), one_chip,
                         ((seq, hidden), f32), (wide, f32), (wide, f32),
                         ((HELD, EXPERT_WIDTH, hidden), f32), ((seq, TOP_K), f32),
                         ((seq, TOP_K), jnp.int32))

    unfused = compiled(products)
    text = unfused.as_text()
    for name in ("grouped_matmul_", "grouped_matmul_dx", "grouped_matmul_dw"):
        assert name in text, name
    if not fused:
        return
    whole = compiled(functools.partial(gm.grouped_swiglu, interpret=False))
    kernels = [line for line in whole.as_text().splitlines() if "tpu_custom_call" in line]
    # the benchmark's roofline finds a kernel of this path by "grouped_matmul" in its name: the
    # forward pair, SwiGLU's derivative, the rows' gradient, the three weights', and the
    # two that hand a move's loop the buffer it fills
    assert all("grouped_matmul" in line for line in kernels), kernels
    assert len(kernels) == 9 and sum("grouped_matmul_unfilled_rows" in line for line in kernels) == 2
    for name in ("grouped_matmul_swiglu", "grouped_matmul_dswiglu", "grouped_matmul_dx", "grouped_matmul_dw"):
        assert any(name in line for line in kernels), name
    assert whole.memory_analysis().temp_size_in_bytes <= unfused.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("heads,seq,width,value,per_head", [(8, 2048, 128, 128, False), (15, 4096, 96, 192, False),
                                                           (15, 4096, 96, 192, True)],
                         ids=["solar", "olmo-hybrid", "olmo-hybrid-head"])
def test_chunked_delta_rule_compiles_at_published_widths(one_chip, no_cache, monkeypatch, heads, seq, width, value,
                                                         per_head):
    """Solar-Open2-250B's delta-rule layer as the cell holds it (8 heads of
    128) and Olmo-Hybrid-7B's Gated DeltaNet (15 heads, keys of 96, values of
    192: blocks no multiple of 128 lanes), chunks of 64, forward and
    backward: the two kernels of the form ``g``'s shape picks (a decay a
    channel: an odd head count padded; a decay a head, as the mixer passes
    it: 15 heads in one grid step), no XLA solve left, and nothing the size of a
    state a token (``[8, 2048, 128, 128]`` float32 is 1 GiB) or of every
    pair's decay is allocated."""
    from swiftsnails_tpu.ops import gated_delta
    from swiftsnails_tpu.ops.gated_delta import gated_delta_rule

    monkeypatch.setattr(gated_delta, "on_tpu", lambda: True)

    def loss(q, k, v, g, beta, w):
        return jnp.sum(gated_delta_rule(q, k, v, g, beta) * w)

    f32, wide, values = jnp.float32, (heads, seq, width), (heads, seq, value)
    decays = (heads, seq) if per_head else wide
    compiled = _compiled(jax.grad(loss, (0, 1, 2, 3, 4)), one_chip, (wide, f32), (wide, f32), (values, f32),
                         (decays, f32), ((heads, seq), f32), (values, f32))
    kernels = [line for line in compiled.as_text().splitlines() if "tpu_custom_call" in line]
    form = "gated_delta_head_" if per_head else "gated_delta_"
    assert len(kernels) == 2 and all(form + "fwd" in line or form + "bwd" in line for line in kernels), kernels
    assert "triangular" not in compiled.as_text().lower()
    assert compiled.memory_analysis().temp_size_in_bytes < heads * seq * width * value * 4 / 4
    assert [o.shape for o in compiled.out_info] == [wide, wide, values, decays, (heads, seq)]


# the Wide&Deep cell's pull (212,992 ids into a 4 GiB table of [2, 128]
# tiles, block 512), a block that is no multiple of the start unroll, and one
# whose waits end in a chunk remainder
@pytest.mark.parametrize("capacity,n,block_rows", [
    (1 << 22, 212_992, 512), (4096, 36, 12), (4096, 384, 192)])
def test_gather_rows_compiles_for_the_chip(one_chip, no_cache, capacity, n, block_rows):
    from swiftsnails_tpu.ops import rowdma

    fn = functools.partial(rowdma.gather_rows, block_rows=block_rows)
    compiled = _compiled(fn, one_chip,
                         ((capacity, 2, 128), jnp.float32), ((n,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (n, 2, 128)


# Keye-VL-2.0-30B-A3B's learned selection at its cell's 16,384 positions: 32 query heads over 4 of 128,
# an indexer of 16 heads of 64, tiles of 512 (a tile's words a [16, 512] int32 block)
KEYE = {"seq": 16384, "heads": 32, "kv": 4, "dim": 128, "index_heads": 16, "index_dim": 64}


@pytest.mark.parametrize("kernel", ["attention", "select", "pass", "index_bwd"])
def test_sparse_kernels_compile_at_published_widths(one_chip, no_cache, kernel):
    """The three attention kernels on a selection's run-time walks (the
    tables as long as the causal walk, the dkv one 8 heads deep: 4,224
    steps in scalar memory), the score kernel and the top-k kernel by
    chunks, the probability pass and the indexer's backward kernel, with the
    32 heads' probabilities recomputed in VMEM."""
    from swiftsnails_tpu.ops import sparse_attention as sa

    m = KEYE
    seq, n = m["seq"], m["seq"] // 512
    causal = n * (n + 1) // 2
    i32, f32 = jnp.int32, jnp.float32
    sel = (((seq // 32, seq), i32), ((6, causal), i32), ((6, 8 * causal), i32))
    main = (((m["heads"], seq, m["dim"]), f32), ((m["kv"], seq, m["dim"]), f32))
    index = (((m["index_heads"], seq, m["index_dim"]), f32), ((seq, m["index_dim"]), f32),
             ((seq, m["index_heads"]), f32))
    selection = lambda a, b, c: fa.Selection(a, b, c)  # noqa: E731
    if kernel == "attention":
        def fn(q, k, v, a, b, c):
            return jax.grad(lambda q, k, v: jnp.sum(fa.selected_attention(
                q, k, v, selection(a, b, c), interpret=False)[0]), (0, 1, 2))(q, k, v)
        shapes = main + main[1:] + sel
    elif kernel == "select":
        def fn(qi, ki, w):
            return sa.select(qi, ki, w, 2048, 512, interpret=False)
        shapes = index
    elif kernel == "pass":
        def fn(q, k, lse, qi, ki, w, a, b, c):
            return sa.probability_pass(q, k, lse, qi, ki, w, selection(a, b, c), 512, interpret=False)
        shapes = main + (((m["heads"], seq), f32),) + index + sel
    else:
        def fn(q, k, lse, qi, ki, w, a, b, c, stats):
            return sa._index_backward(q, k, lse, qi, ki, w, selection(a, b, c), stats, 512, jnp.bfloat16, False)
        shapes = main + (((m["heads"], seq), f32),) + index + sel + (((seq, 128), f32),)
    compiled = _compiled(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()
    if kernel == "select":  # the top-k is a kernel of its own beside the score kernel, not XLA's loop
        assert "sparse_top_k" in compiled.as_text() and "sparse_index_scores" in compiled.as_text()
    # the scores stay in VMEM a tile at a time: nothing the size of [heads, L, L] is allocated
    assert compiled.memory_analysis().temp_size_in_bytes < m["heads"] * seq * seq * 4 / 4
