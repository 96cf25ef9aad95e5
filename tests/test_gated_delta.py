"""The chunked gated delta rule's kernels (``ops/gated_delta.py``, in
interpret mode) against their own definition, the recurrence a token at a
time, small and on the CPU: forward and the gradients of all five inputs, for
one chunk and for three, several heads (an odd count, padded to whole pairs
in the per-channel form, in whole steps in the per-head one) and keys
narrower than values, under decays from next to none down to ``g = -20`` a
token (``exp(-G_j)`` alone would overflow float32 inside a chunk); both
forms, a decay a channel and a decay a head, and the two against each other
on a decay a head."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from swiftsnails_tpu.ops import gated_delta
from swiftsnails_tpu.ops.gated_delta import (
    CORE_SCOPE, _pairs, gated_delta_flops, gated_delta_rule, recurrence)


def _inputs(seq, heads=3, width=16, value_width=16, g_min=-20.0, seed=0, per_head=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(jax.random.normal(ks[0], (heads, seq, width))), unit(jax.random.normal(ks[1], (heads, seq, width)))
    v = jax.random.normal(ks[2], (heads, seq, value_width))
    # log-uniform between -1e-3 and g_min: slow and fast channels (or heads) side by side
    g = -jnp.exp(jax.random.uniform(ks[3], (heads, seq) if per_head else (heads, seq, width),
                                    minval=np.log(1e-3), maxval=np.log(-g_min)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (heads, seq)))  # (0, 2): negative eigenvalues allowed
    return (q, k, v, g, beta), jax.random.normal(ks[5], (heads, seq, value_width))


@pytest.mark.parametrize("seq,chunk,value_width", [(8, 8, 16), (24, 8, 16), (64, 32, 16), (192, 64, 16),
                                                   (64, 32, 32)],
                         ids=["one-chunk", "three-chunks", "two-sub-blocks", "chunk-64", "wider-values"])
def test_chunks_match_the_recurrence_forward_and_backward(seq, chunk, value_width):
    args, w = _inputs(seq, value_width=value_width)
    assert float(args[3].min()) < -15.0 and float(args[3].max()) > -0.01
    fast = lambda *a: gated_delta_rule(*a, chunk=chunk, dtype=jnp.float32)  # noqa: E731
    got, want = jax.jit(fast)(*args), jax.jit(recurrence)(*args)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-4)
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args)  # noqa: E731
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads(fast), grads(recurrence)):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-4, err_msg=name)


def _spread(g, width):
    """A decay a head ``[H, L]`` as the same decay on each of ``width`` channels."""
    return jnp.broadcast_to(g[..., None], g.shape + (width,))


@pytest.mark.parametrize("seq,chunk,heads,width,value_width,trailing", [
    (8, 8, 3, 16, 16, False), (24, 8, 3, 16, 16, True), (192, 64, 3, 16, 16, False), (64, 32, 3, 16, 32, True),
    (32, 16, 27, 8, 16, False)],
    ids=["one-chunk", "three-chunks", "chunk-64", "keys-narrower", "odd-heads-in-steps"])
def test_a_decay_a_head_takes_its_kernels_and_matches_the_recurrence(seq, chunk, heads, width, value_width,
                                                                      trailing):
    """``g [H, L]`` (or ``[H, L, 1]``: ``trailing``) runs the per-head
    kernels, and they are the recurrence with ``g`` on every channel: forward
    and the gradients of all five inputs (``g``'s summed over the channels),
    every one finite; 27 heads go nine to a grid step, three steps, none padded."""
    args, w = _inputs(seq, heads=heads, width=width, value_width=value_width, per_head=True)
    q, k, v, g, beta = args
    assert float(g.min()) < -15.0 and float(g.max()) > -0.01
    assert gated_delta._form(g[..., None]) is gated_delta._HEAD and gated_delta._head_steps(heads) == (min(heads, 9), heads)
    fast = lambda q, k, v, g, b: gated_delta_rule(  # noqa: E731
        q, k, v, g[..., None] if trailing else g, b, chunk=chunk, dtype=jnp.float32)
    slow = lambda q, k, v, g, b: recurrence(q, k, v, _spread(g, width), b)  # noqa: E731
    got, want = jax.jit(fast)(*args), jax.jit(slow)(*args)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-4)
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args)  # noqa: E731
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads(fast), grads(slow)):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)], ids=["float32", "bfloat16"])
def test_the_two_forms_agree_on_a_decay_a_head(dtype, tol):
    """The per-channel kernels given the decay on every channel and the
    per-head kernels given it once: the same outputs and gradients (``g``'s
    summed over the channels), to float32's rounding with float32 operands,
    and within bfloat16's with bfloat16 operands (the per-channel form's
    pairs between sub-blocks among them; the per-head pairs stay float32)."""
    args, w = _inputs(128, heads=5, width=16, value_width=32, g_min=-3.0, per_head=True)
    width = args[0].shape[2]
    head = lambda q, k, v, g, b: gated_delta_rule(q, k, v, g, b, chunk=64, dtype=dtype)  # noqa: E731
    channel = lambda q, k, v, g, b: gated_delta_rule(q, k, v, _spread(g, width), b, chunk=64, dtype=dtype)  # noqa: E731
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))  # noqa: E731
    assert rel(jax.jit(head)(*args), jax.jit(channel)(*args)) < tol
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args)  # noqa: E731
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads(head), grads(channel)):
        assert rel(a, b) < tol, (name, rel(a, b))


def test_the_state_crosses_chunks_and_a_decay_of_one_keeps_it():
    """With ``g = 0`` and ``beta = 1`` the rule is the plain delta rule: a key
    written once is read back exactly by a query many chunks later."""
    seq, width = 64, 8
    k = jnp.zeros((1, seq, width)).at[0, 3, 2].set(1.0)
    v = jnp.zeros((1, seq, width)).at[0, 3].set(jnp.arange(width, dtype=jnp.float32))
    q = jnp.zeros((1, seq, width)).at[0, 60, 2].set(1.0)
    o = gated_delta_rule(q, k, v, jnp.zeros((1, seq, width)), jnp.ones((1, seq)), chunk=8, scale=1.0,
                         dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(o[0, 60]), np.arange(width), atol=1e-6)
    assert float(jnp.abs(o[0, :60]).max()) == 0.0
    fading = gated_delta_rule(q, k, v, jnp.full((1, seq, width), -0.1), jnp.ones((1, seq)), chunk=8, scale=1.0,
                              dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(fading[0, 60]), np.exp(-0.1 * 57) * np.arange(width), rtol=1e-5)


def test_pairwise_sums_never_raise_an_exponent():
    """``sum_c x_rc k_jc exp(G_rc - G_jc)`` for ``j <= r``, as a kernel
    computes them (two heads on the lanes, four sub-blocks): against the
    differences exponentiated one by one in float64, where ``exp(-G_j)``
    alone is past float32 (``G`` reaches -600 inside the chunk)."""
    rng = np.random.default_rng(0)
    x, k = rng.normal(size=(2, 32, 8)), rng.normal(size=(2, 32, 8))
    run = np.cumsum(-rng.uniform(0.0, 40.0, size=(2, 32, 8)), axis=1)
    assert run.min() < -600
    keep = np.tril(np.ones((32, 32), bool))
    want = np.where(keep, np.einsum("hrc,hjc,hrjc->hrj", x, k, np.exp(np.where(
        keep[None, :, :, None], run[:, :, None, :] - run[:, None, :, :], -np.inf))), 0.0)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731

    def kernel(x_ref, k_ref, run_ref, out_ref):
        out_ref[...] = _pairs((x_ref[...],), k_ref[...], run_ref[...], 8, jnp.float32)[0][0]

    got = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct((2, 32, 32), jnp.float32), interpret=True)(
        f32(x), f32(k), f32(run))
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_bfloat16_operands_stay_close_and_shapes_are_checked():
    args, _ = _inputs(64, g_min=-2.0)
    exact = gated_delta_rule(*args, chunk=32, dtype=jnp.float32)
    rounded = gated_delta_rule(*args, chunk=32)  # bfloat16 operands, float32 sums, state and solve
    gap = float(jnp.abs(rounded - exact).max())
    assert 0.0 < gap < 0.03 * float(jnp.abs(exact).max())
    q, k, v, g, beta = args
    with pytest.raises(ValueError):  # 64 tokens are no whole chunks of 48
        gated_delta_rule(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError):  # a chunk of 24 is no whole sub-blocks of 16
        gated_delta_rule(q[:, :48], k[:, :48], v[:, :48], g[:, :48], beta[:, :48], chunk=24)
    with pytest.raises(ValueError):  # beta is one a token and head
        gated_delta_rule(q, k, v, g, g, chunk=32)
    with pytest.raises(ValueError):  # g is one a channel, or one a head
        gated_delta_rule(q, k, v, g[..., :2], beta, chunk=32)


def test_operations_are_counted_and_the_core_is_scoped(monkeypatch):
    got = gated_delta_flops(4096, 8, 128, 128)
    per_token_head = 2 * (3 * 128 * 128 + 64 * 5 * 128)
    assert got == {"fwd": 4096 * 8 * per_token_head, "bwd": 3 * 4096 * 8 * per_token_head}
    monkeypatch.setattr(gated_delta, "on_tpu", lambda: True)  # lowered for a TPU: the kernels are Mosaic calls
    args, w = _inputs(16)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a, chunk=8) * w), argnums=(0, 3))).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "phase_%s_%s" % CORE_SCOPE in text and "tpu_custom_call" in text
    for name in ("gated_delta_fwd", "gated_delta_bwd"):  # the solve is the kernels': no XLA solve is left
        assert name in text, name
    assert "triangular_solve" not in text and "gated_delta_head" not in text
    per_head = jax.jit(jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a, chunk=8) * w), argnums=(0, 3))).trace(
        *args[:3], args[3][..., 0], args[4]).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "phase_%s_%s" % CORE_SCOPE in per_head
    for name in ("gated_delta_head_fwd", "gated_delta_head_bwd"):  # a decay a head: its own pair, and no other
        assert name in per_head, name
    assert not re.search(r"gated_delta_(fwd|bwd)", per_head) and "triangular_solve" not in per_head
