"""The chunked gated delta rule (``ops/gated_delta.py``) against its own
definition, the recurrence a token at a time, small and on the CPU: forward
and the gradients of all five inputs, for one chunk and for three, under
decays from next to none down to ``g = -20`` a token (``exp(-G_j)`` alone
would overflow float32 inside a chunk)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.ops.gated_delta import (
    CORE_SCOPE, _pairs, gated_delta_flops, gated_delta_rule, recurrence)


def _inputs(seq, heads=3, width=16, value_width=16, g_min=-20.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(jax.random.normal(ks[0], (heads, seq, width))), unit(jax.random.normal(ks[1], (heads, seq, width)))
    v = jax.random.normal(ks[2], (heads, seq, value_width))
    # log-uniform between -1e-3 and g_min: slow and fast channels side by side
    g = -jnp.exp(jax.random.uniform(ks[3], (heads, seq, width), minval=np.log(1e-3), maxval=np.log(-g_min)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (heads, seq)))  # (0, 2): negative eigenvalues allowed
    return (q, k, v, g, beta), jax.random.normal(ks[5], (heads, seq, value_width))


@pytest.mark.parametrize("seq,chunk", [(8, 8), (24, 8), (64, 32), (192, 64)],
                         ids=["one-chunk", "three-chunks", "two-sub-blocks", "chunk-64"])
def test_chunks_match_the_recurrence_forward_and_backward(seq, chunk):
    args, w = _inputs(seq)
    assert float(args[3].min()) < -15.0 and float(args[3].max()) > -0.01
    fast = lambda *a: gated_delta_rule(*a, chunk=chunk, dtype=jnp.float32)  # noqa: E731
    got, want = jax.jit(fast)(*args), jax.jit(recurrence)(*args)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-4)
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args)  # noqa: E731
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads(fast), grads(recurrence)):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-4, err_msg=name)


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
    """``sum_c x_rc k_jc exp(G_rc - G_jc)`` for ``j <= r``: against the
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
    got = _pairs(f32(x), f32(k), f32(run), 8, jnp.float32)
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


def test_operations_are_counted_and_the_core_is_scoped():
    got = gated_delta_flops(4096, 8, 128, 128)
    per_token_head = 2 * (3 * 128 * 128 + 64 * 5 * 128)
    assert got == {"fwd": 4096 * 8 * per_token_head, "bwd": 3 * 4096 * 8 * per_token_head}
    args, w = _inputs(16)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a, chunk=8) * w), argnums=(0, 3))).lower(
        *args).as_text(debug_info=True)
    assert "phase_%s_%s" % CORE_SCOPE in text and "triangular_solve" in text  # one solve a chunk, no inverse by powers
