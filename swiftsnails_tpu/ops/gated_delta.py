"""The gated delta rule with a decay per channel (Kimi Delta Attention,
arXiv:2510.26692), by chunks: the recurrent mixer beside
``ops/flash_attention.py``'s softmax.

Per head, with ``q_t, k_t`` of width K, ``v_t`` of width V, a log-decay
``g_t <= 0`` per channel (``alpha_t = exp(g_t)``) and a step ``beta_t``, from
``S_0 = 0 [K, V]``::

    S'_t = diag(alpha_t) S_(t-1)
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = scale * S_t^T q_t

:func:`recurrence` is that, token by token. :func:`gated_delta_rule` computes
the same by chunks of ``C`` tokens, sequential over chunks only. With ``G_r``
the running sum of ``g`` inside a chunk and ``S_0`` the state entering it::

    A_rj = beta_r sum_c k_rc k_jc exp(G_rc - G_jc)            (j < r)
    (I + A) [W | U] = diag(beta) [K * exp(G) | V]              (one solve)
    U~  = U - W S_0
    o_r = scale * (S_0^T (q_r * exp(G_r)) + sum_(j<=r) (sum_c q_rc k_jc exp(G_rc - G_jc)) u~_j)
    S_C = diag(exp(G_C)) S_0 + sum_j (k_j * exp(G_C - G_j)) u~_j^T

No exponent here is ever positive: ``exp(-G_j)`` alone overflows float32
under a fast decay, so the pairwise sums go by sub-blocks of ``SUB`` tokens:
inside a sub-block the differences ``G_rc - G_jc`` themselves are
exponentiated (elementwise, float32), between sub-blocks both sides are
scaled through ``G`` at the row block's first token (``exp(G_r - ref) *
exp(ref - G_j)``, each factor at most 1) and multiplied on the MXU.

What is float32: ``g``, its running sums, every exponential, the triangular
solve, the state. The operands of the matrix products are rounded to
``dtype`` and accumulated in float32, forward and backward.

Everything that is one chunk's alone (:func:`_chunk_parts`) runs for all
chunks at once; :func:`_carry` is what passes from chunk to chunk. The
backward pass keeps the inputs and one ``[K, V]`` state a chunk and head
(never one a token), computes the chunks' parts again and walks the chunks
backwards from the states entering them. It is XLA's throughout (named scope
``phase_kda_core``, which ``benchmark/metrics/kernel.kda_roofline.py`` reads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from swiftsnails_tpu.utils.profiling import part_scope

CHUNK = 64  # tokens a chunk
SUB = 16  # tokens a sub-block of the pairwise sums; CHUNK (or a smaller chunk) is a multiple
CORE_SCOPE = ("kda", "core")  # of ``utils/profiling.PARTS``: the named scope ``phase_kda_core``


def recurrence(q, k, v, g, beta, scale=None):
    """The definition, a token at a time: ``q, k, g [H, L, K]``, ``v [H, L,
    V]``, ``beta [H, L]`` -> ``o [H, L, V]``, float32 throughout."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, :, None] * s
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))[:, None, :]
        return s, scale * jnp.einsum("hkv,hk->hv", s, q_t)

    heads, _, width = q.shape
    by_token = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    _, o = jax.lax.scan(token, jnp.zeros((heads, width, v.shape[-1]), jnp.float32),
                        tuple(by_token(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _dot(spec, a, b, dtype):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), preferred_element_type=jnp.float32)


def _pairs(x, k, run, sub, dtype):
    """``sum_c x_rc k_jc exp(G_rc - G_jc)`` for ``j <= r``, 0 above the
    diagonal: ``x, k, run [..., C, K]`` (``run`` the running sum ``G``, never
    rising along ``C``) -> ``[..., C, C]``."""
    *lead, chunk, width = x.shape
    n = chunk // sub
    blocks = lambda a: a.reshape(*lead, n, sub, width)  # noqa: E731
    xb, kb, gb = blocks(x), blocks(k), blocks(run)
    ref = gb[..., :1, :]  # G at each sub-block's first token
    # between sub-blocks, through the row block's reference: [..., i, r, j-block, s]
    earlier = (jnp.arange(n)[:, None] > jnp.arange(n)[None, :])[:, :, None, None]
    down = jnp.exp(jnp.where(earlier, ref[..., :, None, :, :] - gb[..., None, :, :, :], -jnp.inf))
    across = _dot("...irc,...ijsc->...irjs", xb * jnp.exp(gb - ref), kb[..., None, :, :, :] * down, dtype)
    # inside a sub-block, the differences themselves: [..., i, r, s]
    low = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None]
    decay = jnp.exp(jnp.where(low, gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
    inside = jnp.sum(xb[..., :, None, :] * kb[..., None, :, :] * decay, axis=-1)
    own = jnp.eye(n, dtype=bool)[:, None, :, None]
    return jnp.where(own, inside[..., :, :, None, :], across).reshape(*lead, chunk, chunk)


def _chunk_parts(q, k, v, g, beta, scale, sub, dtype):
    """What a chunk needs of its own tokens alone, for every chunk at once
    (all ``[H, N, C, ...]``): ``q * exp(G) * scale``, the solve's ``W`` and
    ``U``, the pairwise query-key sums, ``k * exp(G_C - G)`` and ``exp(G_C)``."""
    run = jnp.cumsum(g, axis=-2)
    last, since_start = run[..., -1:, :], jnp.exp(run)
    k_in = k * since_start
    strictly = jnp.tril(jnp.ones((q.shape[-2],) * 2, bool), -1)
    a = jnp.where(strictly, _pairs(k, k, run, sub, dtype), 0.0) * beta[..., None]
    solved = jax.lax.linalg.triangular_solve(  # (I + A) X = beta [K exp(G) | V]; A's diagonal is not read
        a, beta[..., None] * jnp.concatenate([k_in, v], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    width = k.shape[-1]
    return (q * since_start * scale, solved[..., :width], solved[..., width:],
            _pairs(q, k, run, sub, dtype) * scale, k * jnp.exp(last - run), jnp.exp(last[..., 0, :]))


def _carry(dtype, state, parts):
    """One chunk, all heads: the state entering it ``[H, K, V]`` and its
    parts ``[H, C, ...]`` -> (the state leaving it, its outputs ``[H, C, V]``)."""
    q_in, w, u, qk, k_out, decay = parts
    fresh = u - _dot("hck,hkv->hcv", w, state, dtype)
    o = _dot("hck,hkv->hcv", q_in, state, dtype) + _dot("hcj,hjv->hcv", qk, fresh, dtype)
    return decay[..., None] * state + _dot("hck,hcv->hkv", k_out, fresh, dtype), o


def _chunked(a, chunk):
    return a.reshape(a.shape[0], a.shape[1] // chunk, chunk, *a.shape[2:])


def _by_chunk(parts):  # [H, N, ...] -> [N, H, ...]: the scan's order
    return tuple(jnp.moveaxis(p, 1, 0) for p in parts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rule(q, k, v, g, beta, chunk, sub, scale, dtype):
    return _rule_fwd(q, k, v, g, beta, chunk, sub, scale, dtype)[0]


def _rule_fwd(q, k, v, g, beta, chunk, sub, scale, dtype):
    with part_scope(*CORE_SCOPE):
        parts = _chunk_parts(*(_chunked(a, chunk) for a in (q, k, v, g, beta)), scale, sub, dtype)

        def step(state, part):
            left, o = _carry(dtype, state, part)
            return left, (o, state)

        start = jnp.zeros((q.shape[0], q.shape[-1], v.shape[-1]), jnp.float32)
        _, (o, entering) = jax.lax.scan(step, start, _by_chunk(parts))
        o = jnp.moveaxis(o, 0, 1).reshape(v.shape)
    return o, (q, k, v, g, beta, entering)


def _rule_bwd(chunk, sub, scale, dtype, res, do):
    q, k, v, g, beta, entering = res
    with part_scope(*CORE_SCOPE):
        parts, parts_vjp = jax.vjp(
            lambda *a: _chunk_parts(*(_chunked(x, chunk) for x in a), scale, sub, dtype), q, k, v, g, beta)

        def step(d_left, xs):  # a chunk again from the state entering it, last chunk first
            state, part, d_o = xs
            _, vjp = jax.vjp(functools.partial(_carry, dtype), state, part)
            return vjp((d_left, d_o))

        _, d_parts = jax.lax.scan(step, jnp.zeros_like(entering[0]),
                                  (entering, _by_chunk(parts), jnp.moveaxis(_chunked(do, chunk), 1, 0)),
                                  reverse=True)
        return parts_vjp(tuple(jnp.moveaxis(d, 0, 1) for d in d_parts))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK, scale=None, dtype=jnp.bfloat16):
    """``q, k, g [H, L, K]``, ``v [H, L, V]``, ``beta [H, L]`` -> ``o [H, L,
    V]`` float32: :func:`recurrence` by chunks of ``chunk`` tokens (``L`` a
    multiple of it). ``g`` must not be positive."""
    heads, seq, width = q.shape
    if seq % chunk:
        raise ValueError(f"{seq} tokens are no whole chunks of {chunk}")
    sub = min(SUB, chunk)
    if chunk % sub:
        raise ValueError(f"a chunk of {chunk} is no whole sub-blocks of {sub}")
    if k.shape != q.shape or g.shape != q.shape or v.shape[:2] != (heads, seq) or beta.shape != (heads, seq):
        raise ValueError("q, k, g [H, L, K], v [H, L, V], beta [H, L]")
    scale = width ** -0.5 if scale is None else float(scale)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    return _rule(f32(q), f32(k), f32(v), f32(g), f32(beta), int(chunk), sub, scale, jnp.dtype(dtype))


def gated_delta_flops(seq: int, heads: int, width: int, value_width: int, chunk: int = CHUNK) -> dict:
    """The matrix-product operations of one call: ``fwd`` (per token and head
    three products with the ``[K, V]`` state and, over the chunk's ``C``
    tokens, the two pairwise sums, the solve's ``W`` and ``U`` and the
    outputs' sum over ``u~``: ``2 (2 K V + V K + C (3 K + 2 V))``, the
    triangles counted as squares) and ``bwd`` (the chunks' parts and carries
    again, then two products for each)."""
    fwd = 2.0 * seq * heads * (3 * width * value_width + chunk * (3 * width + 2 * value_width))
    return {"fwd": fwd, "bwd": 3 * fwd}
