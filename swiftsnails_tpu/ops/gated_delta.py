"""The gated delta rule by chunks, as Mosaic kernels, in two forms: a decay
per channel (Kimi Delta Attention, arXiv:2510.26692) and a decay per head
(Gated DeltaNet, arXiv:2412.06464). The recurrent mixer beside
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
under a fast decay.

*A decay per channel* (``g [H, L, K]``): the pairwise sums go by sub-blocks
of ``SUB`` tokens: inside a sub-block the differences ``G_rc - G_jc``
themselves are exponentiated (elementwise, float32), between sub-blocks both
sides are scaled through ``G`` at the row block's first token (``exp(G_r -
ref) * exp(ref - G_j)``, each factor at most 1) and multiplied on the MXU.

*A decay per head* (``g [H, L]`` or ``[H, L, 1]``): ``G_rc - G_jc`` is one
number a pair, so each pairwise sum is one product masked by the chunk's
``[C, C]`` decays ``D_rj = exp(G_r - G_j)`` (``j <= r``; 0 above the
diagonal): ``A = diag(beta) (K K^T * D)``, and ``Q K^T * D`` for the outputs.
The differences are summed directly, ``G_r - G_j = sum_(j < t <= r) g_t``,
one product with a triangle of ones, so none is a difference of two large
sums. The state decays by the scalar ``exp(G_C)``.

What is float32: ``g``, its running sums, every exponential, the triangular
solve, the state, and the pairwise sums inside a sub-block; in the per-head
form every pairwise sum and its gradients, each one product at float32
precision (rounding the pairs between sub-blocks would add a product at
``dtype`` beside it, not take one away). The operands of the other matrix
products (the per-channel pairwise sums between sub-blocks among them) are
rounded to ``dtype`` and accumulated in float32, forward and backward.

Two kernels a form, each over the grid (group of heads, chunk), a group's
chunks in order; a grid step holds a group's chunk in VMEM and nothing of a
chunk's own goes out to HBM:

* ``gated_delta_fwd`` / ``gated_delta_head_fwd`` compute the chunk's parts
  (:func:`_chunk` / :func:`_head_chunk`: the running sums, the pairwise
  sums, the solve by :func:`_inverse`), then carry the state, which stays in
  a VMEM scratch from chunk to chunk (:func:`_carry`), write ``o`` and keep
  the state entering each chunk for the backward pass.
* ``gated_delta_bwd`` / ``gated_delta_head_bwd`` walk the chunks last first
  from the states kept, compute each chunk's parts again, carry ``dS`` in
  VMEM and give the gradients of all five inputs: through the carry, through
  the solve (a product with the inverse's transpose) and through the
  pairwise sums (:func:`_channel_grads` / :func:`_head_grads`). Through the
  per-head decays ``g``'s gradient is one product of ``dD * D`` with the
  triangle again.

In the per-channel form the pairwise sums inside a sub-block are the work of
the VPU: there two heads' chunks lie side by side on the lanes, channels down
the sublanes (:func:`_pack`), so that a token's sum over its channels is a
sum down the sublanes, and a key ``d`` tokens back is a roll of the lanes; a
grid step holds an even number of heads. A per-head grid step holds any
number of heads, the most up to ``HEAD_STEP`` that divides the heads, so
nothing is padded. The solve (:func:`_inverse`) is float32: the unit-lower diagonal
blocks of ``SUB`` by forward substitution on the VPU, the blocks below joined
by products on the MXU at float32 precision, ``(I + M)^-1 = (I - M)(I +
M^2)`` for the strictly block-lower rest ``M``: each such product is six
passes of the MXU.

All of it stands under the caller's part scope (``scope``: by default
``phase_kda_core``, which ``benchmark/metrics/kernel.kda_roofline.py`` reads;
Gated DeltaNet's mixer passes ``phase_gdn_core``); besides the kernels that
holds only what XLA does at the call's edge (a head count made whole steps by
padding, ``beta``'s trailing axis). The form follows ``g``'s shape alone.
Off the chip the kernels run in interpret mode (``rowdma.on_tpu``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from swiftsnails_tpu.ops.rowdma import on_tpu
from swiftsnails_tpu.utils.profiling import part_scope

CHUNK = 64  # tokens a chunk
SUB = 16  # tokens a sub-block of the pairwise sums; CHUNK (or a smaller chunk) is a multiple
HEADS = 8  # heads a grid step at most: the walk's fixed cost a step is paid once for all of them
HEAD_STEP = 16  # heads a grid step at most in the per-head form, which needs no pairs: Olmo's 15 in one
CORE_SCOPE = ("kda", "core")  # of ``utils/profiling.PARTS``: the named scope ``phase_kda_core``, the default

_VMEM_LIMIT = 64 * 1024 * 1024
_HIGHEST = jax.lax.Precision.HIGHEST
# batched over the leading (head) axis: a @ b, a @ b.T, a.T @ b
_NN = (((2,), (1,)), ((0,), (0,)))
_NT = (((2,), (2,)), ((0,), (0,)))
_TN = (((1,), (1,)), ((0,), (0,)))


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


def _dot(a, b, dims, dtype):
    """A product a head, operands rounded to ``dtype``, float32 sums (float32
    operands at float32 precision)."""
    precision = _HIGHEST if jnp.dtype(dtype) == jnp.float32 else None
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _exact(a, b, dims):
    """A product a head of float32 operands at float32 precision: the running
    sums and the solve."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _ones_where(mask):
    return jnp.where(mask, 1.0, 0.0).astype(jnp.float32)


def _pack(x):
    """``[h, C, W]`` -> ``[h / 2, W, 2 C]``: two heads' tokens side by side on
    the lanes, the width down the sublanes."""
    h, c, w = x.shape
    return jnp.swapaxes(x.reshape(h // 2, 2 * c, w), 1, 2)


def _unpack(x):
    """:func:`_pack`'s inverse."""
    p, w, c2 = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(2 * p, c2 // 2, w)


class _Walk(NamedTuple):
    """The inside sub-block walk's view of a chunk (:func:`_pack`'s layout):
    for ``d`` tokens back, the keys ``d`` back and the decay between."""

    kT: jax.Array  # [h / 2, K, 2 C]
    runT: jax.Array
    sub: int

    def back(self, d):
        """(the keys ``d`` tokens back, ``exp(G_r - G_(r-d))``, 0 where the
        key lies in another sub-block; ``None`` for ``d = 0``)."""
        if not d:
            return self.kT, None
        lane = _iota(self.kT.shape, 2) % self.sub
        rolled = pltpu.roll(self.runT, d, 2)
        return pltpu.roll(self.kT, d, 2), jnp.exp(jnp.where(lane >= d, self.runT - rolled, -jnp.inf))

    def diagonal(self, d):
        """Where in ``[h / 2, C, 2 C]`` (a pair matrix's transpose, packed)
        the pair (token ``r``, token ``r - d``) lies."""
        c2 = self.kT.shape[2]
        shape = (1, c2 // 2, c2)
        return _iota(shape, 1) == _iota(shape, 2) % (c2 // 2) - d


class _Across(NamedTuple):
    """The pairs between sub-blocks: the rows of block ``i`` against the keys
    before it, for every ``i > 0`` at once (``(n - 1) K`` wide)."""

    f: jax.Array  # exp(G_r - ref(r)) [h, C, K]
    keys: jax.Array  # [k exp(ref_i - G) for i > 0] [h, C, (n - 1) K], 0 at and after block i
    decays: tuple  # exp(ref_i - G), 0 at and after block i, each [h, C, K]
    blocks: tuple  # the rows of block i, each [1, C, K]

    def rows(self, x):
        """``x exp(G - ref)``, block ``i``'s rows in the ``i``-th strip."""
        scaled = x * self.f
        return jnp.concatenate([jnp.where(b, scaled, 0.0) for b in self.blocks], axis=2)


def _across(k, run, sub):
    h, c, w = k.shape
    rows = _iota((1, c, w), 1)
    firsts = [run[:, i * sub:i * sub + 1, :] for i in range(c // sub)]
    ref = jnp.concatenate([jnp.broadcast_to(f, (h, sub, w)) for f in firsts], axis=1)
    decays = tuple(jnp.exp(jnp.where(rows < i * sub, f - run, -jnp.inf)) for i, f in enumerate(firsts) if i)
    return _Across(jnp.exp(run - ref), jnp.concatenate([k * e for e in decays], axis=2), decays,
                   tuple(rows // sub == i for i in range(1, c // sub)))


def _pairs(xs, k, run, sub, dtype):
    """``sum_c x_rc k_jc exp(G_rc - G_jc)`` for ``j <= r``, 0 above the
    diagonal, for each ``x`` of ``xs``: ``x, k, run [h, C, K]`` (``h`` even,
    ``run`` the running sum ``G``, never rising along ``C``) -> ``[h, C, C]``
    each; with the walk and the strips, which the backward pass reads."""
    h, c, _ = k.shape
    walk = _Walk(_pack(k), _pack(run), sub)
    xT = [_pack(x) for x in xs]
    inside = [jnp.zeros((h // 2, c, 2 * c), jnp.float32) for _ in xs]
    for d in range(walk.sub):
        keys, decay = walk.back(d)
        if decay is not None:
            keys = keys * decay
        at = walk.diagonal(d)
        inside = [jnp.where(at, jnp.sum(x * keys, axis=1, keepdims=True), a) for x, a in zip(xT, inside)]
    sums = [_unpack(a) for a in inside]
    across = None
    if c > sub:
        across = _across(k, run, sub)
        sums = [s + _dot(across.rows(x), across.keys, _NT, dtype) for x, s in zip(xs, sums)]
    return sums, walk, xT, across


def _pairs_bwd(d_sums, xs, k, walk, xT, across, dtype):
    """The gradients of :func:`_pairs`' sums ``d_sums`` (each ``[h, C, C]``,
    0 where no pair is) -> (the gradient of each ``x``, of ``k``, of ``run``).
    The reference token of a strip cancels out of every pair, and is held."""
    d_xT = [jnp.zeros_like(x) for x in xT]
    d_kT, d_runT = jnp.zeros_like(walk.kT), jnp.zeros_like(walk.kT)
    lanes = walk.kT.shape[2]
    packed = [_pack(d) for d in d_sums]
    for d in range(walk.sub):
        at = walk.diagonal(d)
        weights = [jnp.sum(jnp.where(at, p, 0.0), axis=1, keepdims=True) for p in packed]  # d_sum[r, r - d]
        keys, decay = walk.back(d)
        row_side = keys if decay is None else keys * decay
        d_xT = [dx + w * row_side for dx, w in zip(d_xT, weights)]
        key_side = sum(w * x for w, x in zip(weights, xT))
        if decay is None:
            d_kT = d_kT + key_side
            continue
        key_side = key_side * decay
        term = key_side * keys
        d_kT = d_kT + pltpu.roll(key_side, lanes - d, 2)
        d_runT = d_runT + term - pltpu.roll(term, lanes - d, 2)
    d_xs = [_unpack(dx) for dx in d_xT]
    d_k, d_run = _unpack(d_kT), _unpack(d_runT)
    if across is not None:
        d_keys, w = 0.0, k.shape[2]
        for i, (d, x) in enumerate(zip(d_sums, xs)):
            strips = _dot(d, across.keys, _NN, dtype)
            d_keys = d_keys + _dot(d, across.rows(x), _TN, dtype)
            d_scaled = sum(jnp.where(b, strips[..., j * w:(j + 1) * w], 0.0) for j, b in enumerate(across.blocks))
            d_xs[i] = d_xs[i] + d_scaled * across.f
            d_run = d_run + d_scaled * x * across.f
        for j, e in enumerate(across.decays):
            d_e = d_keys[..., j * w:(j + 1) * w] * e
            d_k = d_k + d_e
            d_run = d_run - d_e * k
    return d_xs, d_k, d_run


def _power_inverse(n, order, eye):
    """``(I + n)^-1`` for ``n^order = 0``: ``(I - n)(I + n^2)(I + n^4)...``."""
    out, power, span = eye - n, n, 2
    while span < order:
        power = _exact(power, power, _NN)
        out = _exact(out, eye + power, _NN)
        span *= 2
    return out


def _inverse(a, sub):
    """``(I + a)^-1`` for ``a [h, C, C]`` strictly lower, float32: the
    diagonal blocks of ``sub`` by forward substitution, a row of every block
    a step on the VPU; the blocks below them by products on the MXU: with
    ``N`` the diagonal blocks of ``a`` and ``M = (I + N)^-1 (a - N)``,
    strictly block-lower, ``(I + a)^-1 = (I + M)^-1 (I + N)^-1``."""
    h, c, _ = a.shape
    rows, cols = _iota((1, c, c), 1), _iota((1, c, c), 2)
    eye = _ones_where(rows == cols)
    own = jnp.where(rows // sub == cols // sub, a, 0.0)
    inv = jnp.broadcast_to(eye, (h, c, c))
    for j in range(sub - 1):  # row j of each block is final: take it out of the rows below it in the block
        inv = inv - jnp.concatenate([own[:, b:b + sub, b + j:b + j + 1] * inv[:, b + j:b + j + 1, :]
                                     for b in range(0, c, sub)], axis=1)
    if c > sub:
        inv = _exact(_power_inverse(_exact(inv, a - own, _NN), c // sub, eye), inv, _NN)
    return inv


class _Parts(NamedTuple):
    """What a chunk needs of its own tokens alone (``[h, C, ...]``)."""

    run: jax.Array  # G
    grow: jax.Array  # exp(G)
    q_in: jax.Array  # q exp(G) scale
    k_in: jax.Array  # k exp(G)
    k_out: jax.Array  # k exp(G_C - G)
    decay: jax.Array  # exp(G_C) [h, 1, K]
    akk: jax.Array  # the keys' pairwise sums below the diagonal
    aqk: jax.Array  # the queries' against the keys, at and below, times scale
    inv: jax.Array  # (I + diag(beta) akk)^-1
    x: jax.Array  # [W | U]
    pairs: tuple  # what the backward pass of the sums reads: (walk, packed rows, strips); a head's decays D


def _chunk(q, k, v, g, beta, scale, sub, dtype):
    """A chunk's parts for ``h`` heads: ``q, k, g [h, C, K]``, ``v [h, C, V]``,
    ``beta [h, C, 1]``."""
    c = q.shape[1]
    rows, cols = _iota((1, c, c), 1), _iota((1, c, c), 2)
    lower = jnp.broadcast_to(_ones_where(cols <= rows), (q.shape[0], c, c))
    run = _exact(lower, g, _NN)  # the running sums, at float32 precision
    last, grow = run[:, c - 1:, :], jnp.exp(run)
    k_in = k * grow
    (akk, aqk), walk, xT, across = _pairs((k, q), k, run, sub, dtype)
    akk = jnp.where(cols < rows, akk, 0.0)
    inv = _inverse(beta * akk, sub)
    x = _exact(inv, beta * jnp.concatenate([k_in, v], axis=2), _NN)
    return _Parts(run, grow, q * grow * scale, k_in, k * jnp.exp(last - run), jnp.exp(last),
                  akk, aqk * scale, inv, x, (walk, xT, across))


def _head_chunk(q, k, v, g, beta, scale, sub, dtype):
    """A chunk's parts for ``h`` heads with a decay a head: ``q, k [h, C,
    K]``, ``v [h, C, V]``, ``g, beta [h, C, 1]``. The pairwise sums are one
    float32 product: the keys' (rows) and the queries' (rows ``C`` on)
    against the keys, times the decays ``D``."""
    h, c, _ = q.shape
    rows, cols = _iota((1, c, c), 1), _iota((1, c, c), 2)
    lower = jnp.broadcast_to(_ones_where(cols <= rows), (h, c, c))
    gaps = _exact(lower, jnp.where(rows > cols, jnp.broadcast_to(g, (h, c, c)), 0.0), _NN)  # G_r - G_j
    run = gaps[:, :, :1] + g[:, :1, :]  # G [h, C, 1]
    decays = jnp.exp(jnp.where(cols <= rows, gaps, -jnp.inf))
    kq = jnp.concatenate([k, q], axis=1)
    both = jnp.concatenate([decays] * 2, axis=1) * _exact(kq, k, _NT)
    akk, aqk = jnp.where(cols < rows, both[:, :c], 0.0), both[:, c:]
    last, grow = run[:, c - 1:, :], jnp.exp(run)
    k_in = k * grow
    inv = _inverse(beta * akk, sub)
    x = _exact(inv, beta * jnp.concatenate([k_in, v], axis=2), _NN)
    return _Parts(run, grow, q * grow * scale, k_in, k * jnp.exp(last - run),
                  jnp.exp(jnp.broadcast_to(last, (h, 1, q.shape[2]))), akk, aqk * scale, inv, x, decays)


def _carry(p, state, dtype):
    """One chunk: the state entering it (transposed, ``[h, V, K]``) -> (its
    outputs ``[h, C, V]``, the state leaving it)."""
    width = p.k_in.shape[2]
    fresh = p.x[..., width:] - _dot(p.x[..., :width], state, _NT, dtype)
    o = _dot(p.q_in, state, _NT, dtype) + _dot(p.aqk, fresh, _NN, dtype)
    return o, state * p.decay + _dot(fresh, p.k_out, _TN, dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, entering_ref, state_ref, *, parts, scale, sub, dtype):
    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    state = state_ref[...]
    entering_ref[...] = state
    p = parts(q_ref[...], k_ref[...], v_ref[...], g_ref[...], b_ref[...], scale, sub, dtype)
    o, state_ref[...] = _carry(p, state, dtype)
    o_ref[...] = o


class _Grads(NamedTuple):
    """The backward pass's gradients of a chunk's parts that the pairwise
    sums and the decays take over (:func:`_channel_grads`, :func:`_head_grads`)."""

    akk: jax.Array  # of the keys' pairwise sums
    aqk: jax.Array  # of the queries' against the keys (``_Parts.aqk``, scale in it)
    q_in: jax.Array
    k_in: jax.Array
    k_out: jax.Array
    decay: jax.Array  # [h, 1, K]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, entering_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, d_state_ref, *, parts, grads, scale, sub, dtype):
    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state_ref[...] = jnp.zeros_like(d_state_ref)

    q, k, v, g, beta = q_ref[...], k_ref[...], v_ref[...], g_ref[...], b_ref[...]
    state, d_leaving, do = entering_ref[...], d_state_ref[...], do_ref[...]
    p = parts(q, k, v, g, beta, scale, sub, dtype)
    c, width = q.shape[1], q.shape[2]
    w = p.x[..., :width]
    # the carry, backwards
    fresh = p.x[..., width:] - _dot(w, state, _NT, dtype)
    d_fresh = _dot(p.aqk, do, _TN, dtype) + _dot(p.k_out, d_leaving, _NT, dtype)
    rows, cols = _iota((1, c, c), 1), _iota((1, c, c), 2)
    d_aqk = jnp.where(cols <= rows, _dot(do, fresh, _NT, dtype), 0.0)
    d_q_in = _dot(do, state, _NN, dtype)
    d_k_out = _dot(fresh, d_leaving, _NN, dtype)
    d_decay = jnp.sum(state * d_leaving, axis=1, keepdims=True)
    d_state_ref[...] = (d_leaving * p.decay + _dot(do, p.q_in, _TN, dtype)
                        - _dot(d_fresh, w, _TN, dtype))
    # the solve, backwards: (I + A)^T dB = [dW | dU]
    d_rhs = _exact(p.inv, jnp.concatenate([-_dot(d_fresh, state, _NN, dtype), d_fresh], axis=2), _TN)
    d_a = -jnp.where(cols < rows, _exact(d_rhs, p.x, _NT), 0.0)
    db_ref[...] = (jnp.sum(d_a * p.akk, axis=2, keepdims=True)
                   + jnp.sum(d_rhs * jnp.concatenate([p.k_in, v], axis=2), axis=2, keepdims=True))
    d_rhs = beta * d_rhs
    dv_ref[...] = d_rhs[..., width:]
    d_k_in = d_rhs[..., :width]
    # the parts, backwards
    grads(p, q, k, _Grads(beta * d_a, d_aqk, d_q_in, d_k_in, d_k_out, d_decay), rows, cols, scale, sub, dtype,
          dq_ref, dk_ref, dg_ref)


def _channel_grads(p, q, k, d, rows, cols, scale, sub, dtype, dq_ref, dk_ref, dg_ref):
    """A decay a channel: through the walk and the strips of :func:`_pairs`."""
    c, width = q.shape[1], q.shape[2]
    (d_k_pairs, d_q_pairs), d_k, d_run = _pairs_bwd((d.akk, d.aqk * scale), (k, q), k, *p.pairs, dtype)
    d_run = d_run + d.q_in * p.q_in + d.k_in * p.k_in - d.k_out * p.k_out
    d_last = jnp.sum(d.k_out * p.k_out, axis=1, keepdims=True) + d.decay * p.decay
    d_run = d_run + jnp.where(_iota((1, c, width), 1) == c - 1, d_last, 0.0)
    dq_ref[...] = d_q_pairs + d.q_in * p.grow * scale
    dk_ref[...] = d_k + d_k_pairs + d.k_in * p.grow + d.k_out * jnp.exp(p.run[:, c - 1:, :] - p.run)
    after = jnp.broadcast_to(_ones_where(cols >= rows), (q.shape[0], c, c))
    dg_ref[...] = _exact(after, d_run, _NN)  # the sums from each token to the chunk's end


def _head_grads(p, q, k, d, rows, cols, scale, sub, dtype, dq_ref, dk_ref, dg_ref):
    """A decay a head: through the float32 product of :func:`_head_chunk`
    and the decays ``D``. ``g_t`` lies in ``G_r - G_j`` for ``j < t <= r``
    and in ``G_r`` for ``t <= r``: with ``E = dD * D`` and ``dG`` the
    gradient of the running sums themselves, ``dg_t = sum_(r >= t) (sum_(j <
    t) E_rj + dG_r)``, one product with the triangle: ``E``'s last column
    (no gap has it) holds ``dG``."""
    c = q.shape[1]
    d_pairs = jnp.concatenate([d.akk, d.aqk * scale], axis=1) * jnp.concatenate([p.pairs] * 2, axis=1)
    by_row = _exact(d_pairs, k, _NN)  # [dKK K; dQK K]
    by_key = _exact(d_pairs, jnp.concatenate([k, q], axis=1), _TN)  # dKK^T K + dQK^T Q
    spread = d.akk * p.akk + d.aqk * p.aqk  # E
    leaving = jnp.sum(d.k_out * p.k_out, axis=2, keepdims=True)
    d_run = jnp.sum(d.q_in * p.q_in + d.k_in * p.k_in, axis=2, keepdims=True) - leaving
    d_last = jnp.sum(leaving, axis=1, keepdims=True) + jnp.sum(d.decay * p.decay, axis=2, keepdims=True)
    d_run = d_run + jnp.where(_iota((1, c, 1), 1) == c - 1, d_last, 0.0)
    after = jnp.broadcast_to(_ones_where(cols >= rows), (q.shape[0], c, c))
    sums = _exact(after, jnp.where(cols == c - 1, d_run, spread), _NN)
    dg_ref[...] = jnp.sum(jnp.where((cols < rows) | (cols == c - 1), sums, 0.0), axis=2, keepdims=True)
    dq_ref[...] = by_row[:, c:] + d.q_in * p.grow * scale
    dk_ref[...] = by_row[:, :c] + by_key + d.k_in * p.grow + d.k_out * jnp.exp(p.run[:, c - 1:, :] - p.run)


def _heads(h):
    """(heads a grid step, the heads padded to whole steps): an even number,
    two heads to the lanes of the inside walk."""
    step = min(HEADS, h + h % 2)
    return step, -(-h // step) * step


def _head_steps(h):
    """(heads a grid step, the heads) of the per-head form: the most up to
    ``HEAD_STEP`` that divide ``h``, so that no head is padded."""
    return max(n for n in range(1, min(HEAD_STEP, h) + 1) if h % n == 0), h


class _Form(NamedTuple):
    """A form of the kernels: their names, a chunk's parts, the gradients
    through them, the grouping of heads."""

    fwd: str
    bwd: str
    parts: object
    grads: object
    heads: object


_CHANNEL = _Form("gated_delta_fwd", "gated_delta_bwd", _chunk, _channel_grads, _heads)
_HEAD = _Form("gated_delta_head_fwd", "gated_delta_head_bwd", _head_chunk, _head_grads, _head_steps)


def _form(g):
    """The form ``g [H, L, K]`` or ``[H, L, 1]`` (a decay a head) takes."""
    return _HEAD if g.shape[2] == 1 else _CHANNEL


def _pad(a, heads):
    return a if a.shape[0] == heads else jnp.pad(a, [(0, heads - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def _call(kernel, name, grid, interpret, in_specs, out_specs, out_shape, scratch):
    params = {"interpret": True} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)}
    return pl.pallas_call(kernel, grid=grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
                          scratch_shapes=[pltpu.VMEM(scratch, jnp.float32)], name=name, **params)


def _specs(step, chunk, n, reverse=False):
    """Block specs of a grid (group of heads, chunk): ``tokens(width)`` the
    step's chunk, ``state`` its entering state; chunks last first with
    ``reverse``."""
    at = (lambda j: n - 1 - j) if reverse else (lambda j: j)  # noqa: E731
    tokens = lambda width: pl.BlockSpec((step, chunk, width), lambda i, j: (i, at(j), 0))  # noqa: E731
    return tokens, lambda value, width: pl.BlockSpec((step, None, value, width), lambda i, j: (i, at(j), 0, 0))


def _forward(q, k, v, g, beta, chunk, sub, scale, dtype, interpret):
    h, seq, width = q.shape
    value, decays = v.shape[2], g.shape[2]
    form = _form(g)
    step, heads = form.heads(h)
    n = seq // chunk
    tokens, state = _specs(step, chunk, n)
    f32 = jnp.float32
    o, entering = _call(
        functools.partial(_fwd_kernel, parts=form.parts, scale=scale, sub=sub, dtype=dtype), form.fwd,
        (heads // step, n), interpret,
        [tokens(width), tokens(width), tokens(value), tokens(decays), tokens(1)],
        [tokens(value), state(value, width)],
        [jax.ShapeDtypeStruct((heads, seq, value), f32), jax.ShapeDtypeStruct((heads, n, value, width), f32)],
        (step, value, width),
    )(*(_pad(a, heads) for a in (q, k, v, g, beta[..., None])))
    return o[:h], entering


def _backward(q, k, v, g, beta, entering, do, chunk, sub, scale, dtype, interpret):
    h, seq, width = q.shape
    value, decays = v.shape[2], g.shape[2]
    form = _form(g)
    step, heads = form.heads(h)
    n = seq // chunk
    tokens, state = _specs(step, chunk, n, reverse=True)
    f32 = jnp.float32
    like = lambda w: jax.ShapeDtypeStruct((heads, seq, w), f32)  # noqa: E731
    grads = _call(
        functools.partial(_bwd_kernel, parts=form.parts, grads=form.grads, scale=scale, sub=sub, dtype=dtype),
        form.bwd, (heads // step, n), interpret,
        [tokens(width), tokens(width), tokens(value), tokens(decays), tokens(1), state(value, width),
         tokens(value)],
        [tokens(width), tokens(width), tokens(value), tokens(decays), tokens(1)],
        [like(width), like(width), like(value), like(decays), like(1)],
        (step, value, width),
    )(*(_pad(a, heads) for a in (q, k, v, g, beta[..., None])), entering, _pad(do, heads))
    dq, dk, dv, dg, dbeta = (a[:h] for a in grads)
    return dq, dk, dv, dg, dbeta[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _rule(q, k, v, g, beta, chunk, sub, scale, dtype, interpret, scope):
    return _rule_fwd(q, k, v, g, beta, chunk, sub, scale, dtype, interpret, scope)[0]


def _rule_fwd(q, k, v, g, beta, chunk, sub, scale, dtype, interpret, scope):
    with part_scope(*scope):
        o, entering = _forward(q, k, v, g, beta, chunk, sub, scale, dtype, interpret)
    return o, (q, k, v, g, beta, entering)


def _rule_bwd(chunk, sub, scale, dtype, interpret, scope, res, do):
    with part_scope(*scope):
        return _backward(*res, do, chunk, sub, scale, dtype, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK, scale=None, dtype=jnp.bfloat16, scope=CORE_SCOPE):
    """``q, k [H, L, K]``, ``v [H, L, V]``, ``beta [H, L]``, ``g [H, L, K]``
    (a decay a channel) or ``[H, L]`` / ``[H, L, 1]`` (a decay a head, which
    takes the per-head kernels) -> ``o [H, L, V]`` float32:
    :func:`recurrence` by chunks of ``chunk`` tokens (``L`` a multiple of
    it), with ``g`` broadcast over the channels. ``g`` must not be positive.
    ``scope``: the pair of ``utils/profiling.PARTS`` the call stands under."""
    heads, seq, width = q.shape
    if seq % chunk:
        raise ValueError(f"{seq} tokens are no whole chunks of {chunk}")
    sub = min(SUB, chunk)
    if chunk % sub:
        raise ValueError(f"a chunk of {chunk} is no whole sub-blocks of {sub}")
    if g.shape == (heads, seq):
        g = g[..., None]
    if (k.shape != q.shape or g.shape not in (q.shape, (heads, seq, 1)) or v.shape[:2] != (heads, seq)
            or beta.shape != (heads, seq)):
        raise ValueError("q, k [H, L, K], g [H, L, K] or [H, L(, 1)], v [H, L, V], beta [H, L]")
    scale = width ** -0.5 if scale is None else float(scale)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    return _rule(f32(q), f32(k), f32(v), f32(g), f32(beta), int(chunk), sub, scale, jnp.dtype(dtype),
                 not on_tpu(), tuple(scope))


def gated_delta_flops(seq: int, heads: int, width: int, value_width: int, chunk: int = CHUNK) -> dict:
    """The matrix-product operations of one call: ``fwd`` (per token and head
    three products with the ``[K, V]`` state and, over the chunk's ``C``
    tokens, the two pairwise sums, the solve's ``W`` and ``U`` and the
    outputs' sum over ``u~``: ``2 (2 K V + V K + C (3 K + 2 V))``, the
    triangles counted as squares) and ``bwd`` (the chunks' parts and carries
    again, then two products for each)."""
    fwd = 2.0 * seq * heads * (3 * width * value_width + chunk * (3 * width + 2 * value_width))
    return {"fwd": fwd, "bwd": 3 * fwd}
