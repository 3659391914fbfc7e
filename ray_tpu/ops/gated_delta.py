"""The gated delta rule: a linear-attention layer's state, made by a scan
over the sequence.

Per head a state ``S`` of ``[Dk, Dv]`` in float32 that every token decays,
corrects and reads. With ``q_t``, ``k_t`` (L2-normalised by the caller, the
query scaled), ``v_t``, a log-decay ``g_t <= 0`` and a step ``beta_t`` in
(0, 1), from the state before the token::

    S'  = exp(g_t) S_{t-1}
    d_t = beta_t (v_t - S'^T k_t)          # what the state gets wrong at k_t
    S_t = S' + k_t d_t^T
    o_t = S_t^T q_t

Three forms of it:

- :func:`gated_delta_recurrence`: those four lines under a ``lax.scan`` over
  the positions. What the tests hold the other two to; never a program's
  path (a prefill chunk of 512 would be 512 dependent steps of a few
  microseconds of work each).
- :func:`gated_delta_chunk`: a run of positions of one sequence, in
  sub-chunks of ``SUB`` positions. Inside a sub-chunk that starts from
  ``S_0``, with ``G_i`` the running sum of ``g`` and ``A_ij = beta_i
  exp(G_i - G_j) (k_i . k_j)`` for ``j < i``, the corrections of all its
  positions solve one triangular system::

      (I + A) D = beta (V - diag(exp G) K S_0)

  ``A`` is strictly lower, and ``T = (I + A)^-1`` is made by halves
  (:func:`unit_lower_inverse`: six levels of small products at 64, of all
  heads and sub-chunks at once). ``T`` does not depend on the state, so ``U = T (beta V)`` and ``W = T
  (beta exp(G) K)`` are made for every sub-chunk at once, and the walk from
  sub-chunk to sub-chunk is three products::

      D   = U - W S_0
      O   = (exp(G) Q) S_0 + (tril(Q K^T) exp(G_i - G_j)) D
      S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T D

  Every exponent is a difference ``G_i - G_j`` with ``j <= i``, at most 0:
  nothing overflows however long the sub-chunk's decay.
- :func:`gated_delta_step`: one position of every slot, the recurrence's
  single step on ``[slots, heads]`` states at once. The state is read by
  one pass that gives both ``S^T k`` and ``S^T q`` (``o_t = exp(g) S^T q +
  (k . q) d``, so the new state need not be read again) and written by one.

A position with ``g = 0`` and ``beta = 0`` changes no state: a caller marks
so the rows of a padded chunk past the prompt's end and the slots of a step
that do not decode.

All three compute in float32 whatever they are given, and their products at
``PRECISION``, true float32: a TPU's default float32 product is one bfloat16
pass, which left the chunk form 8e-4 off the recurrence on outputs and 4e-3
on states, and the rule's work is small beside the layer's projections.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

# Positions of a sub-chunk: the triangular system's size. 64 is the
# published kernels' choice and half an MXU's side.
SUB = 64
PRECISION = lax.Precision.HIGHEST
F32 = jnp.float32


def _mm(a, b):
    return jnp.matmul(a, b, precision=PRECISION)


def gated_delta_recurrence(q, k, v, g, beta, state):
    """The rule, token by token. q, k: [T, H, Dk]; v: [T, H, Dv]; g, beta:
    [T, H]; state: [H, Dk, Dv] float32. Returns (o [T, H, Dv] float32,
    state)."""
    def token(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = jnp.exp(g_t)[:, None, None] * s
        d = b_t[:, None] * (v_t - jnp.einsum(
            "hk,hkv->hv", k_t, s, precision=PRECISION))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=PRECISION)

    state, o = lax.scan(token, state.astype(F32),
                        tuple(a.astype(F32) for a in (q, k, v, g, beta)))
    return o, state


def unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` [..., n, n] strictly lower triangular (n a
    power of two), by halves: with ``I + a = [[P, 0], [C, Q]]`` the inverse
    is ``[[P^-1, 0], [-Q^-1 C P^-1, Q^-1]]``, from blocks of one row (whose
    inverse is 1) up, log2(n) levels of two products each. This is forward
    substitution a block at a time and as stable as a row at a time.

    Not the product ``(I - a)(I + a^2)(I + a^4) ...``, equal in exact
    arithmetic: where neighbouring keys are alike (``k_i . k_j`` near 1) the
    powers of ``a`` grow like binomial coefficients before they cancel, and
    in float32 the served logits moved by 1e-3 where this form and the
    recurrence agree to 1e-5 (tests/test_gated_delta.py)."""
    n = a.shape[-1]
    lead = a.shape[:-2]
    inv = jnp.ones(lead + (n, 1, 1), a.dtype)
    s = 1
    while s < n:
        m = n // (2 * s)
        pairs = a.reshape(lead + (m, 2 * s, m, 2 * s))
        # C of every pair: the block under the diagonal of the m diagonal
        # blocks of 2s.
        c = jnp.stack([pairs[..., j, s:, j, :s] for j in range(m)], axis=-3)
        inv = inv.reshape(lead + (m, 2, s, s))
        p, q = inv[..., 0, :, :], inv[..., 1, :, :]
        low = -_mm(q, _mm(c, p))
        inv = jnp.concatenate(
            [jnp.concatenate([p, jnp.zeros_like(p)], axis=-1),
             jnp.concatenate([low, q], axis=-1)], axis=-2)
        s *= 2
    return inv.reshape(lead + (n, n))


def gated_delta_chunk(q, k, v, g, beta, state):
    """A run of positions of one sequence, chunked. q, k: [T, H, Dk]; v:
    [T, H, Dv]; g, beta: [T, H]; state: [H, Dk, Dv] float32, the state
    before the first position. Returns (o [T, H, Dv] float32, the state
    after the last position). T is any length: the run is padded to whole
    sub-chunks with positions that change nothing."""
    t, h, _ = q.shape
    pad = -t % SUB
    n = (t + pad) // SUB

    def heads_first(a):
        a = jnp.pad(a.astype(F32), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        a = a.reshape(n, SUB, *a.shape[1:])
        return jnp.moveaxis(a, 2, 0)              # [H, n, SUB, ...]

    q, k, v, g, beta = (heads_first(a) for a in (q, k, v, g, beta))
    big = jnp.cumsum(g, axis=-1)                              # G_i
    rows = jnp.arange(SUB)
    upto = rows[:, None] >= rows[None, :]                     # j <= i
    # exp(G_i - G_j) where j <= i: every exponent at most 0.
    decay = jnp.where(upto, jnp.exp(jnp.where(
        upto, big[..., :, None] - big[..., None, :], 0.0)), 0.0)
    kt = jnp.swapaxes(k, -1, -2)
    a = jnp.where(rows[:, None] > rows[None, :],
                  beta[..., None] * decay * _mm(k, kt), 0.0)
    grow = jnp.exp(big)[..., None]                            # exp(G_i)
    rhs_v, rhs_k = beta[..., None] * v, beta[..., None] * grow * k
    inv = unit_lower_inverse(a)
    u, w = _mm(inv, rhs_v), _mm(inv, rhs_k)
    within = decay * _mm(q, kt)                   # zero above the diagonal
    q_in = grow * q
    # exp(G_C - G_j) K, transposed for the state's update, and exp(G_C).
    k_out = jnp.swapaxes(jnp.exp(big[..., -1:] - big)[..., None] * k, -1, -2)
    whole = jnp.exp(big[..., -1])

    def sub_chunk(s, xs):
        u_n, w_n, within_n, q_n, k_n, whole_n = xs
        d = u_n - _mm(w_n, s)
        o = _mm(q_n, s) + _mm(within_n, d)
        return whole_n[:, None, None] * s + _mm(k_n, d), o

    state, o = lax.scan(
        sub_chunk, state.astype(F32),
        tuple(jnp.moveaxis(x, 1, 0)
              for x in (u, w, within, q_in, k_out, whole)))
    # o: [n, H, SUB, Dv] -> [T, H, Dv]
    o = jnp.moveaxis(o, 1, 2).reshape(n * SUB, h, -1)
    return o[:t], state


def gated_delta_step(q, k, v, g, beta, state):
    """One position of every slot. q, k: [B, H, Dk]; v: [B, H, Dv]; g,
    beta: [B, H]; state: [B, H, Dk, Dv] float32. Returns (o [B, H, Dv]
    float32, state). Sums over ``Dk`` and not products of ``[1, Dk]`` by
    ``[Dk, Dv]``: a matrix unit would load every state as its weights for
    one row."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    decay = jnp.exp(g)[..., None]
    # One pass over the state for both reads.
    sk = jnp.sum(state * k[..., None], axis=-2)               # S^T k
    sq = jnp.sum(state * q[..., None], axis=-2)               # S^T q
    d = beta[..., None] * (v - decay * sk)
    o = decay * sq + jnp.sum(k * q, axis=-1, keepdims=True) * d
    state = decay[..., None] * state + k[..., None] * d[..., None, :]
    return o, state
