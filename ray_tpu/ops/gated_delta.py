"""The gated delta rule: a linear-attention layer's state, made by a scan
over the sequence.

Per head a state ``S`` of ``[Dk, Dv]`` in float32 that every token decays,
corrects and reads. With ``q_t``, ``k_t`` (L2-normalised by the caller, the
query scaled), ``v_t``, a log-decay ``g_t <= 0`` and a step ``beta_t`` in
(0, 2), from the state before the token::

    S'  = exp(g_t) S_{t-1}
    d_t = beta_t (v_t - S'^T k_t)          # what the state gets wrong at k_t
    S_t = S' + k_t d_t^T
    o_t = S_t^T q_t

A step of ``I - beta_t k_t k_t^T`` on the decayed state: with unit keys its
eigenvalue along ``k_t`` is ``1 - beta_t``, in (0, 1) for a step in (0, 1)
(Qwen3-Next's ``sigmoid``) and in (-1, 1) for a step in (0, 2)
(Olmo-Hybrid's ``2 sigmoid``, ``linear_allow_neg_eigval``: a token may
overshoot and flip what the state holds along its key). Every form takes
either range: the recurrence multiplies by ``beta``, and the chunked form's
system ``I + A`` has ``beta`` as a factor of ``A``'s rows, all under the
diagonal, so it stays unit lower triangular whatever ``beta`` and its
inverse by substitution divides by nothing.

``q`` and ``k`` are a key head's, and a key head serves ``H // Hk`` value
heads in a row (two in Qwen3-Next): the recurrence and the chunked form take
them once a key head, the one-token step repeated a value head. Keys are
``Dk`` wide and values ``Dv``, any two numbers (128 and 128 in Qwen3-Next,
96 and 192 in Olmo-Hybrid); the kernels take whole 128-lane columns and the
jnp bodies any widths.

Four forms of it:

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
  (:func:`unit_lower_inverse`: forward substitution a block at a time, six
  levels at 64). From sub-chunk to sub-chunk::

      D   = T beta (V - diag(exp G) K S_0)
      O   = (exp(G) Q) S_0 + (tril(Q K^T) exp(G_i - G_j)) D
      S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T D

  Every exponent is a difference ``G_i - G_j`` with ``j <= i``, at most 0:
  nothing overflows however long the sub-chunk's decay. Two
  implementations, chosen by ``ops/kernels.kernel_backend()``: on a TPU a
  Pallas kernel (:func:`_chunk_kernel`) whose grid is (value heads, eight
  a step) by (sub-chunks), in which a sub-chunk's system, its inverse and
  the state stay in VMEM and the operands are read once, as 128-lane
  columns of the caller's ``[T, H * D]``; elsewhere, and as what the kernel
  is held to, :func:`gated_delta_chunk_reference` in plain jnp.
- :func:`gated_delta_chunk_batch` (``gated_delta_chunk`` on operands with a
  leading batch): the chunked form of a batch of whole sequences with a
  backward, what a model trains through. A ``jax.custom_vjp``: the forward
  walks chunks of ``TRAIN_CHUNK`` positions (eight sub-chunks) and keeps
  its operands and the state at each chunk's start; the backward walks the
  chunks in reverse, makes a chunk's systems and states again from the
  state it started from and runs their transposes, the inverse's as ``dA =
  -T^T dT T^T``: the same kind of recurrence run backwards, matrix
  products throughout. On a TPU the forward is the scalar chunk kernel in
  one call, the batch folded into the heads and keys and values
  zero-padded to whole 128-lane columns (96 and 192 become 128 and 256:
  exact, and 1.9 times the needed products), the kernel keeping the
  chunks' states as a third result; and the backward is a kernel too
  (:func:`_chunk_bwd_kernel`, one call, the same folding, padding and
  pairing): a chunk's sub-chunks walked forward from the kept state with
  their states, inverses and corrections kept in VMEM, then walked back,
  the gradient of the state in VMEM from the last chunk to the first and
  the operands' gradients written once as ``[T, H * D]`` columns.
  Elsewhere, and as what that kernel is held to, the jnp chunks in reverse
  under a scan (XLA's batched products at the operands' own widths).
- :func:`gated_delta_step`: one position of every slot, the recurrence's
  single step on a line of a state leaf ``[lines, slots, heads, Dk, Dv]``,
  in place. The update needs ``d_t`` and ``d_t`` a sum over the whole
  state, so the state is gone over twice; in plain jnp
  (:func:`gated_delta_step_reference`, what runs off a TPU and what the
  kernel is held to) that is two fusions, the state read from HBM twice
  and written once. On a TPU a Pallas kernel (:func:`_step_kernel`) takes
  the leaf whole, aliased to its result, and the line as a prefetched
  scalar: a grid step holds one slot's states (32 of 128 x 128, 2 MiB) in
  VMEM, both passes go over VMEM, and a state crosses HBM once each way;
  the blocks of other lines are not visited, so no caller slices a line
  out of a leaf or writes one back. Sums over ``Dk`` on the vector units
  and not products of ``[1, Dk]`` by ``[Dk, Dv]``: a matrix unit would load
  every state as its weights for one row. A state's rows lie on the
  sublanes, so a key, a query and a channel's decay are wanted as columns:
  the kernel lays a head's row over the sublanes and transposes the tile
  (handed over as ``[..., Dk, 1]`` they would pad to the states' own size
  in HBM). The kernel's time is its traffic's: with a copy for its body it
  takes the same 0.634 ms a line of 96 slots, 77% of the chip's 819 GB/s,
  which is what a read and a write together reach on a v5e by any route
  (PR 59).

A position with ``g = 0`` and ``beta = 0`` changes no state: a caller marks
so the rows of a padded chunk past the prompt's end and the slots of a step
that do not decode.

**A decay a key channel** (Kimi Delta Attention). ``g`` is ``[T, H]`` as
above or ``[T, H, Dk]``: ``S' = Diag(exp(g_t)) S_{t-1}``, a number a row of
the state. That is the general rule and the scalar one its case; every form
takes either. In the chunked form ``G_i`` is then a vector and the system's
matrix ``A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)``, a product of two
matrices only where the exponent is split into a factor on row ``i`` and a
factor on row ``j``, and ``exp(-G_j)`` alone overflows float32 after 18
tokens of a decay of -5. So rows are paired about a sum between theirs
(:func:`_channel_pairs`): inside blocks of ``BLOCK`` (16) positions about
``M``, the mean of the block's first and last running sums, ``exp(G_i - M)
exp(M - G_j)``, both exponents within ``15 |floor| / 2`` of 0; a row with a
row of an earlier block about the later block's ``M``, the second exponent
at most 0. The caller states the floor of its gate (``g_floor``: a token's
``g`` is never under it) and the chunked form refuses, at trace time, a
floor at which a block's whole decay ``exp(BLOCK |floor|)`` would not fit
float32, or no floor at all: nothing is clamped, floored or dropped. The
other exponents
(``G_i``, ``G_C - G_j``) are at most 0 as they were. On a TPU the chunked
form is a kernel of its own (:func:`_chunk_kernel_channel`: the scalar
kernel's grid, system, inverse and walk; the pairs a block at a time),
where a key head is a value head; the jnp body otherwise.

All of them compute in float32 whatever they are given, and their products at
``PRECISION``, true float32: a TPU's default float32 product is one bfloat16
pass, which left the chunk form 8e-4 off the recurrence on outputs and 4e-3
on states, and the rule's work is small beside the layer's projections.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.kernels import kernel_backend

# Positions of a sub-chunk: the triangular system's size. 64 is the
# published kernels' choice and half an MXU's side.
SUB = 64
# Positions of a block inside a sub-chunk, for a decay a key channel: rows of
# one block are paired about the middle of its decay, and the block's whole
# decay ``exp(BLOCK |g_floor|)`` is held to float32's range, ``exp(88.7)``.
BLOCK = 16
F32_MAX_EXPONENT = 88.0
# Bytes of states a grid step of the step's kernel holds, read once and
# written once (:func:`states_a_step`).
STEP_BLOCK_BYTES = 2 << 20
# Bytes of states a grid step of the chunk's kernel may hold: the block comes
# in and goes out, each with a copy in flight, beside a sub-chunk's operands
# and the system, in 16 MiB of VMEM. Eight heads of 128 x 128 are 0.5 MiB.
CHUNK_STATE_BYTES = 2 << 20
PRECISION = lax.Precision.HIGHEST
F32 = jnp.float32


def _mm(a, b):
    return jnp.matmul(a, b, precision=PRECISION)


def _a_value_head(q, k, heads: int, axis: int = 1):
    """q, k [T, Hk, Dk] of the key heads (the heads on ``axis``), each for
    the ``heads // Hk`` value heads in a row that it serves: [T, heads,
    Dk]."""
    rep = heads // q.shape[axis]
    if rep == 1:
        return q, k
    return jnp.repeat(q, rep, axis=axis), jnp.repeat(k, rep, axis=axis)


def gated_delta_recurrence(q, k, v, g, beta, state):
    """The rule, token by token. q, k: [T, Hk, Dk], a key head for H // Hk
    value heads in a row; v: [T, H, Dv]; beta: [T, H]; g: [T, H], or
    [T, H, Dk] for a decay a key channel; state: [H, Dk, Dv] float32.
    Returns (o [T, H, Dv] float32, state)."""
    q, k = _a_value_head(q, k, v.shape[1])

    def token(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        if g_t.ndim == 2:                     # a row of the state its own
            s = jnp.exp(g_t)[:, :, None] * s
        else:
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


def _require_floor(g_floor) -> None:
    """A decay a channel is chunked only under the caller's stated floor."""
    if g_floor is None:
        raise ValueError(
            "gated_delta_chunk: a decay a key channel needs g_floor, the "
            "floor of the caller's gate: the chunked form pairs rows inside "
            f"blocks of {BLOCK} and exp({BLOCK} |g_floor|) must fit "
            "float32; nothing is clamped here")
    if not BLOCK * abs(g_floor) < F32_MAX_EXPONENT:
        raise ValueError(
            f"gated_delta_chunk: g_floor {g_floor} over a block of {BLOCK} "
            f"positions gives a factor of exp({BLOCK * abs(g_floor)}), and "
            f"float32 holds exp({F32_MAX_EXPONENT})")


def _channel_pairs(q, k, big):
    """For a decay a key channel: ``sum_c k_ic k_jc exp(G_ic - G_jc)`` and
    the same of ``q_i``, [..., SUB, SUB] each, sound where ``j <= i`` (above
    the diagonal a finite number the caller masks). q, k, big: [..., SUB,
    Dk]. A block of ``BLOCK`` rows is one product against every row up to
    its own end, about ``M``, the mean of the block's first and last
    running sums, a channel: its rows carry ``exp(G_i - M)`` and the rows
    ``j`` it is paired with ``exp(M - G_j)``, which multiply to ``exp(G_i -
    G_j)`` under the sum over channels. Inside the block both exponents lie
    within ``(BLOCK - 1) |g_floor| / 2`` of 0 (37.5 at a floor of -5); a row
    of an earlier block has ``M - G_j <= 0``, and where that underflows the
    pair's true weight is under ``exp(-49)``. About the block's first row
    the factors would reach ``exp(75)`` and ``exp(-75)``: float32 holds
    them, but a TPU's true-float32 product splits a factor in three
    bfloat16 pieces and the third of ``exp(-75)`` is a denormal; about the
    middle no piece of any factor is.
    A row past the block's end is given exponent 0."""
    nb = SUB // BLOCK
    lead = big.shape[:-2]

    def blocks(x):
        return x.reshape(lead + (nb, BLOCK, x.shape[-1]))

    ends = blocks(big)
    mid = 0.5 * (ends[..., :1, :] + ends[..., -1:, :])   # M, a block
    left = jnp.exp(ends - mid)
    seen = jnp.arange(SUB)[None, :] < (jnp.arange(nb)[:, None] + 1) * BLOCK
    right = jnp.exp(jnp.where(seen[..., None],
                              mid - big[..., None, :, :], 0.0)) \
        * k[..., None, :, :]                           # [..., nb, SUB, Dk]
    both = jnp.concatenate([blocks(k) * left, blocks(q) * left], axis=-2)
    pairs = _mm(both, jnp.swapaxes(right, -1, -2))     # [.., nb, 2 BLOCK, SUB]
    return (pairs[..., :BLOCK, :].reshape(lead + (SUB, SUB)),
            pairs[..., BLOCK:, :].reshape(lead + (SUB, SUB)))


def _sub_chunk_terms(q, k, v, g, beta, channel: bool,
                     inverse=unit_lower_inverse):
    """What a sub-chunk's walk needs and no state enters, for every
    sub-chunk at once: q, k [..., n, SUB, Dk], v [..., n, SUB, Dv], beta
    [..., n, SUB] and g as beta or, for a decay a key channel, as k, all
    float32. ``T = (I + A)^-1`` does not depend on the state, so ``U = T
    (beta V)`` and ``W = T (beta exp(G) K)`` are made here and ``D = U - W
    S_0`` under the walk. Returns (U, W, the products inside the sub-chunk,
    exp(G) Q, (exp(G_C - G) K)^T, exp(G_C))."""
    big = jnp.cumsum(g, axis=-2 if channel else -1)           # G_i
    rows = jnp.arange(SUB)
    upto = rows[:, None] >= rows[None, :]                     # j <= i
    if channel:
        kk, qk = _channel_pairs(q, k, big)
        a = jnp.where(rows[:, None] > rows[None, :],
                      beta[..., None] * kk, 0.0)
        grow = jnp.exp(big)                                   # exp(G_i)
    else:
        # exp(G_i - G_j) where j <= i: every exponent at most 0.
        decay = jnp.where(upto, jnp.exp(jnp.where(
            upto, big[..., :, None] - big[..., None, :], 0.0)), 0.0)
        kt = jnp.swapaxes(k, -1, -2)
        a = jnp.where(rows[:, None] > rows[None, :],
                      beta[..., None] * decay * _mm(k, kt), 0.0)
        grow = jnp.exp(big)[..., None]                        # exp(G_i)
    rhs_v, rhs_k = beta[..., None] * v, beta[..., None] * grow * k
    inv = inverse(a)
    u, w = _mm(inv, rhs_v), _mm(inv, rhs_k)
    # within: zero above the diagonal; k_out: exp(G_C - G_j) K, transposed
    # for the state's update; whole: exp(G_C), a number a head or a row of
    # the state its own.
    within = jnp.where(upto, qk, 0.0) if channel else decay * _mm(q, kt)
    q_in = grow * q
    if channel:
        k_out = jnp.swapaxes(jnp.exp(big[..., -1:, :] - big) * k, -1, -2)
        whole = jnp.exp(big[..., -1, :])
    else:
        k_out = jnp.swapaxes(
            jnp.exp(big[..., -1:] - big)[..., None] * k, -1, -2)
        whole = jnp.exp(big[..., -1])
    return u, w, within, q_in, k_out, whole


def _walk(state, terms, channel: bool):
    """From sub-chunk to sub-chunk: ``terms`` of :func:`_sub_chunk_terms`
    with the sub-chunks on the axis after the state's leading ones (heads,
    or a batch and heads). Returns (the state after the last, o [n, ...,
    SUB, Dv])."""
    at = state.ndim - 2
    # ``whole`` laid along the state: a number a head, or a row its own.
    lift = (Ellipsis, None) if channel else (Ellipsis, None, None)

    def sub_chunk(s, xs):
        u_n, w_n, within_n, q_n, k_n, whole_n = xs
        d = u_n - _mm(w_n, s)
        o = _mm(q_n, s) + _mm(within_n, d)
        return whole_n[lift] * s + _mm(k_n, d), o

    return lax.scan(sub_chunk, state,
                    tuple(jnp.moveaxis(x, at, 0) for x in terms))


def gated_delta_chunk_reference(q, k, v, g, beta, state, *, g_floor=None):
    """:func:`gated_delta_chunk` of one sequence in plain jnp: what runs off
    a TPU and what the kernels are held to, for a decay a head and for a
    decay a key channel (``g`` [T, H, Dk], under ``g_floor``)."""
    channel = g.ndim == 3
    if channel:
        _require_floor(g_floor)
    q, k = _a_value_head(q, k, v.shape[1])
    t, h, _ = q.shape
    pad = -t % SUB
    n = (t + pad) // SUB

    def heads_first(a):
        a = jnp.pad(a.astype(F32), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        a = a.reshape(n, SUB, *a.shape[1:])
        return jnp.moveaxis(a, 2, 0)              # [H, n, SUB, ...]

    terms = _sub_chunk_terms(*(heads_first(a) for a in (q, k, v, g, beta)),
                             channel)
    state, o = _walk(state.astype(F32), terms, channel)
    # o: [n, H, SUB, Dv] -> [T, H, Dv]
    o = jnp.moveaxis(o, 1, 2).reshape(n * SUB, h, -1)
    return o[:t], state


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, precision=PRECISION,
                           preferred_element_type=F32)


def _pair_inverses(tiles, rows, cols, right):
    """:func:`unit_lower_inverse` of pairs of heads' ``[SUB, SUB]`` tiles,
    each of ``tiles`` and of the results ``[SUB, 2 SUB]`` with the second
    head's tile in the lanes from SUB up, with no reshape: with ``X_s`` the
    inverses of a tile's diagonal blocks of ``s`` (block-diagonal) and
    ``M_s`` the mask of the block under the diagonal of every pair of them,
    ``X_2s = X_s - X_s (a * M_s) X_s``: the same ``-Q^-1 C P^-1`` of every
    pair, the products' other terms exact zeros. ``X_1 = I``, so the first
    level is ``I - a * M_1`` and no product. A product of the two heads is
    one of 128 lanes deep: ``[P_0 | P_1] [[R_0, 0], [0, R_1]]``. Level by
    level over all the pairs: a pair's next product waits for none of its
    own."""
    def under(s):
        # rows in the odd block of a pair of blocks of s, columns in the
        # even one
        return (((rows & s) != 0) & ((cols & s) == 0)
                & ((rows | (2 * s - 1)) == (cols | (2 * s - 1))))

    def apart(p):
        # [R_0 | R_1] -> [[R_0, 0], [0, R_1]], no lane moved
        return jnp.concatenate([jnp.where(right, 0.0, p),
                                jnp.where(right, p, 0.0)], axis=0)

    eye, first = jnp.where(rows == cols, 1.0, 0.0), under(1)
    xs = [eye - jnp.where(first, a, 0.0) for a in tiles]
    s = 2
    while s < SUB:
        mask = under(s)
        ys = [_dot(jnp.where(mask, a, 0.0), apart(x))
              for a, x in zip(tiles, xs)]
        xs = [x - _dot(x, apart(y)) for x, y in zip(xs, ys)]
        s *= 2
    return xs


def _apart(r0, r1):
    """``[[r0, 0], [0, r1]]``: what two heads' tiles side by side multiply
    to give ``[P_0 r0 | P_1 r1]``."""
    return jnp.concatenate(
        [jnp.concatenate([r0, jnp.zeros_like(r1)], axis=1),
         jnp.concatenate([jnp.zeros_like(r0), r1], axis=1)], axis=0)


def _chunk_kernel(q_ref, k_ref, v_ref, big_ref, beta_ref, s0_ref, o_ref,
                  s_ref, *kept, heads: int, rep: int, dk: int, dv: int,
                  keep: int = 0):
    """One sub-chunk of ``heads`` value heads, two by two. q_ref, k_ref
    [SUB, heads // rep * dk], a key head for ``rep`` value heads, and v_ref,
    o_ref [SUB, heads * dv]: a head's sub-chunk is 128-lane columns of the
    caller's ``[T, H * D]``; big_ref (the running sums ``G_i``) and beta_ref
    [SUB, heads]; s0_ref and s_ref [heads, dk, dv]. s_ref's block is the
    same for every sub-chunk of a head: it is the state, in VMEM from the
    first sub-chunk to the last. With ``keep``, one more result ``kept[0]``
    [heads, dk, dv], a block for every ``keep`` sub-chunks: the state those
    sub-chunks start from, which the trained form's backward walks back
    from.

    A product costs what its rows cost and no less than a fixed 0.1 us,
    and products run one after another, so two heads share every product
    that is 64 deep (their ``[SUB, SUB]`` tiles side by side in the lanes,
    ``[SUB, 2 SUB]``), and the pairs of a step go stage by stage: a pair's
    next product waits for none of its own."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    if keep:
        @pl.when(pl.program_id(1) % keep == 0)
        def _():
            kept[0][...] = s_ref[...]

    rows = lax.broadcasted_iota(jnp.int32, (SUB, 2 * SUB), 0)
    lanes = lax.broadcasted_iota(jnp.int32, (SUB, 2 * SUB), 1)
    right = lanes >= SUB                                # the second head
    cols = lanes & (SUB - 1)
    upto = rows >= cols                                       # j <= i
    last_row = lax.broadcasted_iota(jnp.int32, (SUB, dv), 0) == SUB - 1
    pairs = [(h, h + 1) for h in range(0, heads, 2)]

    def head(ref, h, d):
        return ref[:, h * d:(h + 1) * d].astype(F32)

    def both(ref, pair):
        """A column a head, [SUB, 1], over its head's lanes."""
        return jnp.where(right, ref[:, pair[1]:pair[1] + 1],
                         ref[:, pair[0]:pair[0] + 1])

    # A value head's keys and queries are its key head's, read once.
    keys = [head(k_ref, j, dk) for j in range(heads // rep)]
    queries = [head(q_ref, j, dk) for j in range(heads // rep)]
    k = {h: keys[h // rep] for h in range(heads)}
    q = {h: queries[h // rep] for h in range(heads)}
    grow = {h: jnp.exp(big_ref[:, h:h + 1]) for h in k}     # exp(G_i)
    decay, a, qk = {}, {}, {}
    for pair in pairs:
        h0, h1 = pair
        if h0 // rep == h1 // rep:
            # [K; Q] [K; K]^T: the products against the pair's one key
            # head, for both its value heads' lanes.
            kq = _dot(jnp.concatenate([k[h0], q[h0]], axis=0),
                      jnp.concatenate([k[h0], k[h0]], axis=0),
                      (((1,), (1,)), ((), ())))
            kk, qk[pair] = kq[:SUB], kq[SUB:]
        else:
            # [K_0; Q_0; K_1; Q_1] [K_0; K_1]^T, a head's own in its lanes.
            kq = _dot(jnp.concatenate([k[h0], q[h0], k[h1], q[h1]], axis=0),
                      jnp.concatenate([k[h0], k[h1]], axis=0),
                      (((1,), (1,)), ((), ())))
            kk = jnp.where(right, kq[2 * SUB:3 * SUB], kq[:SUB])
            qk[pair] = jnp.where(right, kq[3 * SUB:], kq[SUB:2 * SUB])
        big = both(big_ref, pair)
        # G_j along the lanes: the diagonal of the column's broadcast.
        along = jnp.sum(jnp.where(rows == cols, big, 0.0), axis=0,
                        keepdims=True)
        decay[pair] = jnp.where(
            upto, jnp.exp(jnp.where(upto, big - along, 0.0)), 0.0)
        a[pair] = jnp.where(
            rows > cols, both(beta_ref, pair) * decay[pair] * kk, 0.0)
    inv = _pair_inverses([a[pair] for pair in pairs], rows, cols, right)
    # [K; exp(G) Q] S_0, a head
    ks = {h: _dot(jnp.concatenate([k[h], grow[h] * q[h]], axis=0), s_ref[h])
          for h in k}
    # (I + A) D = beta (V - exp(G) K S_0)
    rhs = {h: beta_ref[:, h:h + 1] * (head(v_ref, h, dv)
                                      - grow[h] * ks[h][:SUB]) for h in k}
    d = {}
    for pair, t in zip(pairs, inv):
        both_d = _dot(t, _apart(rhs[pair[0]], rhs[pair[1]]))
        d[pair[0]], d[pair[1]] = both_d[:, :dv], both_d[:, dv:]
    for pair in pairs:
        h0, h1 = pair
        within = _dot(decay[pair] * qk[pair], _apart(d[h0], d[h1]))
        o_ref[:, h0 * dv:(h0 + 1) * dv] = ks[h0][SUB:] + within[:, :dv]
        o_ref[:, h1 * dv:(h1 + 1) * dv] = ks[h1][SUB:] + within[:, dv:]
    for h in k:
        big = big_ref[:, h:h + 1]
        # exp(G_C) along the lanes, by a sum that keeps the last row: Mosaic
        # broadcasts one way at a time, not a [1, 1] over a tile.
        whole = jnp.sum(jnp.where(last_row, grow[h], 0.0), axis=0,
                        keepdims=True)
        s_ref[h] = whole * s_ref[h] + _dot(
            jnp.exp(big[SUB - 1:] - big) * k[h], d[h],
            (((0,), (0,)), ((), ())))


def _chunk_kernel_channel(q_ref, k_ref, v_ref, big_ref, beta_ref, s0_ref,
                          o_ref, s_ref, *, heads: int, dk: int, dv: int):
    """:func:`_chunk_kernel` for a decay a key channel: one sub-chunk of
    ``heads`` heads, two by two, a key head a value head. q_ref, k_ref and
    big_ref (the running sums ``G_i``, a number a row and key channel)
    [SUB, heads * dk]; v_ref, o_ref [SUB, heads * dv]; beta_ref [SUB,
    heads]; s0_ref and s_ref [heads, dk, dv], the state in VMEM from the
    first sub-chunk to the last.

    What the scalar kernel gets from one product and a matrix of decays,
    ``exp(G_i - G_j) (k_i . k_j)``, is here :func:`_channel_pairs`'s: a
    block of ``BLOCK`` rows at a time, its rows carrying ``exp(G_i - M)``
    and the rows it meets ``exp(M - G_j)`` about the middle ``M`` of the
    block's decay, two heads a product (``[K_0; Q_0; K_1; Q_1]`` of a block
    against ``[K_0; K_1]`` of the sub-chunk, a head's own in its lanes). The
    system, its inverse and the walk are the scalar kernel's; the state's
    decay is a number a row, ``exp(G_C)`` turned from a row of lanes into a
    column by a sum over a diagonal."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    rows = lax.broadcasted_iota(jnp.int32, (SUB, 2 * SUB), 0)
    lanes = lax.broadcasted_iota(jnp.int32, (SUB, 2 * SUB), 1)
    right = lanes >= SUB                                # the second head
    cols = lanes & (SUB - 1)
    upto = rows >= cols                                       # j <= i
    block_right = lax.broadcasted_iota(
        jnp.int32, (BLOCK, 2 * SUB), 1) >= SUB
    row_of = lax.broadcasted_iota(jnp.int32, (SUB, dk), 0)
    diagonal = (lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
                == lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    pairs = [(h, h + 1) for h in range(0, heads, 2)]

    def head(ref, h, d):
        return ref[:, h * d:(h + 1) * d].astype(F32)

    def both(ref, pair):
        """A column a head, [SUB, 1], over its head's lanes."""
        return jnp.where(right, ref[:, pair[1]:pair[1] + 1],
                         ref[:, pair[0]:pair[0] + 1])

    k = {h: head(k_ref, h, dk) for h in range(heads)}
    q = {h: head(q_ref, h, dk) for h in range(heads)}
    big = {h: head(big_ref, h, dk) for h in range(heads)}
    grow = {h: jnp.exp(big[h]) for h in k}                  # exp(G_i)
    a, within = {}, {}
    for pair in pairs:
        kk, qk = [], []
        for lo in range(0, SUB, BLOCK):
            hi = lo + BLOCK
            mine, met = [], []
            for h in pair:
                ends = big[h][lo:hi]
                mid = 0.5 * (ends[:1] + ends[BLOCK - 1:])   # M, a channel
                left = jnp.exp(ends - mid)
                mine += [k[h][lo:hi] * left, q[h][lo:hi] * left]
                met.append(jnp.exp(jnp.where(row_of < hi, mid - big[h], 0.0))
                           * k[h])
            kq = _dot(jnp.concatenate(mine, axis=0),
                      jnp.concatenate(met, axis=0), (((1,), (1,)), ((), ())))
            kk.append(jnp.where(block_right, kq[2 * BLOCK:3 * BLOCK],
                                kq[:BLOCK]))
            qk.append(jnp.where(block_right, kq[3 * BLOCK:],
                                kq[BLOCK:2 * BLOCK]))
        a[pair] = jnp.where(rows > cols, both(beta_ref, pair)
                            * jnp.concatenate(kk, axis=0), 0.0)
        within[pair] = jnp.where(upto, jnp.concatenate(qk, axis=0), 0.0)
    inv = _pair_inverses([a[pair] for pair in pairs], rows, cols, right)
    # [exp(G) K; exp(G) Q] S_0, a head
    ks = {h: _dot(jnp.concatenate([grow[h] * k[h], grow[h] * q[h]], axis=0),
                  s_ref[h]) for h in k}
    # (I + A) D = beta (V - (exp(G) K) S_0)
    rhs = {h: beta_ref[:, h:h + 1] * (head(v_ref, h, dv) - ks[h][:SUB])
           for h in k}
    d = {}
    for pair, t in zip(pairs, inv):
        both_d = _dot(t, _apart(rhs[pair[0]], rhs[pair[1]]))
        d[pair[0]], d[pair[1]] = both_d[:, :dv], both_d[:, dv:]
    for pair in pairs:
        h0, h1 = pair
        inside = _dot(within[pair], _apart(d[h0], d[h1]))
        o_ref[:, h0 * dv:(h0 + 1) * dv] = ks[h0][SUB:] + inside[:, :dv]
        o_ref[:, h1 * dv:(h1 + 1) * dv] = ks[h1][SUB:] + inside[:, dv:]
    for h in k:
        last = big[h][SUB - 1:]                             # G_C, [1, dk]
        # exp(G_C) down the state's rows: the row of lanes as a column.
        whole = jnp.sum(jnp.where(diagonal, jnp.exp(last), 0.0), axis=1,
                        keepdims=True)
        s_ref[h] = whole * s_ref[h] + _dot(
            jnp.exp(last - big[h]) * k[h], d[h], (((0,), (0,)), ((), ())))


def _heads_a_step(h: int) -> int:
    """Value heads a grid step: the products of a step run one after
    another, and a pair's next one waits for the last unless other pairs'
    lie between. On a v5e 512 rows x 32 heads take 0.47 ms at one pair a
    step, 0.32 at two, 0.28 at four and at eight (PR 49)."""
    return next(n for n in (8, 4, 2) if h % n == 0)


def _chunk_pallas(q, k, v, g, beta, state, keep: int = 0):
    """The chunk kernels' call. With ``keep`` (a decay a head; T whole
    multiples of ``keep`` sub-chunks) a third result: the states every
    ``keep`` sub-chunks start from, [T / (keep SUB), H, Dk, Dv]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, h, dv = v.shape
    dk = q.shape[-1]
    rep = h // q.shape[1]
    pad = -t % SUB
    n = (t + pad) // SUB
    hs = _heads_a_step(h)
    if 4 * hs * dk * dv > CHUNK_STATE_BYTES:
        raise ValueError(
            f"gated_delta_chunk: {hs} heads of {dk} x {dv} float32 are "
            f"{4 * hs * dk * dv / 2 ** 20:.1f} MiB of states a grid step, "
            f"and the kernel holds {CHUNK_STATE_BYTES >> 20} MiB in VMEM "
            "beside their copies in flight; under "
            "force_kernel_backend('reference') the jnp body takes any "
            "widths")

    def rows(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(t + pad, -1)                         # [T, H * D]

    def a_step(a):                                            # [T, H] ->
        return jnp.moveaxis(a.reshape(t + pad, h // hs, hs), 1, 0)

    big = jnp.cumsum(rows(g.astype(F32)).reshape(n, SUB, -1), axis=1)
    wide = lambda d: pl.BlockSpec((SUB, d), lambda i, j: (j, i))  # noqa: E731
    narrow = pl.BlockSpec((None, SUB, hs), lambda i, j: (i, j, 0))
    held = pl.BlockSpec((hs, dk, dv), lambda i, j: (i, 0, 0))
    if g.ndim == 3:              # a decay a key channel: sums as wide as k
        kernel = functools.partial(_chunk_kernel_channel, heads=hs, dk=dk,
                                   dv=dv)
        sums, laid = wide(hs * dk), lambda b: b.reshape(t + pad, h * dk)
    else:
        kernel = functools.partial(_chunk_kernel, heads=hs, rep=rep, dk=dk,
                                   dv=dv, keep=keep)
        sums, laid = narrow, a_step
    kept_spec, kept_shape = [], []
    if keep:
        kept_spec = [pl.BlockSpec((None, hs, dk, dv),
                                  lambda i, j: (j // keep, i, 0, 0))]
        kept_shape = [jax.ShapeDtypeStruct((n // keep, h, dk, dv), F32)]
    o, state, *kept = pl.pallas_call(
        kernel,
        grid=(h // hs, n),
        in_specs=[wide(hs // rep * dk), wide(hs // rep * dk), wide(hs * dv),
                  sums, narrow, held],
        out_specs=[wide(hs * dv), held, *kept_spec],
        out_shape=[jax.ShapeDtypeStruct((t + pad, h * dv), F32),
                   jax.ShapeDtypeStruct((h, dk, dv), F32), *kept_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=kernel_backend() == "interpret",
        name="gated_delta_chunk",
    )(rows(q), rows(k), rows(v), laid(big),
      a_step(rows(beta.astype(F32))), state.astype(F32))
    return (o[:t].reshape(t, h, dv), state, *kept)


def gated_delta_chunk(q, k, v, g, beta, state, *, g_floor=None):
    """A run of positions, chunked. One sequence, q, k: [T, Hk, Dk], a key
    head for H // Hk value heads in a row; v: [T, H, Dv]; beta: [T, H]; g:
    [T, H], or [T, H, Dk] for a decay a key channel, which needs
    ``g_floor``, the floor of the caller's gate (no ``g`` is under it);
    state: [H, Dk, Dv] float32, the state before the first position. Returns
    (o [T, H, Dv] float32, the state after the last position). T is any
    length: the run is padded to whole sub-chunks with positions that change
    nothing.

    Or a batch of sequences, every operand with a leading ``B`` (q [B, T,
    Hk, Dk] ... state [B, H, Dk, Dv]) and a decay a head: the form that
    trains, :func:`gated_delta_chunk_batch`, which has a backward.

    The implementation is ``ops/kernels.kernel_backend()``'s: on a TPU the
    kernel, where the operands' shapes are its own (a head's keys and values
    whole 128-lane columns, value heads two by two, a step's heads whole
    key heads; with a decay a channel, a key head a value head);
    :func:`gated_delta_chunk_reference` otherwise."""
    if v.ndim == 4:
        if g.ndim != 3:
            raise ValueError(
                "gated_delta_chunk: a batch of sequences takes a decay a "
                f"head, g [B, T, H]; g is {g.shape} beside v {v.shape} (a "
                "decay a key channel is chunked one sequence a call)")
        return gated_delta_chunk_batch(q, k, v, g, beta, state)
    h, rep = v.shape[1], v.shape[1] // q.shape[1]
    if kernel_backend() == "reference" or not _kernel_takes(
            h, rep, q.shape[-1], v.shape[-1], g.ndim == 3):
        return gated_delta_chunk_reference(q, k, v, g, beta, state,
                                           g_floor=g_floor)
    if g.ndim == 3:
        _require_floor(g_floor)
    return _chunk_pallas(q, k, v, g, beta, state)


def _kernel_takes(h: int, rep: int, dk: int, dv: int, channel: bool) -> bool:
    """Whether the chunk kernel's blocks hold such operands: a head's keys
    and values whole 128-lane columns, value heads two by two, a step's
    heads whole key heads; with a decay a channel, a key head a value
    head."""
    return not (dk % 128 or dv % 128 or h % 2 or _heads_a_step(h) % rep
                or (channel and rep != 1))


# --------------------------------------------------- the form that trains

# Positions between two states the forward keeps for the backward: a state
# is Dk x Dv float32 a head (72 KiB at 96 x 192), so a sequence of 8,192
# keeps 16 of them a head where a state a sub-chunk would be 128, and the
# backward makes a chunk's eight again from the one it starts from.
TRAIN_CHUNK = 512


@jax.custom_vjp
def _inverse_with_transposes(a):
    """:func:`unit_lower_inverse` whose backward is two products with the
    inverse's transpose, ``dA = -T^T dT T^T``, and not the transposes of
    the six levels that made it."""
    return unit_lower_inverse(a)


def _inverse_fwd(a):
    inv = unit_lower_inverse(a)
    return inv, inv


def _inverse_bwd(inv, ct):
    inv_t = jnp.swapaxes(inv, -1, -2)
    return (-_mm(inv_t, _mm(ct, inv_t)),)


_inverse_with_transposes.defvjp(_inverse_fwd, _inverse_bwd)


def _a_chunk(q, k, v, g, beta, state):
    """One chunk of a batch in plain jnp, matrix products throughout: q, k
    [B, C, H, Dk], v [B, C, H, Dv], g, beta [B, C, H] float32 with C whole
    sub-chunks; state [B, H, Dk, Dv]. Returns (o [B, C, H, Dv], the state
    after the chunk). Differentiable: the backward of the trained form is
    ``jax.vjp`` of this, a chunk at a time."""
    b, c, h = beta.shape
    n = c // SUB

    def heads_first(a):                           # -> [B, H, n, SUB, ...]
        return jnp.moveaxis(a.reshape(b, n, SUB, *a.shape[2:]), 3, 1)

    terms = _sub_chunk_terms(*(heads_first(a) for a in (q, k, v, g, beta)),
                             False, _inverse_with_transposes)
    state, o = _walk(state, terms, False)
    # o: [n, B, H, SUB, Dv] -> [B, C, H, Dv]
    return jnp.moveaxis(o, (0, 3), (1, 2)).reshape(b, c, h, -1), state


def _fold(a):
    """A batch's operand as the chunk kernels take it: [B, T, H, ...] ->
    [T, B * H (to a whole grid step of eight), ... (to whole 128-lane
    columns)], zero-padded."""
    b, t, h = a.shape[:3]
    a = jnp.moveaxis(a, 0, 1).reshape(t, b * h, *a.shape[3:])
    return jnp.pad(a, ((0, 0), (0, -(b * h) % 8))
                   + ((0, -a.shape[-1] % 128),) * (a.ndim - 2))


def _fold_states(s):
    """[..., B, H, Dk, Dv] -> [..., heads', Dk', Dv'], as :func:`_fold`."""
    *lead, b, h, dk, dv = s.shape
    s = s.reshape(*lead, b * h, dk, dv)
    return jnp.pad(s, ((0, 0),) * len(lead) + (
        (0, -(b * h) % 8), (0, -dk % 128), (0, -dv % 128)))


def _unfold(x, like):
    """:func:`_fold`'s way back: [T, heads', ...'] as ``like`` [B, T, H,
    ...], what was padded dropped."""
    b, t, h = like.shape[:3]
    x = x[:, :b * h] if like.ndim == 3 else x[:, :b * h, :like.shape[-1]]
    return jnp.moveaxis(x.reshape(t, b, h, *like.shape[3:]), 0, 1)


def _unfold_states(s, like):
    """:func:`_fold_states`'s way back: [..., heads', Dk', Dv'] with the
    trailing dimensions of ``like`` [..., B, H, Dk, Dv]."""
    b, h, dk, dv = like.shape[-4:]
    return s[..., :b * h, :dk, :dv].reshape(*s.shape[:-3], b, h, dk, dv)


def _batch_forward_kernel(q, k, v, g, beta, state, c: int):
    """:func:`_batch_forward` through the chunk kernel, one call for every
    chunk of every sequence: a head of one sequence is a head like any
    other, so the batch folds into the heads; keys and values are
    zero-padded to whole 128-lane columns and the heads to a whole grid
    step. Exact: a padded channel of a key meets zeros, a padded column of
    a value and a padded head (``g = 0``, ``beta = 0``) leave a zero state
    zero. What is padded is time spent and not work needed. The kernel
    keeps the state every ``c`` positions start from."""
    o, s, starts = _chunk_pallas(*(_fold(a) for a in (q, k, v, g, beta)),
                                 _fold_states(state), keep=c // SUB)
    return (_unfold(o, v), _unfold_states(s, state),
            _unfold_states(starts, state))


def _kernel_takes_a_batch(dk: int, dv: int) -> bool:
    """Whether the trained forward goes through the kernel: its eight padded
    states a grid step have to fit (:data:`CHUNK_STATE_BYTES`)."""
    return kernel_backend() != "reference" and \
        4 * 8 * (dk + -dk % 128) * (dv + -dv % 128) <= CHUNK_STATE_BYTES


def _chunk_bwd_kernel(q_ref, k_ref, v_ref, big_ref, beta_ref, s0_ref, do_ref,
                      dsn_ref, dq_ref, dk_ref, dv_ref, dbig_ref, dbeta_ref,
                      ds_ref, states, inverses, corrections, *, heads: int,
                      dk: int, dv: int, subs: int):
    """One sub-chunk of ``heads`` heads, two by two, of the trained form's
    backward: a key head a value head, the operands' blocks as
    :func:`_chunk_kernel`'s. The grid is (heads a step) by (chunks, last to
    first) by (``2 subs`` steps a chunk): the first ``subs`` walk the chunk's
    sub-chunks forward from the state the forward kept (``s0_ref``) and keep
    in VMEM what the way back needs of each, the state it starts from
    (``states``), its inverse ``T`` (``inverses``, a pair's two side by
    side) and its corrections ``D`` (``corrections``); the last ``subs``
    walk them back. ``ds_ref`` [heads, dk, dv] is the gradient of the state,
    one block for every step of a head: in VMEM from the last sub-chunk of
    the last chunk (where it is ``dsn_ref``, the cotangent of the state
    after the run) to the first of the first, where what it holds is the
    gradient of the state before the run. do_ref is the outputs' cotangent;
    dq_ref, dk_ref, dv_ref the operands' gradients and dbig_ref, dbeta_ref
    [SUB, heads] those of the running sums ``G_i`` and of the steps, whose
    blocks stay at the chunk's last sub-chunk while the walk goes forward
    and are written on the way back alone.

    With ``Γ_ij = exp(G_i - G_j)``, ``P = Γ (Q K^T)``, ``R = beta (V -
    exp(G) K S_0)``, ``D = T R`` and ``K' = exp(G_C - G) K``, given ``dO``
    and ``dS_C``::

        dD   = P^T dO + K' dS_C
        dR   = T^T dD                    # dV = beta dR
        dA   = -strict_lower(dR D^T)     # -T^T (dD R^T) T^T, one product
        dP   = lower(dO D^T)
        dS_0 = exp(G_C) dS_C + (exp(G) Q)^T dO - (beta exp(G) K)^T dR

    ``dQ`` and ``dK`` from ``Γ dP`` and ``M = beta Γ dA`` (``[Γ dP; M]
    [[K_0, 0], [0, K_1]]`` and, with the tiles' rows contracted, ``[Γ dP;
    M]^T [Q; K]``), ``dO S_0^T``, ``dR S_0^T`` and ``D dS_C^T``; ``dG`` and
    ``dbeta`` row sums of elementwise products. A tile's transpose is never
    taken: ``P^T`` is made from ``K Q^T`` and ``exp(G_j - G_i)`` as ``P``
    from ``Q K^T``, and a product with ``T^T`` or ``M^T`` contracts the
    tile's rows. Every exponent is a difference ``G_i - G_j <= 0``."""
    from jax.experimental import pallas as pl

    # (read here and not under a ``pl.when``: the interpreter knows a grid
    # index at the body's top level alone)
    step, first = pl.program_id(2), pl.program_id(1) == 0
    rows = lax.broadcasted_iota(jnp.int32, (SUB, 2 * SUB), 0)
    lanes = lax.broadcasted_iota(jnp.int32, (SUB, 2 * SUB), 1)
    right = lanes >= SUB                                # the second head
    cols = lanes & (SUB - 1)
    upto = rows >= cols                                       # j <= i
    last_row = lax.broadcasted_iota(jnp.int32, (SUB, dv), 0) == SUB - 1
    last_of = lax.broadcasted_iota(jnp.int32, (SUB, 1), 0) == SUB - 1
    head_of = lax.broadcasted_iota(jnp.int32, (SUB, heads), 1)
    pairs = [(h, h + 1) for h in range(0, heads, 2)]
    nt = (((1,), (1,)), ((), ()))                 # a b^T
    tn = (((0,), (0,)), ((), ()))                 # a^T b

    def head(ref, h, d):
        return ref[:, h * d:(h + 1) * d].astype(F32)

    def both(ref, pair):
        """A column a head, [SUB, 1], over its head's lanes."""
        return jnp.where(right, ref[:, pair[1]:pair[1] + 1],
                         ref[:, pair[0]:pair[0] + 1])

    def of_pair(x):
        """A pair's two [2 SUB, 2 SUB] products, a head's own in its lanes:
        [SUB, 2 SUB]."""
        return jnp.where(right, x[SUB:], x[:SUB])

    def side(x):
        return jnp.concatenate(x, axis=1)

    def stack(x):
        return jnp.concatenate(x, axis=0)

    def summed(x):
        return jnp.sum(x, axis=1, keepdims=True)

    def half_summed(x, n):
        """The rows' sums of a pair's tile over the lanes of its head ``n``
        (0 or 1)."""
        return summed(jnp.where(right if n else ~right, x, 0.0))

    def decays(pair, transposed=False):
        """exp(G_i - G_j) where j <= i of a pair, and with ``transposed``
        its transpose beside it."""
        big = both(big_ref, pair)
        # G_j along the lanes: the diagonal of the column's broadcast.
        along = jnp.sum(jnp.where(rows == cols, big, 0.0), axis=0,
                        keepdims=True)
        fall = jnp.where(upto, jnp.exp(jnp.where(upto, big - along, 0.0)),
                         0.0)
        if not transposed:
            return fall
        return fall, jnp.where(cols >= rows, jnp.exp(jnp.where(
            cols >= rows, along - big, 0.0)), 0.0)

    def whole_of(h):
        """exp(G_C) along the lanes, [1, dv]: a sum that keeps the last row
        (Mosaic broadcasts one way at a time)."""
        return jnp.sum(jnp.where(last_row, jnp.exp(big_ref[:, h:h + 1]), 0.0),
                       axis=0, keepdims=True)

    def fade_of(h):
        big = big_ref[:, h:h + 1]
        return jnp.exp(big[SUB - 1:] - big)                   # exp(G_C - G)

    @pl.when(step < subs)
    def _():
        """The way forward: sub-chunk ``step`` from ``states[step]``."""
        @pl.when(step == 0)
        def _():
            states[0] = s0_ref[...]

        k = {h: head(k_ref, h, dk) for h in range(heads)}
        a = []
        for pair in pairs:
            both_k = stack([k[pair[0]], k[pair[1]]])
            kk = of_pair(_dot(both_k, both_k, nt))
            a.append(jnp.where(rows > cols, both(beta_ref, pair)
                               * decays(pair) * kk, 0.0))
        inv = _pair_inverses(a, rows, cols, right)
        s = {h: states[step, h] for h in k}
        # (I + A) D = beta (V - exp(G) K S_0)
        rhs = {h: beta_ref[:, h:h + 1] * (
            head(v_ref, h, dv) - jnp.exp(big_ref[:, h:h + 1])
            * _dot(k[h], s[h])) for h in k}
        d = {}
        for n, (pair, t) in enumerate(zip(pairs, inv)):
            inverses[step, n] = t
            both_d = _dot(t, _apart(rhs[pair[0]], rhs[pair[1]]))
            d[pair[0]], d[pair[1]] = both_d[:, :dv], both_d[:, dv:]
        for h in k:
            corrections[step, h] = d[h]

        @pl.when(step < subs - 1)
        def _():
            for h in k:
                states[step + 1, h] = whole_of(h) * s[h] + _dot(
                    fade_of(h) * k[h], d[h], tn)

    @pl.when(step >= subs)
    def _():
        """The way back: sub-chunk ``2 subs - 1 - step``."""
        sub = 2 * subs - 1 - step

        @pl.when((step == subs) & first)
        def _():
            ds_ref[...] = dsn_ref[...]

        k = {h: head(k_ref, h, dk) for h in range(heads)}
        q = {h: head(q_ref, h, dk) for h in k}
        d_o = {h: head(do_ref, h, dv) for h in k}
        s = {h: states[sub, h] for h in k}
        d = {h: corrections[sub, h] for h in k}
        d_s = {h: ds_ref[h] for h in k}
        grow = {h: jnp.exp(big_ref[:, h:h + 1]) for h in k}     # exp(G_i)
        fade = {h: fade_of(h) for h in k}
        whole = {h: whole_of(h) for h in k}
        kout = {h: fade[h] * k[h] for h in k}                 # K'
        decay, kk, qk, d_d = {}, {}, {}, {}
        for pair in pairs:
            h0, h1 = pair
            # [K_0; Q_0; K_1; Q_1] [K_0; K_1]^T, a head's own in its lanes
            kq = _dot(stack([k[h0], q[h0], k[h1], q[h1]]),
                      stack([k[h0], k[h1]]), nt)
            kk[pair] = jnp.where(right, kq[2 * SUB:3 * SUB], kq[:SUB])
            qk[pair] = jnp.where(right, kq[3 * SUB:], kq[SUB:2 * SUB])
            # (K Q^T)_ji exp(G_i - G_j): P^T with no transpose
            decay[pair], back = decays(pair, transposed=True)
            p_t = back * of_pair(_dot(stack([k[h0], k[h1]]),
                                      stack([q[h0], q[h1]]), nt))
            # dD = P^T dO + K' dS_C
            both_dd = _dot(p_t, _apart(d_o[h0], d_o[h1]))
            d_d[h0] = both_dd[:, :dv] + _dot(kout[h0], d_s[h0])
            d_d[h1] = both_dd[:, dv:] + _dot(kout[h1], d_s[h1])
        d_r, grad, lower = {}, {}, {}
        for n, pair in enumerate(pairs):
            h0, h1 = pair
            # dR = T^T dD: the tiles' rows contracted, a head's own block
            both_dr = _dot(inverses[sub, n], side([d_d[h0], d_d[h1]]), tn)
            d_r[h0], d_r[h1] = both_dr[:SUB, :dv], both_dr[SUB:, dv:]
            # [dR; dO] D^T: dA = -strict_lower(dR D^T), dP = lower(dO D^T)
            x = _dot(stack([side([d_r[h0], d_r[h1]]),
                            side([d_o[h0], d_o[h1]])]),
                     _apart(d[h0], d[h1]), nt)
            gda = decay[pair] * jnp.where(rows > cols, -x[:SUB], 0.0)  # Γ dA
            gdp = decay[pair] * jnp.where(upto, x[SUB:], 0.0)      # Γ dP
            m = both(beta_ref, pair) * gda                    # beta Γ dA
            tiles = stack([gdp, m])
            # [Γ dP; M] [[K_0, 0], [0, K_1]] and [Γ dP; M]^T [Q; K]
            grad[pair] = (_dot(tiles, _apart(k[h0], k[h1])),
                          _dot(tiles, stack([side([q[h0], q[h1]]),
                                             side([k[h0], k[h1]])]), tn))
            # what the rows' sums read: dbeta's Γ dA (K K^T), and dG's dP P +
            # dA A, a row's sum less its column's
            w = gdp * qk[pair] + m * kk[pair]
            w = w - jnp.where(rows == cols,
                              jnp.sum(w, axis=0, keepdims=True), 0.0)
            lower[pair] = (gda * kk[pair], w)
        d_big = jnp.zeros((SUB, heads), F32)
        d_beta = jnp.zeros((SUB, heads), F32)
        for pair in pairs:
            for n, h in enumerate(pair):
                at = slice(n * dk, (n + 1) * dk)
                beta = beta_ref[:, h:h + 1]
                # [dO; dR] S_0^T, and D dS_C^T
                from_s = _dot(stack([d_o[h], d_r[h]]), s[h], nt)
                d_os, d_rs = from_s[:SUB], from_s[SUB:]
                d_kout = _dot(d[h], d_s[h], nt)
                dq_ref[:, h * dk:(h + 1) * dk] = \
                    grad[pair][0][:SUB, at] + grow[h] * d_os
                dk_ref[:, h * dk:(h + 1) * dk] = (
                    grad[pair][0][SUB:, at]
                    + grad[pair][1][n * SUB:(n + 1) * SUB, at]
                    - beta * grow[h] * d_rs + fade[h] * d_kout)
                dv_ref[:, h * dv:(h + 1) * dv] = beta * d_r[h]
                r_s = grow[h] * summed(k[h] * d_rs)
                d_beta = jnp.where(
                    head_of == h, summed(d_r[h] * head(v_ref, h, dv)) - r_s
                    + half_summed(lower[pair][0], n), d_beta)
                # exp(G_C) in K' and before S_0: the last row's, less a
                # row's own
                out = summed(d_kout * kout[h])
                ends = jnp.sum(out, axis=0, keepdims=True) + jnp.sum(
                    whole[h] * jnp.sum(d_s[h] * s[h], axis=0, keepdims=True),
                    axis=1, keepdims=True)
                d_big = jnp.where(
                    head_of == h, half_summed(lower[pair][1], n)
                    + grow[h] * summed(q[h] * d_os) - beta * r_s - out
                    + jnp.where(last_of, ends, 0.0), d_big)
                ds_ref[h] = whole[h] * d_s[h] + _dot(
                    stack([grow[h] * q[h], -(beta * grow[h]) * k[h]]),
                    stack([d_o[h], d_r[h]]), tn)
        dbig_ref[...] = d_big
        dbeta_ref[...] = d_beta


def _chunk_bwd_pallas(q, k, v, g, beta, starts, d_o, d_state, subs: int):
    """The backward kernel's call, on operands as :func:`_chunk_pallas`
    takes them (a key head a value head, T whole chunks of ``subs``
    sub-chunks; starts [T / (subs SUB), H, Dk, Dv], the state every chunk
    starts from). Returns the gradients of q, k, v, g, beta and the state
    before the run."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, h, dv = v.shape
    dk = q.shape[-1]
    hs = _heads_a_step(h)
    chunks = t // (subs * SUB)

    def rows(a):
        return a.reshape(t, -1)                               # [T, H * D]

    def a_step(a):                                            # [T, H] ->
        return jnp.moveaxis(a.reshape(t, h // hs, hs), 1, 0)

    def from_steps(a):                                        # -> [T, H]
        return jnp.moveaxis(a, 0, 1).reshape(t, h)

    def sub(j, s, waits: bool):
        """The sub-chunk a step is at: forward, then back; what only the
        way back reads or writes waits at the chunk's last."""
        s = jnp.maximum(s, subs) if waits else jnp.maximum(s, 2 * subs - 1 - s)
        return (chunks - 1 - j) * subs + 2 * subs - 1 - s

    def wide(d, waits=False):
        return pl.BlockSpec((SUB, d), lambda i, j, s: (sub(j, s, waits), i))

    def narrow(waits=False):
        return pl.BlockSpec((None, SUB, hs),
                            lambda i, j, s: (i, sub(j, s, waits), 0))

    held = pl.BlockSpec((hs, dk, dv), lambda i, j, s: (i, 0, 0))
    # what a chunk keeps for its way back: the states, the pairs' inverses
    # and the corrections (8 + 1 + 4 MiB at eight heads of 128 x 256)
    kept = [(subs, hs, dk, dv), (subs, hs // 2, SUB, 2 * SUB),
            (subs, hs, SUB, dv)]
    big = jnp.cumsum(g.reshape(t // SUB, SUB, h), axis=1).reshape(t, h)
    dq, dk_, dv_, d_big, d_beta, d_state = pl.pallas_call(
        functools.partial(_chunk_bwd_kernel, heads=hs, dk=dk, dv=dv,
                          subs=subs),
        grid=(h // hs, chunks, 2 * subs),
        in_specs=[wide(hs * dk), wide(hs * dk), wide(hs * dv), narrow(),
                  narrow(),
                  pl.BlockSpec((None, hs, dk, dv),
                               lambda i, j, s: (chunks - 1 - j, i, 0, 0)),
                  wide(hs * dv, True), held],
        out_specs=[wide(hs * dk, True), wide(hs * dk, True),
                   wide(hs * dv, True), narrow(True), narrow(True), held],
        out_shape=[jax.ShapeDtypeStruct((t, h * dk), F32),
                   jax.ShapeDtypeStruct((t, h * dk), F32),
                   jax.ShapeDtypeStruct((t, h * dv), F32),
                   jax.ShapeDtypeStruct((h // hs, t, hs), F32),
                   jax.ShapeDtypeStruct((h // hs, t, hs), F32),
                   jax.ShapeDtypeStruct((h, dk, dv), F32)],
        scratch_shapes=[pltpu.VMEM(shape, F32) for shape in kept],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            # beside them a sub-chunk's operands and gradients with their
            # copies in flight and what the body spills (Mosaic's default
            # is 16 MiB; a v5e has 128)
            vmem_limit_bytes=(32 << 20) + 4 * sum(
                math.prod(shape) for shape in kept)),
        interpret=kernel_backend() == "interpret",
        name="gated_delta_chunk_bwd",
    )(rows(q), rows(k), rows(v), a_step(big), a_step(beta), starts,
      rows(d_o), d_state)
    # G_i is the sum of g up to i inside a sub-chunk: dg_i the sum of dG from
    # i to the sub-chunk's end
    d_g = lax.cumsum(from_steps(d_big).reshape(t // SUB, SUB, h), axis=1,
                     reverse=True).reshape(t, h)
    return (dq.reshape(t, h, dk), dk_.reshape(t, h, dk),
            dv_.reshape(t, h, dv), d_g, from_steps(d_beta), d_state)


def _batch_backward_kernel(saved, cts):
    """:func:`_batch_rule_bwd` through the backward's kernel, one call for
    every chunk of every sequence, folded and padded as
    :func:`_batch_forward_kernel` folds the forward's: a padded channel's
    and a padded head's gradients are dropped."""
    *operands, starts = saved
    d_o, d_state = cts
    *grads, d_first = _chunk_bwd_pallas(
        *(_fold(a) for a in operands), _fold_states(starts), _fold(d_o),
        _fold_states(d_state), _chunks(d_o.shape[1])[0] // SUB)
    return tuple(_unfold(x, a) for x, a in zip(grads, operands)) + (
        _unfold_states(d_first, d_state),)


def _chunks(t: int) -> tuple[int, int]:
    """(positions of a chunk, chunks) for a run of ``t`` positions: whole
    sub-chunks, ``TRAIN_CHUNK`` where the run is longer than one."""
    c = min(TRAIN_CHUNK, t + -t % SUB)
    return c, -(-t // c)


def _a_slice(a, i, c: int):
    return lax.dynamic_slice_in_dim(a, i * c, c, axis=1)


def _batch_forward(q, k, v, g, beta, state):
    """The chunks in order. Returns (o, the last state, the state at every
    chunk's start [chunks, B, H, Dk, Dv])."""
    c, n = _chunks(beta.shape[1])
    if _kernel_takes_a_batch(q.shape[-1], v.shape[-1]):
        return _batch_forward_kernel(q, k, v, g, beta, state, c)

    def chunk(s, i):
        o, after = _a_chunk(*(_a_slice(a, i, c) for a in (q, k, v, g, beta)),
                            s)
        return after, (o, s)

    state, (o, starts) = lax.scan(chunk, state, jnp.arange(n))
    # o: [chunks, B, C, H, Dv] -> [B, T, H, Dv]
    o = jnp.moveaxis(o, 0, 1).reshape(beta.shape[0], n * c, *o.shape[3:])
    return o, state, starts


@jax.custom_vjp
def _batch_rule(q, k, v, g, beta, state):
    o, state, _ = _batch_forward(q, k, v, g, beta, state)
    return o, state


def _batch_rule_fwd(q, k, v, g, beta, state):
    o, state, starts = _batch_forward(q, k, v, g, beta, state)
    return (o, state), (q, k, v, g, beta, starts)


def _batch_rule_bwd(saved, cts):
    """The chunks in reverse: from the state a chunk started from, the
    chunk's forward again and its transposes, which carry the gradient of
    the state to the chunk before. The kernel where the forward went
    through its own (:func:`_kernel_takes_a_batch`), else ``jax.vjp`` of the
    jnp chunk under a scan."""
    q, k, v, g, beta, starts = saved
    if _kernel_takes_a_batch(q.shape[-1], v.shape[-1]):
        return _batch_backward_kernel(saved, cts)
    d_o, d_state = cts
    c, n = _chunks(beta.shape[1])

    def chunk(d_s, i):
        operands = tuple(_a_slice(a, i, c) for a in (q, k, v, g, beta))
        _, pull = jax.vjp(_a_chunk, *operands, starts[i])
        *d_operands, d_s = pull((_a_slice(d_o, i, c), d_s))
        return d_s, tuple(d_operands)

    d_state, grads = lax.scan(chunk, d_state, jnp.arange(n), reverse=True)
    # a gradient: [chunks, B, C, H, ...] -> [B, T, H, ...]
    return tuple(jnp.moveaxis(x, 0, 1).reshape(a.shape)
                 for x, a in zip(grads, (q, k, v, g, beta))) + (d_state,)


_batch_rule.defvjp(_batch_rule_fwd, _batch_rule_bwd)


def gated_delta_chunk_batch(q, k, v, g, beta, state):
    """The chunked form of a batch of whole sequences, with a backward: what
    a model trains through. q, k: [B, T, Hk, Dk]; v: [B, T, H, Dv]; g,
    beta: [B, T, H] (a decay a head); state: [B, H, Dk, Dv]. Returns (o [B,
    T, H, Dv] float32, the states after the last position). Any T, any
    number of heads, ``Dk`` and ``Dv`` their own.

    The forward walks chunks of ``TRAIN_CHUNK`` positions and keeps, for the
    backward, its operands and the state at each chunk's start: not a state
    a token, not a sub-chunk's matrices. The backward walks the chunks in
    reverse; a chunk makes its sub-chunks' systems and states again from the
    state it started from and runs their transposes (in jnp ``jax.vjp`` of
    :func:`_a_chunk`, the inverse's by ``dA = -T^T dT T^T``): matrix
    products throughout, no scan a token. What it is held to is ``jax.grad``
    through :func:`gated_delta_recurrence` (tests/test_gated_delta.py).

    Both passes are ``ops/kernels.kernel_backend()``'s: on a TPU one call
    each at padded widths, the chunk kernel (:func:`_batch_forward_kernel`),
    which keeps the chunks' states itself, and the backward's kernel
    (:func:`_batch_backward_kernel`: the same transposes, a sub-chunk a grid
    step, what a chunk's way back needs kept in VMEM); the jnp chunks under
    a scan elsewhere, forward and in reverse."""
    q, k = _a_value_head(q, k, v.shape[2], axis=2)
    t = beta.shape[1]
    c, n = _chunks(t)
    pad = n * c - t

    def whole_chunks(a):
        a = a.astype(F32)
        return a if not pad else jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

    o, state = _batch_rule(*(whole_chunks(a) for a in (q, k, v, g, beta)),
                           state.astype(F32))
    return o[:, :t], state


def gated_delta_step_reference(q, k, v, g, beta, state, line):
    """:func:`gated_delta_step` in plain jnp: what runs off a TPU and what
    the kernel is held to. A line is sliced out of the leaf, read by one
    pass that gives both ``S^T k`` and ``S^T q`` (``o_t = exp(g) S^T q +
    (k . q) d``) and written back by a second."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    s = lax.dynamic_index_in_dim(state, line, 0, keepdims=False)
    decay = jnp.exp(g)[..., None]
    if g.ndim == k.ndim:
        # ``S'^T k = S^T (exp(g) k)``: the decayed state is read through
        # decayed keys and queries, in the one pass, and written once.
        sk = jnp.sum(s * (decay * k[..., None]), axis=-2)      # S'^T k
        sq = jnp.sum(s * (decay * q[..., None]), axis=-2)      # S'^T q
        d = beta[..., None] * (v - sk)
        o = sq + jnp.sum(k * q, axis=-1, keepdims=True) * d
        s = decay * s + k[..., None] * d[..., None, :]
    else:
        sk = jnp.sum(s * k[..., None], axis=-2)                # S^T k
        sq = jnp.sum(s * q[..., None], axis=-2)                # S^T q
        d = beta[..., None] * (v - decay * sk)
        o = decay * sq + jnp.sum(k * q, axis=-1, keepdims=True) * d
        s = decay[..., None] * s + k[..., None] * d[..., None, :]
    return o, lax.dynamic_update_index_in_dim(state, s, line, 0)


# Heads the step's kernel writes out in a row inside its loop over a block's
# heads: a head's two passes wait for its own sums, and the next heads' fill
# the wait. On a v5e a line of 96 slots x 32 heads takes 0.799 ms at one,
# 0.643 at two, 0.638 at four, 0.636 at eight and at all 32 written out,
# which takes three times as long to trace and compile (PR 59).
STEP_HEADS_IN_A_ROW = 8


def _step_kernel(line_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref,
                 o_ref, out_ref):
    """One slot's ``heads`` states. q_ref, k_ref [heads, Dk]; v_ref, o_ref
    [heads, Dv]; beta_ref [1, heads]; g_ref [1, heads] for a decay a head,
    [heads, Dk] for a decay a key channel; s_ref and out_ref [heads, Dk,
    Dv], the same block of the leaf: read from HBM once, written once.

    A state's rows lie on the sublanes, so what scales a row (a key's and a
    query's channels, a channel's decay) is wanted down a column, the same
    in every lane: a head's row of ``Dk`` numbers is laid over the
    sublanes and that tile transposed. A decay a head is one number over
    the whole state. The sums over ``Dk`` are sums of a state's
    sublanes."""
    from jax.experimental import pallas as pl

    del line_ref  # read by the block specs' index maps
    heads, dk, dv = s_ref.shape
    lane = lax.broadcasted_iota(jnp.int32, (1, heads), 1)

    def column(row):              # [1, Dk] -> [Dk, Dv]
        return jnp.broadcast_to(row, (dv, dk)).T

    def of_head(ref, h):          # [1, heads] -> head h's number, [1, 1]
        return jnp.sum(jnp.where(lane == h, ref[...], 0.0), axis=1,
                       keepdims=True)

    def head(h):
        row = pl.ds(h, 1)
        k, q = k_ref[row, :], q_ref[row, :]
        k_col = column(k)
        if g_ref.shape == k_ref.shape:
            decay = column(jnp.exp(g_ref[row, :]))
        else:
            decay = jnp.broadcast_to(jnp.exp(of_head(g_ref, h)), (1, dv))
        s = decay * s_ref[h]                                  # S'
        sk = jnp.sum(s * k_col, axis=0, keepdims=True)        # S'^T k
        sq = jnp.sum(s * column(q), axis=0, keepdims=True)    # S'^T q
        d = of_head(beta_ref, h) * (v_ref[row, :] - sk)
        o_ref[row, :] = sq + jnp.sum(k * q, axis=1, keepdims=True) * d
        out_ref[h] = s + k_col * d

    n = next(n for n in (STEP_HEADS_IN_A_ROW, 4, 2, 1) if heads % n == 0)

    def heads_in_a_row(i, carry):
        for j in range(n):
            head(i * n + j)
        return carry

    lax.fori_loop(0, heads // n, heads_in_a_row, 0)


def states_a_step(h: int, dk: int, dv: int) -> int:
    """States a grid step of a step kernel (this module's and
    ops/ssd.py's): heads of one slot, the most
    that are ``STEP_BLOCK_BYTES`` or less, divide ``h`` and tile the
    operands' ``[h, D]`` (a multiple of 8, or all of them); 0 where no
    number does. On a v5e a line of 96 slots x 32 heads of 128 x 128 takes
    0.710 ms at 8 a step (0.5 MiB), 0.638 at 16 and 0.638 at 32 (2 MiB); a
    line of 16 slots 0.118, 0.114 and 0.111: a step's fixed cost shows
    under 1 MiB, and the block's first read and last write, which nothing
    hides, do not grow past it (PR 59)."""
    most = min(h, STEP_BLOCK_BYTES // (4 * dk * dv))
    return next((n for n in range(most, 0, -1)
                 if h % n == 0 and (n % 8 == 0 or n == h)), 0)


def step_in_place(kernel, name: str, line, rows, state, hs: int):
    """A step kernel's call on a line of a state leaf ``[lines, B, h, Dk,
    Dv]``, in place (this module's step and ops/ssd.py's): the grid is
    (slots, ``h // hs``), the leaf is the last operand and aliased to the
    second result, its block ``hs`` states of the slot on the line that the
    prefetched scalar names, and the first result is ``[B, h, Dv]`` float32
    in blocks of ``hs`` rows. ``rows`` are the operands before the leaf:
    ``[B, h, D]`` in blocks of ``hs`` rows, or ``[B, h // hs, 1, n]`` a row
    a block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, dk, dv = state.shape[1:]
    wide = lambda d: pl.BlockSpec(                            # noqa: E731
        (None, hs, d), lambda i, j, line: (i, j, 0))
    narrow = lambda n: pl.BlockSpec(                          # noqa: E731
        (None, None, 1, n), lambda i, j, line: (i, j, 0, 0))
    held = pl.BlockSpec((None, None, hs, dk, dv),
                        lambda i, j, line: (line[0], i, j, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // hs),
            in_specs=[*((wide if a.ndim == 3 else narrow)(a.shape[-1])
                        for a in rows), held],
            out_specs=[wide(dv), held]),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # Operands count the scalar-prefetch argument: the leaf, written in
        # place, comes after it and the rows.
        input_output_aliases={len(rows) + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=kernel_backend() == "interpret",
        name=name,
    )(jnp.asarray(line, jnp.int32).reshape(1), *rows, state)


def _step_pallas(q, k, v, g, beta, state, line):
    b, h, dk = k.shape
    hs = states_a_step(h, dk, v.shape[-1])
    # (before the rows' reshapes, where it stood before the call was shared:
    # the program's equations keep their order)
    line = jnp.asarray(line, jnp.int32).reshape(1)

    def a_step(a):                 # [B, H] -> [B, H // hs, 1, hs]
        return a.reshape(b, h // hs, 1, hs)

    return step_in_place(
        _step_kernel, "gated_delta_step", line,
        [q, k, v, g if g.ndim == 3 else a_step(g), a_step(beta)], state, hs)


def gated_delta_step(q, k, v, g, beta, state, line):
    """One position of every slot, on line ``line`` of a state leaf, in
    place. q, k: [B, H, Dk]; v: [B, H, Dv]; beta: [B, H]; g: [B, H], or
    [B, H, Dk] for a decay a key channel (a row of the state scaled by its
    own number, where the scalar scales the whole state); state: [lines, B,
    H, Dk, Dv] float32, the leaf as a serving module holds it; line: an
    index, traced or not. Returns (o [B, H, Dv] float32, the leaf with that
    line's states after the position and every other line as it was)."""
    if kernel_backend() == "reference" or k.shape[-1] % 128 \
            or v.shape[-1] % 128 \
            or not states_a_step(*k.shape[1:], v.shape[-1]):
        return gated_delta_step_reference(q, k, v, g, beta, state, line)
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    return _step_pallas(q, k, v, g, beta, state, line)
