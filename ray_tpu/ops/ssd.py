"""Mamba-2's rule (the state-space dual form): a linear-attention layer's
matrix state under a decay a head, with no correction.

Per head a state ``S`` of ``[N, P]`` in float32 (``N`` the state's size,
``P`` the head's channels). With a step ``dt_t > 0`` and a rate ``A < 0`` a
head, ``B_t`` and ``C_t`` of ``N`` numbers **the same for every head** and
the head's input ``x_t`` of ``P`` channels, from the state before the
token::

    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T
    y_t = S_t^T C_t

It is the gated rule of ops/gated_delta.py with the correction left out
(``d_t = v_t`` where the delta rule has ``beta_t (v_t - S'^T k_t)``; ``B`` the
key, ``C`` the query, ``dt x`` the value, ``g = dt A`` the log-decay), so its
chunked form is that module's with ``T = I`` and no system to solve. Inside a
sub-chunk of ``SUB`` positions that starts from ``S_0``, with ``G_i`` the
running sum of ``g``::

    Y   = (exp(G) C) S_0 + (tril(C B^T) exp(G_i - G_j)) (dt X)
    S_C = exp(G_C) S_0 + (exp(G_C - G) B)^T (dt X)

Every exponent is a difference ``G_i - G_j`` with ``j <= i``, at most 0
(``dt > 0``, ``A < 0``): nothing overflows however fast a head forgets, and
nothing is clamped or floored. ``C B^T`` is **one ``[SUB, SUB]`` product a
sub-chunk for all the heads**; only the decay's mask differs by head. The
skip ``D x`` and the output's gate are the caller's.

**The state as it is stored.** A head's ``[N, P]`` with ``P`` = 64 would
fill half of a tile's 128 lanes (and a float32 leaf pads them: twice the
memory and twice the traffic), so ``pack = lane_heads(heads, P)`` heads lie
side by side in the lanes: the state of ``heads`` heads is ``[heads // pack,
N, pack * P]``, group ``g`` holding heads ``g pack`` to ``g pack + pack - 1``
(:func:`to_stored`, :func:`from_stored`). All three forms take and give the
stored layout, on which the rule is a plain matrix's: a token's ``x`` of a
group is a row of lanes, ``B`` and ``C`` columns down the sublanes that every
group shares, the read ``S^T C`` a sum over sublanes that leaves a row.

Three forms of it:

- :func:`ssd_recurrence`: the two lines under a ``lax.scan`` over the
  positions. What the tests hold the other two to; never a program's path.
- :func:`ssd_chunk`: a run of positions of one sequence from a given state,
  in sub-chunks of ``SUB``, as matrix products: the within-chunk products a
  head (``[SUB, SUB]`` by ``[SUB, P]``), the state's read and update for all
  heads at once (``C S`` and ``B^T (w dt X)`` on the stored layout's ``[N,
  heads * P]``). Plain jnp on every backend: a Pallas kernel that keeps
  the state in VMEM from sub-chunk to sub-chunk, as ``gated_delta_chunk``'s
  does, is ROADMAP R5 (h) (0.59 ms for 512 rows x 128 heads on a v5e, 5 of
  a chunk's 41 ms in the cell: PR 62).
- :func:`ssd_step`: one position of every slot on a line of a state leaf
  ``[lines, slots, groups, N, pack * P]``, in place. The update needs no sum
  over the state before it (no correction), so the state is gone over once:
  read, decayed, added to, read out, written. On a TPU a Pallas kernel
  (:func:`_step_kernel`) through ``ops/gated_delta.step_in_place``, that
  module's step's scaffolding: the leaf whole and aliased to its result, the
  line a prefetched scalar, a grid step ``states_a_step`` groups of one slot
  (32 of 128 x 128, 2 MiB: half a slot's layer at 128 heads of 64), a state
  across HBM once each way. ``B`` and ``C`` are wanted down the sublanes,
  the same in every lane: a slot's row of ``N`` numbers is laid over the
  sublanes and that tile transposed, twice a grid step and not a head.
  Elsewhere, and as what the kernel is held to,
  :func:`ssd_step_reference` in plain jnp.

A position with ``dt = 0`` changes no state (``exp(0) S + B 0``) and reads
``y = S^T C``, which a caller drops: a padded chunk's rows past the prompt's
end, a slot of a step that does not decode.

All three compute in float32 whatever they are given, their products at
``PRECISION``, true float32, as the delta rule's do and for its reason.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.gated_delta import (
    F32,
    PRECISION,
    states_a_step,
    step_in_place,
)
from ray_tpu.ops.kernels import kernel_backend

# Positions of a sub-chunk. No system is solved, so the size is free: the
# within-chunk products cost ``2 SUB P`` a head and token beside ``4 N P``
# for the state's read and update, and a decay's mask ``SUB`` exponentials;
# the published kernels' 256 (``mamba_chunk_size``) is equal in exact
# arithmetic.
SUB = 64
LANES = 128


def lane_heads(heads: int, p: int) -> int:
    """Heads side by side in a stored state's lanes: the most that divide
    ``heads`` and fit ``LANES`` (2 at 128 heads of 64; 1 at heads of 128 or
    wider)."""
    return next(n for n in range(max(1, LANES // p), 0, -1)
                if heads % n == 0)


def state_shape(heads: int, n: int, p: int) -> tuple[int, int, int]:
    """(groups, N, pack * P): a slot's stored state in one layer."""
    pack = lane_heads(heads, p)
    return heads // pack, n, pack * p


def to_stored(s, pack: int):
    """[..., heads, N, P] -> [..., heads // pack, N, pack * P]."""
    *lead, h, n, p = s.shape
    s = s.reshape(*lead, h // pack, pack, n, p)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, h // pack, n, pack * p)


def from_stored(s, pack: int):
    """[..., groups, N, pack * P] -> [..., groups * pack, N, P]."""
    *lead, g, n, w = s.shape
    s = s.reshape(*lead, g, n, pack, w // pack)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, g * pack, n, w // pack)


def _mm(a, b):
    return jnp.matmul(a, b, precision=PRECISION)


def ssd_recurrence(x, dt, a, b, c, state):
    """The rule, token by token. x: [T, H, P]; dt: [T, H] (a step, 0 for a
    row that changes nothing); a: [H], negative; b, c: [T, N], every head's;
    state: [groups, N, pack * P] float32, the stored layout. Returns (y [T,
    H, P] float32, state)."""
    pack = x.shape[1] // state.shape[0]

    def token(s, row):
        x_t, dt_t, b_t, c_t = row
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + b_t[None, :, None] * (dt_t[:, None] * x_t)[:, None, :]
        return s, jnp.einsum("n,hnp->hp", c_t, s, precision=PRECISION)

    a = a.astype(F32)
    state, y = lax.scan(token, from_stored(state.astype(F32), pack),
                        tuple(v.astype(F32) for v in (x, dt, b, c)))
    return y, to_stored(state, pack)


def ssd_chunk(x, dt, a, b, c, state):
    """A run of positions of one sequence, chunked. x: [T, H, P]; dt: [T, H]
    (0 for a row that changes nothing); a: [H], negative; b, c: [T, N], every
    head's; state: [groups, N, pack * P] float32, the state before the first
    position, as stored. Returns (y [T, H, P] float32, the state after the
    last position). T is any length: the run is padded to whole sub-chunks
    with positions that change nothing.

    The within-chunk part does not depend on the state and is made for
    every sub-chunk at once; the state walks the sub-chunks under a scan, as
    one ``[N, heads * P]`` matrix."""
    t, h, p = x.shape
    n_state = b.shape[-1]
    pad = -t % SUB
    n = (t + pad) // SUB

    def chunks(v):
        v = jnp.pad(v.astype(F32), ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape(n, SUB, *v.shape[1:])

    x, dt, b, c = (chunks(v) for v in (x, dt, b, c))
    big = jnp.cumsum(dt * a.astype(F32), axis=1)              # G_i [n,SUB,H]
    dtx = dt[..., None] * x                                   # [n,SUB,H,P]
    rows = jnp.arange(SUB)
    upto = (rows[:, None] >= rows[None, :])[None, :, :, None]  # j <= i
    # exp(G_i - G_j) where j <= i: every exponent at most 0.
    decay = jnp.where(upto, jnp.exp(jnp.where(
        upto, big[:, :, None, :] - big[:, None, :, :], 0.0)), 0.0)
    scores = _mm(c, jnp.swapaxes(b, -1, -2))        # C B^T, once for all heads
    within = jnp.einsum("nijh,njhp->nihp", scores[..., None] * decay, dtx,
                        precision=PRECISION)
    grow = jnp.exp(big)                                       # exp(G_i)
    whole = grow[:, -1]                                       # exp(G_C) [n,H]
    # exp(G_C - G_j) dt X: what a position leaves in the state at the
    # sub-chunk's end.
    left = (jnp.exp(big[:, -1:] - big)[..., None] * dtx).reshape(
        n, SUB, h * p)

    def sub_chunk(s, xs):                                     # s [N, H * P]
        c_n, b_n, left_n, whole_n = xs
        read = _mm(c_n, s)                                    # C S_0
        s = jnp.repeat(whole_n, p)[None, :] * s + _mm(b_n.T, left_n)
        return s, read

    pack = h // state.shape[0]
    # The state as given and as returned are arrays of their own, in the
    # stored layout: without the barriers XLA carries the matrix's layout
    # back through the caller's slice of a slot and copies the whole leaf
    # into it (3.4 GiB at the cell's 96 slots x 9 layers).
    state = lax.optimization_barrier(state.astype(F32))
    s0 = jnp.moveaxis(from_stored(state, pack), 1, 0).reshape(
        n_state, h * p)
    s1, read = lax.scan(sub_chunk, s0, (c, b, left, whole))
    y = within + grow[..., None] * read.reshape(n, SUB, h, p)
    s1 = jnp.moveaxis(s1.reshape(n_state, h, p), 0, 1)
    return (y.reshape(n * SUB, h, p)[:t],
            lax.optimization_barrier(to_stored(s1, pack)))


def _step_operands(x, dt, a, groups: int):
    """What a step brings to a group's lanes: ``dt x`` and the decay
    ``exp(dt A)`` of each lane's head, [B, groups, pack * P] both."""
    bsz, h, p = x.shape
    dt = dt.astype(F32)
    dtx = (dt[..., None] * x.astype(F32)).reshape(bsz, groups, -1)
    decay = jnp.repeat(jnp.exp(dt * a.astype(F32)), p, axis=-1).reshape(
        bsz, groups, -1)
    return dtx, decay


def ssd_step_reference(x, dt, a, b, c, state, line):
    """:func:`ssd_step` in plain jnp: what runs off a TPU and what the
    kernel is held to. A line is sliced out of the leaf, gone over once and
    written back."""
    dtx, decay = _step_operands(x, dt, a, state.shape[2])
    s = lax.dynamic_index_in_dim(state, line, 0, keepdims=False)
    s = decay[:, :, None, :] * s \
        + b.astype(F32)[:, None, :, None] * dtx[:, :, None, :]
    y = jnp.sum(s * c.astype(F32)[:, None, :, None], axis=-2)
    return y.reshape(x.shape), lax.dynamic_update_index_in_dim(
        state, s, line, 0)


# Groups the step's kernel writes out in a row inside its loop over a
# block's groups (ops/gated_delta.STEP_HEADS_IN_A_ROW, for its reason).
STEP_GROUPS_IN_A_ROW = 8


def _step_kernel(line_ref, dtx_ref, decay_ref, b_ref, c_ref, s_ref, y_ref,
                 out_ref):
    """One slot's ``groups`` stored states. dtx_ref, decay_ref, y_ref
    [groups, W]; b_ref, c_ref [1, N]; s_ref and out_ref [groups, N, W], the
    same block of the leaf: read from HBM once, written once. ``B`` and
    ``C`` go down the sublanes, the same in every lane, for every group of
    the block."""
    from jax.experimental import pallas as pl

    del line_ref  # read by the block specs' index maps
    groups, n, w = s_ref.shape
    b_col = jnp.broadcast_to(b_ref[...], (w, n)).T            # [N, W]
    c_col = jnp.broadcast_to(c_ref[...], (w, n)).T

    def group(g):
        row = pl.ds(g, 1)
        s = decay_ref[row, :] * s_ref[g] + b_col * dtx_ref[row, :]
        y_ref[row, :] = jnp.sum(s * c_col, axis=0, keepdims=True)
        out_ref[g] = s

    k = next(k for k in (STEP_GROUPS_IN_A_ROW, 4, 2, 1) if groups % k == 0)

    def in_a_row(i, carry):
        for j in range(k):
            group(i * k + j)
        return carry

    lax.fori_loop(0, groups // k, in_a_row, 0)


def _step_pallas(x, dt, a, b, c, state, line):
    bsz = x.shape[0]
    groups, n, w = state.shape[2:]
    gs = states_a_step(groups, n, w)
    dtx, decay = _step_operands(x, dt, a, groups)

    def shared(v):                 # [B, N] -> [B, groups // gs, 1, N]
        return jnp.broadcast_to(v.astype(F32)[:, None, None, :],
                                (bsz, groups // gs, 1, n))

    y, state = step_in_place(_step_kernel, "ssd_step", line,
                             [dtx, decay, shared(b), shared(c)], state, gs)
    return y.reshape(x.shape), state


def ssd_step(x, dt, a, b, c, state, line):
    """One position of every slot, on line ``line`` of a state leaf, in
    place. x: [B, H, P]; dt: [B, H] (0 for a slot that keeps its state);
    a: [H], negative; b, c: [B, N]; state: [lines, B, groups, N, pack * P]
    float32, the leaf as a serving module holds it; line: an index, traced
    or not. Returns (y [B, H, P] float32, the leaf with that line's states
    after the position and every other line as it was)."""
    groups, n, w = state.shape[2:]
    if kernel_backend() == "reference" or n % LANES or w % LANES \
            or not states_a_step(groups, n, w):
        return ssd_step_reference(x, dt, a, b, c, state, line)
    return _step_pallas(x, dt, a, b, c, state, line)
