"""The selective scan of a Mamba-1 layer: a diagonal recurrence whose decay
depends on the token.

A layer keeps a state ``h`` of ``N`` numbers a channel (16 x 5,120 at
Phi-4-mini-flash's widths), float32. Token ``t`` brings a step ``dt_t`` and
an input ``x_t`` a channel and two vectors ``B_t`` and ``C_t`` of ``N``,
and with ``A`` (negative, a number a state and channel) and ``D`` (a number
a channel)::

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]

Every (n, c) is a recurrence of its own: nothing is contracted along the
sequence, so no matrix product carries a run of positions the way the gated
delta rule's chunked form does (ops/gated_delta.py). A run is a walk in
time that is parallel over the ``N x channels`` lanes: exponentials and
multiply-adds on the vector units, with the state held on the chip from the
first token to the last.

**The layout.** The state is ``[N, channels]``, the channels last: a
float32 array whose last dimension were ``N`` = 16 would fill an eighth of
the 128 lanes a row is stored in. The published ``A_log`` is ``[channels,
N]``; the program keeps it transposed, the same numbers.

Three functions:

- :func:`selective_scan_recurrence`: the equations above as a ``lax.scan``
  over the tokens. The tests' yardstick, and what runs off a TPU.
- :func:`selective_scan_chunk`: a run of positions of one sequence (a
  prefill chunk). On a TPU a Pallas kernel (``selective_scan_chunk`` in a
  device trace): the grid is (blocks of 1,024 channels, blocks of time);
  a block's state is 16 vector registers of 8 x 128 channels, one a state
  index, carried through a loop over the block's tokens and from time block
  to time block in VMEM. ``B_t[n]`` and ``C_t[n]`` are scalars (in SMEM)
  against whole registers of channels, so a token is 16 exponentials and
  some hundred multiply-adds a register and nothing crosses lanes or
  sublanes.
- :func:`selective_scan_step`: one position of every slot (a decode step),
  elementwise over ``[slots, N, channels]``: XLA fuses it into one pass
  over the states, read once and written once.

A row that is not ``valid`` (a padded chunk's tail, a slot that does not
decode) enters with ``dt = 0``: it decays nothing and adds nothing, and its
``y`` is zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.kernels import kernel_backend

# Channels of one grid step: 8 sublanes x 128 lanes, a vector register a
# state index.
_ROWS, _LANES = 8, 128
_BLOCK_CHANNELS = _ROWS * _LANES
# Tokens of one grid step, at most.
_BLOCK_TIME = 128
# Tokens a trip of the kernel's loop, written out.
_UNROLL = 8


def _valid_steps(dt, valid):
    """``dt`` with 0 where a row is not valid: such a row decays nothing and
    adds nothing."""
    return dt if valid is None else jnp.where(valid[..., None], dt, 0.0)


def _valid_rows(y, valid):
    """``y`` with zeros where a row is not valid."""
    return y if valid is None else jnp.where(valid[..., None], y, 0.0)


def selective_scan_recurrence(x, dt, a, b, c, d, h0, valid=None):
    """x, dt [T, C]; a [N, C]; b, c [T, N]; d [C]; h0 [N, C]; valid [T] bool
    or None -> (y [T, C], h [N, C]), all float32: the recurrence token by
    token."""
    x, dt, a, b, c, d, h0 = (jnp.asarray(v, jnp.float32)
                             for v in (x, dt, a, b, c, d, h0))
    dt = _valid_steps(dt, valid)

    def tick(h, row):
        x_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t[None] * a) * h + (dt_t * x_t)[None] * b_t[:, None]
        return h, (h * c_t[:, None]).sum(0) + d * x_t

    h, y = lax.scan(tick, h0, (x, dt, b, c))
    return _valid_rows(y, valid), h


def _chunk_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, h0_ref, y_ref,
                  h_ref, carry_ref, *, block_t: int, n_state: int,
                  unroll: int):
    """Grid step (channel block i, time block j). ``b_ref`` and ``c_ref``
    are the whole ``[T * N]`` vectors in SMEM; ``x_ref``, ``dt_ref``,
    ``y_ref`` are [block_t, 8, 128]; ``a_ref``, ``h0_ref``, ``h_ref`` and
    the carry [N, 8, 128]; ``d_ref`` [8, 128]."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        carry_ref[...] = h0_ref[...]

    a = [a_ref[n] for n in range(n_state)]
    d = d_ref[...]
    base = j * block_t * n_state

    def tick(t, h):
        x, dt = x_ref[t], dt_ref[t]
        u = dt * x
        at = base + t * n_state
        y = d * x
        new = []
        for n in range(n_state):
            hn = jnp.exp(dt * a[n]) * h[n] + u * b_ref[at + n]
            y = y + hn * c_ref[at + n]
            new.append(hn)
        y_ref[t] = y
        return tuple(new)

    def ticks(g, h):
        # ``unroll`` tokens a trip, written out: a token's exponentials and
        # its products with ``B`` do not wait for the state before it.
        for k in range(unroll):
            h = tick(g * unroll + k, h)
        return h

    h = lax.fori_loop(0, block_t // unroll, ticks,
                      tuple(carry_ref[n] for n in range(n_state)))
    for n in range(n_state):
        carry_ref[n] = h[n]

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        h_ref[...] = carry_ref[...]


def _block_time(t: int) -> int:
    """Tokens a grid step: the largest power of two up to ``_BLOCK_TIME``
    that divides ``t`` (``t`` is a multiple of 8)."""
    bt = 8
    while bt < _BLOCK_TIME and t % (2 * bt) == 0:
        bt *= 2
    return bt


def _selective_scan_pallas(x, dt, a, b, c, d, h0, *, unroll: int = _UNROLL):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, ch = x.shape
    n = a.shape[0]
    t_pad = -(-t // 8) * 8
    if t_pad != t:
        # A padded row has ``dt = 0``: the state passes it unchanged.
        pad = ((0, t_pad - t), (0, 0))
        x, dt, b, c = (jnp.pad(v, pad) for v in (x, dt, b, c))
    bt = _block_time(t_pad)
    rows = ch // _LANES
    fold = lambda v: v.reshape(*v.shape[:-1], rows, _LANES)  # noqa: E731
    seq = pl.BlockSpec((bt, _ROWS, _LANES), lambda i, j, *_: (j, i, 0))
    state = pl.BlockSpec((n, _ROWS, _LANES), lambda i, j, *_: (0, i, 0))
    y, h = pl.pallas_call(
        functools.partial(_chunk_kernel, block_t=bt, n_state=n,
                          unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(ch // _BLOCK_CHANNELS, t_pad // bt),
            in_specs=[seq, seq, state,
                      pl.BlockSpec((_ROWS, _LANES), lambda i, j, *_: (i, 0)),
                      state],
            out_specs=[seq, state],
            scratch_shapes=[pltpu.VMEM((n, _ROWS, _LANES), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((t_pad, rows, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((n, rows, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # A channel block is a scan of its own; time carries its state.
            dimension_semantics=("parallel", "arbitrary")),
        interpret=kernel_backend() == "interpret",
        name="selective_scan_chunk",
    )(b.reshape(-1), c.reshape(-1), fold(x), fold(dt), fold(a), fold(d),
      fold(h0))
    return y.reshape(t_pad, ch)[:t], h.reshape(n, ch)


def selective_scan_chunk(x, dt, a, b, c, d, h0, valid=None):
    """A run of T positions of one sequence from the state ``h0``: the
    arguments and results of :func:`selective_scan_recurrence`. The kernel
    where the backend has one and the channels are whole blocks of 1,024;
    the recurrence elsewhere (tiny test widths, and off a TPU)."""
    if kernel_backend() == "reference" or x.shape[-1] % _BLOCK_CHANNELS:
        return selective_scan_recurrence(x, dt, a, b, c, d, h0, valid)
    x, dt, a, b, c, d, h0 = (jnp.asarray(v, jnp.float32)
                             for v in (x, dt, a, b, c, d, h0))
    y, h = _selective_scan_pallas(x, _valid_steps(dt, valid), a, b, c, d, h0)
    return _valid_rows(y, valid), h


def selective_scan_step(x, dt, a, b, c, d, h, valid=None):
    """One position of every slot: x, dt [B, C]; a [N, C]; b, c [B, N];
    d [C]; h [B, N, C]; valid [B] bool or None -> (y [B, C], h [B, N, C]).
    A slot that is not valid keeps its state bit for bit (``exp(0) h +
    0``)."""
    x, dt, a, b, c, d = (jnp.asarray(v, jnp.float32)
                         for v in (x, dt, a, b, c, d))
    dt = _valid_steps(dt, valid)
    h = (jnp.exp(dt[:, None] * a[None]) * h
         + (dt * x)[:, None] * b[:, :, None])
    return _valid_rows((h * c[:, :, None]).sum(1) + d * x, valid), h
