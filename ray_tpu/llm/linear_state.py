"""The two slot-cache leaves of a linear-attention layer whose state is a
matrix a head (the gated delta rule's, ops/gated_delta.py; Mamba-2's,
ops/ssd.py, the rule without a correction), for the serving modules of the
models that have one (llm/qwen3_next_serving.py, llm/ling_serving.py,
llm/granite_serving.py), the slot second in both:

- ``state`` ``[linear lines, slots, heads, Dk, Dv]`` float32: the rule's
  state, of one size whatever the length;
- ``conv`` ``[linear lines, slots, (taps - 1) * conv_dim]``: the last rows
  of that layer's ``[q | k | v]`` before its convolution, one after the
  other in a slot's row (llm/lfm2_serving.py's layout and for its reason).

A model may keep the state as several such leaves, each over some of its
linear layers (llm/ling_serving.py, and why), and then a layer's line in its
``state`` leaf is not its line in ``conv``: the chunk's two functions take
both (``conv_line``, the same where it is not given). The leaf's last three
axes are the rule's own affair (Mamba-2's heads lie two by two in the lanes:
ops/ssd.state_shape).

Both ride every loop as carry. A prefill chunk reads its slot's two rows, or
zeros where the chunk is a prompt's first (whatever the slot held before),
and writes the state after its last valid row and the window that ends
there (a layer at a time, ``chunk_start`` and ``chunk_end``; or the slot's
states on all lines at once before and after the layers, ``slot_states`` and
``put_slot_states``, beside ``window_start`` and ``window_end`` a layer);
a decode step reads a line of every slot's window and writes it back, a
slot that does not decode its window as it was. A step's states are
not this module's: ``ops/gated_delta.gated_delta_step`` takes the ``state``
leaf and the line and updates that line in place (a slot that does not
decode it leaves bit for bit, given ``g = 0`` and ``beta = 0``), so no
program slices a line of states out of the leaf or writes one back
(``ops/ssd.ssd_step`` the same, given ``dt = 0``). All under
``linear_state``.

The programs of these models count two things of their own after the routed
layers' (``COUNTERS``): ``linear_state_updates`` ((slot, linear layer) pairs
a decode program updated for a line that decodes) and
``linear_chunk_tokens`` ((valid token, linear layer) pairs through the
chunked form); :func:`with_own_counts` puts a program's two after its
router counts.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ray_tpu.models.routed import MOE_COUNTERS, layer_of
from ray_tpu.util import tracing

COUNTERS = MOE_COUNTERS + ("linear_state_updates", "linear_chunk_tokens")


def init_state(lines: int, max_slots: int, heads: int, dk: int, dv: int):
    """A zeroed ``state`` leaf of ``lines`` lines."""
    return jnp.zeros((lines, max_slots, heads, dk, dv), jnp.float32)


def init_conv(lines: int, max_slots: int, taps: int, conv_dim: int, dtype):
    """A zeroed ``conv`` leaf of ``lines`` lines."""
    return jnp.zeros((lines, max_slots, (taps - 1) * conv_dim), dtype)


def init_leaves(lines: int, max_slots: int, heads: int, dk: int, dv: int,
                taps: int, conv_dim: int, dtype) -> dict:
    """Zeroed ``state`` and ``conv`` leaves, a line a linear layer."""
    return {"state": init_state(lines, max_slots, heads, dk, dv),
            "conv": init_conv(lines, max_slots, taps, conv_dim, dtype)}


def window_start(cs, line, slot, kv_len):
    """A chunk's window before it: the slot's rows [1, 1, (taps - 1) *
    conv_dim] of line ``line``, zeros at a prompt's start."""
    with tracing.part("linear_state"):
        return jnp.where(kv_len > 0, lax.dynamic_slice(
            cs, (line, slot, 0), (1, 1, cs.shape[2])), 0)


def window_end(cs, window, line, slot, n_valid):
    """A chunk's window after it: the rows of ``window`` [1, taps - 1 + C,
    conv_dim] that end at the last of the chunk's ``n_valid`` valid
    tokens."""
    keep = cs.shape[2] // window.shape[2]
    with tracing.part("linear_state"):
        last = lax.dynamic_slice_in_dim(window, n_valid, keep, axis=1)
        return lax.dynamic_update_slice(
            cs, last.astype(cs.dtype).reshape(1, 1, -1), (line, slot, 0))


def chunk_start(st, cs, line, slot, kv_len, conv_line=None):
    """A chunk's slot before it: the window's rows [1, 1, (taps - 1) *
    conv_dim] and the state [1, 1, heads, Dk, Dv], zeros at a prompt's
    start."""
    prior = window_start(cs, line if conv_line is None else conv_line, slot,
                         kv_len)
    with tracing.part("linear_state"):
        s0 = jnp.where(kv_len > 0, lax.dynamic_slice(
            st, (line, slot, 0, 0, 0), (1, 1, *st.shape[2:])), 0.0)
    return prior, s0


def chunk_end(st, cs, s1, window, line, slot, n_valid, conv_line=None):
    """A chunk's slot after it: the state ``s1`` [heads, Dk, Dv] and the
    rows of ``window`` [1, taps - 1 + C, conv_dim] that end at the last of
    the chunk's ``n_valid`` valid tokens."""
    with tracing.part("linear_state"):
        st = lax.dynamic_update_slice(st, s1[None, None],
                                      (line, slot, 0, 0, 0))
    return st, window_end(cs, window, line if conv_line is None
                          else conv_line, slot, n_valid)


def slot_states(st, slot, kv_len):
    """A slot's states on every line before a chunk, [lines, heads, Dk,
    Dv], zeros at a prompt's start: read once for the whole program, where
    a leaf holds many lines and a program's layers are written out
    (llm/granite_serving.py). A leaf that a program's layers read and
    update in turn has, for every update, two uses (the next layer's read
    and the next update), and the compiler, short of memory by its own
    count, computes such an update a second time (ROADMAP R5 (g)); read
    once before the layers and written once after them
    (:func:`put_slot_states`), the leaf has one read and one update."""
    with tracing.part("linear_state"):
        return jnp.where(kv_len > 0, lax.dynamic_slice(
            st, (0, slot, 0, 0, 0), (st.shape[0], 1, *st.shape[2:])),
            0.0)[:, 0]


def put_slot_states(st, states, slot):
    """The leaf with a slot's states on every line, [lines, heads, Dk, Dv],
    written after a chunk."""
    with tracing.part("linear_state"):
        return lax.dynamic_update_slice(st, states[:, None],
                                        (0, slot, 0, 0, 0))


def step_start(cs, line, conv_dim: int):
    """Every slot's window before a step: [slots, taps - 1, conv_dim]."""
    with tracing.part("linear_state"):
        return layer_of(cs, line).reshape(cs.shape[1], -1, conv_dim)


def step_end(cs, window, prior, line, write_mask):
    """Every slot's window after a step, moved on a row; a slot with
    ``write_mask`` false its window as it was. (The states the rule's step
    has already written, in place in their leaf.)"""
    with tracing.part("linear_state"):
        new = jnp.where(write_mask[:, None, None], window[:, 1:], prior)
        return lax.dynamic_update_index_in_dim(
            cs, new.astype(cs.dtype).reshape(new.shape[0], -1), line, 0)


def with_own_counts(counts, lines: int, own):
    """A program's router counts and then its two own: ``own`` is
    (linear_state_updates, linear_chunk_tokens) of ONE linear layer, and
    every one of the ``lines`` linear layers counts the same."""
    with tracing.part("moe_combine"):
        return jnp.concatenate(
            [counts, lines * jnp.stack(own).astype(jnp.int32)])
