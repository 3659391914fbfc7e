"""The two slot-cache leaves of a linear-attention layer whose state is a
matrix a head (the gated delta rule's, ops/gated_delta.py), for the serving
modules of the models that have one (llm/qwen3_next_serving.py,
llm/ling_serving.py), the slot second in both:

- ``state`` ``[linear lines, slots, heads, Dk, Dv]`` float32: the rule's
  state, of one size whatever the length;
- ``conv`` ``[linear lines, slots, (taps - 1) * conv_dim]``: the last rows
  of that layer's ``[q | k | v]`` before its convolution, one after the
  other in a slot's row (llm/lfm2_serving.py's layout and for its reason).

A model may keep the state as several such leaves, each over some of its
linear layers (llm/ling_serving.py, and why), and then a layer's line in its
``state`` leaf is not its line in ``conv``: the chunk's two functions take
both (``conv_line``, the same where it is not given).

Both ride every loop as carry. A prefill chunk reads its slot's two rows, or
zeros where the chunk is a prompt's first (whatever the slot held before),
and writes the state after its last valid row and the window that ends
there; a decode step reads a line of every slot's window and writes it
back, a slot that does not decode its window as it was. A step's states are
not this module's: ``ops/gated_delta.gated_delta_step`` takes the ``state``
leaf and the line and updates that line in place (a slot that does not
decode it leaves bit for bit, given ``g = 0`` and ``beta = 0``), so no
program slices a line of states out of the leaf or writes one back. All
under ``linear_state``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ray_tpu.models.routed import layer_of
from ray_tpu.util import tracing


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


def chunk_start(st, cs, line, slot, kv_len, conv_line=None):
    """A chunk's slot before it: the window's rows [1, 1, (taps - 1) *
    conv_dim] and the state [1, 1, heads, Dk, Dv], zeros at a prompt's
    start."""
    conv_line = line if conv_line is None else conv_line
    with tracing.part("linear_state"):
        prior = jnp.where(kv_len > 0, lax.dynamic_slice(
            cs, (conv_line, slot, 0), (1, 1, cs.shape[2])), 0)
        s0 = jnp.where(kv_len > 0, lax.dynamic_slice(
            st, (line, slot, 0, 0, 0), (1, 1, *st.shape[2:])), 0.0)
    return prior, s0


def chunk_end(st, cs, s1, window, line, slot, n_valid, conv_line=None):
    """A chunk's slot after it: the state ``s1`` [heads, Dk, Dv] and the
    rows of ``window`` [1, taps - 1 + C, conv_dim] that end at the last of
    the chunk's ``n_valid`` valid tokens."""
    conv_line = line if conv_line is None else conv_line
    keep = cs.shape[2] // window.shape[2]
    with tracing.part("linear_state"):
        st = lax.dynamic_update_slice(st, s1[None, None],
                                      (line, slot, 0, 0, 0))
        last = lax.dynamic_slice_in_dim(window, n_valid, keep, axis=1)
        cs = lax.dynamic_update_slice(
            cs, last.astype(cs.dtype).reshape(1, 1, -1),
            (conv_line, slot, 0))
    return st, cs


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
