"""What models/phi4flash.py supplies to the scheduler
(llm/served.ServedModel): a cache with five kinds of leaf (one of them
nothing at all) and the programs that run against it.

``{"k", "v", "rk", "rv", "state", "conv"}``, the slot second in all six:

- ``k``, ``v`` ``[1, slots, kv_pairs, max_seq, 2 d]``: the model's one
  **full line**, the keys and values of layer ``L/2 + 1`` (a packed pair a
  head: models/phi4flash.py), which that layer and every cross attention
  after it read: ``line_readers`` reads a step of the same bytes;
- ``rk``, ``rv`` ``[window_lines, slots, kv_pairs, sliding_window, 2 d]``:
  a **ring** a window layer, which does not grow with ``max_seq``.
  Position ``p`` lies in row ``p % sliding_window``, so the ring holds the
  window that ends at the last position written and is read whole: there is
  no positional term, only the mask knows positions, and a ring's rows need
  no order;
- ``state`` ``[ssm_lines, slots, d_state, d_inner]`` float32: the
  selective scan's state of a scan layer (ops/selective_scan.py: the
  channels last);
- ``conv`` ``[ssm_lines, slots, (taps - 1) * d_inner]``: the last rows of
  that layer's ``x`` before its convolution, one after the other in a
  slot's row (llm/lfm2_serving.py's layout and for its reason);
- and for the gated memory units and the cross attentions, nothing.

All ride every loop as carry. What llm/lfm2_serving.py and
llm/qwen3_next_serving.py say of a state that is not a line holds here for
three kinds of leaf:

- a prefill chunk is padded, so what it leaves is what stands after the
  prompt's last token: a row past the prompt's end enters the scan with
  ``dt = 0``, the window kept ends at the last valid row, and a ring takes
  the last ``sliding_window`` valid rows; a chunk that starts at ``kv_len =
  0`` starts from zeros, and sees none of the ring's rows, whatever the
  slot held before;
- a decode step runs every slot, so a slot with ``write_mask`` false keeps
  its state, its window and its rings bit for bit;
- the state at an earlier length is nowhere, so a prompt's prefix cannot
  be adopted from another slot's line (``ServedModel.prefix_from_line``).

**A chunk skips the cross-decoder.** Layers ``L/2 + 2`` to ``L - 1`` write
nothing that a later position reads, so a prompt needs them at its last
position only. ``prefill_chunk`` runs the self-decoder on the chunk and,
under ``lax.cond`` on ``kv_len + C >= length`` (run-time scalars: one
program a chunk size), the cross-decoder and the head on the prompt's last
row alone; a chunk that is not the last returns zeros for logits nobody
reads. Exact, not an approximation (tests/test_phi4flash.py holds a chunk
that skipped and one forced not to to the same cache and logits).

The programs keep the contract's names and signatures and return, beside
their result, int32[3] counts (``COUNTERS``): ``ssm_state_updates`` ((slot,
scan layer) pairs a decode program updated for a line that decodes),
``ssm_chunk_tokens`` ((valid token, scan layer) pairs through the chunk
form) and ``cross_decoder_chunks_skipped`` (prefill chunks that ran the
self-decoder alone).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm.rings import ring_after_chunk, ring_positions
from ray_tpu.llm.served import ServedModel, token_step_programs
from ray_tpu.models import phi4flash
from ray_tpu.models.phi4flash import Phi4FlashConfig
from ray_tpu.models.routed import layer_of
from ray_tpu.ops.decode_attention import (
    decode_attention,
    decode_kv_block,
    decode_plan_of,
    kv_row_write,
)
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.prefill_attention import prefill_attention, prefill_kv_write
from ray_tpu.ops.selective_scan import (
    selective_scan_chunk,
    selective_scan_step,
)
from ray_tpu.util import tracing

COUNTERS = ("ssm_state_updates", "ssm_chunk_tokens",
            "cross_decoder_chunks_skipped")
_LEAVES = ("k", "v", "rk", "rv", "state", "conv")


def init_cache(cfg: Phi4FlashConfig, max_slots: int, max_seq: int):
    dt = cfg.jnp_dtype
    line = (1, max_slots, cfg.kv_pairs, max_seq, cfg.pair_dim)
    ring = (cfg.window_lines, max_slots, cfg.kv_pairs, cfg.sliding_window,
            cfg.pair_dim)
    return {
        "k": jnp.zeros(line, dt), "v": jnp.zeros(line, dt),
        "rk": jnp.zeros(ring, dt), "rv": jnp.zeros(ring, dt),
        "state": jnp.zeros((cfg.ssm_lines, max_slots, cfg.mamba_d_state,
                            cfg.d_inner), jnp.float32),
        "conv": jnp.zeros((cfg.ssm_lines, max_slots,
                           (cfg.mamba_d_conv - 1) * cfg.d_inner), dt)}


def _counts(cfg, updates, chunk_tokens, skipped):
    return jnp.stack([cfg.ssm_lines * updates, cfg.ssm_lines * chunk_tokens,
                      skipped]).astype(jnp.int32)


def _prefill_impl(cfg: Phi4FlashConfig, params, cache, tokens, kv_len,
                  length, slot, kmesh=None, always_cross: bool = False):
    """``prefill_chunk``'s body. ``always_cross`` (static) runs the
    cross-decoder whether or not the chunk is the prompt's last: the tests'
    comparison."""
    c = tokens.shape[0]
    w = cfg.sliding_window
    keep, di = cfg.mamba_d_conv - 1, cfg.d_inner
    scale = cfg.head_dim ** -0.5
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][None]              # [1, C, H]
    with tracing.part("attn"):
        positions = kv_len + jnp.arange(c)
        valid = positions < length
        # The chunk's rows that are the prompt's: all but a last chunk's
        # padding.
        n_valid = jnp.clip(length - kv_len, 0, c)
        held = ring_positions(kv_len, w)
        kpos = jnp.concatenate([held, positions])
        visible = phi4flash.window_visible(positions, kpos, w) \
            & (kpos < length)[None]
        # What each ring row holds after the chunk, and the chunk's row it
        # takes that from.
        fresh, source = ring_after_chunk(kv_len, n_valid, w, c)

    def ssm(line, sp, xn, state):
        kc, vc, rk, rv, st, cs = state
        x_in, z = phi4flash.ssm_inputs(cfg, sp, xn)
        with tracing.part("ssm_state"):
            # The slot's window and state, or zeros at a prompt's start.
            prior = jnp.where(kv_len > 0, lax.dynamic_slice(
                cs, (line, slot, 0), (1, 1, keep * di)), 0)
            h0 = jnp.where(kv_len > 0, lax.dynamic_slice(
                st, (line, slot, 0, 0), (1, 1, *st.shape[2:])), 0.0)
        window = phi4flash.conv_window(prior.reshape(1, keep, di), x_in)
        xc, dt, a, b, cc = phi4flash.ssm_scan_inputs(cfg, sp, window, c)
        with tracing.part("ssm"), tracing.part("ssm_scan"):
            y, h1 = selective_scan_chunk(xc[0], dt[0], a, b[0], cc[0],
                                         sp["d"], h0[0, 0], valid)
        with tracing.part("ssm_state"):
            st = lax.dynamic_update_slice(st, h1[None, None],
                                          (line, slot, 0, 0))
            # The window's rows that end at the last valid token.
            last = lax.dynamic_slice_in_dim(window, n_valid, keep, axis=1)
            cs = lax.dynamic_update_slice(
                cs, last.astype(cs.dtype).reshape(1, 1, -1), (line, slot, 0))
        return y[None].astype(xn.dtype), z, (kc, vc, rk, rv, st, cs)

    def window(line, q, k, v, state):
        kc, vc, rk, rv, st, cs = state

        def ring(stack):
            return lax.dynamic_slice(
                stack, (line, slot, 0, 0, 0), (1, 1, *stack.shape[2:]))[0]

        def turned(stack, old, new):
            # The ring after the chunk: its last valid rows, each in the
            # row of its position.
            rows = jnp.where(fresh, jnp.take(new[0], source, axis=1), old[0])
            return lax.dynamic_update_slice(
                stack, rows.astype(stack.dtype)[None, None],
                (line, slot, 0, 0, 0))

        with tracing.part("cache"):
            rk0, rv0 = ring(rk), ring(rv)                  # [1, P, W, 2d]
        o = phi4flash.packed_attention(
            q, jnp.concatenate([rk0.astype(k.dtype), k], axis=2),
            jnp.concatenate([rv0.astype(v.dtype), v], axis=2), visible, scale)
        with tracing.part("cache"):
            rk, rv = turned(rk, rk0, k), turned(rv, rv0, v)
        return o, (kc, vc, rk, rv, st, cs)

    def full(line, q, k, v, state):
        kc, vc, *rest = state
        with tracing.part("cache"):
            kc, vc = prefill_kv_write(kc, vc, k[0], v[0], 0, slot, kv_len)
        o = prefill_attention(q[0], kc, vc, 0, slot, kv_len, length,
                              sm_scale=scale, kmesh=kmesh)
        return o[None], (kc, vc, *rest)

    x, m, state = phi4flash.self_decoder(
        cfg, params, x, {"ssm": ssm, "window": window, "full": full},
        tuple(cache[k] for k in _LEAVES))
    cache = dict(zip(_LEAVES, state))
    last = kv_len + c >= length

    def answer(x, m):
        """The cross-decoder and the head on the prompt's last row."""
        with tracing.part("head"):
            row = jnp.clip(length - 1 - kv_len, 0, c - 1)
            x1 = lax.dynamic_slice_in_dim(x, row, 1, axis=1)  # [1, 1, H]
            m1 = lax.dynamic_slice_in_dim(m, row, 1, axis=1)

        def cross(q):
            return prefill_attention(
                q[0], cache["k"], cache["v"], 0, slot, length - 1, length,
                sm_scale=scale, kmesh=kmesh)[None]

        x1 = phi4flash.cross_decoder(cfg, params, x1, m1, cross)
        return phi4flash.lm_head(cfg, params, x1[0, 0], kmesh)

    if always_cross:
        logits = answer(x, m)
    else:
        logits = lax.cond(
            last, answer,
            lambda x, m: jnp.zeros((cfg.vocab_size,), jnp.float32), x, m)
    return cache, logits, _counts(cfg, 0, n_valid,
                                  1 - last.astype(jnp.int32))


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: Phi4FlashConfig, params, cache, tokens, kv_len,
                  length, slot, *, kmesh: KernelMesh | None = None):
    """Prefill ONE chunk of one sequence (the contract's program, see
    llm/llama_serving.prefill_chunk). Returns (cache, last-token logits [V],
    counts); the logits are zeros where the chunk is not the prompt's last
    (nobody reads them)."""
    return _prefill_impl(cfg, params, cache, tokens, kv_len, length, slot,
                         kmesh)


def _decode_impl(cfg: Phi4FlashConfig, params, cache, tokens, positions0,
                 write_mask, kmesh=None):
    """One token per slot against the line, the rings and the states.
    Returns (cache, logits [B, V], counts). A slot with ``write_mask``
    false writes no row, keeps its state, its window and its rings, and its
    logits mean nothing."""
    b = tokens.shape[0]
    w = cfg.sliding_window
    keep, di = cfg.mamba_d_conv - 1, cfg.d_inner
    scale = cfg.head_dim ** -0.5
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][:, None]           # [B, 1, H]
    with tracing.part("attn"):
        lengths = jnp.where(write_mask, positions0 + 1, 0)
        # A ring is read whole once it is full, and every row of it is at
        # or before the token: the walk's own mask by position is slack.
        ring_lengths = jnp.minimum(lengths, w)
        ring_row = jnp.mod(positions0, w)
        ring_seen = jnp.full((b,), w, jnp.int32)
        # The full line's eight readers attend at the same lengths, and the
        # rings' at theirs: two walks of live blocks, planned here and not
        # in the loop.
        plan = decode_plan_of(lengths, cache["k"], kmesh=kmesh)
        ring_plan = decode_plan_of(ring_lengths, cache["rk"], kmesh=kmesh)

    def ssm(line, sp, xn, state):
        kc, vc, rk, rv, st, cs = state
        x_in, z = phi4flash.ssm_inputs(cfg, sp, xn)
        with tracing.part("ssm_state"):
            prior = layer_of(cs, line).reshape(b, keep, di)
        window = phi4flash.conv_window(prior, x_in)
        xc, dt, a, bb, cc = phi4flash.ssm_scan_inputs(cfg, sp, window, 1)
        with tracing.part("ssm"), tracing.part("ssm_scan"):
            # A slot that does not decode decays nothing and adds nothing:
            # its state is written back as it was.
            y, h1 = selective_scan_step(
                xc[:, 0], dt[:, 0], a, bb[:, 0], cc[:, 0], sp["d"],
                layer_of(st, line), write_mask)
        with tracing.part("ssm_state"):
            st = lax.dynamic_update_index_in_dim(st, h1, line, 0)
            new = jnp.where(write_mask[:, None, None], window[:, 1:], prior)
            cs = lax.dynamic_update_index_in_dim(
                cs, new.astype(cs.dtype).reshape(b, -1), line, 0)
        return y[:, None].astype(xn.dtype), z, (kc, vc, rk, rv, st, cs)

    def window(line, q, k, v, state):
        kc, vc, rk, rv, st, cs = state
        with tracing.part("cache"):
            rk, rv = kv_row_write(rk, rv, k, v, line, ring_row, write_mask,
                                  kmesh=kmesh)
        o = decode_attention(q, rk, rv, line, ring_lengths, ring_seen,
                             plan=ring_plan, sm_scale=scale, kmesh=kmesh)
        return o, (kc, vc, rk, rv, st, cs)

    def attend(q, kc, vc):
        return decode_attention(q, kc, vc, 0, lengths, positions0, plan=plan,
                                sm_scale=scale, kmesh=kmesh)

    def full(line, q, k, v, state):
        kc, vc, *rest = state
        with tracing.part("cache"):
            kc, vc = kv_row_write(kc, vc, k, v, 0, positions0, write_mask,
                                  kmesh=kmesh)
        return attend(q, kc, vc), (kc, vc, *rest)

    x, m, state = phi4flash.self_decoder(
        cfg, params, x, {"ssm": ssm, "window": window, "full": full},
        tuple(cache[k] for k in _LEAVES))
    cache = dict(zip(_LEAVES, state))
    x = phi4flash.cross_decoder(
        cfg, params, x, m, lambda q: attend(q, cache["k"], cache["v"]))
    return (cache, phi4flash.lm_head(cfg, params, x[:, 0], kmesh),
            _counts(cfg, write_mask.sum(), 0, 0))


decode_step, decode_burst = token_step_programs(_decode_impl, COUNTERS)


def _refuse(config) -> None:
    """What this model does not run, said at construction."""
    for bad, what in (
            (config.speculative_model is not None,
             "a speculative draft: a rejected token's step of the scan's "
             "state and its row of a ring cannot be taken back"),
            (config.kv_block_size > 0,
             "kv_block_size > 0: a slot has a full line and rings of "
             "another length, and the block pool has one kind of line "
             "(ROADMAP R4)")):
        if bad:
            raise ValueError(f"Phi4FlashConfig does not support {what}")


SERVED = ServedModel(
    init_params=phi4flash.init_params,
    param_logical_axes=phi4flash.param_logical_axes,
    init_cache=init_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    kv_block=lambda cfg, max_seq: decode_kv_block(
        max_seq, cfg.pair_dim, cfg.jnp_dtype.itemsize),
    counters=COUNTERS,
    constants=lambda cfg: {"ssm_lines": cfg.ssm_lines,
                           "window_lines": cfg.window_lines,
                           "full_lines": 1,
                           "line_readers": cfg.line_readers,
                           "window": cfg.sliding_window,
                           "ssm_state_bytes": cfg.ssm_state_bytes},
    # A line is not all of a slot: the hand-off would have to ship the
    # rings, the states and the windows too, and a prefix has none to adopt.
    kv_handoff=False,
    prefix_from_line=False,
    refuse=_refuse,
)
