"""What models/lfm2.py supplies to the scheduler (llm/served.ServedModel): a
cache with two kinds of leaf and the programs that run against it.

``{"kv", "conv"}``, the slot second in both:

- ``kv`` ``[attention_lines, slots, kv_heads, max_seq, 2 * head_dim]``: the
  lines that grow with the sequence, one a ``full_attention`` layer, keys
  and values of a head side by side in one row (a head of 64 alone fills
  half a lane row and would cost a cached position twice its bytes:
  ops/decode_attention.py, "Heads of half a lane row");
- ``conv`` ``[conv_lines, slots, (conv_L_cache - 1) * hidden]``: the state
  of a ``conv`` layer, the last rows of its gated input one after the other
  in a slot's row (slots on the sublanes: the layout XLA gives the decode
  step's update of every slot, so that a burst begins and ends with no
  re-layout of the leaf), of one size whatever the length.

Both ride every loop as carry. Three things follow from a state that is not
a line:

- a prefill chunk is padded, so the state it leaves is the one after the
  prompt's last token (``length - 1``), not after the chunk's last row; a
  chunk that starts at ``kv_len = 0`` starts from zeros whatever the slot
  held before;
- a decode step runs every slot, so a slot with ``write_mask`` false (idle,
  or between two chunks of its prompt) keeps its state untouched;
- the state at an earlier length is nowhere, so a prompt's prefix cannot be
  adopted from another slot's line (``ServedModel.prefix_from_line``).

The programs keep the contract's names (``prefill_chunk``, ``decode_step``,
``decode_burst``: a device trace shows ``jit_<name>``; the last two are
built from ``_decode_impl`` by llm/served.token_step_programs;
``mixed_burst``, the burst whose steps carry a prefill chunk each, from it
and ``_mixed_impl`` by llm/served.mixed_burst_program) and signatures, and
return the routed layers' counts
(models/routed.MOE_COUNTERS, int32[6], summed over the program's layers and
steps) beside their result; the scheduler adds them up where it fetches the
tokens.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm.served import (
    ServedModel,
    mixed_burst_program,
    mixed_rows,
    token_step_programs,
)
from ray_tpu.models import lfm2
from ray_tpu.models.lfm2 import ATTENTION, CONV, Lfm2Config
from ray_tpu.models.routed import MOE_COUNTERS, layer_of
from ray_tpu.ops.decode_attention import (
    decode_attention,
    decode_kv_block,
    decode_plan_of,
    kv_row_write,
)
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.prefill_attention import prefill_attention, prefill_kv_write
from ray_tpu.ops.rope import rope_frequencies
from ray_tpu.util import tracing


def init_cache(cfg: Lfm2Config, max_slots: int, max_seq: int):
    dt = cfg.jnp_dtype
    return {
        "kv": jnp.zeros((cfg.attention_lines, max_slots, cfg.num_kv_heads,
                         max_seq, 2 * cfg.head_dim), dt),
        "conv": jnp.zeros((cfg.conv_lines, max_slots,
                           (cfg.conv_L_cache - 1) * cfg.hidden_size), dt)}


def _run(cfg, params, x, cache, operators, valid, kmesh):
    x, (kv, conv), counts = lfm2.run_layers(
        cfg, params, x, operators, (cache["kv"], cache["conv"]), valid, kmesh)
    return x, {"kv": kv, "conv": conv}, counts


# A program's operators are made of these halves: the chunk's (one slot,
# ``c`` rows from ``kv_len`` on) and the lines' (every slot, a row each).
# ``prefill_chunk`` runs the first, ``_decode_impl`` the second, and a mixed
# step both, on the rows of one array.

def _chunk_prior(cfg, cs, line, slot, kv_len):
    """The state a chunk's convolution starts from: the slot's, or zeros at
    a prompt's start."""
    keep = cfg.conv_L_cache - 1
    with tracing.part("conv_state"):
        prior = lax.dynamic_slice(
            cs, (line, slot, 0), (1, 1, keep * cfg.hidden_size))
        return jnp.where(kv_len > 0, prior, 0).reshape(
            1, keep, cfg.hidden_size)


def _chunk_keep(cfg, cs, zz, line, slot, n_valid):
    """The state a chunk leaves its slot. zz is the prior rows, then the
    chunk's: the rows that end at the last valid token."""
    with tracing.part("conv_state"):
        last = lax.dynamic_slice_in_dim(zz, n_valid, cfg.conv_L_cache - 1,
                                        axis=1)
        return lax.dynamic_update_slice(
            cs, last.astype(cs.dtype).reshape(1, 1, -1), (line, slot, 0))


def _chunk_attend(kv, q, k, v, line, slot, kv_len, length, kmesh):
    """A chunk's rows written to its slot's line and attended from it:
    q [1, nh, C, D], k and v [1, nkv, C, D] -> (kv, o [1, C, nh * D])."""
    with tracing.part("cache"):
        kv, _ = prefill_kv_write(kv, None, k[0], v[0], line, slot, kv_len)
    o = prefill_attention(q[0], kv, None, line, slot, kv_len, length,
                          kmesh=kmesh)
    return kv, o.transpose(1, 0, 2).reshape(1, q.shape[2], -1)


def _lines_prior(cfg, cs, line):
    """Every slot's state of one convolution: [B, conv_L_cache - 1, H]."""
    with tracing.part("conv_state"):
        return layer_of(cs, line).reshape(
            cs.shape[1], cfg.conv_L_cache - 1, cfg.hidden_size)


def _lines_keep(cs, zz, prior, line, write_mask):
    """The states after a step: a slot that does not decode keeps its own."""
    with tracing.part("conv_state"):
        new = jnp.where(write_mask[:, None, None], zz[:, 1:], prior)
        return lax.dynamic_update_index_in_dim(
            cs, new.astype(cs.dtype).reshape(new.shape[0], -1), line, 0)


def _lines_attend(kv, q, k, v, line, lengths, positions0, write_mask, plan,
                  kmesh):
    """The lines' rows written and attended from: q [B, nh, 1, D], k and v
    [B, nkv, 1, D] -> (kv, o [B, 1, nh * D])."""
    with tracing.part("cache"):
        kv, _ = kv_row_write(kv, None, k, v, line, positions0, write_mask,
                             kmesh=kmesh)
    o = decode_attention(q, kv, None, line, lengths, positions0, plan=plan,
                         kmesh=kmesh)
    return kv, o.transpose(0, 2, 1, 3).reshape(q.shape[0], 1, -1)


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: Lfm2Config, params, cache, tokens, kv_len, length,
                  slot, *, kmesh: KernelMesh | None = None):
    """Prefill ONE chunk of one sequence (the contract's program, see
    llm/llama_serving.prefill_chunk). Returns (cache, last-token logits [V],
    counts)."""
    c = tokens.shape[0]
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][None]              # [1, C, H]
    with tracing.part("attn"):
        positions = kv_len + jnp.arange(c)
        valid = (positions < length)[None]
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)
        # The chunk's rows that are the prompt's: all but a last chunk's
        # padding.
        n_valid = jnp.clip(length - kv_len, 0, c)

    def conv(line, cp, xn, state):
        kv, cs = state
        y, zz = lfm2.short_conv(cfg, cp, xn,
                                _chunk_prior(cfg, cs, line, slot, kv_len))
        return y, (kv, _chunk_keep(cfg, cs, zz, line, slot, n_valid))

    def attention(line, ap, xn, state):
        kv, cs = state
        q, k, v = lfm2.attention_heads(cfg, ap, xn, positions, inv_freq)
        kv, o = _chunk_attend(kv, q, k, v, line, slot, kv_len, length,
                              kmesh)
        return (o @ ap["wo"]).astype(xn.dtype), (kv, cs)

    x, cache, counts = _run(cfg, params, x, cache,
                            {CONV: conv, ATTENTION: attention}, valid, kmesh)
    # The head on the one row that is kept.
    with tracing.part("head"):
        last = x[0, jnp.clip(length - 1 - kv_len, 0, c - 1)]
    return cache, lfm2.lm_head(cfg, params, last, kmesh), counts


def _decode_impl(cfg: Lfm2Config, params, cache, tokens, positions0,
                 write_mask, kmesh=None):
    """One token per slot against the cache and the states. Returns (cache,
    logits [B, V], counts). A slot with ``write_mask`` false writes no row,
    keeps its state, is routed nowhere, and its logits mean nothing."""
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][:, None]           # [B, 1, H]
    with tracing.part("attn"):
        positions = positions0[:, None]
        lengths = jnp.where(write_mask, positions0 + 1, 0)
        valid = write_mask[:, None]
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)
        # Both attentions attend at the same lengths: one walk of the live
        # blocks, planned here and not in the loops.
        plan = decode_plan_of(lengths, cache["kv"], kmesh=kmesh)

    def conv(line, cp, xn, state):
        kv, cs = state
        prior = _lines_prior(cfg, cs, line)
        y, zz = lfm2.short_conv(cfg, cp, xn, prior)
        return y, (kv, _lines_keep(cs, zz, prior, line, write_mask))

    def attention(line, ap, xn, state):
        kv, cs = state
        q, k, v = lfm2.attention_heads(cfg, ap, xn, positions, inv_freq)
        kv, o = _lines_attend(kv, q, k, v, line, lengths, positions0,
                              write_mask, plan, kmesh)
        return (o @ ap["wo"]).astype(xn.dtype), (kv, cs)

    x, cache, counts = _run(cfg, params, x, cache,
                            {CONV: conv, ATTENTION: attention}, valid, kmesh)
    return cache, lfm2.lm_head(cfg, params, x[:, 0], kmesh), counts


def _mixed_impl(cfg: Lfm2Config, params, cache, tokens, positions0,
                write_mask, chunk, kv_len, length, slot, kmesh=None):
    """``mixed_step`` of llm/served.mixed_burst_program. The norms, the
    projections and the routed layer see all rows at once (a routed layer's
    experts are fetched once for both: one layer-step in its counts); the
    operators split them, the chunk's rows to the chunk's halves and the
    lines' to the lines'."""
    c, b = chunk.shape[0], tokens.shape[0]
    with tracing.part("attn"):
        ids, positions, valid, lengths, lines_of = mixed_rows(
            chunk, tokens, kv_len, length, positions0, write_mask)
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)
        n_valid = jnp.clip(length - kv_len, 0, c)
        plan = decode_plan_of(lengths, cache["kv"], kmesh=kmesh)
    with tracing.part("embed"):
        x = params["embed_tokens"][ids][None]             # [1, C + B, H]

    def conv(line, cp, xn, state):
        kv, cs = state
        z, gate_out = lfm2.conv_gates(cp, xn)
        prior = _lines_prior(cfg, cs, line)
        zz = lfm2.conv_window(_chunk_prior(cfg, cs, line, slot, kv_len),
                              z[:, :c])
        zz_lines = lfm2.conv_window(prior, z[0, c:, None])
        v = jnp.concatenate([lfm2.conv_taps(cfg, cp, zz, c),
                             lfm2.conv_taps(cfg, cp, zz_lines, 1)
                             .reshape(1, b, -1)], axis=1)
        # The lines' update writes every slot's row, the chunk's slot its
        # old state: the chunk's comes after it.
        cs = _lines_keep(cs, zz_lines, prior, line, write_mask)
        cs = _chunk_keep(cfg, cs, zz, line, slot, n_valid)
        return lfm2.conv_out(cp, gate_out, v, xn.dtype), (kv, cs)

    def attention(line, ap, xn, state):
        kv, cs = state
        q, k, v = lfm2.attention_heads(cfg, ap, xn, positions, inv_freq)
        kv, o = _chunk_attend(kv, q[:, :, :c], k[:, :, :c], v[:, :, :c],
                              line, slot, kv_len, length, kmesh)
        kv, o_lines = _lines_attend(
            kv, *(a[0, :, c:].transpose(1, 0, 2)[:, :, None]
                  for a in (q, k, v)),
            line, lengths, positions0, write_mask, plan, kmesh)
        o = jnp.concatenate([o, o_lines.reshape(1, b, -1)], axis=1)
        return (o @ ap["wo"]).astype(xn.dtype), (kv, cs)

    x, cache, counts = _run(cfg, params, x, cache,
                            {CONV: conv, ATTENTION: attention}, valid[None],
                            kmesh)
    return cache, lfm2.lm_head(cfg, params, lines_of(x), kmesh), counts


decode_step, decode_burst = token_step_programs(_decode_impl, MOE_COUNTERS)
mixed_burst = mixed_burst_program(_decode_impl, _mixed_impl, MOE_COUNTERS)


def _refuse(config) -> None:
    """What this model does not run, said at construction."""
    if config.speculative_model is not None:
        raise ValueError(
            "Lfm2Config does not support a speculative draft: a rejected "
            "token's rows lie past the accepted length and are overwritten, "
            "its step of the convolution's state cannot be taken back")


SERVED = ServedModel(
    init_params=lfm2.init_params,
    param_logical_axes=lfm2.param_logical_axes,
    init_cache=init_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    # The positions a step's attention fetches at a time, a call; a cached
    # position is a packed row in each of the attention lines.
    kv_block=lambda cfg, max_seq: decode_kv_block(
        max_seq, 2 * cfg.head_dim, cfg.jnp_dtype.itemsize),
    counters=MOE_COUNTERS,
    constants=lambda cfg: {"moe_experts_held": cfg.experts_held,
                           "attention_lines": cfg.attention_lines,
                           "conv_lines": cfg.conv_lines},
    # A line is not all of a slot: the hand-off would have to ship the
    # convolution's states too, and a prefix has none to adopt.
    kv_handoff=False,
    prefix_from_line=False,
    refuse=_refuse,
    # A chunk and a step are both bound by the experts' bytes: riding, a
    # chunk's rows pass the routed layers on the step's fetch.
    mixed_burst=mixed_burst,
)
