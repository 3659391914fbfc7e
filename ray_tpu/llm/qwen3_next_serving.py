"""What models/qwen3_next.py supplies to the scheduler
(llm/served.ServedModel): a cache with three kinds of leaf and the programs
that run against it.

``{"k", "v", "state", "conv"}``, the slot second in all four:

- ``k``, ``v`` ``[attention_lines, slots, kv_heads, max_seq, head_dim]``:
  the lines that grow with the sequence, one a gated attention layer (the
  per-head slot cache of llm/served.py; a head of 256 fills two lane rows,
  so keys and values are leaves of their own);
- ``state`` ``[linear_lines, slots, value_heads, Dk, Dv]`` float32: the
  gated delta rule's state of a Gated DeltaNet layer, a matrix a value
  head that every token of the sequence has decayed and corrected
  (ops/gated_delta.py), of one size whatever the length;
- ``conv`` ``[linear_lines, slots, (taps - 1) * conv_dim]``: the last rows
  of that layer's ``[q | k | v]`` before its convolution, one after the
  other in a slot's row (llm/lfm2_serving.py's layout and for its reason).

All ride every loop as carry. What llm/lfm2_serving.py says of a state that
is not a line holds here for two leaves:

- a prefill chunk is padded, so the state it leaves is the one after the
  prompt's last token: a row past the prompt's end enters the rule with
  ``g = 0`` and ``beta = 0`` and changes nothing, and the window kept ends
  at the last valid row; a chunk that starts at ``kv_len = 0`` starts from
  zeros whatever the slot held before;
- a decode step runs every slot, so a slot with ``write_mask`` false keeps
  its state (``g = 0``, ``beta = 0``) and its window;
- the state at an earlier length is nowhere, so a prompt's prefix cannot
  be adopted from another slot's line (``ServedModel.prefix_from_line``).

Prefill runs the rule's chunked form (sub-chunks of 64 positions, the state
handed from sub-chunk to sub-chunk and, through the cache, from chunk to
chunk); a decode step its one-token case on every slot's state:
``gated_delta_step`` takes the stacked leaf and the scan's line and writes
that line's states in place (a kernel on a TPU, the leaf aliased to its
result: this module neither slices a line of states out nor writes one
back; ``linear_state.step_end`` keeps the window).

The programs keep the contract's names and signatures and return, beside
their result, int32[8] counts summed over the program's layers and steps
(``COUNTERS``): the routed layers' (models/routed.MOE_COUNTERS) and two of
this model's own, ``linear_state_updates`` ((slot, linear layer) pairs a
decode program updated for a line that decodes) and ``linear_chunk_tokens``
((valid token, linear layer) pairs through the chunked form).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.llm import linear_state
from ray_tpu.llm.served import ServedModel, token_step_programs
from ray_tpu.models import qwen3_next
from ray_tpu.models.qwen3_next import ATTENTION, LINEAR, Qwen3NextConfig
from ray_tpu.ops.decode_attention import (
    decode_attention,
    decode_kv_block,
    decode_plan_of,
    kv_row_write,
)
from ray_tpu.ops.gated_delta import gated_delta_chunk, gated_delta_step
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.prefill_attention import prefill_attention, prefill_kv_write
from ray_tpu.ops.rope import rope_frequencies
from ray_tpu.util import tracing

COUNTERS = linear_state.COUNTERS


def init_cache(cfg: Qwen3NextConfig, max_slots: int, max_seq: int):
    dt = cfg.jnp_dtype
    lines = (cfg.attention_lines, max_slots, cfg.num_kv_heads, max_seq,
             cfg.head_dim)
    return {
        "k": jnp.zeros(lines, dt), "v": jnp.zeros(lines, dt),
        **linear_state.init_leaves(
            cfg.linear_lines, max_slots, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.conv_dim, dt)}


_LEAVES = ("k", "v", "state", "conv")


def _run(cfg, params, x, cache, operators, valid, own, kmesh):
    """Every layer with the cache's leaves as carry. ``own`` is
    (linear_state_updates, linear_chunk_tokens) of ONE linear layer."""
    x, leaves, counts = qwen3_next.run_layers(
        cfg, params, x, operators, tuple(cache[k] for k in _LEAVES), valid,
        kmesh)
    counts = linear_state.with_own_counts(counts, cfg.linear_lines, own)
    return x, dict(zip(_LEAVES, leaves)), counts


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: Qwen3NextConfig, params, cache, tokens, kv_len,
                  length, slot, *, kmesh: KernelMesh | None = None):
    """Prefill ONE chunk of one sequence (the contract's program, see
    llm/llama_serving.prefill_chunk). Returns (cache, last-token logits [V],
    counts)."""
    c = tokens.shape[0]
    keep = cfg.linear_conv_kernel_dim - 1
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][None]              # [1, C, H]
    with tracing.part("attn"):
        positions = kv_len + jnp.arange(c)
        valid = (positions < length)[None]
        inv_freq = rope_frequencies(cfg.rotary_dim, cfg.rope_theta)
        # The chunk's rows that are the prompt's: all but a last chunk's
        # padding.
        n_valid = jnp.clip(length - kv_len, 0, c)

    def linear(line, lp, xn, state):
        kc, vc, st, cs = state
        mixed, z, g, beta = qwen3_next.linear_inputs(cfg, lp, xn)
        prior, s0 = linear_state.chunk_start(st, cs, line, slot, kv_len)
        window = qwen3_next.conv_window(
            prior.reshape(1, keep, cfg.conv_dim), mixed)
        q, k, v = qwen3_next.linear_key_heads(cfg, lp, window, c)
        with tracing.part("linear_attn"), tracing.part("delta_rule"):
            # A padded row decays nothing and corrects nothing.
            o, s1 = gated_delta_chunk(
                q[0], k[0], v[0], jnp.where(valid[0, :, None], g[0], 0.0),
                jnp.where(valid[0, :, None], beta[0], 0.0), s0[0, 0])
        st, cs = linear_state.chunk_end(st, cs, s1, window, line, slot,
                                        n_valid)
        return (qwen3_next.linear_output(cfg, lp, o[None], z, xn.dtype),
                (kc, vc, st, cs))

    def attention(line, ap, xn, state):
        kc, vc, st, cs = state
        q, k, v, gate = qwen3_next.attention_heads(cfg, ap, xn, positions,
                                                   inv_freq)
        with tracing.part("cache"):
            kc, vc = prefill_kv_write(kc, vc, k[0], v[0], line, slot, kv_len)
        o = prefill_attention(q[0], kc, vc, line, slot, kv_len, length,
                              kmesh=kmesh)
        o = o.transpose(1, 0, 2).reshape(1, c, -1)
        return (qwen3_next.attention_output(ap, o, gate, xn.dtype),
                (kc, vc, st, cs))

    x, cache, counts = _run(
        cfg, params, x, cache, {LINEAR: linear, ATTENTION: attention}, valid,
        (jnp.zeros((), jnp.int32), n_valid), kmesh)
    # The head on the one row that is kept.
    with tracing.part("head"):
        last = x[0, jnp.clip(length - 1 - kv_len, 0, c - 1)]
    return cache, qwen3_next.lm_head(cfg, params, last, kmesh), counts


def _decode_impl(cfg: Qwen3NextConfig, params, cache, tokens, positions0,
                 write_mask, kmesh=None):
    """One token per slot against the lines and the states. Returns (cache,
    logits [B, V], counts). A slot with ``write_mask`` false writes no row,
    keeps its state and its window, is routed nowhere, and its logits mean
    nothing."""
    b = tokens.shape[0]
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][:, None]           # [B, 1, H]
    with tracing.part("attn"):
        positions = positions0[:, None]
        lengths = jnp.where(write_mask, positions0 + 1, 0)
        valid = write_mask[:, None]
        inv_freq = rope_frequencies(cfg.rotary_dim, cfg.rope_theta)
        # Every attention attends at the same lengths: one walk of the live
        # blocks, planned here and not in the loop.
        plan = decode_plan_of(lengths, cache["k"], kmesh=kmesh)

    def linear(line, lp, xn, state):
        kc, vc, st, cs = state
        mixed, z, g, beta = qwen3_next.linear_inputs(cfg, lp, xn)
        prior = linear_state.step_start(cs, line, cfg.conv_dim)
        window = qwen3_next.conv_window(prior, mixed)
        q, k, v = qwen3_next.linear_heads(cfg, lp, window, 1)
        with tracing.part("linear_attn"), tracing.part("delta_rule"):
            # A slot that does not decode decays nothing and corrects
            # nothing: its state is left as it was.
            o, st = gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0], jnp.where(valid, g[:, 0], 0.0),
                jnp.where(valid, beta[:, 0], 0.0), st, line)
        cs = linear_state.step_end(cs, window, prior, line, write_mask)
        return (qwen3_next.linear_output(cfg, lp, o[:, None], z, xn.dtype),
                (kc, vc, st, cs))

    def attention(line, ap, xn, state):
        kc, vc, st, cs = state
        q, k, v, gate = qwen3_next.attention_heads(cfg, ap, xn, positions,
                                                   inv_freq)
        with tracing.part("cache"):
            kc, vc = kv_row_write(kc, vc, k, v, line, positions0, write_mask,
                                  kmesh=kmesh)
        o = decode_attention(q, kc, vc, line, lengths, positions0, plan=plan,
                             kmesh=kmesh)
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, -1)
        return (qwen3_next.attention_output(ap, o, gate, xn.dtype),
                (kc, vc, st, cs))

    x, cache, counts = _run(
        cfg, params, x, cache, {LINEAR: linear, ATTENTION: attention}, valid,
        (write_mask.sum(), jnp.zeros((), jnp.int32)), kmesh)
    return cache, qwen3_next.lm_head(cfg, params, x[:, 0], kmesh), counts


decode_step, decode_burst = token_step_programs(_decode_impl, COUNTERS)


def _refuse(config) -> None:
    """What this model does not run, said at construction."""
    if config.speculative_model is not None:
        raise ValueError(
            "Qwen3NextConfig does not support a speculative draft: a "
            "rejected token's rows lie past the accepted length and are "
            "overwritten, its step of the rule's state cannot be taken back")


SERVED = ServedModel(
    init_params=qwen3_next.init_params,
    param_logical_axes=qwen3_next.param_logical_axes,
    init_cache=init_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    kv_block=lambda cfg, max_seq: decode_kv_block(
        max_seq, cfg.head_dim, cfg.jnp_dtype.itemsize),
    counters=COUNTERS,
    constants=lambda cfg: {"moe_experts_held": cfg.experts_held,
                           "attention_lines": cfg.attention_lines,
                           "linear_lines": cfg.linear_lines,
                           "linear_state_bytes": cfg.linear_state_bytes},
    # A line is not all of a slot: the hand-off would have to ship the
    # states and the windows too, and a prefix has none to adopt.
    kv_handoff=False,
    prefix_from_line=False,
    refuse=_refuse,
)
