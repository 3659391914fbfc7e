"""What models/granite.py supplies to the scheduler (llm/served.ServedModel):
a slot that is mostly state, and the programs that run against it.

``{"k", "v", "state", "conv"}``, the slot second in all four:

- ``state`` ``[linear_lines, slots, groups, N, pack * P]`` float32:
  Mamba-2's state of a layer, every head's ``[N, P]``, two heads side by
  side in the lanes at heads of 64 (ops/ssd.state_shape: 128 heads of 128 x
  64 are 64 groups of 128 x 128, 4 MiB a slot and layer), of one size
  whatever the length;
- ``conv`` ``[linear_lines, slots, (taps - 1) * conv_dim]``: the last rows
  of that layer's ``[x | B | C]`` before its convolution
  (llm/linear_state.py, Qwen3-Next's and Ling's two kinds of leaf);
- ``k``, ``v`` ``[attention_lines, slots, kv_heads, max_seq, head_dim]``:
  the lines that grow with the sequence, one an attention layer (laid as
  llm/qwen3_next_serving.init_cache lays its own).

At the published widths nine Mamba layers to an attention layer make a slot
of 2,048 positions 36 MiB of state beside at most 8 MiB of keys and values:
a decode step's cache traffic is the states', whatever the lines' lengths.

All ride every loop as carry. What llm/qwen3_next_serving.py says of a
state that is not a line holds here: a padded chunk's rows past the
prompt's end and a slot that does not decode enter the rule with ``dt = 0``
and change no state, bit for bit; a chunk that starts at ``kv_len = 0``
starts from zeros whatever the slot held before; a prompt's prefix cannot be
adopted from another slot's line.

Prefill runs the rule's chunked form (ops/ssd.ssd_chunk: matrix products a
sub-chunk, the state handed from sub-chunk to sub-chunk and, through the
cache, from chunk to chunk; the slot's nine states are read out of the leaf
once before the layers and written back once after them, 36 MiB each way, so
that the leaf has one read and one update in the program and none that the
compiler could compute twice: ``linear_state.slot_states``); a decode step
its one-token case on every slot's state: ``ssd_step`` takes the stacked
leaf and the scan's line and writes that line's states in place (a kernel on
a TPU, the leaf aliased to its result: this module neither slices a line of
states out nor writes one back; ``linear_state.step_end`` keeps the
window).

The programs keep the contract's names and signatures and return, beside
their result, int32[8] counts summed over the program's layers and steps
(``linear_state.COUNTERS``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm import linear_state
from ray_tpu.llm.served import ServedModel, token_step_programs
from ray_tpu.models import granite
from ray_tpu.models.granite import ATTENTION, MAMBA, GraniteConfig
from ray_tpu.models.qwen3_next import conv_window
from ray_tpu.models.routed import layer_of
from ray_tpu.ops.decode_attention import (
    decode_attention,
    decode_kv_block,
    decode_plan_of,
    kv_row_write,
)
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.prefill_attention import prefill_attention, prefill_kv_write
from ray_tpu.ops.ssd import ssd_chunk, ssd_step
from ray_tpu.util import tracing

COUNTERS = linear_state.COUNTERS


def init_cache(cfg: GraniteConfig, max_slots: int, max_seq: int):
    dt = cfg.jnp_dtype
    lines = (cfg.attention_lines, max_slots, cfg.num_kv_heads, max_seq,
             cfg.head_dim)
    return {
        "k": jnp.zeros(lines, dt), "v": jnp.zeros(lines, dt),
        **linear_state.init_leaves(
            cfg.linear_lines, max_slots, *cfg.state_shape, cfg.mamba_d_conv,
            cfg.conv_dim, dt)}


_LEAVES = ("k", "v", "state", "conv")


def _run(cfg, params, x, cache, operators, valid, own, kmesh):
    """Every layer with the cache's leaves as carry. ``own`` is
    (linear_state_updates, linear_chunk_tokens) of ONE Mamba layer."""
    x, leaves, counts = granite.run_layers(
        cfg, params, x, operators, tuple(cache[k] for k in _LEAVES), valid,
        kmesh)
    counts = linear_state.with_own_counts(counts, cfg.linear_lines, own)
    return x, dict(zip(_LEAVES, leaves)), counts


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: GraniteConfig, params, cache, tokens, kv_len, length,
                  slot, *, kmesh: KernelMesh | None = None):
    """Prefill ONE chunk of one sequence (the contract's program, see
    llm/llama_serving.prefill_chunk). Returns (cache, last-token logits [V],
    counts)."""
    c = tokens.shape[0]
    keep = cfg.mamba_d_conv - 1
    x = granite.embed(cfg, params, tokens)[None]              # [1, C, H]
    with tracing.part("attn"):
        valid = (kv_len + jnp.arange(c) < length)[None]
        # The chunk's rows that are the prompt's: all but a last chunk's
        # padding.
        n_valid = jnp.clip(length - kv_len, 0, c)

    def mamba(line, lp, xn, state):
        kc, vc, slot_st, cs = state
        xbc, z, dt = granite.mamba_inputs(cfg, lp, xn)
        prior = linear_state.window_start(cs, line, slot, kv_len)
        window = conv_window(prior.reshape(1, keep, cfg.conv_dim), xbc)
        xs, bs, cs_in = granite.mamba_heads(cfg, lp, window, c)
        with tracing.part("linear_attn"), tracing.part("ssd"):
            # A padded row has no step: it decays nothing and adds nothing.
            y, s1 = ssd_chunk(
                xs[0], jnp.where(valid[0, :, None], dt[0], 0.0),
                -jnp.exp(lp["a_log"]), bs[0], cs_in[0],
                layer_of(slot_st, line))
        with tracing.part("linear_state"):
            slot_st = lax.dynamic_update_index_in_dim(slot_st, s1, line, 0)
        cs = linear_state.window_end(cs, window, line, slot, n_valid)
        return (granite.mamba_output(cfg, lp, y[None], xs, z, xn.dtype),
                (kc, vc, slot_st, cs))

    def attention(line, ap, xn, state):
        kc, vc, st, cs = state
        q, k, v = granite.attention_heads(cfg, ap, xn)
        with tracing.part("cache"):
            kc, vc = prefill_kv_write(kc, vc, k[0], v[0], line, slot, kv_len)
        o = prefill_attention(q[0], kc, vc, line, slot, kv_len, length,
                              sm_scale=cfg.attention_multiplier, kmesh=kmesh)
        o = o.transpose(1, 0, 2).reshape(1, c, -1)
        return granite.attention_output(ap, o, xn.dtype), (kc, vc, st, cs)

    # The slot's states on every line, read once before the layers and
    # written once after them (linear_state.slot_states says why).
    states = cache["state"]
    x, cache, counts = _run(
        cfg, params, x,
        {**cache, "state": linear_state.slot_states(states, slot, kv_len)},
        {MAMBA: mamba, ATTENTION: attention}, valid,
        (jnp.zeros((), jnp.int32), n_valid), kmesh)
    cache["state"] = linear_state.put_slot_states(states, cache["state"],
                                                  slot)
    # The head on the one row that is kept.
    with tracing.part("head"):
        last = x[0, jnp.clip(length - 1 - kv_len, 0, c - 1)]
    return cache, granite.lm_head(cfg, params, last, kmesh), counts


def _decode_impl(cfg: GraniteConfig, params, cache, tokens, positions0,
                 write_mask, kmesh=None):
    """One token per slot against the states and the lines. Returns (cache,
    logits [B, V], counts). A slot with ``write_mask`` false writes no row,
    keeps its state and its window, is routed nowhere, and its logits mean
    nothing."""
    b = tokens.shape[0]
    x = granite.embed(cfg, params, tokens)[:, None]           # [B, 1, H]
    with tracing.part("attn"):
        lengths = jnp.where(write_mask, positions0 + 1, 0)
        valid = write_mask[:, None]
        # Every attention attends at the same lengths: one walk of the live
        # blocks, planned here and not in the loop.
        plan = decode_plan_of(lengths, cache["k"], kmesh=kmesh)

    def mamba(line, lp, xn, state):
        kc, vc, st, cs = state
        xbc, z, dt = granite.mamba_inputs(cfg, lp, xn)
        prior = linear_state.step_start(cs, line, cfg.conv_dim)
        window = conv_window(prior, xbc)
        xs, bs, cs_in = granite.mamba_heads(cfg, lp, window, 1)
        with tracing.part("linear_attn"), tracing.part("ssd"):
            # A slot that does not decode has no step: its state is left
            # as it was.
            y, st = ssd_step(
                xs[:, 0], jnp.where(valid, dt[:, 0], 0.0),
                -jnp.exp(lp["a_log"]), bs[:, 0], cs_in[:, 0], st, line)
        cs = linear_state.step_end(cs, window, prior, line, write_mask)
        return (granite.mamba_output(cfg, lp, y[:, None], xs, z, xn.dtype),
                (kc, vc, st, cs))

    def attention(line, ap, xn, state):
        kc, vc, st, cs = state
        q, k, v = granite.attention_heads(cfg, ap, xn)
        with tracing.part("cache"):
            kc, vc = kv_row_write(kc, vc, k, v, line, positions0, write_mask,
                                  kmesh=kmesh)
        o = decode_attention(q, kc, vc, line, lengths, positions0, plan=plan,
                             sm_scale=cfg.attention_multiplier, kmesh=kmesh)
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, -1)
        return granite.attention_output(ap, o, xn.dtype), (kc, vc, st, cs)

    x, cache, counts = _run(
        cfg, params, x, cache, {MAMBA: mamba, ATTENTION: attention}, valid,
        (write_mask.sum(), jnp.zeros((), jnp.int32)), kmesh)
    return cache, granite.lm_head(cfg, params, x[:, 0], kmesh), counts


decode_step, decode_burst = token_step_programs(_decode_impl, COUNTERS)


def _refuse(config) -> None:
    """What this model does not run, said at construction."""
    if config.speculative_model is not None:
        raise ValueError(
            "GraniteConfig does not support a speculative draft: a rejected "
            "token's rows lie past the accepted length and are overwritten, "
            "its step of the rule's state cannot be taken back")


SERVED = ServedModel(
    init_params=granite.init_params,
    param_logical_axes=granite.param_logical_axes,
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
