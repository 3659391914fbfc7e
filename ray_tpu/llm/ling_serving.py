"""What models/ling.py supplies to the scheduler (llm/served.ServedModel): a
slot with a delta-rule state and a latent line, and the programs that run
against both.

``{"latent", "conv", "state0", "state1", ...}``, the slot second in all:

- ``latent`` ``[latent_lines, slots, max_seq, latent_row]``: the lines that
  grow with the sequence, one a gated latent attention layer (llm/latent.py:
  the row every head reads, written in place and attended from the live
  blocks of a slot's line);
- ``conv`` ``[linear_lines, slots, (taps - 1) * conv_dim]``: Kimi Delta
  Attention's convolutions' window, and ``state<i>`` ``[lines, slots, heads,
  D, D]`` float32: its state, of one size whatever the length
  (llm/linear_state.py, Qwen3-Next's two kinds of leaf). **No state leaf is
  updated twice in a step** (:func:`_state_leaves`): a KDA layer of a group
  that ``models/ling.run_layers`` writes out has a leaf of its own (one
  line), and the groups it scans share a leaf a place in the group, a line a
  group, written once an iteration. Under one stacked leaf over all ten
  layers (1.875 GiB) a decode burst at the cell's 96 slots came out wrong on
  the chip (a sound ``decode_step``; margins of 2 to 5 after a burst): the
  compiler, short of memory by its own count, rematerialised a layer's
  update of the leaf where the next layer's read and the next update both
  used it (``add_dynamic-update-slice_fusion.N.remat``: ``decay * S + k
  d^T`` a second time from the buffer the first had already written in
  place), so that layer's states were decayed and corrected twice a step.
  An update whose only use is the step's result has nothing to be
  rematerialised for (tests/test_tpu_aot.py holds that none is). Since
  PR 59 a step's update is a kernel's in-place operand, which the compiler
  cannot compute a second time, so the hazard is gone from the decode
  programs; whether the ten leaves could be one again is ROADMAP R5 (g).

All ride every loop as carry. What llm/qwen3_next_serving.py says of a
state that is not a line holds here: a padded chunk's rows past the prompt's
end and a slot that does not decode enter the rule with ``g = 0`` and
``beta = 0`` and change no state, bit for bit; a chunk that starts at
``kv_len = 0`` starts from zeros whatever the slot held before; a prompt's
prefix cannot be adopted from another slot's line.

Prefill runs the rule's chunked form with a decay a key channel under the
gate's floor (``cfg.kda_lower_bound``); a decode step its one-token case on
every slot's state: ``gated_delta_step`` takes a layer's leaf and the line
and writes the states in place (a kernel on a TPU, the leaf aliased to its
result: this module neither slices a line of states out nor writes one
back; ``linear_state.step_end`` keeps the window).

The programs keep the contract's names and signatures and return, beside
their result, int32[8] counts summed over the program's layers and steps
(``COUNTERS``): the routed layers' (models/routed.MOE_COUNTERS) and
``linear_state_updates`` and ``linear_chunk_tokens``, counted as
llm/qwen3_next_serving.py counts them.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.llm import latent, linear_state
from ray_tpu.llm.served import ServedModel, token_step_programs
from ray_tpu.models import ling
from ray_tpu.models.deepseek import kv_up_projections
from ray_tpu.models.ling import KDA, LATENT, LingConfig
from ray_tpu.models.mla import mla_project
from ray_tpu.models.qwen3_next import conv_window
from ray_tpu.ops.gated_delta import gated_delta_chunk, gated_delta_step
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.util import tracing

COUNTERS = linear_state.COUNTERS


def _state_leaves(cfg: LingConfig) -> list[int]:
    """The lines of each state leaf, in the leaves' order: one leaf of one
    line a KDA layer of the groups written out, then one leaf a place in
    the group with a line a scanned group."""
    places = cfg.layer_group_size - 1
    scanned = cfg.groups - cfg.written_groups
    return [1] * (cfg.written_groups * places) + [scanned] * (
        places if scanned else 0)


def _state_at(cfg: LingConfig, repeat, at: int):
    """(leaf, line in it) of the KDA layer at place ``at`` of group
    ``repeat``: an int for a group written out, the scan's counter
    otherwise."""
    places = cfg.layer_group_size - 1
    if isinstance(repeat, int):
        return repeat * places + at, 0
    return cfg.written_groups * places + at, repeat - cfg.written_groups


def init_cache(cfg: LingConfig, max_slots: int, max_seq: int):
    return {
        **latent.init_cache(cfg, cfg.latent_lines, max_slots, max_seq),
        "conv": linear_state.init_conv(
            cfg.linear_lines, max_slots, cfg.short_conv_kernel_size,
            cfg.conv_dim, cfg.jnp_dtype),
        **{f"state{i}": linear_state.init_state(
            lines, max_slots, cfg.linear_num_heads, cfg.linear_head_dim,
            cfg.linear_head_dim)
           for i, lines in enumerate(_state_leaves(cfg))}}


def _run(cfg, params, x, cache, operators, valid, own, kmesh):
    """Every layer with the cache's leaves as carry: (latent, conv, the
    state leaves). ``own`` is (linear_state_updates, linear_chunk_tokens) of
    ONE KDA layer."""
    names = ("latent", "conv") + tuple(
        f"state{i}" for i in range(len(_state_leaves(cfg))))
    x, leaves, counts = ling.run_layers(
        cfg, params, x, operators, tuple(cache[k] for k in names), valid,
        kmesh)
    counts = linear_state.with_own_counts(counts, cfg.linear_lines, own)
    return x, dict(zip(names, leaves)), counts


def _with_state(state: tuple, leaf: int, st, cs) -> tuple:
    """The carried leaves with state leaf ``leaf`` and the windows put
    back."""
    return state[:1] + (cs,) + state[2:2 + leaf] + (st,) + state[3 + leaf:]


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: LingConfig, params, cache, tokens, kv_len, length,
                  slot, *, kmesh: KernelMesh | None = None):
    """Prefill ONE chunk of one sequence (the contract's program, see
    llm/llama_serving.prefill_chunk). Returns (cache, last-token logits [V],
    counts)."""
    c = tokens.shape[0]
    keep = cfg.short_conv_kernel_size - 1
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][None]              # [1, C, H]
    with tracing.part("attn"):
        positions = kv_len + jnp.arange(c)
        valid = (positions < length)[None]
        # The chunk's rows that are the prompt's: all but a last chunk's
        # padding.
        n_valid = jnp.clip(length - kv_len, 0, c)

    def kda(repeat, at, lp, xn, state):
        leaf, at_line = _state_at(cfg, repeat, at)
        st, cs = state[2 + leaf], state[1]
        line = repeat * (cfg.layer_group_size - 1) + at
        mixed, z, g, beta = ling.kda_inputs(cfg, lp, xn)
        prior, s0 = linear_state.chunk_start(st, cs, at_line, slot, kv_len,
                                             conv_line=line)
        window = conv_window(
            prior.reshape(1, keep, cfg.conv_dim), mixed)
        q, k, v = ling.kda_heads(cfg, lp, window, c)
        with tracing.part("linear_attn"), tracing.part("kda_rule"):
            # A padded row decays nothing and corrects nothing.
            o, s1 = gated_delta_chunk(
                q[0], k[0], v[0],
                jnp.where(valid[0, :, None, None], g[0], 0.0),
                jnp.where(valid[0, :, None], beta[0], 0.0), s0[0, 0],
                g_floor=cfg.kda_lower_bound)
        st, cs = linear_state.chunk_end(st, cs, s1, window, at_line, slot,
                                        n_valid, conv_line=line)
        return (ling.kda_output(cfg, lp, o[None], z, xn.dtype),
                _with_state(state, leaf, st, cs))

    def attention(line, at, ap, xn, state):
        lat = state[0]
        q_n, q_r, rows = mla_project(cfg, ap, xn, positions, kmesh,
                                     keep_product=True)
        lat = latent.chunk_write(lat, rows, line, slot, kv_len)
        with tracing.part("latent_prefill"):
            up = kv_up_projections(cfg, ap["wkv_b"])
        o = latent.chunk_attend(cfg, lat, q_n, q_r, up, line, slot, kv_len,
                                length)
        return (ling.latent_output(cfg, ap, xn, o, xn.dtype),
                (lat,) + state[1:])

    x, cache, counts = _run(
        cfg, params, x, cache, {KDA: kda, LATENT: attention}, valid,
        (jnp.zeros((), jnp.int32), n_valid), kmesh)
    # The head on the one row that is kept.
    with tracing.part("head"):
        last = x[0, jnp.clip(length - 1 - kv_len, 0, c - 1)]
    return cache, ling.lm_head(cfg, params, last, kmesh), counts


def _decode_impl(cfg: LingConfig, params, cache, tokens, positions,
                 write_mask, kmesh=None):
    """One token per slot against the lines and the states. Returns (cache,
    logits [B, V], counts). A slot with ``write_mask`` false writes no row,
    keeps its state and its window, is routed nowhere, and its logits mean
    nothing."""
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][:, None]           # [B, 1, H]
    with tracing.part("attn"):
        lengths = jnp.where(write_mask, positions + 1, 0)
        valid = write_mask[:, None]

    def kda(repeat, at, lp, xn, state):
        leaf, at_line = _state_at(cfg, repeat, at)
        st, cs = state[2 + leaf], state[1]
        line = repeat * (cfg.layer_group_size - 1) + at
        mixed, z, g, beta = ling.kda_inputs(cfg, lp, xn)
        prior = linear_state.step_start(cs, line, cfg.conv_dim)
        window = conv_window(prior, mixed)
        q, k, v = ling.kda_heads(cfg, lp, window, 1)
        with tracing.part("linear_attn"), tracing.part("kda_rule"):
            # A slot that does not decode decays nothing and corrects
            # nothing: its state is left as it was.
            o, st = gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0],
                jnp.where(valid[..., None], g[:, 0], 0.0),
                jnp.where(valid, beta[:, 0], 0.0), st, at_line)
        cs = linear_state.step_end(cs, window, prior, line, write_mask)
        return (ling.kda_output(cfg, lp, o[:, None], z, xn.dtype),
                _with_state(state, leaf, st, cs))

    def attention(line, at, ap, xn, state):
        lat = state[0]
        q_n, q_r, rows = mla_project(cfg, ap, xn, positions[:, None], kmesh,
                                     keep_product=True)
        lat = latent.lines_write(lat, rows, line, positions, write_mask,
                                 kmesh)
        up = kv_up_projections(cfg, ap["wkv_b"])
        o = latent.lines_attend(cfg, lat, q_n, q_r, up, line, lengths,
                                positions, kmesh)
        return (ling.latent_output(cfg, ap, xn, o, xn.dtype),
                (lat,) + state[1:])

    x, cache, counts = _run(
        cfg, params, x, cache, {KDA: kda, LATENT: attention}, valid,
        (write_mask.sum(), jnp.zeros((), jnp.int32)), kmesh)
    return cache, ling.lm_head(cfg, params, x[:, 0], kmesh), counts


decode_step, decode_burst = token_step_programs(_decode_impl, COUNTERS)


def _refuse(config) -> None:
    """What this model does not run, said at construction."""
    if config.speculative_model is not None:
        raise ValueError(
            "LingConfig does not support a speculative draft: a rejected "
            "token's step of the rule's state cannot be taken back (the "
            "family's multi-token-prediction module is not here either)")


SERVED = ServedModel(
    init_params=ling.init_params,
    param_logical_axes=ling.param_logical_axes,
    init_cache=init_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    kv_block=latent.kv_block,
    counters=COUNTERS,
    constants=lambda cfg: {"moe_experts_held": cfg.experts_held,
                           "latent_lines": cfg.latent_lines,
                           "linear_lines": cfg.linear_lines,
                           "linear_state_bytes": cfg.linear_state_bytes},
    # A line is not all of a slot: the hand-off would have to ship the
    # states and the windows too, and a prefix has none to adopt.
    kv_handoff=False,
    prefix_from_line=False,
    refuse=_refuse,
)
