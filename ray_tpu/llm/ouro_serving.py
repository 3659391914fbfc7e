"""What models/ouro.py supplies to the scheduler (llm/served.ServedModel): a
cache with a line for every (pass, layer) and the programs that run the
looped stack against it.

The cache is the per-head K/V slot layout (llm/served.py), ``{"k", "v"}``
of ``[lines, slots, kv_heads, max_seq, head_dim]``, with ``lines =
total_ut_steps * num_layers``: the weights are shared by the passes, the
keys and values are not, so a cached position costs ``total_ut_steps``
times a plain decoder's. The programs loop over the passes around the scan over the
layers; the stacked weights are read again by every pass and the cache
rides both loops as carry, never as scan xs/ys: line ``cfg.cache_line(t,
l)`` is handed to the dense decoder's kernels (ops/prefill_attention.py,
ops/decode_attention.py) as their ``layer``.

The programs keep the contract's names (``prefill_chunk``, ``decode_step``,
``decode_burst``: a device trace shows ``jit_<name>``; the last two are
built from ``_decode_impl`` by llm/served.token_step_programs) and
signatures, and return models/ouro.LOOP_COUNTERS (int32[2], over valid
tokens) beside their result; the scheduler adds them up where it fetches
the tokens.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm.served import (
    ServedModel,
    copy_prefix_kv,
    token_step_programs,
)
from ray_tpu.models import ouro
from ray_tpu.models.ouro import OuroConfig
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


def init_cache(cfg: OuroConfig, max_slots: int, max_seq: int):
    shape = (cfg.cache_lines, max_slots, cfg.num_kv_heads, max_seq,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.jnp_dtype),
            "v": jnp.zeros(shape, cfg.jnp_dtype)}


def _run_loop(cfg, params, x, cache, positions, attend_line, valid, kmesh):
    """Every pass over every layer, the cache as carry of both loops.
    ``attend_line(line, q, k, v, (k_all, v_all)) -> (o, (k_all, v_all))``
    writes the new rows into ``line`` and attends there. Returns (the
    picked pass's normed state, cache, counts)."""
    with tracing.part("attn"):
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)

    def stack(x, step, kv):
        def body(carry, scanned):
            x, kv = carry
            lp, layer = scanned
            x, kv = ouro.block(
                cfg, lp, x, positions, inv_freq,
                partial(attend_line, cfg.cache_line(step, layer)), kv, kmesh)
            return (x, kv), None

        with tracing.part("stack"):
            return lax.scan(body, (x, kv), (params["layers"],
                                            jnp.arange(cfg.num_layers)))[0]

    x, (k_all, v_all), _, chosen = ouro.loop(
        cfg, params, x, stack, (cache["k"], cache["v"]), kmesh)
    return x, {"k": k_all, "v": v_all}, ouro.loop_counts(chosen, valid)


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: OuroConfig, params, cache, tokens, kv_len, length,
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

    def attend_line(line, q, k, v, kv):
        with tracing.part("cache"):
            kv = prefill_kv_write(*kv, k[0], v[0], line, slot, kv_len)
        o = prefill_attention(q[0], *kv, line, slot, kv_len, length,
                              kmesh=kmesh)
        return o[None], kv

    x, cache, counts = _run_loop(cfg, params, x, cache, positions,
                                 attend_line, valid, kmesh)
    # The head on the one row that is kept.
    with tracing.part("head"):
        last = x[0, jnp.clip(length - 1 - kv_len, 0, c - 1)]
    return cache, ouro.lm_head(params, last), counts


def _multi_token_impl(cfg: OuroConfig, params, cache, tokens, positions0,
                      write_mask, kmesh=None):
    """K tokens per slot in one pass of the whole loop against the cache
    (see llm/llama_serving._multi_token_impl). Returns
    (cache, logits [B, K, V], counts)."""
    b, k = tokens.shape
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]                    # [B, K, H]
    with tracing.part("attn"):
        positions = positions0[:, None] + jnp.arange(k)[None, :]
        lengths = jnp.where(write_mask, positions0 + k, 0)
        valid = jnp.broadcast_to(write_mask[:, None], (b, k))
        # All 192 lines attend at the same lengths: one walk of the live
        # blocks, planned here and not in the loops.
        plan = decode_plan_of(lengths, cache["k"], kmesh=kmesh)

    def attend_line(line, q, kk, v, kv):
        with tracing.part("cache"):
            kv = kv_row_write(*kv, kk, v, line, positions0, write_mask,
                              kmesh=kmesh)
        return decode_attention(q, *kv, line, lengths, positions0,
                                plan=plan, kmesh=kmesh), kv

    x, cache, counts = _run_loop(cfg, params, x, cache, positions,
                                 attend_line, valid, kmesh)
    return cache, ouro.lm_head(params, x), counts


def _decode_impl(cfg: OuroConfig, params, cache, tokens, positions,
                 write_mask, kmesh=None):
    """One decode step for every slot, the single step ``decode_step`` and
    ``decode_burst`` are built from. Returns (cache, logits [B, V],
    counts)."""
    cache, logits, counts = _multi_token_impl(
        cfg, params, cache, tokens[:, None], positions, write_mask, kmesh)
    return cache, logits[:, 0], counts


decode_step, decode_burst = token_step_programs(_decode_impl,
                                                ouro.LOOP_COUNTERS)


def _refuse(config) -> None:
    """What this model does not run, said at construction."""
    cfg = config.model_config()
    if cfg.early_exit_threshold < 1:
        raise ValueError(
            "OuroConfig does not support early_exit_threshold "
            f"{cfg.early_exit_threshold} (under 1): a token that leaves the "
            "loop early still owes its later passes' cache lines to the "
            "tokens after it, and the scheduler's bursts and its count of "
            "cached positions assume equal work a token")


SERVED = ServedModel(
    init_params=ouro.init_params,
    param_logical_axes=ouro.param_logical_axes,
    init_cache=init_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    # The K/V slot cache's own: every leaf's second axis is the slot, so it
    # moves all the lines a slot has, here one a (pass, layer).
    copy_prefix_kv=copy_prefix_kv,
    kv_block=lambda cfg, max_seq: decode_kv_block(
        max_seq, cfg.head_dim, cfg.jnp_dtype.itemsize),
    counters=ouro.LOOP_COUNTERS,
    constants=lambda cfg: {"loop_steps": cfg.total_ut_steps},
    refuse=_refuse,
)
