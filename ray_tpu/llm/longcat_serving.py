"""What models/longcat.py supplies to the scheduler (llm/served.ServedModel):
the latent cache and the programs that run against it.

The cache is one array ``[2 * num_layers, slots, max_seq, latent_row]``:
per attention, slot and position the row every head reads
(ops/latent_attention.py; ``kv_lora_rank + qk_rope_head_dim`` values and
zeros up to whole lanes, 576 of 640), a fifth to a third of what per-head
keys and values of a comparable model take. It rides every layer loop as carry,
never as scan xs/ys: prefill writes a chunk's rows in place and reads the
live blocks of the slot's line; a decode step writes its one row a slot and
attention in place and attends in the absorbed form.

The programs keep the contract's names (``prefill_chunk``, ``decode_step``,
``decode_burst``: a device trace shows ``jit_<name>``; the last two are
built from ``_decode_impl`` by llm/served.token_step_programs) and
signatures, and return the routed layers' counts
(models/longcat.MOE_COUNTERS, int32[6], summed over the program's layers
and steps) beside their result; the scheduler adds them up where it fetches
the tokens.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm.served import ServedModel, token_step_programs
from ray_tpu.models import longcat
from ray_tpu.models.longcat import LongcatConfig
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.latent_attention import (
    latent_decode_attention,
    latent_kv_block,
    latent_prefill_attention,
    latent_row_write,
)
from ray_tpu.util import tracing


def init_cache(cfg: LongcatConfig, max_slots: int, max_seq: int):
    return {"latent": jnp.zeros(
        (cfg.num_attention_layers, max_slots, max_seq, cfg.latent_row),
        cfg.jnp_dtype)}


def _run_layers(cfg, params, x, lat, attn, valid, kmesh):
    """The double layers over x with the latent cache as carry. Returns
    (x, lat, counts)."""

    def body(carry, layer):
        x, lat, counts = carry
        x, (lat, _), c = longcat.double_layer(
            cfg, params["layers"], layer, x, attn, (lat, layer), valid, kmesh)
        with tracing.part("moe_combine"):
            return (x, lat, counts + c), None

    with tracing.part("stack"):
        (x, lat, counts), _ = lax.scan(
            body,
            (x, lat, jnp.zeros((len(longcat.MOE_COUNTERS),), jnp.int32)),
            jnp.arange(cfg.num_layers))
    return x, lat, counts


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: LongcatConfig, params, cache, tokens, kv_len, length,
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

    def attn(i, ap, xn, state):
        lat, layer = state
        a = 2 * layer + i
        q_n, q_r, rows = longcat.mla_project(cfg, ap, xn, positions, kmesh)
        with tracing.part("cache"):
            lat = lax.dynamic_update_slice(
                lat, rows.astype(lat.dtype)[None], (a, slot, kv_len, 0))
        w_kb, w_vb = longcat.kv_up_projections(cfg, ap["wkv_b"])
        o = latent_prefill_attention(q_n[0], q_r[0], lat, w_kb, w_vb, a,
                                     slot, kv_len, length,
                                     rope_dim=cfg.qk_rope_head_dim,
                                     sm_scale=cfg.sm_scale)
        return (o.reshape(1, c, -1) @ ap["wo"]).astype(xn.dtype), (lat, layer)

    x, lat, counts = _run_layers(cfg, params, x, cache["latent"], attn,
                                 valid, kmesh)
    with tracing.part("head"):
        last = x[0, jnp.clip(length - 1 - kv_len, 0, c - 1)]
    return {"latent": lat}, longcat.lm_head(cfg, params, last, kmesh), counts


def _multi_token_impl(cfg: LongcatConfig, params, cache, tokens, positions0,
                      write_mask, kmesh=None):
    """K tokens per slot in one pass against the latent cache (see
    llm/llama_serving._multi_token_impl). Returns (cache, logits
    [B, K, V], counts)."""
    b, k = tokens.shape
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]                    # [B, K, H]
    with tracing.part("attn"):
        positions = positions0[:, None] + jnp.arange(k)[None, :]
        lengths = jnp.where(write_mask, positions0 + k, 0)
        valid = jnp.broadcast_to(write_mask[:, None], (b, k))

    def attn(i, ap, xn, state):
        lat, layer = state
        a = 2 * layer + i
        q_n, q_r, rows = longcat.mla_project(cfg, ap, xn, positions, kmesh)
        with tracing.part("cache"):
            lat = latent_row_write(lat, rows, a, positions0, write_mask,
                                   kmesh=kmesh)
        w_kb, w_vb = longcat.kv_up_projections(cfg, ap["wkv_b"])
        # Absorbed: the key up-projection goes into the query, the value
        # up-projection onto the mix of latent rows.
        q = jnp.concatenate(
            [jnp.einsum("bkhd,rhd->bkhr", q_n, w_kb), q_r], axis=-1)
        o = latent_decode_attention(q, lat, a, lengths, positions0,
                                    rank=cfg.kv_lora_rank,
                                    sm_scale=cfg.sm_scale, kmesh=kmesh)
        o = jnp.einsum("bkhr,rhd->bkhd", o, w_vb).reshape(b, k, -1)
        return (o @ ap["wo"]).astype(xn.dtype), (lat, layer)

    x, lat, counts = _run_layers(cfg, params, x, cache["latent"], attn,
                                 valid, kmesh)
    return {"latent": lat}, longcat.lm_head(cfg, params, x, kmesh), counts


def _decode_impl(cfg: LongcatConfig, params, cache, tokens, positions,
                 write_mask, kmesh=None):
    """One decode step for every slot, the single step ``decode_step`` and
    ``decode_burst`` are built from. Returns (cache, logits [B, V],
    counts)."""
    cache, logits, counts = _multi_token_impl(
        cfg, params, cache, tokens[:, None], positions, write_mask, kmesh)
    return cache, logits[:, 0], counts


decode_step, decode_burst = token_step_programs(_decode_impl,
                                                longcat.MOE_COUNTERS)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
@tracing.part("cache")
def copy_prefix_kv(cfg: LongcatConfig, cache, src_slot, dst_slot):
    """Copy one slot's whole latent line to another slot, all attentions
    at once (prefix adoption from a live donor)."""
    line = lax.dynamic_slice_in_dim(cache["latent"], src_slot, 1, 1)
    return {"latent": lax.dynamic_update_slice(
        cache["latent"], line, (0, dst_slot, 0, 0))}


def _refuse(config) -> None:
    """What this model does not run, said at construction."""
    if config.tensor_parallel_size > 1:
        raise ValueError("LongcatConfig does not support "
                         "tensor_parallel_size > 1: its programs run on one "
                         "device")


SERVED = ServedModel(
    init_params=longcat.init_params,
    param_logical_axes=longcat.param_logical_axes,
    init_cache=init_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    copy_prefix_kv=copy_prefix_kv,
    kv_block=lambda cfg, max_seq: latent_kv_block(max_seq),
    counters=longcat.MOE_COUNTERS,
    constants=lambda cfg: {"moe_experts_held": cfg.experts_held},
    kv_handoff=False,
    refuse=_refuse,
)
