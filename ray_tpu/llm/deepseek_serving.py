"""What models/deepseek.py supplies to the scheduler (llm/served.ServedModel):
the latent cache and the programs that run against it.

The cache is llm/longcat_serving.py's with a line a layer: one array
``[num_layers, slots, max_seq, latent_row]``, per layer, slot and position
the row every head reads (ops/latent_attention.py; ``kv_lora_rank +
qk_rope_head_dim`` values and zeros up to whole lanes, 576 of 640). It
rides every layer loop as carry: prefill writes a chunk's rows in place and
reads the live blocks of the slot's line (up-projected to every head,
under the scope ``latent_prefill``); a decode step writes its one row a
slot and layer in place and attends in the absorbed form, all heads of a
slot one tile of rows against a single read of the line.

The programs keep the contract's names and signatures and return the routed
layers' counts (models/deepseek.COUNTERS, int32[7], summed over the
program's layers and steps) beside their result.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm.served import ServedModel, token_step_programs
from ray_tpu.models import deepseek
from ray_tpu.models.deepseek import DeepseekV2Config
from ray_tpu.models.longcat import mla_project
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.latent_attention import (
    latent_decode_attention,
    latent_kv_block,
    latent_prefill_attention,
    latent_row_write,
)
from ray_tpu.util import tracing


def init_cache(cfg: DeepseekV2Config, max_slots: int, max_seq: int):
    return {"latent": jnp.zeros(
        (cfg.num_layers, max_slots, max_seq, cfg.latent_row),
        cfg.jnp_dtype)}


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: DeepseekV2Config, params, cache, tokens, kv_len,
                  length, slot, *, kmesh: KernelMesh | None = None):
    """Prefill ONE chunk of one sequence (the contract's program, see
    llm/llama_serving.prefill_chunk). Returns (cache, last-token logits [V],
    counts)."""
    c = tokens.shape[0]
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][None]              # [1, C, H]
    with tracing.part("attn"):
        positions = kv_len + jnp.arange(c)
        valid = (positions < length)[None]

    def attn(index, ap, xn, lat):
        q_n, q_r, rows = mla_project(cfg, ap, xn, positions, kmesh,
                                     keep_product=True)
        with tracing.part("cache"):
            lat = lax.dynamic_update_slice(
                lat, rows.astype(lat.dtype)[None], (index, slot, kv_len, 0))
        with tracing.part("latent_prefill"):
            w_kb, w_vb = deepseek.kv_up_projections(cfg, ap["wkv_b"])
            o = latent_prefill_attention(q_n[0], q_r[0], lat, w_kb, w_vb,
                                         index, slot, kv_len, length,
                                         rope_dim=cfg.qk_rope_head_dim,
                                         sm_scale=cfg.sm_scale)
        return (o.reshape(1, c, -1) @ ap["wo"]).astype(xn.dtype), lat

    x, lat, counts = deepseek.run_layers(cfg, params, x, attn,
                                         cache["latent"], valid, kmesh)
    with tracing.part("head"):
        last = x[0, jnp.clip(length - 1 - kv_len, 0, c - 1)]
    return {"latent": lat}, deepseek.lm_head(cfg, params, last, kmesh), counts


def _decode_impl(cfg: DeepseekV2Config, params, cache, tokens, positions,
                 write_mask, kmesh=None):
    """One decode step for every slot, the single step ``decode_step`` and
    ``decode_burst`` are built from. Returns (cache, logits [B, V],
    counts)."""
    b = tokens.shape[0]
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][:, None]           # [B, 1, H]
    with tracing.part("attn"):
        lengths = jnp.where(write_mask, positions + 1, 0)
        valid = write_mask[:, None]

    def attn(index, ap, xn, lat):
        q_n, q_r, rows = mla_project(cfg, ap, xn, positions[:, None], kmesh,
                                     keep_product=True)
        with tracing.part("cache"):
            lat = latent_row_write(lat, rows, index, positions, write_mask,
                                   kmesh=kmesh)
        w_kb, w_vb = deepseek.kv_up_projections(cfg, ap["wkv_b"])
        # Absorbed: the key up-projection goes into the query, the value
        # up-projection onto the mix of latent rows.
        q = jnp.concatenate(
            [jnp.einsum("bkhd,rhd->bkhr", q_n, w_kb), q_r], axis=-1)
        o = latent_decode_attention(q, lat, index, lengths, positions,
                                    rank=cfg.kv_lora_rank,
                                    sm_scale=cfg.sm_scale, kmesh=kmesh)
        o = jnp.einsum("bkhr,rhd->bkhd", o, w_vb).reshape(b, 1, -1)
        return (o @ ap["wo"]).astype(xn.dtype), lat

    x, lat, counts = deepseek.run_layers(cfg, params, x, attn,
                                         cache["latent"], valid, kmesh)
    return ({"latent": lat}, deepseek.lm_head(cfg, params, x[:, 0], kmesh),
            counts)


decode_step, decode_burst = token_step_programs(_decode_impl,
                                                deepseek.COUNTERS)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
@tracing.part("cache")
def copy_prefix_kv(cfg: DeepseekV2Config, cache, src_slot, dst_slot):
    """Copy one slot's whole latent line to another slot, all layers at
    once (prefix adoption from a live donor)."""
    line = lax.dynamic_slice_in_dim(cache["latent"], src_slot, 1, 1)
    return {"latent": lax.dynamic_update_slice(
        cache["latent"], line, (0, dst_slot, 0, 0))}


def _refuse(config) -> None:
    """What this model does not run, said at construction."""
    if config.tensor_parallel_size > 1:
        raise ValueError("DeepseekV2Config does not support "
                         "tensor_parallel_size > 1: its programs run on one "
                         "device")


SERVED = ServedModel(
    init_params=deepseek.init_params,
    param_logical_axes=deepseek.param_logical_axes,
    init_cache=init_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    copy_prefix_kv=copy_prefix_kv,
    kv_block=lambda cfg, max_seq: latent_kv_block(max_seq),
    counters=deepseek.COUNTERS,
    constants=lambda cfg: {"moe_experts_held": cfg.experts_held},
    kv_handoff=False,
    refuse=_refuse,
)
