"""What models/longcat.py supplies to the scheduler (llm/served.ServedModel):
the programs that run against the latent cache (llm/latent.py), a line an
attention: ``2 * num_layers`` of them.

This model's own: a double layer holds two attentions (``double_layer``
calls ``attn(i, ...)`` for each of the pair, and cache line ``2 * layer + i``
is its), the routed layer runs beside the first dense MLP, and the latent
norms' outputs carry ``mla_scales``.

``decode_step`` and ``decode_burst`` are built from ``_decode_impl`` by
llm/served.token_step_programs; every program returns the routed layers'
counts (models/longcat.MOE_COUNTERS, int32[6], summed over its layers and
steps) beside its result.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm import latent
from ray_tpu.llm.latent import copy_prefix_kv
from ray_tpu.llm.served import ServedModel, token_step_programs
from ray_tpu.models import longcat
from ray_tpu.models.longcat import LongcatConfig
from ray_tpu.models.mla import kv_up_projections, mla_project
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.util import tracing


def init_cache(cfg: LongcatConfig, max_slots: int, max_seq: int):
    return latent.init_cache(cfg, cfg.num_attention_layers, max_slots,
                             max_seq)


def _run_layers(cfg, params, x, lat, attn, valid, kmesh):
    """The double layers over x with the latent cache as carry.
    ``attn(index, ap, xn, lat) -> (out, lat)`` is one attention on cache
    line ``index``, ``2 * layer + i`` for attention i of a pair. Returns
    (x, lat, counts)."""

    def body(carry, layer):
        x, lat, counts = carry
        x, lat, c = longcat.double_layer(
            cfg, params["layers"], layer, x,
            lambda i, ap, xn, lat: attn(2 * layer + i, ap, xn, lat), lat,
            valid, kmesh)
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

    def attn(index, ap, xn, lat):
        q_n, q_r, rows = mla_project(cfg, ap, xn, positions, kmesh)
        lat = latent.chunk_write(lat, rows, index, slot, kv_len)
        with tracing.part("latent_prefill"):
            up = kv_up_projections(cfg, ap["wkv_b"])
        o = latent.chunk_attend(cfg, lat, q_n, q_r, up, index, slot,
                                kv_len, length)
        return (o @ ap["wo"]).astype(xn.dtype), lat

    x, lat, counts = _run_layers(cfg, params, x, cache["latent"], attn,
                                 valid, kmesh)
    with tracing.part("head"):
        last = x[0, jnp.clip(length - 1 - kv_len, 0, c - 1)]
    return {"latent": lat}, longcat.lm_head(cfg, params, last, kmesh), counts


def _decode_impl(cfg: LongcatConfig, params, cache, tokens, positions,
                 write_mask, kmesh=None):
    """One decode step for every slot, the single step ``decode_step`` and
    ``decode_burst`` are built from. Returns (cache, logits [B, V],
    counts)."""
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][:, None]           # [B, 1, H]
    with tracing.part("attn"):
        lengths = jnp.where(write_mask, positions + 1, 0)
        valid = write_mask[:, None]

    def attn(index, ap, xn, lat):
        q_n, q_r, rows = mla_project(cfg, ap, xn, positions[:, None], kmesh)
        lat = latent.lines_write(lat, rows, index, positions, write_mask,
                                 kmesh)
        up = kv_up_projections(cfg, ap["wkv_b"])
        o = latent.lines_attend(cfg, lat, q_n, q_r, up, index, lengths,
                                positions, kmesh)
        return (o @ ap["wo"]).astype(xn.dtype), lat

    x, lat, counts = _run_layers(cfg, params, x, cache["latent"], attn,
                                 valid, kmesh)
    return ({"latent": lat}, longcat.lm_head(cfg, params, x[:, 0], kmesh),
            counts)


decode_step, decode_burst = token_step_programs(_decode_impl,
                                                longcat.MOE_COUNTERS)


SERVED = ServedModel(
    init_params=longcat.init_params,
    param_logical_axes=longcat.param_logical_axes,
    init_cache=init_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    copy_prefix_kv=copy_prefix_kv,
    kv_block=latent.kv_block,
    counters=longcat.MOE_COUNTERS,
    constants=lambda cfg: {"moe_experts_held": cfg.experts_held},
    kv_handoff=False,
)
