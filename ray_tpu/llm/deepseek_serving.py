"""What models/deepseek.py supplies to the scheduler (llm/served.ServedModel):
the programs that run against the latent cache (llm/latent.py), a line a
layer.

This model's own: the layers are ``deepseek.run_layers``'s (a leading dense
layer, then the routed ones beside their shared experts), ``wkv_b`` is
stored a head at a time (``deepseek.kv_up_projections``), the queries'
product is kept an array of its own (``mla_project(keep_product=True)``),
and a prefill chunk may ride a decode step (``_mixed_impl``).

``decode_step`` and ``decode_burst`` are built from ``_decode_impl`` by
llm/served.token_step_programs, ``mixed_burst`` from it and ``_mixed_impl``
by llm/served.mixed_burst_program; every program returns the routed layers'
counts (models/deepseek.COUNTERS, int32[7], summed over its layers and
steps) beside its result.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm import latent
from ray_tpu.llm.latent import copy_prefix_kv
from ray_tpu.llm.served import (
    ServedModel,
    mixed_burst_program,
    mixed_rows,
    token_step_programs,
)
from ray_tpu.models import deepseek
from ray_tpu.models.deepseek import DeepseekV2Config
from ray_tpu.models.mla import mla_project
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.util import tracing


def init_cache(cfg: DeepseekV2Config, max_slots: int, max_seq: int):
    return latent.init_cache(cfg, cfg.num_layers, max_slots, max_seq)


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
        lat = latent.chunk_write(lat, rows, index, slot, kv_len)
        with tracing.part("latent_prefill"):
            up = deepseek.kv_up_projections(cfg, ap["wkv_b"])
        o = latent.chunk_attend(cfg, lat, q_n, q_r, up, index, slot,
                                kv_len, length)
        return (o @ ap["wo"]).astype(xn.dtype), lat

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
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][:, None]           # [B, 1, H]
    with tracing.part("attn"):
        lengths = jnp.where(write_mask, positions + 1, 0)
        valid = write_mask[:, None]

    def attn(index, ap, xn, lat):
        q_n, q_r, rows = mla_project(cfg, ap, xn, positions[:, None], kmesh,
                                     keep_product=True)
        lat = latent.lines_write(lat, rows, index, positions, write_mask,
                                 kmesh)
        up = deepseek.kv_up_projections(cfg, ap["wkv_b"])
        o = latent.lines_attend(cfg, lat, q_n, q_r, up, index, lengths,
                                positions, kmesh)
        return (o @ ap["wo"]).astype(xn.dtype), lat

    x, lat, counts = deepseek.run_layers(cfg, params, x, attn,
                                         cache["latent"], valid, kmesh)
    return ({"latent": lat}, deepseek.lm_head(cfg, params, x[:, 0], kmesh),
            counts)


def _mixed_impl(cfg: DeepseekV2Config, params, cache, tokens, positions,
                write_mask, chunk, kv_len, length, slot, kmesh=None):
    """``mixed_step`` of llm/served.mixed_burst_program. The norms, the
    projections, ``wo``, the dense SwiGLU, the shared experts and the routed
    layer see all rows at once (a layer's weights and its touched experts
    are fetched once for both: one layer-step in its counts); the attention
    splits them, the chunk's rows to the chunk's halves and the lines' to
    the lines'."""
    c, b = chunk.shape[0], tokens.shape[0]
    with tracing.part("attn"):
        ids, at, valid, lengths, lines_of = mixed_rows(
            chunk, tokens, kv_len, length, positions, write_mask)
    with tracing.part("embed"):
        x = params["embed_tokens"][ids][None]             # [1, C + B, H]

    def attn(index, ap, xn, lat):
        q_n, q_r, rows = mla_project(cfg, ap, xn, at, kmesh,
                                     keep_product=True)
        # The lines' rows, a row a slot: [1, B, ...] -> [B, 1, ...].
        q_n_l, q_r_l, rows_l = (a[0, c:, None] for a in (q_n, q_r, rows))
        lat = latent.chunk_write(lat, rows[:, :c], index, slot, kv_len)
        lat = latent.lines_write(lat, rows_l, index, positions, write_mask,
                                 kmesh)
        with tracing.part("latent_prefill"):
            up = deepseek.kv_up_projections(cfg, ap["wkv_b"])
        o = latent.chunk_attend(cfg, lat, q_n[:, :c], q_r[:, :c], up, index,
                                slot, kv_len, length)
        o_l = latent.lines_attend(cfg, lat, q_n_l, q_r_l, up, index, lengths,
                                  positions, kmesh)
        # The two outputs stay arrays of their own before they are joined
        # (``mla_project``'s ``keep_product``, for the other side): XLA
        # otherwise folds the chunk's way back from head-major into the
        # join, and the chunk's kernel beside it runs 4% slower (0.9 ms of
        # a step's 47.6 at 4,096 cached rows, my chip run, PR 53).
        o, o_l = lax.optimization_barrier((o, o_l))
        o = jnp.concatenate([o, o_l.reshape(1, b, -1)], axis=1)
        return (o @ ap["wo"]).astype(xn.dtype), lat

    x, lat, counts = deepseek.run_layers(cfg, params, x, attn,
                                         cache["latent"], valid[None], kmesh)
    return ({"latent": lat}, deepseek.lm_head(cfg, params, lines_of(x), kmesh),
            counts)


decode_step, decode_burst = token_step_programs(_decode_impl,
                                                deepseek.COUNTERS)
mixed_burst = mixed_burst_program(_decode_impl, _mixed_impl,
                                  deepseek.COUNTERS)


SERVED = ServedModel(
    init_params=deepseek.init_params,
    param_logical_axes=deepseek.param_logical_axes,
    init_cache=init_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    copy_prefix_kv=copy_prefix_kv,
    kv_block=latent.kv_block,
    counters=deepseek.COUNTERS,
    constants=lambda cfg: {"moe_experts_held": cfg.experts_held},
    kv_handoff=False,
    # A chunk and a step fetch the same weights, a layer's held experts
    # first among them: riding, a chunk's rows pass every product on the
    # step's fetch.
    mixed_burst=mixed_burst,
)
