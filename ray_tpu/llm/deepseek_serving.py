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

The programs keep the contract's names and signatures (``decode_step`` and
``decode_burst`` are built from ``_decode_impl`` by
llm/served.token_step_programs; ``mixed_burst``, the burst whose steps carry
a prefill chunk each, from it and ``_mixed_impl`` by
llm/served.mixed_burst_program) and return the routed layers' counts
(models/deepseek.COUNTERS, int32[7], summed over the program's layers and
steps) beside their result.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm.served import (
    ServedModel,
    mixed_burst_program,
    token_step_programs,
)
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


# A program's attention is made of these halves: the chunk's (one slot, C
# rows from ``kv_len`` on, keys and values up-projected) and the lines'
# (every slot, a row each, in the absorbed form). ``prefill_chunk`` runs the
# first, ``_decode_impl`` the second, and a mixed step both, on the rows of
# one array and on one ``up``, a layer's ``kv_up_projections``.

def _chunk_write(lat, rows, index, slot, kv_len):
    """A chunk's rows [1, C, row] written to its slot's line."""
    with tracing.part("cache"):
        return lax.dynamic_update_slice(
            lat, rows.astype(lat.dtype)[None], (index, slot, kv_len, 0))


def _chunk_attend(cfg, lat, q_n, q_r, up, index, slot, kv_len, length):
    """A chunk attended from its slot's line, its own rows written: q_n
    [1, C, nh, Dn], q_r [1, C, nh, Dr] -> [1, C, nh * Dv]."""
    with tracing.part("latent_prefill"):
        o = latent_prefill_attention(q_n[0], q_r[0], lat, *up, index, slot,
                                     kv_len, length,
                                     rope_dim=cfg.qk_rope_head_dim,
                                     sm_scale=cfg.sm_scale)
    return o.reshape(1, q_n.shape[1], -1)


def _lines_write(lat, rows, index, positions, write_mask, kmesh):
    """Every decoding line's row written: rows [B, 1, row]."""
    with tracing.part("cache"):
        return latent_row_write(lat, rows, index, positions, write_mask,
                                kmesh=kmesh)


def _lines_attend(cfg, lat, q_n, q_r, up, index, lengths, positions, kmesh):
    """The lines attended from, their rows written: q_n [B, 1, nh, Dn], q_r
    [B, 1, nh, Dr] -> [B, 1, nh * Dv]. Absorbed: the key up-projection goes
    into the query, the value up-projection onto the mix of latent rows."""
    w_kb, w_vb = up
    q = jnp.concatenate(
        [jnp.einsum("bkhd,rhd->bkhr", q_n, w_kb), q_r], axis=-1)
    o = latent_decode_attention(q, lat, index, lengths, positions,
                                rank=cfg.kv_lora_rank,
                                sm_scale=cfg.sm_scale, kmesh=kmesh)
    return jnp.einsum("bkhr,rhd->bkhd", o, w_vb).reshape(q.shape[0], 1, -1)


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
        lat = _chunk_write(lat, rows, index, slot, kv_len)
        with tracing.part("latent_prefill"):
            up = deepseek.kv_up_projections(cfg, ap["wkv_b"])
        o = _chunk_attend(cfg, lat, q_n, q_r, up, index, slot, kv_len,
                          length)
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
        lat = _lines_write(lat, rows, index, positions, write_mask, kmesh)
        up = deepseek.kv_up_projections(cfg, ap["wkv_b"])
        o = _lines_attend(cfg, lat, q_n, q_r, up, index, lengths, positions,
                          kmesh)
        return (o @ ap["wo"]).astype(xn.dtype), lat

    x, lat, counts = deepseek.run_layers(cfg, params, x, attn,
                                         cache["latent"], valid, kmesh)
    return ({"latent": lat}, deepseek.lm_head(cfg, params, x[:, 0], kmesh),
            counts)


def _mixed_impl(cfg: DeepseekV2Config, params, cache, tokens, positions,
                write_mask, chunk, kv_len, length, slot, kmesh=None):
    """A decode step that carries a prefill chunk: ``prefill_chunk``'s
    ``chunk`` [C] of ``slot`` (``write_mask`` false there, as between two
    chunks) and ``_decode_impl``'s token a line, [1, C + B, H] through every
    layer. The norms, the projections, ``wo``, the dense SwiGLU, the shared
    experts and the routed layer see all rows at once (a layer's weights
    and its touched experts are fetched once for both: one layer-step in
    its counts); the attention splits them, the chunk's rows to the chunk's
    halves and the lines' to the lines'. Returns (cache, the lines' logits
    [B, V], counts): a riding chunk gives no token."""
    c, b = chunk.shape[0], tokens.shape[0]
    with tracing.part("embed"):
        x = params["embed_tokens"][jnp.concatenate([chunk, tokens])][None]
    with tracing.part("attn"):
        at = jnp.concatenate([kv_len + jnp.arange(c), positions])
        lengths = jnp.where(write_mask, positions + 1, 0)
        valid = jnp.concatenate([at[:c] < length, write_mask])[None]

    def attn(index, ap, xn, lat):
        q_n, q_r, rows = mla_project(cfg, ap, xn, at, kmesh,
                                     keep_product=True)
        # The lines' rows, a row a slot: [1, B, ...] -> [B, 1, ...].
        q_n_l, q_r_l, rows_l = (a[0, c:, None] for a in (q_n, q_r, rows))
        lat = _chunk_write(lat, rows[:, :c], index, slot, kv_len)
        lat = _lines_write(lat, rows_l, index, positions, write_mask, kmesh)
        with tracing.part("latent_prefill"):
            up = deepseek.kv_up_projections(cfg, ap["wkv_b"])
        o = _chunk_attend(cfg, lat, q_n[:, :c], q_r[:, :c], up, index, slot,
                          kv_len, length)
        o_l = _lines_attend(cfg, lat, q_n_l, q_r_l, up, index, lengths,
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
                                         cache["latent"], valid, kmesh)
    return ({"latent": lat}, deepseek.lm_head(cfg, params, x[0, c:], kmesh),
            counts)


decode_step, decode_burst = token_step_programs(_decode_impl,
                                                deepseek.COUNTERS)
mixed_burst = mixed_burst_program(_decode_impl, _mixed_impl,
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
    # A chunk and a step fetch the same weights, a layer's held experts
    # first among them: riding, a chunk's rows pass every product on the
    # step's fetch.
    mixed_burst=mixed_burst,
)
