"""The latent slot cache and the halves of an attention against it, for the
serving modules of the models whose attention is models/mla.py's.

The cache is one array ``{"latent": [lines, slots, max_seq, latent_row]}``:
per cache line (an attention's: a layer has one or two), slot and position
the row every head reads (ops/latent_attention.py; ``kv_lora_rank +
qk_rope_head_dim`` values and zeros up to whole lanes, 576 of 640), a fifth
to a third of per-head keys and values. It rides every layer loop as
carry, never as scan xs/ys.

A program's attention is made of the four halves below: the chunk's (one
slot, C rows from ``kv_len`` on, written in place and attended from the
live blocks of the slot's line, keys and values up-projected) and the
lines' (every slot, a row each, written in place and attended in the
absorbed form, a single read of the line for all heads). ``prefill_chunk``
closes over the first two, a decode step over the last two, a mixed step
(llm/served.mixed_burst_program) over all four, on the rows of one
``mla_project`` and one ``up``, a layer's ``kv_up_projections`` as its model
stores them. ``index`` is the cache line.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.latent_attention import (
    latent_decode_attention,
    latent_kv_block,
    latent_prefill_attention,
    latent_row_write,
)
from ray_tpu.util import tracing


def init_cache(cfg, lines: int, max_slots: int, max_seq: int):
    """A zeroed cache of ``lines`` lines, a model's own count of them."""
    return {"latent": jnp.zeros((lines, max_slots, max_seq, cfg.latent_row),
                                cfg.jnp_dtype)}


def kv_block(cfg, max_seq: int) -> int:
    """The positions a decode step's attention fetches at a time."""
    return latent_kv_block(max_seq)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
@tracing.part("cache")
def copy_prefix_kv(cfg, cache, src_slot, dst_slot):
    """Copy one slot's whole latent line to another slot, all cache lines
    at once (prefix adoption from a live donor)."""
    line = lax.dynamic_slice_in_dim(cache["latent"], src_slot, 1, 1)
    return {"latent": lax.dynamic_update_slice(
        cache["latent"], line, (0, dst_slot, 0, 0))}


def chunk_write(lat, rows, index, slot, kv_len):
    """A chunk's rows [1, C, row] written to its slot's line."""
    with tracing.part("cache"):
        return lax.dynamic_update_slice(
            lat, rows.astype(lat.dtype)[None], (index, slot, kv_len, 0))


def chunk_attend(cfg, lat, q_n, q_r, up, index, slot, kv_len, length):
    """A chunk attended from its slot's line, its own rows written: q_n
    [1, C, nh, Dn], q_r [1, C, nh, Dr] -> [1, C, nh * Dv]."""
    with tracing.part("latent_prefill"):
        o = latent_prefill_attention(q_n[0], q_r[0], lat, *up, index, slot,
                                     kv_len, length,
                                     rope_dim=cfg.qk_rope_head_dim,
                                     sm_scale=cfg.sm_scale)
    return o.reshape(1, q_n.shape[1], -1)


def lines_write(lat, rows, index, positions, write_mask, kmesh):
    """Every decoding line's row written: rows [B, 1, row]."""
    with tracing.part("cache"):
        return latent_row_write(lat, rows, index, positions, write_mask,
                                kmesh=kmesh)


def lines_attend(cfg, lat, q_n, q_r, up, index, lengths, positions, kmesh):
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
