"""Latent (MLA) attention as models/longcat.py, models/deepseek.py and
models/ling.py have it: the projections down to the latent row and out to the heads'
queries, the up-projections of the cached rows, and the attention over whole
sequences that the two ``forward``s run. Per position a model caches
``c_kv`` (after its norm and scale) and the rotated shared key ``k_r``,
``kv_lora_rank + qk_rope_head_dim`` values for all heads; the serving
programs attend against those rows (llm/latent.py). ``cfg`` is any of
the models' configurations: it says whether the queries come through a
low-rank pair (``q_lora_rank``; None: one matrix ``wq``, no ``wq_a``,
``q_a_norm``, ``wq_b``) and which pairs its rotary turns
(``mla_rope_interleaved``: adjacent pairs where true, a half against the
other where false).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import (
    apply_rope,
    apply_rope_interleaved,
    rope_frequencies,
)


def mla_scales(cfg) -> tuple[float, float]:
    """What the two latent norms' outputs are multiplied by."""
    sq = (math.sqrt(cfg.hidden_size / cfg.q_lora_rank)
          if cfg.mla_scale_q_lora else 1.0)
    skv = (math.sqrt(cfg.hidden_size / cfg.kv_lora_rank)
           if cfg.mla_scale_kv_lora else 1.0)
    return sq, skv


def mla_project(cfg, ap: dict, xn, positions, kmesh=None,
                keep_product: bool = False):
    """xn: [B, S, H] (normed); positions [S] or [B, S]. Returns the heads'
    queries q_n [B, S, nh, Dn] and q_r [B, S, nh, Dr] (rotated), and the
    rows to cache [B, S, latent_row]: ``c_kv`` after norm and scale, the
    rotated shared key, zeros up to the row's width. ``keep_product`` keeps
    the queries' product an array of its own before it is split into heads
    (models/ouro.block's finding: XLA otherwise folds the split into the
    product and copies the whole stacked ``wq_b`` transposed at the top of
    a decode program, 0.6 GB at 128 heads)."""
    b, s, _ = xn.shape
    sq, skv = mla_scales(cfg)
    dt = xn.dtype
    if cfg.q_lora_rank is None:
        q = xn @ ap["wq"]
    else:
        cq = rms_norm(xn @ ap["wq_a"], ap["q_a_norm"], cfg.norm_eps, kmesh)
        q = (cq * sq).astype(dt) @ ap["wq_b"]
    if keep_product:
        q = lax.optimization_barrier(q)
    q = q.reshape(b, s, cfg.num_heads, cfg.qk_head_dim)
    q_n, q_r = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    kv = xn @ ap["wkv_a"]
    ckv = rms_norm(kv[..., :cfg.kv_lora_rank], ap["kv_a_norm"], cfg.norm_eps,
                   kmesh)
    ckv = (ckv * skv).astype(dt)
    inv_freq = rope_frequencies(cfg.qk_rope_head_dim, cfg.rope_theta,
                                cfg.rope_scaling)
    rope = apply_rope_interleaved if cfg.mla_rope_interleaved else apply_rope
    q_r = rope(q_r.transpose(0, 2, 1, 3), positions,
               inv_freq).transpose(0, 2, 1, 3)
    k_r = rope(kv[:, None, :, cfg.kv_lora_rank:], positions, inv_freq)[:, 0]
    pad = jnp.zeros((b, s, cfg.latent_row - cfg.latent_dim), dt)
    return q_n, q_r, jnp.concatenate([ckv, k_r, pad], axis=-1)


def kv_up_projections(cfg, wkv_b):
    """wkv_b [rank, nh * (Dn + Dv)] -> the key half [rank, nh, Dn] and the
    value half [rank, nh, Dv] (a head's output is its keys, then its
    values)."""
    w = wkv_b.reshape(cfg.kv_lora_rank, cfg.num_heads,
                      cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_attend_full(cfg, ap: dict, xn, kmesh=None,
                    up_projections=kv_up_projections):
    """Causal latent attention over whole sequences, keys and values
    up-projected (no cache), before the output projection. xn: [B, S, H]
    -> [B, S, nh * Dv]. ``up_projections(cfg, wkv_b)`` gives the two halves
    [rank, nh, D] of a model's ``wkv_b`` (models/deepseek.py stores its a
    head at a time)."""
    b, s, _ = xn.shape
    q_n, q_r, rows = mla_project(cfg, ap, xn, jnp.arange(s), kmesh)
    w_kb, w_vb = up_projections(cfg, ap["wkv_b"])
    ckv = rows[..., :cfg.kv_lora_rank]
    k_r = rows[..., cfg.kv_lora_rank:cfg.latent_dim]
    k_n = jnp.einsum("bsr,rhd->bshd", ckv, w_kb)
    v = jnp.einsum("bsr,rhd->bshd", ckv, w_vb)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r,
                           preferred_element_type=jnp.float32))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores * cfg.sm_scale, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(xn.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)


def mla_full(cfg, ap: dict, xn, kmesh=None,
             up_projections=kv_up_projections):
    """:func:`mla_attend_full` through ``wo``: xn [B, S, H] -> [B, S, H]."""
    o = mla_attend_full(cfg, ap, xn, kmesh, up_projections)
    return (o @ ap["wo"]).astype(xn.dtype)
