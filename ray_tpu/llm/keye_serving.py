"""What models/keye.py supplies to the scheduler (llm/served.ServedModel): a
cache with a second kind of leaf, read whole where the first is read in
part, and the programs that run against it.

``{"k", "v", "index_k"}``, the slot second in all three:

- ``k``, ``v`` ``[layers, slots, kv_heads, max_seq, head_dim]``: the per-head
  slot cache of llm/served.py;
- ``index_k`` ``[layers, slots, 1, index_head_dim, max_seq]``: the indexer's
  key, one a cached position and layer whatever the heads (128 bytes where
  keys and values are 2,048), the positions last
  (ops/sparse_attention.py says why), written with a chunk's and a step's
  rows and never evicted: every later query of the line scores it.

A prefill chunk and a decode step do the same three things a layer
(ops/sparse_attention.py) after they have written their rows: score every
position each row may see against the whole ``index_k`` line, find each
row's ``index_topk`` best as a threshold and a tie's cut, and attend under
that mask. A chunk is one line of 512 rows, a step a row of every slot; a
slot that does not decode sees nothing, writes nothing and gives zeros.

The programs keep the contract's names and signatures and return, beside
their result, int32[11] counts summed over the program's layers and steps
(``COUNTERS``): the routed layers' (models/routed.MOE_COUNTERS) and the
selection's (models/keye.INDEX_COUNTERS).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.llm.served import ServedModel, token_step_programs
from ray_tpu.models import keye, sdar
from ray_tpu.models.keye import INDEX_COUNTERS, KeyeConfig
from ray_tpu.models.lfm2 import attention_heads
from ray_tpu.models.routed import MOE_COUNTERS
from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.decode_attention import decode_kv_block, kv_row_write
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.prefill_attention import prefill_kv_write
from ray_tpu.ops.rope import rope_frequencies
from ray_tpu.util import tracing

COUNTERS = MOE_COUNTERS + INDEX_COUNTERS
_LEAVES = ("k", "v", "index_k")


def init_cache(cfg: KeyeConfig, max_slots: int, max_seq: int):
    dt = cfg.jnp_dtype
    lines = (cfg.num_layers, max_slots, cfg.num_kv_heads, max_seq,
             cfg.head_dim)
    return {"k": jnp.zeros(lines, dt), "v": jnp.zeros(lines, dt),
            "index_k": jnp.zeros((cfg.num_layers, max_slots, 1,
                                  cfg.index_head_dim, max_seq), dt)}


def _attend(cfg, q, qi, w, leaves, layer, slots, q0, limits):
    """The three steps of a layer for N lines of C rows whose own rows are
    written: q [N, H, C, D], qi [N, J, C, Di], w [N, J, C]. Returns the
    heads' outputs [N, C, H * D]."""
    kc, vc, ic = leaves
    n, _, c, _ = q.shape
    with tracing.part("indexer"):
        scores = sa.index_scores(qi, w, ic, layer, slots, q0, limits)
    with tracing.part("index_select"):
        # A row sees through its own position, and nothing past the line.
        live = jnp.minimum(q0[:, None] + jnp.arange(1, c + 1)[None, :],
                           limits[:, None])
        thr, pcut = sa.topk_threshold(
            scores.reshape(n * c, -1), cfg.index_topk, live.reshape(-1))
    with tracing.part("sparse_attn"):
        o = sa.sparse_attention(q, kc, vc, scores, thr.reshape(n, c),
                                pcut.reshape(n, c), layer, slots, q0, limits)
        return o.transpose(0, 2, 1, 3).reshape(n, c, -1)


def _run(cfg, params, x, cache, attention, valid, kmesh):
    """Every layer with the cache's leaves and the selection's counts as
    carry."""
    zero = jnp.zeros((len(INDEX_COUNTERS),), jnp.int32)
    x, (*leaves, own), moe = sdar.run_layers(
        cfg, params, x, attention, (*(cache[k] for k in _LEAVES), zero),
        valid, kmesh)
    return x, dict(zip(_LEAVES, leaves)), jnp.concatenate([moe, own])


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: KeyeConfig, params, cache, tokens, kv_len, length,
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
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)
        slots, q0 = jnp.reshape(slot, (1,)), jnp.reshape(kv_len, (1,))
        # The chunk's own rows end at kv_len + c: a padded row sees no
        # further than the prompt.
        limits = jnp.minimum(q0 + c, length)
        seen = jnp.where(valid[0], positions + 1, 0)

    def attention(layer, ap, xn, state):
        kc, vc, ic, own = state
        q, k, v = attention_heads(cfg, ap, xn, positions, inv_freq)
        with tracing.part("indexer"):
            qi, ki, w = keye.indexer(
                cfg, keye.indexer_leaves(params["layers"], layer), xn,
                positions)
        with tracing.part("cache"):
            kc, vc = prefill_kv_write(kc, vc, k[0], v[0], layer, slot, kv_len)
            ic = sa.index_chunk_write(ic, ki[0], layer, slot, kv_len)
        o = _attend(cfg, q, qi, w, (kc, vc, ic), layer, slots, q0, limits)
        with tracing.part("index_select"):
            own = own + keye.index_counts(cfg, seen, step=False)
        return (o @ ap["wo"]).astype(xn.dtype), (kc, vc, ic, own)

    x, cache, counts = _run(cfg, params, x, cache, attention, valid, kmesh)
    # The head on the one row that is kept.
    with tracing.part("head"):
        last = x[0, jnp.clip(length - 1 - kv_len, 0, c - 1)]
    return cache, sdar.lm_head(cfg, params, last, kmesh), counts


def _decode_impl(cfg: KeyeConfig, params, cache, tokens, positions0,
                 write_mask, kmesh=None):
    """One token per slot against the lines. Returns (cache, logits [B, V],
    counts). A slot with ``write_mask`` false writes no row, sees nothing,
    is routed nowhere, and its logits mean nothing."""
    b = tokens.shape[0]
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][:, None]           # [B, 1, H]
    with tracing.part("attn"):
        positions = positions0[:, None]
        lengths = jnp.where(write_mask, positions0 + 1, 0)
        valid = write_mask[:, None]
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)
        slots = jnp.arange(b, dtype=jnp.int32)

    def attention(layer, ap, xn, state):
        kc, vc, ic, own = state
        q, k, v = attention_heads(cfg, ap, xn, positions, inv_freq)
        with tracing.part("indexer"):
            qi, ki, w = keye.indexer(
                cfg, keye.indexer_leaves(params["layers"], layer), xn,
                positions)
        with tracing.part("cache"):
            kc, vc = kv_row_write(kc, vc, k, v, layer, positions0,
                                  write_mask, kmesh=kmesh)
            ic = sa.index_rows_write(ic, ki[:, 0], layer, positions0,
                                     write_mask)
        o = _attend(cfg, q, qi, w, (kc, vc, ic), layer, slots, positions0,
                    lengths)
        with tracing.part("index_select"):
            own = own + keye.index_counts(cfg, lengths, step=True)
        return (o @ ap["wo"]).astype(xn.dtype), (kc, vc, ic, own)

    x, cache, counts = _run(cfg, params, x, cache, attention, valid, kmesh)
    return cache, sdar.lm_head(cfg, params, x[:, 0], kmesh), counts


decode_step, decode_burst = token_step_programs(_decode_impl, COUNTERS)


def _refuse(config) -> None:
    """What this model does not run, said at construction."""
    if config.speculative_model is not None:
        raise ValueError(
            "KeyeConfig does not support a speculative draft: a verify "
            "forward over several proposed tokens would select for each of "
            "them, and no such program is written")


SERVED = ServedModel(
    init_params=keye.init_params,
    param_logical_axes=keye.param_logical_axes,
    init_cache=init_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    kv_block=lambda cfg, max_seq: decode_kv_block(
        max_seq, cfg.head_dim, cfg.jnp_dtype.itemsize),
    counters=COUNTERS,
    constants=lambda cfg: {"moe_experts_held": cfg.experts_held,
                           "attention_lines": cfg.num_layers,
                           "index_topk": cfg.index_topk},
    # A line is three leaves: the hand-off ships keys and values alone, and
    # a prefix adopted without its index keys would be scored against
    # zeros (ROADMAP R11 (a)).
    kv_handoff=False,
    prefix_from_line=False,
    refuse=_refuse,
)
