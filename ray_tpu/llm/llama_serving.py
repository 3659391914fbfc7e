"""What models/llama.py supplies to the scheduler (llm/served.ServedModel):
the programs of the dense decoder the engine was written around, against
the per-head K/V slot cache (llm/served.init_kv_cache; a line a layer).

The cache rides every layer loop as carry, never as scan xs/ys: a prefill
chunk writes its rows of its slot and layer in place and reads only the
live blocks of that slot's line (ops/prefill_attention.py); a decode step
does the same for its one row a slot (ops/decode_attention.py), both
grouped over the query heads of a KV head, so no operation of a chunk or
decode program has a whole layer of the cache as operand.

The weights ride every layer loop as scan xs and are read where they lie:
q, k and v are one product against one stacked leaf, ``wqkv``, which
``program_params`` fuses once where the engine places a tree, so that no
scheduled program copies a weight stack or a layer's slice of one.

``prefill_chunk``, ``decode_step`` and ``decode_burst`` are the scheduler's
three programs (the last two built from ``_decode_step_impl`` by
llm/served.token_step_programs); ``mixed_burst`` is the burst whose steps
carry a prefill chunk each (from ``_decode_step_impl`` and ``_mixed_impl`` by
llm/served.mixed_burst_program): a step reads every layer's weights for a
row a line, a chunk is bound by its products, and riding, the chunk's rows
pass every product on the step's fetch; ``draft_propose`` and
``spec_verify_step`` are the two of speculative decoding, which this model
alone supplies; ``prefill`` is the whole-prompt program no schedule runs:
the oracle ``prefill_chunk`` is held to (tests/test_llm.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.llm.served import (
    NEG_INF,
    ServedModel,
    copy_prefix_kv,
    init_kv_cache,
    mixed_burst_program,
    mixed_rows,
    token_step_programs,
)
from ray_tpu.models import llama as llama_model
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.decode_attention import (
    decode_attention,
    decode_kv_block,
    decode_plan_of,
    kv_row_write,
)
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.prefill_attention import prefill_attention, prefill_kv_write
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.util import tracing


def _fused_qkv(cfg: LlamaConfig, wq, wk, wv):
    """The stacked ``wq``, ``wk`` and ``wv`` as one leaf ``[L, H, nkv *
    (n_rep + 2) * D]``, its columns grouped by KV head: a group's ``n_rep``
    query heads in their published order, then its key head, then its value
    head. Grouped so, the last axis splits over ``tp`` on whole groups and
    a product's result reshapes to ``[rows, nkv, n_rep + 2, D]``."""
    L, h = wq.shape[:2]
    return jnp.concatenate(
        [w.reshape(L, h, cfg.num_kv_heads, -1, cfg.head_dim)
         for w in (wq, wk, wv)], axis=3).reshape(L, h, -1)


_fuse_qkv = jax.jit(_fused_qkv, static_argnums=0)


def program_params(cfg: LlamaConfig, params):
    """``ServedModel.program_params``: the tree with ``layers.wqkv``
    (:func:`_fused_qkv`) beside its leaves, every one of which is the buffer
    it was. The scheduled programs multiply q, k and v as one product against
    that leaf, which a layer loop reads in place from HBM: three stacked
    leaves XLA re-lays out whole once a burst, copies a layer's slice of
    each out of, and, where a re-laid stack fits the fast memory (``wk`` at
    12 layers, 96 MiB), evicts and fetches back in every layer (PERF.md
    section 6, PR 57). ``wq``, ``wk`` and ``wv`` stay in the tree for the
    oracle ``prefill`` and for who reads the weights by name (ROADMAP
    R0 (f)); no scheduled program reads them, so they cost memory and no
    time. A tree that has the leaf is returned as it is. The programs call
    this on entry: handed a tree without the leaf (a test, a probe), they
    fuse it inside the program, at the cost this function is there to pay
    once."""
    layers = params["layers"]
    if "wqkv" in layers:
        return params
    wqkv = _fuse_qkv(cfg, layers["wq"], layers["wk"], layers["wv"])
    return {**params, "layers": {**layers, "wqkv": wqkv}}


def param_logical_axes(cfg: LlamaConfig) -> dict:
    """models/llama.py's axes and the fused leaf's: whole groups over
    ``tp``."""
    axes = llama_model.param_logical_axes(cfg)
    return {**axes, "layers": {**axes["layers"],
                               "wqkv": ("layers", "embed", "kv_heads")}}


def _split_qkv(cfg: LlamaConfig, rows):
    """A product against ``wqkv`` [..., nkv * (n_rep + 2) * D] -> q [...,
    nh, D], k and v [..., nkv, D]."""
    n_rep = cfg.num_heads // cfg.num_kv_heads
    rows = rows.reshape(*rows.shape[:-1], cfg.num_kv_heads, n_rep + 2,
                        cfg.head_dim)
    q = rows[..., :n_rep, :].reshape(*rows.shape[:-3], cfg.num_heads,
                                     cfg.head_dim)
    return q, rows[..., n_rep, :], rows[..., n_rep + 1, :]


def _qkv_rows(lp, xn):
    """xn [..., H] @ ``wqkv`` -> [..., nkv * (n_rep + 2) * D], kept as an
    array of its own. With the split into heads fused into the product XLA
    computes it as ``[rows, nkv, n_rep + 2, D]``, wants the leaf
    output-major for that, re-lays the whole stack out once a burst (0.56
    GiB of temporaries at 12 layers) and copies a layer's slice of it into
    the fast memory in every layer; behind the barrier the product is the
    MLP's plain kind and a ``dynamic-slice`` of the stack feeds it in place
    (``devbench/llama_bench.py aot``, PR 57)."""
    return lax.optimization_barrier(xn @ lp["wqkv"])


def _project_qkv(cfg: LlamaConfig, lp, xn):
    """xn [B, S, H] -> q [B, nh, S, D], k and v [B, nkv, S, D]."""
    return tuple(a.transpose(0, 2, 1, 3)
                 for a in _split_qkv(cfg, _qkv_rows(lp, xn)))


def _repeat_kv(x, n_rep: int):
    if n_rep == 1:
        return x
    b, h, s, d = x.shape
    return jnp.broadcast_to(x[:, :, None], (b, h, n_rep, s, d)).reshape(
        b, h * n_rep, s, d)


@tracing.part("mlp")
def _mlp(cfg: LlamaConfig, lp, x, kmesh):
    dt = x.dtype
    xn = rms_norm(x, lp["mlp_norm"], cfg.norm_eps, kmesh)
    gate = jax.nn.silu((xn @ lp["w_gate"]).astype(jnp.float32)).astype(dt)
    up = xn @ lp["w_up"]
    # The product is kept as an array of its own: fused into the down
    # projection as its operand, XLA computes it again for every tile of
    # the output (a chunk of 512 at Mistral widths: 0.61 ms a layer against
    # 0.33, my chip run, PR 28).
    act = lax.optimization_barrier(gate * up)
    return x + (act @ lp["w_down"]).astype(dt)


@tracing.part("head")
def _lm_head(cfg: LlamaConfig, params, x, kmesh):
    """x: [B, S, H] → fp32 logits [B, S, V]."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)
    head = (params["embed_tokens"].T if cfg.tie_embeddings
            else params["lm_head"])
    return x.astype(jnp.float32) @ head.astype(jnp.float32)


# A scheduled program's attention is made of these halves: the chunk's (one
# slot, C rows from ``kv_len`` on) and the lines' (every slot, K rows each
# from its position on). ``prefill_chunk`` runs the first,
# ``_multi_token_impl`` the second, and a mixed step both, on the rows of
# one array.

def _chunk_attend(k_all, v_all, q, k, v, layer, slot, kv_len, length, kmesh):
    """A chunk's rows written to its slot's line and attended from it:
    q [1, nh, C, D], k and v [1, nkv, C, D] -> (k_all, v_all,
    o [1, C, nh * D])."""
    with tracing.part("cache"):
        k_all, v_all = prefill_kv_write(k_all, v_all, k[0], v[0], layer,
                                        slot, kv_len)
    o = prefill_attention(q[0], k_all, v_all, layer, slot, kv_len, length,
                          kmesh=kmesh)
    return k_all, v_all, o.transpose(1, 0, 2).reshape(1, q.shape[2], -1)


def _lines_attend(k_all, v_all, q, k, v, layer, lengths, positions0,
                  write_mask, plan, kmesh):
    """The lines' rows written and attended from: q [B, nh, K, D], k and v
    [B, nkv, K, D] -> (k_all, v_all, o [B, K, nh * D])."""
    with tracing.part("cache"):
        k_all, v_all = kv_row_write(k_all, v_all, k, v, layer, positions0,
                                    write_mask, kmesh=kmesh)
    o = decode_attention(q, k_all, v_all, layer, lengths, positions0,
                         plan=plan, kmesh=kmesh)
    b, _, rows, _ = q.shape
    return k_all, v_all, o.transpose(0, 2, 1, 3).reshape(b, rows, -1)


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill(cfg: LlamaConfig, params, cache, tokens, length, slot, *,
            kmesh: KernelMesh | None = None):
    """Prefill ONE sequence into cache slot ``slot``.

    ``kmesh`` (here and on every program below): the engine's mesh when
    tensor-parallel, for the Pallas kernels (ops/kernels.py); None on one
    device.

    tokens: [S_bucket] (padded), length: scalar int32 (true prompt length),
    returns (cache, next_token_logits [V]).
    """
    s = tokens.shape[0]
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][None]  # [1, S, H]
    with tracing.part("attn"):
        positions = jnp.arange(s)
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta,
                                    cfg.rope_scaling)
        n_rep = cfg.num_heads // cfg.num_kv_heads
        causal = (positions[None, :] <= positions[:, None])  # [S, S]
        valid = positions[None, :] < length
        mask = (causal & valid)[None, None]  # [1, 1, S, S]

    def body(x, scanned):
        lp, k_l, v_l = scanned  # k_l/v_l: [slots, Hkv, max_seq, D]
        b, s_, _ = x.shape
        with tracing.part("attn"):
            xn = rms_norm(x, lp["attn_norm"], cfg.norm_eps, kmesh)
            # The three leaves, not the fused one the scheduled programs
            # read: the oracle holds the leaf's grouping too.
            q, k, v = ((xn @ lp[w]).reshape(b, s_, -1, cfg.head_dim)
                       .transpose(0, 2, 1, 3) for w in ("wq", "wk", "wv"))
            q = apply_rope(q, positions, inv_freq)
            k = apply_rope(k, positions, inv_freq)
            # Write this layer's K/V into the slot (positions 0..S).
            with tracing.part("cache"):
                k_l = lax.dynamic_update_slice(
                    k_l, k[0].astype(k_l.dtype)[None], (slot, 0, 0, 0))
                v_l = lax.dynamic_update_slice(
                    v_l, v[0].astype(v_l.dtype)[None], (slot, 0, 0, 0))
            kr, vr = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, kr).astype(jnp.float32)
            scores = scores / np.sqrt(cfg.head_dim) \
                + jnp.where(mask, 0.0, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            o = jnp.einsum("bhqk,bhkd->bhqd", probs, vr)
            o = o.transpose(0, 2, 1, 3).reshape(b, s_, -1)
            x = x + (o @ lp["wo"]).astype(x.dtype)
        x = _mlp(cfg, lp, x, kmesh)
        return x, (k_l, v_l)

    with tracing.part("stack"):
        x, (new_k, new_v) = lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
    logits = _lm_head(cfg, params, x, kmesh)[0]  # [S, V]
    with tracing.part("head"):
        last = logits[jnp.maximum(length - 1, 0)]
    return {"k": new_k, "v": new_v}, last


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: LlamaConfig, params, cache, tokens, kv_len, length,
                  slot, *, kmesh: KernelMesh | None = None):
    """Prefill ONE chunk of one sequence (chunked prefill — long prompts are
    split so decode steps interleave between chunks instead of stalling
    behind a whole-prompt prefill; reference shape: vLLM chunked prefill /
    enable_chunked_prefill).

    tokens: [C] chunk (padded), kv_len: tokens already cached for this slot,
    length: true total prompt length. Queries attend to cache[0..kv_len) +
    the chunk's own causal prefix. Returns (cache, last-token logits [V]).

    The convention of ``_multi_token_impl``: the stacked cache rides the
    layer loop as carry, a layer writes the chunk's C rows of its slot in
    place and ops/prefill_attention.py reads the slot's live blocks straight
    out of the stack, so no operation of the program has the whole cache, or
    a whole layer of it, as operand or result.
    """
    c = tokens.shape[0]
    num_layers = cache["k"].shape[0]
    params = program_params(cfg, params)
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][None]  # [1, C, H]
    with tracing.part("attn"):
        positions = kv_len + jnp.arange(c)
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta,
                                    cfg.rope_scaling)

    def body(carry, scanned):
        x, k_all, v_all = carry
        lp, layer = scanned
        with tracing.part("attn"):
            xn = rms_norm(x, lp["attn_norm"], cfg.norm_eps, kmesh)
            q, k, v = _project_qkv(cfg, lp, xn)
            q = apply_rope(q, positions, inv_freq)
            k = apply_rope(k, positions, inv_freq)
            k_all, v_all, o = _chunk_attend(k_all, v_all, q, k, v, layer,
                                            slot, kv_len, length, kmesh)
            x = x + (o @ lp["wo"]).astype(x.dtype)
        x = _mlp(cfg, lp, x, kmesh)
        return (x, k_all, v_all), None

    with tracing.part("stack"):
        (x, new_k, new_v), _ = lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(num_layers)))
    # The head on the one row that is kept.
    with tracing.part("head"):
        last = lax.dynamic_slice_in_dim(
            x, jnp.clip(length - 1 - kv_len, 0, c - 1), 1, 1)  # [1, 1, H]
    return {"k": new_k, "v": new_v}, _lm_head(cfg, params, last, kmesh)[0, 0]


def _decode_step_impl(cfg: LlamaConfig, params, cache, tokens, positions,
                      write_mask, kmesh: KernelMesh | None = None):
    """One decode step for EVERY slot, the single step ``decode_step`` and
    ``decode_burst`` are built from (llm/served.token_step_programs, where
    the arguments are described). Returns (cache, logits [B, V]).

    Exactly the K=1 case of the multi-token body (speculative
    verification runs it at K > 1) — ONE implementation of the
    masked-attention/KV-write math, so the two paths can never diverge.
    """
    cache, logits = _multi_token_impl(cfg, params, cache, tokens[:, None],
                                      positions, write_mask, kmesh)
    with tracing.part("head"):
        return cache, logits[:, 0]


def _multi_token_impl(cfg: LlamaConfig, params, cache, tokens, positions0,
                      write_mask, kmesh=None):
    """Consume K tokens per slot in one pass against the KV cache.

    tokens: [B, K]; positions0: [B] — tokens[:, j] is written at
    positions0 + j (contiguous); query j attends kv through its own
    position. Returns (cache, logits [B, K, V]).

    The stacked cache rides the layer loop as carry, not as scan xs/ys: a
    layer writes its K new rows of each slot in place and
    ops/decode_attention.py reads the layer's live blocks straight out of
    the stack, so no operation of the program has a whole layer of the
    cache, or the whole cache, as operand or result. A slot with
    ``write_mask`` false has length 0: nothing of its line is read, and its
    logits mean nothing."""
    b, k = tokens.shape
    num_layers = cache["k"].shape[0]
    params = program_params(cfg, params)
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]  # [B, K, H]
    with tracing.part("attn"):
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta,
                                    cfg.rope_scaling)
        positions = positions0[:, None] + jnp.arange(k)[None, :]  # [B, K]
        lengths = jnp.where(write_mask, positions0 + k, 0)
        # Every layer attends at the same lengths: one walk of the live
        # blocks, planned here and not in the loop.
        plan = decode_plan_of(lengths, cache["k"], kmesh=kmesh)

    def body(carry, scanned):
        x, k_all, v_all = carry
        lp, layer = scanned
        with tracing.part("attn"):
            xn = rms_norm(x, lp["attn_norm"], cfg.norm_eps, kmesh)
            q, kk, v = _project_qkv(cfg, lp, xn)
            q = apply_rope(q, positions, inv_freq)
            kk = apply_rope(kk, positions, inv_freq)
            k_all, v_all, o = _lines_attend(k_all, v_all, q, kk, v, layer,
                                            lengths, positions0, write_mask,
                                            plan, kmesh)
            x = x + (o @ lp["wo"]).astype(x.dtype)
        x = _mlp(cfg, lp, x, kmesh)
        return (x, k_all, v_all), None

    with tracing.part("stack"):
        (x, new_k, new_v), _ = lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(num_layers)))
    logits = _lm_head(cfg, params, x, kmesh)  # [B, K, V]
    return {"k": new_k, "v": new_v}, logits


def _mixed_impl(cfg: LlamaConfig, params, cache, tokens, positions0,
                write_mask, chunk, kv_len, length, slot, kmesh=None):
    """``mixed_step`` of llm/served.mixed_burst_program. The norms, the
    ``wqkv`` product, ``wo`` and the MLP see all rows at once: a layer's
    weights are fetched once for both. The attention splits them, the
    chunk's rows to the chunk's half and the lines' to the lines'. The
    product's rows are split before its heads: the heads of all 528 rows
    split first (``_project_qkv``'s order) cost 1.6 ms a step more at
    docqa's 16 layers; a barrier on the halves' outputs, the lines' half
    first and the lines' rows first gave nothing (my chip runs, PR 55, when
    the product was three)."""
    c, b = chunk.shape[0], tokens.shape[0]
    num_layers = cache["k"].shape[0]
    params = program_params(cfg, params)
    with tracing.part("attn"):
        ids, at, _, lengths, lines_of = mixed_rows(
            chunk, tokens, kv_len, length, positions0, write_mask)
        at_chunk, at_lines = at[:c], at[c:, None]
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta,
                                    cfg.rope_scaling)
        plan = decode_plan_of(lengths, cache["k"], kmesh=kmesh)
    with tracing.part("embed"):
        x = params["embed_tokens"][ids][None]  # [1, C + B, H]

    def body(carry, scanned):
        x, k_all, v_all = carry
        lp, layer = scanned
        with tracing.part("attn"):
            xn = rms_norm(x, lp["attn_norm"], cfg.norm_eps, kmesh)
            rows = _qkv_rows(lp, xn)[0]  # [C + B, nkv * (n_rep + 2) * D]
            # head-major a half: the chunk's [1, n, C, D], the lines'
            # [B, n, 1, D]
            q, k, v = (a.transpose(1, 0, 2)[None]
                       for a in _split_qkv(cfg, rows[:c]))
            q_l, k_l, v_l = (a[:, :, None]
                             for a in _split_qkv(cfg, rows[c:]))
            q, k = (apply_rope(a, at_chunk, inv_freq) for a in (q, k))
            q_l, k_l = (apply_rope(a, at_lines, inv_freq)
                        for a in (q_l, k_l))
            k_all, v_all, o = _chunk_attend(k_all, v_all, q, k, v, layer,
                                            slot, kv_len, length, kmesh)
            k_all, v_all, o_l = _lines_attend(k_all, v_all, q_l, k_l, v_l,
                                              layer, lengths, positions0,
                                              write_mask, plan, kmesh)
            o = jnp.concatenate([o, o_l.reshape(1, b, -1)], axis=1)
            x = x + (o @ lp["wo"]).astype(x.dtype)
        x = _mlp(cfg, lp, x, kmesh)
        return (x, k_all, v_all), None

    with tracing.part("stack"):
        (x, new_k, new_v), _ = lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(num_layers)))
    logits = _lm_head(cfg, params, lines_of(x)[:, None], kmesh)  # [B, 1, V]
    with tracing.part("head"):
        return {"k": new_k, "v": new_v}, logits[:, 0]


decode_step, decode_burst = token_step_programs(_decode_step_impl)
mixed_burst = mixed_burst_program(_decode_step_impl, _mixed_impl)


# ---------------------------------------------------------------------------
# Speculative decoding (reference capability: the vLLM speculative-decoding
# path behind the reference's llm serving stack). Decode is HBM-bound on
# TPU — one token per full weight read; verifying K draft tokens in one
# forward amortizes the weight traffic K-fold when the draft is right.
# Rollback is FREE in this cache design: entries written beyond the
# accepted prefix sit at positions >= next_pos, which every later read
# masks (kv_pos <= position) and every later write overwrites.


@partial(jax.jit, static_argnums=(0, 5), static_argnames=("kmesh",),
         donate_argnums=(2,))
def draft_propose(cfg: LlamaConfig, params, cache, token0, positions0,
                  k: int, write_mask, *, kmesh: KernelMesh | None = None):
    """Greedy-propose ``k`` tokens with the draft model in ONE dispatch
    (lax.scan over its decode step). Writes draft KV for token0 and the
    first k-1 proposals. Returns (cache, proposals [B, k])."""

    def step(carry, _):
        c, tok, pos = carry
        c, logits = _decode_step_impl(cfg, params, c, tok, pos, write_mask,
                                      kmesh)
        with tracing.part("sample"):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (c, nxt, pos + 1), nxt

    # k+1 iterations: the extra step writes the LAST proposal's KV inside
    # this same dispatch (its own proposal is discarded), so a
    # full-acceptance tick needs no separate one-token catch-up prefill.
    with tracing.part("stack"):
        (cache, _, _), toks = lax.scan(step, (cache, token0, positions0),
                                       None, length=k + 1)
    with tracing.part("sample"):
        return cache, toks.T[:, :k]  # [B, k]


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def spec_verify_step(cfg: LlamaConfig, params, cache, tokens, positions0,
                     write_mask, *, kmesh: KernelMesh | None = None):
    """Target forward over K tokens per slot in one pass (the jitted
    multi-token body decode_step is the K=1 case of).

    tokens: [B, K] — the last sampled token followed by the draft
    proposals; positions0: [B] — where tokens[:, 0] is written. Writes
    K/V for all K positions (contiguous) and returns (cache,
    logits [B, K, V]): logits[:, j] scores the token at position
    positions0 + j + 1, which is what acceptance compares against."""
    return _multi_token_impl(cfg, params, cache, tokens, positions0,
                             write_mask, kmesh)

SERVED = ServedModel(
    init_params=llama_model.init_params,
    param_logical_axes=param_logical_axes,
    program_params=program_params,
    init_cache=init_kv_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    copy_prefix_kv=copy_prefix_kv,
    kv_block=lambda cfg, max_seq: decode_kv_block(
        max_seq, cfg.head_dim, cfg.jnp_dtype.itemsize),
    # Heads and the MLP's columns shard over ``tp``, the cache with them.
    tensor_parallel=True,
    draft_propose=draft_propose,
    spec_verify_step=spec_verify_step,
    mixed_burst=mixed_burst,
)
