"""Prefill attention: one chunk of one sequence against its slot's line of a
stacked KV cache.

A prefill chunk gives one slot C new tokens at positions ``kv_len ..
kv_len + C``. Their keys and values are already in the cache; the queries
attend to the slot's line up to their own position. The line is long
(``max_seq``), mostly dead while a prompt is going in, and shared by the
``num_heads // num_kv_heads`` query heads of each KV head. So the op has the
shape of ops/decode_attention.py, with a block of the chunk's tokens where
that one has every row of a slot:

- **grouped**: the G query heads of one KV head times a block of the
  chunk's tokens are one tile of rows against a single read of that head's
  keys and values; no ``[1, H, S, D]`` copy exists;
- **length-aware**: blocks of the line at or past ``min(kv_len + C,
  length)``, and past the last position a tile's queries may see, are
  neither fetched (the index map clamps to the last live block, so the
  pipeline keeps the buffer it has) nor computed; blocks wholly below the
  tile's first query need no mask. ``layer``, ``slot``, ``kv_len`` and
  ``length`` are run-time scalars (scalar prefetch): one program per chunk
  size serves every slot and every cached length;
- **in place**: it receives the whole stacked cache ``[L, B, Hkv, S, D]``
  and its block specs index layer and slot. A caller that handed it
  ``cache[l]`` would make XLA materialise that slice on every layer.

Scores, the running maximum and sum, and the PV accumulation are float32;
the operands stay in the cache's dtype and the probabilities are cast to it
before the second matmul. Query t sees key positions ``<= kv_len + t`` and
``< length``; a row that sees nothing gives zeros. Rows of a padded chunk
past the prompt's end see what the last real row sees, and mean nothing.

``block`` (static, 1 for a causal model) makes the mask block-causal: the
positions are cut into blocks of that many and a query sees every key of
its own block and of the blocks before it, so query t sees through the last
position of the block that holds ``kv_len + t`` (models/sdar.py). At 1 the
block is the position and every program is what it was.

The three implementations of ops/kernels.py: the Mosaic kernel on a TPU,
the same body through the Pallas interpreter for tests, and a jnp reference
elsewhere (the dense ``[C, max_seq]`` form the engine used to run).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.decode_attention import (
    NEG_INF,
    decode_kv_block,
    packed_kernel,
    packed_rows,
)
from ray_tpu.ops.kernels import KernelMesh, kernel_backend

# Rows of one tile of queries: G heads times a block of the chunk's tokens.
# The float32 scores of a tile against one block of keys are rows x block x 4
# bytes of VMEM (2.5 MiB at 1,024 x 640), and there are a few arrays of that
# size alive at once.
_TILE_ROWS = 1024


def prefill_q_block(chunk: int, group: int, itemsize: int = 2) -> int:
    """Tokens of the chunk in one tile of queries: a multiple of the packed
    sublane tile (16 rows of a 16-bit dtype), the whole padded chunk where it
    is short, and otherwise what keeps the tile within ``_TILE_ROWS``."""
    tile = 32 // itemsize
    cap = max(tile, _TILE_ROWS // group // tile * tile)
    return min(cap, -(-chunk // tile) * tile)


def _last_seen(qpos, block: int):
    """The last key position a query at ``qpos`` may see."""
    if block == 1:
        return qpos
    return qpos + (block - 1 - lax.rem(qpos, block))


def prefill_attention_reference(q, k_cache, v_cache, layer, slot, kv_len,
                                length, sm_scale: float | None = None,
                                block: int = 1):
    """Masked softmax over the slot's whole line, grouped like the kernel
    (no repeated K/V), float32 scores and accumulation."""
    h, c, d = q.shape
    hkv, s = k_cache.shape[2], k_cache.shape[3]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)

    def line(stack):
        return lax.dynamic_slice(stack, (layer, slot, 0, 0, 0),
                                 (1, 1, hkv, s, stack.shape[-1]))[0, 0]

    if v_cache is None:  # packed: keys, then values, in one row
        kl, vl = line(k_cache)[..., :d], line(k_cache)[..., d:]
    else:
        kl, vl = line(k_cache), line(v_cache)
    qg = q.reshape(hkv, (h // hkv) * c, d)
    scores = jnp.einsum("hrd,hsd->hrs", qg, kl.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(s)[None, :]
    qpos = kv_len + jnp.arange(c)[:, None]
    visible = (kpos <= _last_seen(qpos, block)) & (kpos < length)  # [C, S]
    visible = jnp.tile(visible, (h // hkv, 1))[None]          # rows g*C + t
    scores = jnp.where(visible, scores, NEG_INF)
    p = jnp.where(visible,
                  jnp.exp(scores - scores.max(-1, keepdims=True)), 0.0)
    denom = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("hrs,hsd->hrd", p.astype(q.dtype), vl.astype(q.dtype),
                     preferred_element_type=jnp.float32) / denom
    return out.astype(q.dtype).reshape(h, c, d)


def _tile_end(kv_len, limit, tile, block_q: int, block: int = 1):
    """One past the last key position any query of tile ``tile`` sees."""
    end = kv_len + (tile + 1) * block_q
    if block > 1:   # through the block of the tile's last query
        end = _last_seen(end - 1, block) + 1
    return jnp.minimum(end, limit)


def _prefill_attention_kernel(sc_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                              l_ref, acc_ref, *, block_q: int, block_k: int,
                              sm_scale: float, block: int = 1):
    from jax.experimental import pallas as pl

    # sc_ref: layer, slot (read by the index maps), kv_len, limit.
    tile, blk = pl.program_id(1), pl.program_id(2)
    kv_len, limit = sc_ref[2], sc_ref[3]
    group, _, d = q_ref.shape
    rows = group * block_q
    q0 = kv_len + tile * block_q          # position of the tile's first query
    end = _tile_end(kv_len, limit, tile, block_q, block)

    @pl.when(blk == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(masked: bool):
        # Row r of the tile is query head g, token t, r = g * block_q + t.
        q = q_ref[...].reshape(rows, d)
        s = lax.dot_general(q, k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            kpos = blk * block_k + lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 1)
            tok = lax.rem(lax.broadcasted_iota(jnp.int32, (rows, block_k), 0),
                          block_q)
            visible = (kpos <= _last_seen(q0 + tok, block)) & (kpos < limit)
            s = jnp.where(visible, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            # The select keeps a row with nothing visible yet at zero
            # (exp(NEG_INF - NEG_INF) would be one).
            p = jnp.where(visible, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    live = blk * block_k < end
    # Every key of the block is at or below the tile's first query.
    whole = (blk + 1) * block_k <= jnp.minimum(q0 + 1, limit)

    @pl.when(live & whole)
    def _():
        step(masked=False)

    @pl.when(live & jnp.logical_not(whole))
    def _():
        step(masked=True)

    @pl.when(blk == pl.num_programs(2) - 1)
    def _():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = o.reshape(group, block_q, d).astype(o_ref.dtype)


def _prefill_attention_pallas(q, k_cache, v_cache, layer, slot, kv_len,
                              length, *, sm_scale: float,
                              block_q: int | None = None,
                              block_k: int | None = None, block: int = 1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, c, dq = q.shape
    hkv, s, d = k_cache.shape[2:]
    packed = v_cache is None
    caches = (k_cache,) if packed else (k_cache, v_cache)
    group = h // hkv
    block_k = block_k or decode_kv_block(s, d, k_cache.dtype.itemsize)
    if s % block_k:
        raise ValueError(f"prefill_attention: block {block_k} does not "
                         f"divide the cache line of {s} positions")
    block_q = block_q or prefill_q_block(c, group, q.dtype.itemsize)
    c_pad = -(-c // block_q) * block_q
    qg = q.reshape(hkv, group, c, dq)
    if c_pad != c or packed:
        # A packed row's first lanes are its key: zeros meet its value.
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, c_pad - c), (0, d - dq)))
    # The chunk's own rows end at kv_len + c: a padded query sees no further.
    limit = jnp.clip(jnp.minimum(kv_len + c, length), 0, s)
    scalars = jnp.stack([jnp.asarray(layer, jnp.int32),
                         jnp.asarray(slot, jnp.int32),
                         jnp.asarray(kv_len, jnp.int32),
                         limit.astype(jnp.int32)])

    def kv_index(i, t, j, sc):
        end = _tile_end(sc[2], sc[3], t, block_q, block)
        last_live = jnp.maximum(pl.cdiv(end, block_k) - 1, 0)
        return (sc[0], sc[1], i, jnp.minimum(j, last_live), 0)

    def q_index(i, t, j, sc):
        return (i, 0, t, 0)

    rows = group * block_q
    kv_spec = pl.BlockSpec((None, None, None, block_k, d), kv_index)
    q_spec = pl.BlockSpec((None, group, block_q, d), q_index)
    kernel = functools.partial(_prefill_attention_kernel, block_q=block_q,
                               block_k=block_k, sm_scale=sm_scale, block=block)
    out = pl.pallas_call(
        packed_kernel(kernel, 1) if packed else kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(hkv, c_pad // block_q, s // block_k),
            in_specs=[q_spec] + [kv_spec] * len(caches),
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((hkv, group, c_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # The float32 scores of a tile, their mask and probabilities,
            # beside the blocks themselves; never under the default.
            vmem_limit_bytes=max(32 << 20, 12 * rows * block_k * 4)),
        interpret=kernel_backend() == "interpret",
        name="prefill_attention",
    )(scalars, qg, *caches)
    return out[:, :, :c, d - dq:].reshape(h, c, dq)


def prefill_attention(q, k_cache, v_cache, layer, slot, kv_len, length, *,
                      sm_scale: float | None = None,
                      kmesh: KernelMesh | None = None,
                      block_q: int | None = None,
                      block_k: int | None = None, block: int = 1):
    """q: [H, C, D], the chunk's queries at positions ``kv_len + arange(C)``
    (query head h of KV head ``h // (H // Hkv)``); k_cache, v_cache:
    [L, B, Hkv, S, D], or the packed stack [L, B, Hkv, S, 2 D] and None
    (ops/decode_attention.py), the chunk's rows already written; layer, slot, kv_len,
    length: int32 scalars. Returns [H, C, D]. ``block_q`` and ``block_k``
    override :func:`prefill_q_block` and ``decode_kv_block`` (tests and the
    kernel's own benchmark); ``block`` > 1 is the block-causal mask of the
    module's docstring. Under a mesh of several devices pass its
    ``kmesh``: the kernel then runs on each device's heads."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if kernel_backend() == "reference":
        return prefill_attention_reference(q, k_cache, v_cache, layer, slot,
                                           kv_len, length, scale, block)
    fn = functools.partial(_prefill_attention_pallas, sm_scale=scale,
                           block_q=block_q, block_k=block_k, block=block)
    if kmesh is not None:
        heads = P(kmesh.heads, None, None)
        # One slot's line: the slots reach every device whole.
        cache = P(None, None, kmesh.heads, None, None)
        fn = kmesh.shard(fn, in_specs=(heads, cache,
                                       None if v_cache is None else cache,
                                       P(), P(), P(), P()),
                         out_specs=heads)
    as_i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    return fn(q, k_cache, v_cache, as_i32(layer), as_i32(slot),
              as_i32(kv_len), as_i32(length))


def prefill_kv_write(k_cache, v_cache, new_k, new_v, layer, slot, kv_len):
    """Write the chunk's rows into the stacked caches in place: new_k, new_v
    [Hkv, C, D] go to ``[layer, slot, :, kv_len : kv_len + C]``. Returns the
    caches ((stack, None) for a packed stack and ``v_cache=None``). The caller keeps ``kv_len + C`` within the line
    (dynamic_update_slice would clamp the start and overwrite earlier rows).

    A dynamic_update_slice and not a kernel like ``kv_row_write``: the update
    is a block of whole rows of one slot, XLA writes it into the loop's carry
    where it lies, and the compiled program shows no other operation on the
    stack (tests/test_tpu_aot.py holds it to that)."""
    def put(stack, new):
        return lax.dynamic_update_slice(
            stack, new.astype(stack.dtype)[None, None],
            (layer, slot, 0, kv_len, 0))
    if v_cache is None:
        return put(k_cache, packed_rows(new_k, new_v)), None
    return put(k_cache, new_k), put(v_cache, new_v)
