"""What models/mimo.py supplies to the scheduler (llm/served.ServedModel): a
cache of two geometries and the programs that run against it.

``{"kv", "ring"}``, the slot second in both, a row a key and a value of one
KV head side by side (``cfg.kv_row`` lanes: ops/decode_attention.py's
packed convention, the value padded to a key's width):

- ``kv`` ``[full_lines, slots, num_kv_heads, max_seq, kv_row]``: a **full
  line** a full layer, which grows with ``max_seq``. A step writes its row
  at the token's position and reads the live blocks; a chunk writes its
  rows and attends through ops/prefill_attention.py;
- ``ring`` ``[window_lines, slots, swa_num_kv_heads, sliding_window,
  kv_row]``: a **ring** a window layer (llm/rings.py), which does not.
  A step writes its row at ``position % sliding_window`` and reads the ring
  whole, the layer's sink in the softmax (ops/decode_attention.py); a chunk
  attends ``[ring | chunk]`` under a mask by position with the sink, in
  jnp, and leaves the ring the chunk's last ``sliding_window`` valid rows.

The two kinds of layer have different KV head counts, so every program has
the row write, the plan of live blocks and the attention in two shapes, one
a geometry; the plans are made once a step, before the layers.

What llm/phi4flash_serving.py says of its rings holds here: a chunk that
starts at ``kv_len = 0`` sees none of the ring's rows, whatever the slot
held before; a slot with ``write_mask`` false keeps its rings bit for bit;
the ring at an earlier length is nowhere, so a prompt's prefix cannot be
adopted from another slot's line (``ServedModel.prefix_from_line``).

**A chunk's window attention is banded.** Query ``p`` sees keys ``p - W +
1 .. p``, so a chunk of ``n`` blocks of ``W`` queries needs, for block
``i``, the ``W`` rows before it (the ring's for block 0, block ``i - 1``'s
else) and its own: scores ``[n, heads, W, 2 W]`` and not ``[heads, C, W +
C]``, 2.5 times fewer at a chunk of 512 and a window of 128. A chunk of at
most ``W`` rows (or one ``W`` does not divide) is one block against ``[ring
| chunk]``. Exact either way: the mask by position is the same.

The programs keep the contract's names and signatures and return, beside
their result, int32[9] counts (``COUNTERS``): models/routed.py's six,
``window_positions_read`` and ``full_positions_read``, the positions a
step's or a chunk's attention fetched from rings and from full lines (a
line's length rounded up to the kernel's block), each times its KV heads,
and ``attn_positions_read``, their sum (the denominator of the rings'
share).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm.rings import ring_after_chunk, ring_positions
from ray_tpu.llm.served import ServedModel, token_step_programs
from ray_tpu.models import mimo
from ray_tpu.models.mimo import FULL, WINDOW, MimoConfig
from ray_tpu.models.routed import MOE_COUNTERS, layer_of
from ray_tpu.ops.decode_attention import (
    decode_attention,
    decode_kv_block,
    decode_plan,
    kv_positions_read,
    kv_row_write,
    packed_rows,
)
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.prefill_attention import prefill_attention, prefill_kv_write
from ray_tpu.util import tracing

COUNTERS = MOE_COUNTERS + ("window_positions_read", "full_positions_read",
                           "attn_positions_read")

def full_kv_block(cfg: MimoConfig, max_seq: int) -> int:
    """Positions per block of a full line: ``decode_kv_block`` at the bytes
    a step of the decode kernel's walk fetches, not at one head's. That
    function caps one head's block at 160 KiB of keys (and as much of
    values) and was measured at 8 KV heads: 16 such blocks, 2.5 MiB a
    step. A full line here is 4 packed heads of 768 bytes a position: by
    one head's cap its block would be 128 positions, 384 KiB a step, where
    the walk's fixed cost a step shows; by the same bytes a step it is 512.
    On the chip, 24 lines of 30,720: 7.5 / 3.18 / 3.10 ms a layer in
    blocks of 128 / 512 / 1,024 and a chunk's ``prefill_attention`` 23.3 /
    9.1 / 8.9; at 2,048 live rows 0.54 / 0.27 / 0.30
    (``MIMO_FULL_BLOCK=<n> devbench/mimo_bench.py step``, my chip runs,
    PR 54)."""
    return decode_kv_block(max_seq, cfg.num_kv_heads * cfg.kv_row // 16,
                           cfg.jnp_dtype.itemsize)


def init_cache(cfg: MimoConfig, max_slots: int, max_seq: int):
    dt = cfg.jnp_dtype
    return {
        "kv": jnp.zeros((cfg.full_lines, max_slots, cfg.num_kv_heads,
                         max_seq, cfg.kv_row), dt),
        "ring": jnp.zeros((cfg.window_lines, max_slots, cfg.swa_num_kv_heads,
                           cfg.sliding_window, cfg.kv_row), dt)}


def _counts(cfg, moe, window_positions, full_positions):
    ring = cfg.window_lines * cfg.swa_num_kv_heads * window_positions
    line = cfg.full_lines * cfg.num_kv_heads * full_positions
    return jnp.concatenate(
        [moe, jnp.stack([ring, line, ring + line]).astype(jnp.int32)])


def _chunk_window(cfg: MimoConfig, kv_len, length, c: int):
    """What a chunk of ``c`` rows at ``kv_len`` needs to attend a window
    layer, the same for every such layer: (``blocks``, ``visible``).
    ``blocks(old, new)`` lays a slot's ring rows ``old`` [1, kv heads, W,
    x] and the chunk's ``new`` [1, kv heads, C, x] out as the keys (or
    values) of each block of queries, [n, kv heads, K, x]; ``visible`` [n,
    C / n, K] is the mask by position. ``n`` is 1 and K ``W + C`` unless
    the chunk is whole windows (the module docstring)."""
    w = cfg.sliding_window
    n = c // w if c > w and c % w == 0 else 1
    positions = kv_len + jnp.arange(c)
    held = ring_positions(kv_len, w)

    def blocks(old, new):
        if n == 1:
            return jnp.concatenate([old, new], axis=2)
        heads, x = new.shape[1], new.shape[3]
        before = jnp.concatenate([old, new[:, :, :c - w]], axis=2)
        both = jnp.concatenate([before.reshape(heads, n, w, x),
                                new.reshape(heads, n, w, x)], axis=2)
        return both.transpose(1, 0, 2, 3)

    kpos = blocks(held[None, None, :, None],
                  positions[None, None, :, None])[:, 0, :, 0]
    visible = mimo.window_visible(positions.reshape(n, c // n), kpos, w) \
        & (kpos < length)[:, None, :]
    return blocks, visible


def _prefill_impl(cfg: MimoConfig, params, cache, tokens, kv_len, length,
                  slot, kmesh=None):
    c = tokens.shape[0]
    w, d, dv = cfg.sliding_window, cfg.head_dim, cfg.v_head_dim
    max_seq = cache["kv"].shape[3]
    block = full_kv_block(cfg, max_seq)
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][None]              # [1, C, H]
    with tracing.part("attn"):
        positions = kv_len + jnp.arange(c)
        valid = (positions < length)[None]
        # The chunk's rows that are the prompt's: all but a last chunk's
        # padding.
        n_valid = jnp.clip(length - kv_len, 0, c)
        fresh, source = ring_after_chunk(kv_len, n_valid, w, c)
        with tracing.part("window_attn"):
            blocks, visible = _chunk_window(cfg, kv_len, length, c)
        with tracing.part("cache"):
            # The slot's rings, all window layers': what the layers read
            # and turn, and what goes back into the leaf after them. The
            # leaf itself stays out of the loops: carried through them with
            # no kernel to hold its layout, XLA re-laid all of it out
            # positions-major around them, twice 94 MiB a chunk at 24 slots
            # (devbench/mimo_bench.py aot).
            rings = lax.dynamic_slice_in_dim(cache["ring"], slot, 1, axis=1)

    def window(line, wqkv, sink, xn, state):
        kv, rings = state
        q, k, v = mimo.attention_heads(cfg, WINDOW, wqkv, xn, positions)
        with tracing.part("cache"):
            old = layer_of(rings, line)                    # [1, P, W, 2 D]
        with tracing.part("window_attn"):
            n = visible.shape[0]
            o = mimo.sunk_attention(
                q[0].reshape(-1, n, c // n, d).transpose(1, 0, 2, 3),
                blocks(old[..., :d].astype(k.dtype), k),
                blocks(old[..., d:d + dv].astype(v.dtype), v[..., :dv]),
                visible, sink, cfg.sm_scale)              # [n, nh, C/n, Dv]
            o = o.transpose(1, 0, 2, 3).reshape(1, -1, c, dv)
        with tracing.part("cache"):
            # The ring after the chunk: its last valid rows, each in the
            # row of its position.
            turned = jnp.where(fresh, jnp.take(packed_rows(k, v)[0], source,
                                               axis=1), old[0])
            rings = lax.dynamic_update_index_in_dim(
                rings, turned.astype(rings.dtype)[None], line, 0)
        return o, (kv, rings)

    def full(line, wqkv, sink, xn, state):
        kv, rings = state
        q, k, v = mimo.attention_heads(cfg, FULL, wqkv, xn, positions)
        with tracing.part("cache"):
            kv, _ = prefill_kv_write(kv, None, k[0], v[0], line, slot, kv_len)
        o = prefill_attention(q[0], kv, None, line, slot, kv_len, length,
                              sm_scale=cfg.sm_scale, kmesh=kmesh,
                              block_k=block)
        return o[None], (kv, rings)

    x, (kv, rings), moe = mimo.run_layers(
        cfg, params, x, {FULL: full, WINDOW: window}, (cache["kv"], rings),
        valid, kmesh)
    with tracing.part("head"):
        last = x[0, jnp.clip(length - 1 - kv_len, 0, c - 1)]
    with tracing.part("attn"), tracing.part("cache"):
        ring = lax.dynamic_update_slice(cache["ring"], rings,
                                        (0, slot, 0, 0, 0))
    with tracing.part("attn"):
        seen = jnp.clip(jnp.minimum(kv_len + c, length), 0, max_seq)
        counts = _counts(cfg, moe, w, kv_positions_read(seen, block))
    return ({"kv": kv, "ring": ring}, mimo.lm_head(cfg, params, last, kmesh),
            counts)


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: MimoConfig, params, cache, tokens, kv_len, length,
                  slot, *, kmesh: KernelMesh | None = None):
    """Prefill ONE chunk of one sequence (the contract's program, see
    llm/llama_serving.prefill_chunk). Returns (cache, last-token logits [V],
    counts)."""
    return _prefill_impl(cfg, params, cache, tokens, kv_len, length, slot,
                         kmesh)


def _decode_impl(cfg: MimoConfig, params, cache, tokens, positions0,
                 write_mask, kmesh=None):
    """One token per slot against the full lines and the rings. Returns
    (cache, logits [B, V], counts). A slot with ``write_mask`` false writes
    no row, keeps its rings, and its logits mean nothing."""
    b = tokens.shape[0]
    w = cfg.sliding_window
    max_seq = cache["kv"].shape[3]
    block = full_kv_block(cfg, max_seq)
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][:, None]           # [B, 1, H]
    with tracing.part("attn"):
        lengths = jnp.where(write_mask, positions0 + 1, 0)
        valid = write_mask[:, None]
        # A ring is read whole once it is full, and every row of it is at
        # or before the token: the walk's own mask by position is slack.
        ring_lengths = jnp.minimum(lengths, w)
        ring_row = jnp.mod(positions0, w)
        ring_seen = jnp.full((b,), w, jnp.int32)
        # Two walks of live blocks, one a geometry, planned here and not in
        # the loop: every full layer attends at the same lengths, and every
        # window layer at its own.
        plan = decode_plan(lengths, block, max_seq, kmesh=kmesh)
        ring_plan = decode_plan(ring_lengths, w, w, kmesh=kmesh)

    def window(line, wqkv, sink, xn, state):
        kv, ring = state
        q, k, v = mimo.attention_heads(cfg, WINDOW, wqkv, xn,
                                       positions0[:, None])
        with tracing.part("cache"):
            ring, _ = kv_row_write(ring, None, k, v, line, ring_row,
                                   write_mask, kmesh=kmesh)
        with tracing.part("window_attn"):
            o = decode_attention(q, ring, None, line, ring_lengths,
                                 ring_seen, plan=ring_plan,
                                 sm_scale=cfg.sm_scale, kmesh=kmesh, block=w,
                                 sink=sink)
        return o, (kv, ring)

    def full(line, wqkv, sink, xn, state):
        kv, ring = state
        q, k, v = mimo.attention_heads(cfg, FULL, wqkv, xn,
                                       positions0[:, None])
        with tracing.part("cache"):
            kv, _ = kv_row_write(kv, None, k, v, line, positions0,
                                 write_mask, kmesh=kmesh)
        o = decode_attention(q, kv, None, line, lengths, positions0,
                             plan=plan, sm_scale=cfg.sm_scale, kmesh=kmesh,
                             block=block)
        return o, (kv, ring)

    x, (kv, ring), moe = mimo.run_layers(
        cfg, params, x, {FULL: full, WINDOW: window},
        (cache["kv"], cache["ring"]), valid, kmesh)
    with tracing.part("attn"):
        counts = _counts(
            cfg, moe, kv_positions_read(ring_lengths, w).sum(),
            kv_positions_read(jnp.minimum(lengths, max_seq), block).sum())
    return ({"kv": kv, "ring": ring},
            mimo.lm_head(cfg, params, x[:, 0], kmesh), counts)


decode_step, decode_burst = token_step_programs(_decode_impl, COUNTERS)


def _refuse(config) -> None:
    """What this model does not run, said at construction."""
    for bad, what in (
            (config.speculative_model is not None,
             "a speculative draft: a rejected token's row of a ring cannot "
             "be taken back"),
            (config.kv_block_size > 0,
             "kv_block_size > 0: a slot has full lines and rings of another "
             "length and head count, and the block pool has one kind of "
             "line (ROADMAP R4)")):
        if bad:
            raise ValueError(f"MimoConfig does not support {what}")


SERVED = ServedModel(
    init_params=mimo.init_params,
    param_logical_axes=mimo.param_logical_axes,
    init_cache=init_cache,
    prefill_chunk=prefill_chunk,
    decode_step=decode_step,
    decode_burst=decode_burst,
    kv_block=full_kv_block,
    counters=COUNTERS,
    constants=lambda cfg: {"window_lines": cfg.window_lines,
                           "full_lines": cfg.full_lines,
                           "window": cfg.sliding_window,
                           "window_kv_heads": cfg.swa_num_kv_heads,
                           "full_kv_heads": cfg.num_kv_heads,
                           "kv_row_lanes": cfg.kv_row,
                           "moe_experts_held": cfg.experts_held},
    # A line is not all of a slot: the hand-off would have to ship the rings
    # too, and a prefix has none to adopt.
    kv_handoff=False,
    prefix_from_line=False,
    refuse=_refuse,
)
