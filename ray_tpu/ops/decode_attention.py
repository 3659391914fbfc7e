"""Decode attention: a few query rows per slot against a stacked KV cache.

A decode step gives every slot K new tokens (1, or ``speculative_tokens +
1`` under verification) that attend to that slot's cache line. The line is
long (``max_seq``), mostly not yet written, and shared by the
``num_heads // num_kv_heads`` query heads of each KV head. So the op is

- **grouped**: the G query heads of one KV head times the K tokens are one
  small tile of G*K rows against a single read of that head's keys and
  values; no ``[B, Hkv, G, S, D]`` copy exists;
- **length-aware**: it takes a per-slot length (0: an empty or prefilling
  slot), and its grid is the live blocks of this step and nothing else:
  one flat list of (slot, block) pairs in slot order (:func:`decode_plan`),
  as long as the lengths make it (a run-time grid bound). An empty slot
  has no step, so nothing of its line is fetched; a block at or past a
  line's length has none either; and consecutive steps being consecutive
  live blocks, the pipeline's look-ahead of one step always has the next
  live block in flight, of this line or of the next, while the current one
  is multiplied. Lengths, positions, the layer and the plan are run-time
  scalars (scalar prefetch): one program serves every length. The grid
  this replaced was the rectangle (slots, blocks of a line): a dead block
  was skipped by ``pl.when`` and its index clamped to the line's last live
  block. On the v5e that cost 0.13 us a dead step, a fetch of block 0 for
  every empty slot (its clamp is 0 and the slot changes), and every
  line's first block exposed behind the dead steps of the line before it
  (2.9 us a line at Mistral-7B's widths, where a live step is 2.8): 208
  us a call at 25 lines of 64 to 900 in 32 slots of 2,048, now 114;
- **in place**: it receives the whole stacked cache ``[L, B, Hkv, S, D]``
  and the layer index, and its block specs index the layer. A caller that
  handed it ``cache[l]`` would make XLA materialise that slice on every
  layer of every step.

Scores, the running maximum and sum, and the PV accumulation are float32;
the operands stay in the cache's dtype. Query j of a slot sees key positions
``<= positions0 + j`` and ``< length``; a row that sees nothing (an empty
slot) gives zeros. Where the new rows are blocks that attend both ways
(``rows_a_limit`` g > 1, a static fact about the input) the g rows of a
block share their limit: query j sees ``<= positions0 + (j // g) * g``, so
one read of the line serves two blocks whose ends differ
(llm/sdar_serving.py: a block's clean rows beside the next block's open
ones).

**Heads of half a lane row.** A head of 64 values fills half of the 128
lanes a row of the cache is stored in, so a ``[..., S, 64]`` cache costs a
cached position, in HBM and in every read, what a head of 128 costs. Such a
model keeps keys and values of a head side by side in one row instead: one
*packed* stack ``[L, B, Hkv, S, 2 D]``, keys in the first D lanes, values
in the last, passed as ``k_cache`` with ``v_cache=None`` to every function
here and in ops/prefill_attention.py. The kernel's body does not change:
the queries are padded with D zeros, so their product with a packed row is
the product with its key; the probabilities' product with the packed rows
carries the values' mix in its last D lanes, which is what is kept. On a
128-wide MXU neither product costs more than its unpacked form, and a
block is fetched once.

**A sink.** A model may give every query head a learned logit that joins
the softmax's denominator and has no row of values (``sink`` [H] float32;
None: no such logit, and the program is what it was). It is where a slot's
running maximum and sum start, at the line's first live block: the maximum
at the sink, the sum at one, ``exp(sink - sink)``. From there the walk is
the same, so the sink enters exactly, and a row that sees no key gives
zeros as before (an accumulator of zeros over a sum of one).

``kv_row_write`` is the other half of the convention: the step's K new rows
of each slot go into the same stack in place, through a kernel too, because
XLA would re-lay the whole cache out around a row update of its own.

Both come in the three implementations of ops/kernels.py: the Mosaic kernel
on a TPU, the same body through the Pallas interpreter for tests, and a jnp
reference elsewhere.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.kernels import KernelMesh, kernel_backend

NEG_INF = -1e30

# One block of one KV head's keys, in bytes. A grid step takes the longer of
# its bytes (about 745 GB/s through the pipeline) and its compute, and the
# compute hardly grows with the block: the products of 16 rows a head are
# bound by their passes, not their width. On the v5e at Mistral-7B widths a
# step of 128 positions is 1.3 us, of 256 2.3, of 512 2.8 (its bytes), so a
# short block pays for steps what it saves in rows read past a line's end:
# 32 lines of 1,024 to 2,900 in 3,072 took 664 / 627 / 417 / 421 us a layer
# in blocks of 128 / 256 / 512 / 768, 25 lines of 64 to 900 in 2,048 139 /
# 149 / 114 / 148 in 128 / 256 / 512 / 1,024, 16 lines of 3,200 269 in 128
# and 164 in 640, and at 16 KV heads of one query row 8 lines of 768 took
# 69 / 74 / 63 / 75 in 128 / 256 / 384 / 768 (devbench/
# decode_attention_bench.py, on the walk of live blocks). With head_dim 128
# in bf16 the cap is 640 positions.
_HEAD_BLOCK_BYTES = 160 * 1024


def decode_kv_block(max_seq: int, head_dim: int, itemsize: int = 2) -> int:
    """Positions per block of the sequence axis: the largest multiple of 128
    that divides ``max_seq`` and keeps one head's block within
    ``_HEAD_BLOCK_BYTES`` (512 of 2,048, 640 of 3,200); the whole line where
    no multiple of 128 divides it (tiny test caches). The scheduler's
    ``kv_positions_read`` counter rounds lengths up with this function."""
    cap = max(128, _HEAD_BLOCK_BYTES // (head_dim * itemsize))
    fits = [b for b in range(128, min(cap, max_seq) + 1, 128)
            if max_seq % b == 0]
    return fits[-1] if fits else max_seq


def _stack_spec(kmesh: KernelMesh) -> P:
    """The stacked cache [L, B, Hkv, S, D]: slots over the batch axes, KV
    heads over the head axis, like the [B, H, ...] operands beside it."""
    return P(None, kmesh.batch or None, kmesh.heads, None, None)


def kv_positions_read(lengths, block: int):
    """Positions of each line the kernel fetches: the length rounded up to
    whole blocks (numpy or jnp integers)."""
    return -(-lengths // block) * block


def _sink_rows(sink, hkv: int, k: int):
    """sink [H] -> [Hkv, G * K, 1] float32: row ``g * K + j`` of KV head h
    is query head ``h * G + g``'s."""
    return jnp.repeat(sink.astype(jnp.float32).reshape(hkv, -1), k,
                      axis=1)[..., None]


def decode_attention_reference(q, k_cache, v_cache, layer, lengths,
                               positions0, sm_scale: float | None = None,
                               sink=None, rows_a_limit: int = 1):
    """Masked softmax over the whole line, grouped like the kernel (no
    repeated K/V), float32 scores and accumulation."""
    b, h, k, d = q.shape
    hkv, s = k_cache.shape[2], k_cache.shape[3]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    kl = lax.dynamic_index_in_dim(k_cache, layer, 0, keepdims=False)
    if v_cache is None:  # packed: keys, then values, in one row
        kl, vl = kl[..., :d], kl[..., d:]
    else:
        vl = lax.dynamic_index_in_dim(v_cache, layer, 0, keepdims=False)
    qg = q.reshape(b, hkv, (h // hkv) * k, d)
    scores = jnp.einsum("bhrd,bhsd->bhrs", qg, kl.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(s)[None, None, :]
    qpos = positions0[:, None] + jnp.arange(k)[None, :]      # [B, K]
    if rows_a_limit > 1:    # the rows of a block share its first row's limit
        qpos = qpos - jnp.arange(k)[None, :] % rows_a_limit
    visible = ((kpos <= qpos[:, :, None])
               & (kpos < lengths[:, None, None]))            # [B, K, S]
    visible = jnp.tile(visible, (1, h // hkv, 1))[:, None]   # rows g*K + j
    scores = jnp.where(visible, scores, NEG_INF)
    top = scores.max(-1, keepdims=True)
    if sink is not None:
        sunk = _sink_rows(sink, hkv, k)
        top = jnp.maximum(top, sunk)
    p = jnp.where(visible, jnp.exp(scores - top), 0.0)
    denom = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    if sink is not None:
        denom = denom + jnp.exp(sunk - top)
    out = jnp.einsum("bhrs,bhsd->bhrd", p.astype(q.dtype),
                     vl.astype(q.dtype),
                     preferred_element_type=jnp.float32) / denom
    return out.astype(q.dtype).reshape(b, h, k, d)


class DecodePlan(NamedTuple):
    """The kernel's grid for one decode step: the live blocks of every
    line, in slot order. ``n_live`` int32[1] is how many there are; the
    other four are int32 over the static worst case ``slots * (max_seq //
    block)`` and mean something below ``n_live`` only: step t works on
    block ``block[t]`` of slot ``slot[t]``, which is that slot's ``first``
    and / or ``last`` live block. Under a mesh that divides the slots every
    field is the concatenation of the shards' own plans."""

    n_live: jax.Array
    slot: jax.Array
    block: jax.Array
    first: jax.Array
    last: jax.Array


def _plan_spec(kmesh: KernelMesh) -> DecodePlan:
    """Every field over the batch axes, like the lengths it is made from."""
    return DecodePlan(*[kmesh.rows_spec(1)] * 5)


def _decode_plan(lengths, block: int, max_seq: int) -> DecodePlan:
    blocks_of = -(-jnp.clip(lengths, 0, max_seq).astype(jnp.int32) // block)
    ends = jnp.cumsum(blocks_of)
    t = jnp.arange(lengths.shape[0] * (max_seq // block), dtype=jnp.int32)
    # Slots whose blocks all lie before step t: the slot step t belongs to.
    # Past the last live step it is clamped, so that every entry indexes
    # the cache.
    slot = jnp.minimum((ends[None, :] <= t[:, None]).sum(-1, dtype=jnp.int32),
                       lengths.shape[0] - 1)
    blk = jnp.clip(t - (ends - blocks_of)[slot], 0, max_seq // block - 1)
    live = t < ends[-1]
    return DecodePlan(ends[-1:], slot, blk,
                      (live & (blk == 0)).astype(jnp.int32),
                      (live & (blk == blocks_of[slot] - 1)).astype(jnp.int32))


def decode_plan(lengths, block: int, max_seq: int, *,
                kmesh: KernelMesh | None = None) -> DecodePlan:
    """The walk :func:`decode_attention` makes at these ``lengths`` [B]
    (0: an empty slot; clamped to ``max_seq``) in blocks of ``block``:
    ``sum(cdiv(length, block))`` steps. It depends on nothing else, so a
    program whose layers all attend at the same lengths builds it once,
    before its layer loop, and hands it to every call. Under a ``kmesh``
    each device plans its own slots."""
    fn = functools.partial(_decode_plan, block=block, max_seq=max_seq)
    if kmesh is not None:
        fn = kmesh.shard(fn, in_specs=(kmesh.rows_spec(1),),
                         out_specs=_plan_spec(kmesh))
    return fn(lengths)


def decode_plan_of(lengths, k_cache, *, kmesh: KernelMesh | None = None):
    """:func:`decode_plan` at the block in which :func:`decode_attention`
    reads this stacked cache ``[L, B, Hkv, S, D]``."""
    s, d = k_cache.shape[3:]
    return decode_plan(lengths, decode_kv_block(s, d, k_cache.dtype.itemsize),
                       s, kmesh=kmesh)


def _decode_attention_kernel(len_ref, pos_ref, layer_ref, slot_ref, blk_ref,
                             first_ref, last_ref, q_ref, k_ref, v_ref, o_ref,
                             m_ref, l_ref, acc_ref, *, block: int,
                             k_tokens: int, sm_scale: float,
                             rows_a_limit: int = 1, sink_ref=None):
    """Grid step t: block ``blk[t]`` of slot ``slot[t]``, all KV heads. The
    one axis carries a slot's running maximum, sum and accumulator from its
    first live block to its last, so it is ``"arbitrary"``; the v5e has one
    TensorCore and loses nothing. A chip with two would want the list cut
    where the live blocks halve, each half a walk of its own."""
    from jax.experimental import pallas as pl

    del layer_ref  # read by the block specs' index maps
    t = pl.program_id(0)
    slot, blk = slot_ref[t], blk_ref[t]
    length = len_ref[slot]
    hkv, rows, _ = q_ref.shape

    @pl.when(first_ref[t] == 1)
    def _():
        if sink_ref is None:
            m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        else:   # the softmax starts at the sink: exp(sink - sink) summed
            m_ref[...] = sink_ref[...]
            l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    kpos = blk * block + lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    # Row r of the tile is query head g, token j, r = g * K + j.
    tok = lax.rem(lax.broadcasted_iota(jnp.int32, (rows, block), 0), k_tokens)
    if rows_a_limit > 1:    # the rows of a block share its first row's limit
        tok = tok - lax.rem(tok, rows_a_limit)
    visible = (kpos <= pos_ref[slot] + tok) & (kpos < length)
    for h in range(hkv):
        s = lax.dot_general(q_ref[h], k_ref[h], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.where(visible, s * sm_scale, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # The select keeps a row with nothing visible yet at zero
        # (exp(NEG_INF - NEG_INF) would be one).
        p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = alpha * l_ref[h] + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[h],
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(last_ref[t] == 1)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def packed_kernel(kernel, n_scalars: int):
    """``kernel(*scalars, q_ref, k_ref, v_ref, o_ref, *scratch)`` for a
    packed stack: the one block read is its keys and its values."""
    def packed(*refs):
        q, kv = n_scalars, n_scalars + 1
        return kernel(*refs[:kv + 1], refs[kv], *refs[kv + 1:])
    return packed


def sunk_kernel(kernel, n_scalars: int):
    """``kernel`` with one operand more between its scalars and its
    queries, the sink's rows, handed on as ``sink_ref``."""
    def sunk(*refs):
        return kernel(*refs[:n_scalars], *refs[n_scalars + 1:],
                      sink_ref=refs[n_scalars])
    return sunk


def _decode_attention_pallas(q, k_cache, v_cache, layer, lengths, positions0,
                             plan, sink=None, *, sm_scale: float, block: int,
                             rows_a_limit: int = 1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, k, dq = q.shape
    hkv, s, d = k_cache.shape[2:]
    packed = v_cache is None
    caches = (k_cache,) if packed else (k_cache, v_cache)
    if plan.slot.shape[0] != b * (s // block):
        raise ValueError(
            f"decode_attention: a plan of {plan.slot.shape[0]} steps for "
            f"{b} lines of {s // block} blocks of {block}")
    # G*K rows a KV head, padded to whole sublane tiles of the operand dtype.
    rows = (h // hkv) * k
    tile = 32 // q.dtype.itemsize
    rows_p = -(-rows // tile) * tile
    qg = q.reshape(b, hkv, rows, dq)
    if rows_p != rows or packed:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows_p - rows), (0, d - dq)))

    def kv_index(t, lens, pos, lyr, slot, blk, first, last):
        return (lyr[0], slot[t], 0, blk[t], 0)

    def q_index(t, lens, pos, lyr, slot, blk, first, last):
        return (slot[t], 0, 0, 0)

    kv_spec = pl.BlockSpec((None, None, hkv, block, d), kv_index)
    q_spec = pl.BlockSpec((None, hkv, rows_p, d), q_index)
    # The lengths go in as given: the plan keeps the walk inside the line,
    # and no position lies past its end for a longer length to unmask.
    kernel = functools.partial(_decode_attention_kernel, block=block,
                               k_tokens=k, sm_scale=sm_scale,
                               rows_a_limit=rows_a_limit)
    sunk = []
    if sink is not None:
        # One more operand before the queries, the same rows every step.
        kernel = sunk_kernel(kernel, 7)
        sunk = [jnp.pad(_sink_rows(sink, hkv, k),
                        ((0, 0), (0, rows_p - rows), (0, 0)))]
    out = pl.pallas_call(
        packed_kernel(kernel, 7 + len(sunk)) if packed else kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            # A run-time bound: the steps that exist are the live blocks.
            grid=(plan.n_live[0],),
            in_specs=[pl.BlockSpec((hkv, rows_p, 1), lambda t, *_: (0, 0, 0))
                      ] * len(sunk) + [q_spec] + [kv_spec] * len(caches),
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((hkv, rows_p, 1), jnp.float32),
                            pltpu.VMEM((hkv, rows_p, 1), jnp.float32),
                            pltpu.VMEM((hkv, rows_p, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows_p, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # One axis, and it carries a slot's accumulators from block to
            # block.
            dimension_semantics=("arbitrary",),
            # K and V blocks of every head, double-buffered, and as much
            # again for what the body keeps; never under the default.
            vmem_limit_bytes=max(
                16 << 20, 8 * hkv * block * d * k_cache.dtype.itemsize)),
        interpret=kernel_backend() == "interpret",
        name="decode_attention",
    )(lengths.astype(jnp.int32), positions0.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), plan.slot, plan.block,
      plan.first, plan.last, *sunk, qg, *caches)
    # The walk never visits a slot with no live block, so nothing wrote its
    # rows of the output.
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0)
    return out[:, :, :rows, d - dq:].reshape(b, h, k, dq)


def decode_attention(q, k_cache, v_cache, layer, lengths, positions0, *,
                     plan: DecodePlan | None = None,
                     sm_scale: float | None = None,
                     kmesh: KernelMesh | None = None,
                     block: int | None = None, sink=None,
                     rows_a_limit: int = 1):
    """q: [B, H, K, D] (K new tokens a slot, query head h of KV head
    ``h // (H // Hkv)``); k_cache, v_cache: [L, B, Hkv, S, D], or the packed
    stack [L, B, Hkv, S, 2 D] and None, the new rows
    already written; layer: int32 scalar; lengths, positions0: [B] int32.
    Returns [B, H, K, D]. ``plan`` is :func:`decode_plan` of these lengths,
    the cache's ``decode_kv_block`` and S (and this ``kmesh``), built by a
    caller that attends many layers at the same lengths; without one the
    call plans for itself. ``block`` overrides :func:`decode_kv_block`
    (tests and the kernel's own benchmark). ``sink`` [H] float32 is the
    module docstring's: a logit a query head in every softmax's
    denominator. ``rows_a_limit`` g (static; it divides K): the new rows
    in runs of g see the same keys, those ``<= positions0 + (j // g) * g``
    (the module docstring's). Under a mesh of several devices
    pass its ``kmesh``: the kernel then runs on each device's heads."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if q.shape[2] % rows_a_limit:
        raise ValueError(f"decode_attention: rows_a_limit {rows_a_limit} "
                         f"does not divide the {q.shape[2]} new rows")
    if kernel_backend() == "reference":
        return decode_attention_reference(q, k_cache, v_cache, layer,
                                          lengths, positions0, scale, sink,
                                          rows_a_limit)
    s = k_cache.shape[3]
    block = block or decode_kv_block(s, k_cache.shape[-1],
                                     k_cache.dtype.itemsize)
    if s % block:
        raise ValueError(f"decode_attention: block {block} does not divide "
                         f"the cache line of {s} positions")
    if plan is None:
        plan = decode_plan(lengths, block, s, kmesh=kmesh)
    fn = functools.partial(_decode_attention_pallas, sm_scale=scale,
                           block=block, rows_a_limit=rows_a_limit)
    sunk = () if sink is None else (sink,)
    if kmesh is not None:
        heads, cache = kmesh.heads_spec(4), _stack_spec(kmesh)
        rows = kmesh.rows_spec(1)
        fn = kmesh.shard(fn, in_specs=(heads, cache,
                                       None if v_cache is None else cache,
                                       P(), rows, rows, _plan_spec(kmesh),
                                       *[P(kmesh.heads)] * len(sunk)),
                         out_specs=heads)
    return fn(q, k_cache, v_cache, jnp.asarray(layer, jnp.int32), lengths,
              positions0, plan, *sunk)


def packed_rows(new_k, new_v):
    """A packed stack's rows: a head's key, then its value."""
    return jnp.concatenate([new_k, new_v], axis=-1)


def kv_row_write_reference(k_cache, v_cache, new_k, new_v, layer,
                           positions0, write_mask):
    b, _, k, _ = new_k.shape
    s = k_cache.shape[3]
    pos = positions0[:, None] + jnp.arange(k)[None, :]
    pos = jnp.where(write_mask[:, None], pos, s)  # out of bounds: dropped
    slots = jnp.arange(b)[:, None]

    def put(stack, new):
        rows = new.transpose(0, 2, 1, 3).astype(stack.dtype)  # [B, K, Hkv, D]
        return stack.at[layer, slots, :, pos, :].set(rows, mode="drop")
    if v_cache is None:
        return put(k_cache, packed_rows(new_k, new_v)), None
    return put(k_cache, new_k), put(v_cache, new_v)


def _kv_window(max_seq: int) -> int:
    """Rows of the window the write kernel reads, merges and writes back:
    one packed tile of a 16-bit dtype (two of a 32-bit one)."""
    return 16 if max_seq % 16 == 0 else max_seq


def _window_index(p0, t, window: int, k_tokens: int):
    """Window of grid step ``t`` of a slot whose rows start at ``p0``: the
    one holding ``p0``, then (K > 1, rows across a boundary) the next; the
    same window twice where the rows do not cross, so nothing moves on the
    second step. A masked slot (negative ``p0``) visits window 0 and hits
    no row."""
    first = jnp.maximum(p0, 0) // window
    last = jnp.maximum(p0 + k_tokens - 1, 0) // window
    return jnp.minimum(first + t, last)


def _kv_row_write_kernel(pos_ref, layer_ref, *refs, window: int,
                         k_tokens: int):
    """``refs``: the new rows, the windows read and the windows written of
    each stack (keys and values, or the one packed stack)."""
    from jax.experimental import pallas as pl

    del layer_ref  # read by the block specs' index maps
    n = len(refs) // 3
    p0 = pos_ref[pl.program_id(0)]
    base = _window_index(p0, pl.program_id(1), window, k_tokens) * window
    row = base + lax.broadcasted_iota(jnp.int32, refs[n].shape, 1)
    for new_ref, win_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                         refs[2 * n:]):
        win = win_ref[...]
        for j in range(k_tokens):
            win = jnp.where(row == p0 + j, new_ref[j], win)
        out_ref[...] = win


def _kv_row_write_pallas(k_cache, v_cache, new_k, new_v, layer, positions0,
                         write_mask):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if v_cache is None:
        caches, news = (k_cache,), (packed_rows(new_k, new_v),)
    else:
        caches, news = (k_cache, v_cache), (new_k, new_v)
    n = len(caches)
    b, hkv, k, d = news[0].shape
    s = k_cache.shape[3]
    window = _kv_window(s)
    if k > window:
        raise ValueError(f"kv_row_write: {k} rows a slot exceed the window "
                         f"of {window}")
    steps = 1 if k == 1 else 2
    # [B, K, Hkv, 1, D]: a row is a tile of its own, broadcast over a window.
    rows = [new.astype(k_cache.dtype).transpose(0, 2, 1, 3)[:, :, :, None, :]
            for new in news]
    # A masked slot's rows sit at negative positions: no window row is hit.
    # Positions are the engine's to keep inside the line; clamping the
    # window is only so that a wrong one cannot index past the array.
    pos = jnp.where(write_mask, positions0, -k).astype(jnp.int32)

    def win_index(i, t, pos, lyr):
        w = jnp.minimum(_window_index(pos[i], t, window, k),
                        s // window - 1)
        return (lyr[0], i, 0, w, 0)

    def new_index(i, t, pos, lyr):
        return (i, 0, 0, 0, 0)

    win_spec = pl.BlockSpec((None, None, hkv, window, d), win_index)
    new_spec = pl.BlockSpec((None, k, hkv, 1, d), new_index)
    out = pl.pallas_call(
        functools.partial(_kv_row_write_kernel, window=window, k_tokens=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, steps),
            in_specs=[new_spec] * n + [win_spec] * n,
            out_specs=[win_spec] * n),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in caches],
        # Operands count the scalar-prefetch arguments: the caches come
        # after them and the new rows, and are written in place.
        input_output_aliases={2 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=kernel_backend() == "interpret",
        name="kv_row_write",
    )(pos, jnp.asarray(layer, jnp.int32).reshape(1), *rows, *caches)
    return (out[0], None) if v_cache is None else tuple(out)


def kv_row_write(k_cache, v_cache, new_k, new_v, layer, positions0,
                 write_mask, *, kmesh: KernelMesh | None = None):
    """Write the K new rows of every slot into layer ``layer`` of the
    stacked caches, in place: new_k, new_v [B, Hkv, K, D] go to
    ``[layer, b, :, positions0[b] : positions0[b] + K]`` where
    ``write_mask[b]``; a masked slot's line is left as it is. Returns the
    caches. With ``v_cache=None`` ``k_cache`` is the packed stack: each
    row gets a head's key and value side by side, and (stack, None) comes
    back.

    A kernel and not a dynamic_update_slice: XLA lays a cache it updates by
    rows out position-major, and then copies the whole cache into the
    layout the attention kernel reads, on every layer of every step."""
    if kernel_backend() == "reference":
        return kv_row_write_reference(k_cache, v_cache, new_k, new_v, layer,
                                      positions0, write_mask)
    fn = _kv_row_write_pallas
    if kmesh is not None:
        heads, cache = kmesh.heads_spec(4), _stack_spec(kmesh)
        rows = kmesh.rows_spec(1)
        v_spec = None if v_cache is None else cache
        fn = kmesh.shard(
            fn, in_specs=(cache, v_spec, heads, heads, P(), rows, rows),
            out_specs=(cache, v_spec))
    return fn(k_cache, v_cache, new_k, new_v, jnp.asarray(layer, jnp.int32),
              positions0, write_mask)
