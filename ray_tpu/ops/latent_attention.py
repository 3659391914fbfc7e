"""Latent (MLA) attention against a stacked latent cache.

Multi-head latent attention caches, per position and attention layer, one
row shared by every head: the compressed key/value ``c_kv`` (``rank``
values, after its norm) followed by the rotated positional key ``k_r``. The
row is padded with zeros to whole 128-lane tiles (576 values in a row of
640: a TPU stores an array whose last dimension is no multiple of 128 with
another dimension innermost, and a kernel that wants rows then has XLA copy
the whole cache around every call). The row is the key of all heads (its
``rank + Dr`` values) and their value (its first ``rank`` values) at once, in the absorbed form: a head's query is multiplied into
the key up-projection beforehand (``q~ = q_n W_kb^T``), and its output is
a mix of latent rows that the value up-projection is applied to afterwards.

``latent_decode_attention`` and ``latent_row_write`` keep the convention of
ops/decode_attention.py: the whole stack ``[L, B, S, D]`` and a layer index
go in, block specs index the layer, lengths and positions are run-time
scalars, blocks at or past a line's length are neither fetched nor computed,
and the step's new rows are written in place. The difference is the shape of
the work: all ``H`` heads (64) times the K new tokens are one tile of rows
against a single read of the line, and each cached byte is used by every
head, so the kernel does H * (D + rank) * 2 FLOPs a position against D * 2
bytes (121 a byte at 64 heads, 576 and 512): MXU work and HBM reads of the
same order, where grouped-query decode is bound by bytes alone.

``latent_prefill_attention`` is the chunk's side, the latent form of
ops/prefill_attention.py: C queries of one slot against the live blocks of
its line, in the up-projected form (at 512 queries a block it is 38.7 GFLOP
a visit at 128 heads against 73 absorbed). Its grid is tiles of heads by
blocks of the line. A step reads the block's rows once, up-projects them
with the tile's slices of the two up-projections, scores the chunk's
queries, and mixes, all in VMEM: the heads' keys and values ([512, 128,
256]) and the float32 scores ([128, 512, 512], 134 MB) of a visit, which
XLA's loop over live blocks wrote to HBM and read back (19% of the MXU's
peak, PR 45), do not exist outside the kernel. That loop stays as the
reference.

Scores, the running maximum and sum, and the accumulation are float32; the
operands stay in the cache's dtype, up-projected rows and probabilities are
rounded to it. The three implementations are those of ops/kernels.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.decode_attention import _kv_window, _window_index
from ray_tpu.ops.kernels import KernelMesh, kernel_backend

NEG_INF = -1e30

# Positions a block. A grid step costs about half a microsecond whatever it
# moves (ops/decode_attention.py), and a block of 1,024 rows of 576 is 1.2
# MB: long lines in few steps, at most one block read past a line's end.
_MAX_BLOCK = 1024


def latent_kv_block(max_seq: int, cap: int = _MAX_BLOCK) -> int:
    """Positions per block of the sequence axis: the largest multiple of
    128 up to ``cap`` that divides ``max_seq``; the whole line where none
    does (tiny test caches). The scheduler's ``kv_positions_read`` rounds
    lengths up with this function."""
    fits = [b for b in range(128, min(cap, max_seq) + 1, 128)
            if max_seq % b == 0]
    return fits[-1] if fits else max_seq


def _visible(lengths, positions0, k: int, s: int):
    """[B, K, S]: query j of a slot sees key positions <= positions0 + j
    and < length."""
    kpos = jnp.arange(s)[None, None, :]
    qpos = positions0[:, None] + jnp.arange(k)[None, :]
    return (kpos <= qpos[:, :, None]) & (kpos < lengths[:, None, None])


def latent_decode_attention_reference(q, cache, layer, lengths, positions0,
                                      rank: int, sm_scale: float):
    """Masked softmax over the whole line, float32 scores and sums."""
    b, k, _, d = q.shape
    line = lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    line = line[..., :d].astype(q.dtype)                     # [B, S, D]
    scores = jnp.einsum("bkhd,bsd->bkhs", q, line,
                        preferred_element_type=jnp.float32) * sm_scale
    visible = _visible(lengths, positions0, k, line.shape[1])[:, :, None]
    scores = jnp.where(visible, scores, NEG_INF)
    p = jnp.where(visible,
                  jnp.exp(scores - scores.max(-1, keepdims=True)), 0.0)
    denom = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkhs,bsr->bkhr", p.astype(q.dtype), line[..., :rank],
                     preferred_element_type=jnp.float32) / denom
    return out.astype(q.dtype)


def _latent_decode_kernel(len_ref, pos_ref, layer_ref, q_ref, kv_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, block: int, heads: int,
                          rank: int, sm_scale: float):
    """q_ref [rows, rank + Dr], kv_ref [block, W] with W >= rank + Dr (the
    row's padding is not read), o_ref [rows, rank]."""
    from jax.experimental import pallas as pl

    del layer_ref  # read by the block specs' index maps
    slot, blk = pl.program_id(0), pl.program_id(1)
    length = len_ref[slot]
    rows = q_ref.shape[0]

    @pl.when(blk == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(blk * block < length)
    def _():
        contract = (((1,), (1,)), ((), ()))
        c = kv_ref[:, :rank]                                 # key and value
        s = lax.dot_general(q_ref[:, :rank], c, contract,
                            preferred_element_type=jnp.float32)
        s += lax.dot_general(q_ref[:, rank:], kv_ref[:, rank:q_ref.shape[1]],
                             contract, preferred_element_type=jnp.float32)
        kpos = blk * block + lax.broadcasted_iota(jnp.int32, (rows, block), 1)
        # Row r of the tile is token j, head h, r = j * H + h.
        tok = lax.broadcasted_iota(jnp.int32, (rows, block), 0) // heads
        visible = (kpos <= pos_ref[slot] + tok) & (kpos < length)
        s = jnp.where(visible, s * sm_scale, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # The select keeps a row with nothing visible yet at zero
        # (exp(NEG_INF - NEG_INF) would be one).
        p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(blk == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def _latent_decode_pallas(q, cache, layer, lengths, positions0, *, rank: int,
                          sm_scale: float, block: int | None = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, k, h, d = q.shape
    s, width = cache.shape[2], cache.shape[3]
    block = block or latent_kv_block(s)
    if s % block:
        raise ValueError(f"latent_decode_attention: block {block} does not "
                         f"divide the cache line of {s} positions")
    # K * H rows a slot, padded to whole sublane tiles of the operand dtype.
    rows = k * h
    tile = 32 // q.dtype.itemsize
    rows_p = -(-rows // tile) * tile
    qr = q.reshape(b, rows, d)
    if rows_p != rows:
        qr = jnp.pad(qr, ((0, 0), (0, rows_p - rows), (0, 0)))

    def kv_index(i, j, lens, pos, lyr):
        last_live = jnp.maximum(pl.cdiv(lens[i], block) - 1, 0)
        return (lyr[0], i, jnp.minimum(j, last_live), 0)

    def q_index(i, j, lens, pos, lyr):
        return (i, 0, 0)

    out = pl.pallas_call(
        functools.partial(_latent_decode_kernel, block=block, heads=h,
                          rank=rank, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, s // block),
            in_specs=[pl.BlockSpec((None, rows_p, d), q_index),
                      pl.BlockSpec((None, None, block, width), kv_index)],
            out_specs=pl.BlockSpec((None, rows_p, rank), q_index),
            scratch_shapes=[pltpu.VMEM((rows_p, 1), jnp.float32),
                            pltpu.VMEM((rows_p, 1), jnp.float32),
                            pltpu.VMEM((rows_p, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, rows_p, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # The line's block twice (the pipeline's two buffers), the
            # float32 scores and probabilities of a step, and room beside.
            vmem_limit_bytes=max(
                32 << 20, 6 * block * (width * cache.dtype.itemsize
                                       + rows_p * 4))),
        interpret=kernel_backend() == "interpret",
        name="latent_decode_attention",
    )(jnp.minimum(lengths, s).astype(jnp.int32), positions0.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qr, cache)
    return out[:, :rows].reshape(b, k, h, rank)


def _stack_spec(kmesh: KernelMesh) -> P:
    """The stacked latent cache [L, B, S, D]: slots over the batch axes; a
    row belongs to every head, so nothing else is split."""
    return P(None, kmesh.batch or None, None, None)


def latent_decode_attention(q, cache, layer, lengths, positions0, *,
                            rank: int, sm_scale: float,
                            kmesh: KernelMesh | None = None,
                            block: int | None = None):
    """q: [B, K, H, D], the absorbed queries of K new tokens a slot (the
    first ``rank`` values against ``c_kv``, the rest against ``k_r``);
    cache: [L, B, S, W], W >= D (rows padded to whole lanes), the new rows
    already written; layer: int32 scalar;
    lengths, positions0: [B] int32. Returns the latent outputs
    [B, K, H, rank] (softmax-weighted sums of ``c_kv`` rows), to which the
    caller applies the value up-projection. A slot of length 0 gives
    zeros."""
    if kernel_backend() == "reference":
        return latent_decode_attention_reference(
            q, cache, layer, lengths, positions0, rank, sm_scale)
    fn = functools.partial(_latent_decode_pallas, rank=rank,
                           sm_scale=sm_scale, block=block)
    if kmesh is not None:
        rows4, rows1 = kmesh.rows_spec(4), kmesh.rows_spec(1)
        fn = kmesh.shard(
            fn, in_specs=(rows4, _stack_spec(kmesh), P(), rows1, rows1),
            out_specs=rows4)
    return fn(q, cache, jnp.asarray(layer, jnp.int32), lengths, positions0)


def latent_row_write_reference(cache, new, layer, positions0, write_mask):
    b, k, _ = new.shape
    s = cache.shape[2]
    pos = positions0[:, None] + jnp.arange(k)[None, :]
    pos = jnp.where(write_mask[:, None], pos, s)  # out of bounds: dropped
    slots = jnp.arange(b)[:, None]
    return cache.at[layer, slots, pos, :].set(new.astype(cache.dtype),
                                              mode="drop")


def _latent_row_write_kernel(pos_ref, layer_ref, new_ref, win_ref, out_ref,
                             *, window: int, k_tokens: int):
    from jax.experimental import pallas as pl

    del layer_ref  # read by the block specs' index maps
    p0 = pos_ref[pl.program_id(0)]
    base = _window_index(p0, pl.program_id(1), window, k_tokens) * window
    row = base + lax.broadcasted_iota(jnp.int32, win_ref.shape, 0)
    w = win_ref[...]
    for j in range(k_tokens):
        w = jnp.where(row == p0 + j, new_ref[j], w)
    out_ref[...] = w


def _latent_row_write_pallas(cache, new, layer, positions0, write_mask):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, k, d = new.shape
    s = cache.shape[2]
    window = _kv_window(s)
    if k > window:
        raise ValueError(f"latent_row_write: {k} rows a slot exceed the "
                         f"window of {window}")
    steps = 1 if k == 1 else 2
    # [B, K, 1, D]: a row is a tile of its own, broadcast over a window. A
    # masked slot's rows sit at negative positions: no window row is hit.
    rows = new.astype(cache.dtype)[:, :, None, :]
    pos = jnp.where(write_mask, positions0, -k).astype(jnp.int32)

    def win_index(i, t, pos, lyr):
        w = jnp.minimum(_window_index(pos[i], t, window, k), s // window - 1)
        return (lyr[0], i, w, 0)

    win_spec = pl.BlockSpec((None, None, window, d), win_index)
    return pl.pallas_call(
        functools.partial(_latent_row_write_kernel, window=window,
                          k_tokens=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, steps),
            in_specs=[pl.BlockSpec((None, k, 1, d),
                                   lambda i, t, pos, lyr: (i, 0, 0, 0)),
                      win_spec],
            out_specs=win_spec),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        # Operands count the scalar-prefetch arguments: 3 is the cache,
        # written in place.
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=kernel_backend() == "interpret",
        name="latent_row_write",
    )(pos, jnp.asarray(layer, jnp.int32).reshape(1), rows, cache)


def latent_row_write(cache, new, layer, positions0, write_mask, *,
                     kmesh: KernelMesh | None = None):
    """Write the K new rows of every slot into layer ``layer`` of the
    stacked latent cache, in place: new [B, K, D] goes to
    ``[layer, b, positions0[b] : positions0[b] + K]`` where
    ``write_mask[b]``; a masked slot's line is left as it is. A kernel for
    the reason ``kv_row_write`` is one."""
    if kernel_backend() == "reference":
        return latent_row_write_reference(cache, new, layer, positions0,
                                          write_mask)
    fn = _latent_row_write_pallas
    if kmesh is not None:
        rows = kmesh.rows_spec(1)
        fn = kmesh.shard(
            fn, in_specs=(_stack_spec(kmesh), kmesh.rows_spec(3), P(), rows,
                          rows),
            out_specs=_stack_spec(kmesh))
    return fn(cache, new, jnp.asarray(layer, jnp.int32), positions0,
              write_mask)


def latent_prefill_attention_reference(q_n, q_r, cache, w_kb, w_vb, layer,
                                       slot, kv_len, length, *, rope_dim: int,
                                       sm_scale: float, block: int):
    """Plain XLA: a loop over the live blocks (its trip count a run-time
    scalar) with the running maximum and sum of a blocked softmax; a block's
    up-projected rows and float32 scores are arrays of their own."""
    c, h, _ = q_n.shape
    d = cache.shape[3]
    rank, dv = w_kb.shape[0], w_vb.shape[2]
    qpos = kv_len + jnp.arange(c)
    live = jnp.minimum(kv_len + c, length)
    n_blocks = (live + block - 1) // block

    def body(j, carry):
        m, l, acc = carry
        rows = lax.dynamic_slice(cache, (layer, slot, j * block, 0),
                                 (1, 1, block, d))[0, 0].astype(q_n.dtype)
        ckv, kr = rows[:, :rank], rows[:, rank:rank + rope_dim]
        kn = jnp.einsum("sr,rhd->shd", ckv, w_kb)
        v = jnp.einsum("sr,rhd->shd", ckv, w_vb)
        sc = jnp.einsum("chd,shd->hcs", q_n, kn,
                        preferred_element_type=jnp.float32)
        sc += jnp.einsum("chd,sd->hcs", q_r, kr,
                         preferred_element_type=jnp.float32)
        kpos = j * block + jnp.arange(block)
        visible = ((kpos[None, :] <= qpos[:, None])
                   & (kpos[None, :] < length))[None]          # [1, C, blk]
        sc = jnp.where(visible, sc * sm_scale, NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1, keepdims=True))
        p = jnp.where(visible, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "hcs,shd->hcd", p.astype(q_n.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((h, c, 1), NEG_INF, jnp.float32),
            jnp.zeros((h, c, 1), jnp.float32),
            jnp.zeros((h, c, dv), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_blocks, body, init)
    out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(1, 0, 2).astype(q_n.dtype)


# What a head tile's blocks and scratch may take of VMEM (128 MiB on a
# v5e): the tile is the most heads that fit, so a line's block is re-read
# for few tiles and the grid has few steps (a dead one costs what a live
# one's bookkeeping does). At the published widths that is 16 heads; tiles
# of 8 and of 32 read within 2% of it on the chip (PR 46).
_PREFILL_VMEM = 56 << 20


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _prefill_vmem(tile: int, chunk: int, block: int, rank: int, dn: int,
                  dr: int, dv: int, width: int, itemsize: int) -> int:
    """Bytes of VMEM a grid step of ``tile`` heads holds: the pipeline's two
    buffers of each operand and of the output, the float32 accumulator and
    statistics, and a head's scores, probabilities and up-projected rows."""
    operands = (block * _lanes(width)
                + tile * rank * (_lanes(dn) + _lanes(dv))
                + tile * chunk * (_lanes(dn) + _lanes(dr) + _lanes(dv)))
    scratch = tile * chunk * (_lanes(dv) + 2 * 128) * 4
    head = 4 * chunk * _lanes(block) * 4 + 4 * block * _lanes(dn + dv)
    return 2 * operands * itemsize + scratch + head


def latent_prefill_head_tile(heads: int, chunk: int, block: int, rank: int,
                             dn: int, dr: int, dv: int, width: int,
                             itemsize: int = 2) -> int:
    """Heads a tile of the chunk's kernel: the largest divisor of ``heads``
    whose blocks fit ``_PREFILL_VMEM`` and whose outputs are whole lanes."""
    fits = [t for t in range(1, heads + 1)
            if heads % t == 0 and (t == heads or t * dv % 128 == 0)
            and _prefill_vmem(t, chunk, block, rank, dn, dr, dv, width,
                              itemsize) <= _PREFILL_VMEM]
    return fits[-1] if fits else 1


def _latent_prefill_kernel(sc_ref, qn_ref, qr_ref, kv_ref, wk_ref, wv_ref,
                           o_ref, m_ref, l_ref, acc_ref, *, block: int,
                           sm_scale: float):
    """qn_ref [T, C, Dn], qr_ref [T, C, Dr] the tile's T heads; kv_ref
    [block, W] one block of the line, W >= rank + Dr; wk_ref [T, rank, Dn],
    wv_ref [T, rank, Dv]; o_ref [C, T * Dv]."""
    from jax.experimental import pallas as pl

    # sc_ref: layer, slot (read by the index maps), kv_len, limit.
    blk = pl.program_id(1)
    kv_len, limit = sc_ref[2], sc_ref[3]
    tile, c, _ = qn_ref.shape
    rank, dv = wv_ref.shape[1:]
    dt = qn_ref.dtype

    @pl.when(blk == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def visit(masked: bool):
        nt = (((1,), (1,)), ((), ()))
        ckv = kv_ref[:, :rank].astype(dt)
        kr = kv_ref[:, rank:rank + qr_ref.shape[2]].astype(dt)

        def head(h):
            # The head's keys and values of the block, rounded as the
            # reference's einsum rounds them; they never leave VMEM.
            kn = jnp.dot(ckv, wk_ref[h],
                         preferred_element_type=jnp.float32).astype(dt)
            v = jnp.dot(ckv, wv_ref[h],
                        preferred_element_type=jnp.float32).astype(dt)
            s = lax.dot_general(qn_ref[h], kn, nt,
                                preferred_element_type=jnp.float32)
            s += lax.dot_general(qr_ref[h], kr, nt,
                                 preferred_element_type=jnp.float32)
            s *= sm_scale
            if masked:
                kpos = blk * block + lax.broadcasted_iota(
                    jnp.int32, (c, block), 1)
                qpos = kv_len + lax.broadcasted_iota(jnp.int32, (c, block), 0)
                visible = (kpos <= qpos) & (kpos < limit)
                s = jnp.where(visible, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:
                # The select keeps a row with nothing visible yet at zero
                # (exp(NEG_INF - NEG_INF) would be one).
                p = jnp.where(visible, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p.astype(dt), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

        # Two heads a trip: one's softmax (vector unit) runs beside the
        # other's products (MXU), 134 against 111 TFLOP/s at 128 heads and
        # 15,360 cached rows, and a trip of four or of all gains no more;
        # a whole tile unrolled compiles for 11 s and not 2.
        pair = 2 - tile % 2

        def heads(g, carry):
            for k in range(pair):
                head(g * pair + k)
            return carry

        lax.fori_loop(0, tile // pair, heads, 0)

    live = blk * block < limit
    # Every key of the block is at or below the chunk's first query.
    whole = (blk + 1) * block <= jnp.minimum(kv_len + 1, limit)

    @pl.when(live & whole)
    def _():
        visit(masked=False)

    @pl.when(live & jnp.logical_not(whole))
    def _():
        visit(masked=True)

    @pl.when(blk == pl.num_programs(1) - 1)
    def _():
        for h in range(tile):
            o = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            o_ref[:, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)


def _latent_prefill_pallas(q_n, q_r, cache, w_kb, w_vb, layer, slot, kv_len,
                           length, *, rope_dim: int, sm_scale: float,
                           block: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c, h, dn = q_n.shape
    s, width = cache.shape[2], cache.shape[3]
    rank, dv = w_kb.shape[0], w_vb.shape[2]
    if s % block:
        raise ValueError(f"latent_prefill_attention: block {block} does not "
                         f"divide the cache line of {s} positions")
    itemsize = q_n.dtype.itemsize
    sub = 32 // itemsize
    c_pad = -(-c // sub) * sub
    tile = latent_prefill_head_tile(h, c_pad, block, rank, dn, rope_dim, dv,
                                    width, itemsize)

    def heads_first(x):
        # [C, H, D] -> [H, C_pad, D]: a head is a leading index in the
        # kernel. XLA folds the transposition into what produces x.
        return jnp.pad(x.transpose(1, 0, 2),
                       ((0, 0), (0, c_pad - c), (0, 0)))

    # The chunk's own rows end at kv_len + c: a padded query sees no further.
    limit = jnp.clip(jnp.minimum(kv_len + c, length), 0, s)
    scalars = jnp.stack([layer, slot, kv_len, limit])

    def kv_index(i, j, sc):
        last_live = jnp.maximum(pl.cdiv(sc[3], block) - 1, 0)
        return (sc[0], sc[1], jnp.minimum(j, last_live), 0)

    def head_index(i, j, sc):
        return (i, 0, 0)

    out = pl.pallas_call(
        functools.partial(_latent_prefill_kernel, block=block,
                          sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h // tile, s // block),
            in_specs=[pl.BlockSpec((tile, c_pad, dn), head_index),
                      pl.BlockSpec((tile, c_pad, rope_dim), head_index),
                      pl.BlockSpec((None, None, block, width), kv_index),
                      pl.BlockSpec((tile, rank, dn), head_index),
                      pl.BlockSpec((tile, rank, dv), head_index)],
            out_specs=pl.BlockSpec((c_pad, tile * dv),
                                   lambda i, j, sc: (0, i)),
            scratch_shapes=[pltpu.VMEM((tile, c_pad, 1), jnp.float32),
                            pltpu.VMEM((tile, c_pad, 1), jnp.float32),
                            pltpu.VMEM((tile, c_pad, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((c_pad, h * dv), q_n.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM + (8 << 20)),
        interpret=kernel_backend() == "interpret",
        name="latent_prefill_attention",
    )(scalars, heads_first(q_n), heads_first(q_r), cache,
      w_kb.transpose(1, 0, 2), w_vb.transpose(1, 0, 2))
    return out[:c].reshape(c, h, dv)


def latent_prefill_attention(q_n, q_r, cache, w_kb, w_vb, layer, slot,
                             kv_len, length, *, rope_dim: int,
                             sm_scale: float, block: int | None = None):
    """One chunk of one slot against its line, the chunk's own rows already
    written. q_n [C, H, Dn] and q_r [C, H, Dr] (rotated) are the queries at
    positions kv_len .. kv_len + C - 1; cache [L, B, S, W] with W >= rank +
    Dr = rank + ``rope_dim``; w_kb
    [rank, H, Dn] and w_vb [rank, H, Dv] the two halves of the key/value
    up-projection. Query i sees positions <= kv_len + i and < length.
    Returns [C, H, Dv]; a row that sees nothing gives zeros.

    Up-projected, a block of the line at a time: the block's ``c_kv`` rows
    give a head's keys and values ([blk, rank] x [rank, Dn] and x [rank,
    Dv]), then scores and the weighted sum at the heads' own widths (Dn +
    Dr and Dv), with the running maximum and sum of a blocked softmax. The
    kernel's grid is tiles of heads (:func:`latent_prefill_head_tile`) by
    blocks of the line: the up-projected rows and the float32 scores of a
    visit live in VMEM, and the output is all that goes to HBM. ``layer``,
    ``slot``, ``kv_len`` and ``length`` are scalar-prefetched (one program a
    chunk size serves every slot and cached length); a block past ``min(kv_len
    + C, length)`` is neither fetched nor computed, and only a block that
    holds a position past the chunk's first query or the line's end is
    masked."""
    block = block or latent_kv_block(cache.shape[2], 512)
    impl = (latent_prefill_attention_reference
            if kernel_backend() == "reference" else _latent_prefill_pallas)
    as_i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    return impl(q_n, q_r, cache, w_kb, w_vb, as_i32(layer), as_i32(slot),
                as_i32(kv_len), as_i32(length), rope_dim=rope_dim,
                sm_scale=sm_scale, block=block)
