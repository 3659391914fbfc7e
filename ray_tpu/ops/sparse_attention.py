"""Learned sparse attention over a slot cache: an indexer scores every cached
position of a line, each query keeps its ``topk`` best, and the attention
reads those alone (the DeepSeek sparse attention of models/keye.py).

Three ops, each against the stacked caches in place (``layer`` and the
lines' slots are run-time scalars, as in ops/prefill_attention.py), each for
``N`` lines of ``C`` query rows: a prefill chunk is one line of C rows, a
decode step a row of every slot.

- :func:`index_scores`: ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
  in float32 for every position ``s`` the row may see (``s <= q0 + t`` and
  ``s < limit``), ``-inf`` elsewhere. The keys are a second cache leaf,
  ``[L, B, 1, Di, S]``: one key of ``Di`` a position whatever the heads,
  the positions last (a row of 64 values is half a lane row, and XLA lays
  such an array out positions-minor by itself: stored the other way round
  every program copied the leaf in and out around its kernels).
  Key blocks past a tile's last query are neither fetched nor multiplied,
  and past the longest line's last seen position (up to a whole chunk of
  the selection's columns) there is no grid step and nothing is written:
  neither reader looks there. A chunk's tile is 16 index heads x 64 rows,
  a step's its row's 16 heads and its result the one row.
- :func:`topk_threshold`: the selection as two numbers a row. ``thr`` is
  the row's k-th largest score (``-inf`` where it sees no more than k
  positions: it keeps them all) and ``pcut`` the position of the last score
  *equal* to ``thr`` that is kept: ``lax.top_k`` settles a tie to the lower
  position, so of the scores equal to the k-th the first ``k - #{I > thr}``
  in position order are in the set (0.0 and -0.0 are equal: a sum of
  ``w * relu`` gives either). The set is exactly
  ``{s: I[s] > thr or (I[s] == thr and s <= pcut)}`` (:func:`kept`), no
  approximation. No sort: the k-th largest is found by building its bit
  pattern from the top bit down, a count of ``I >= candidate`` a bit (32
  passes over a row that stays in fast memory), the tie's cut likewise over
  the bits of a position.
- :func:`sparse_attention`: softmax attention of each row over its set. The
  set arrives as the scores and the two numbers, and the mask is made a
  tile at a time: a flash pass over the line's live blocks, grouped and
  length-aware like ops/prefill_attention.py. It reads the whole live
  line, for a chunk's 512 rows (each has a list of its own; a pass under
  the mask does 16 index heads of extra work a tile and no gather) and for
  a decode row alike: a list of 2,048 scattered positions is 2,048 x 8
  fetches of 256 bytes from a cache laid out a head a line, which costs
  more than the line (PERF.md). A grid step takes a block of 1,024 keys of
  every KV head: a chunk's tile is each head's group x 128 rows under 128
  masks, a step's its token's group of heads under the token's one mask,
  and the key blocks past the longest line's last row are no grid steps
  at all (a run-time bound).

The three implementations of ops/kernels.py each: the Mosaic kernel on a
TPU, its body through the Pallas interpreter for tests, a jnp reference
elsewhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.decode_attention import NEG_INF
from ray_tpu.ops.kernels import kernel_backend
from ray_tpu.ops.prefill_attention import prefill_q_block

# The order-preserving int32 key of float32 -inf: ``bits ^ 0x7fffffff``.
_KEY_NEG_INF = -2139095041
_INT_MIN = -2147483648


def _divisor_block(n: int, cap: int) -> int:
    """The largest multiple of 128 up to ``cap`` that divides ``n``; ``n``
    where none does (tiny test lines)."""
    fits = [b for b in range(128, min(cap, n) + 1, 128) if n % b == 0]
    return fits[-1] if fits else n


def _as_i32(x):
    return jnp.asarray(x, jnp.int32)


def _line_scalars(layer, slots, q0, limits, s: int):
    return (_as_i32(layer).reshape(1), _as_i32(slots), _as_i32(q0),
            jnp.clip(_as_i32(limits), 0, s))


# ------------------------------------------------------------ index scores

def index_scores_reference(q, w, index_k, layer, slots, q0, limits):
    n, _, c, _ = q.shape
    s = index_k.shape[4]
    keys = lax.dynamic_index_in_dim(index_k, layer, 0, keepdims=False)
    keys = keys[slots, 0]                                     # [N, Di, S]
    dots = jnp.einsum("njcd,nds->njcs", q, keys.astype(q.dtype),
                      preferred_element_type=jnp.float32)
    scores = jnp.sum(w[..., None] * jnp.maximum(dots, 0.0), axis=1)
    kpos = jnp.arange(s)[None, None, :]
    qpos = (q0[:, None] + jnp.arange(c)[None, :])[:, :, None]
    seen = (kpos <= qpos) & (kpos < limits[:, None, None])
    return jnp.where(seen, scores, -jnp.inf)


def _index_scores_kernel(layer_ref, slot_ref, q0_ref, lim_ref, q_ref, w_ref,
                         k_ref, o_ref, *, bk: int):
    from jax.experimental import pallas as pl

    del layer_ref, slot_ref  # read by the index maps
    n, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    # The tile: ``heads`` x ``tq`` rows of products, row h * tq + t index
    # head h of the line's row t. A chunk's ``q_ref`` is [heads, tq, Di]; a
    # step's [heads, Di], its one row's heads and nothing else.
    tq = o_ref.shape[0]
    heads, di = q_ref.shape[0], q_ref.shape[-1]
    limit = lim_ref[n]
    first = q0_ref[n] + t * tq
    end = jnp.minimum(first + tq, limit)
    live = j * bk < end

    @pl.when(live)
    def _():
        dots = jnp.dot(q_ref[...].reshape(heads * tq, di), k_ref[...],
                       preferred_element_type=jnp.float32)
        dots = jnp.maximum(dots, 0.0) * w_ref[...].reshape(heads * tq, 1)
        if tq == 1:
            # The heads one after another: the order in which a chunk's
            # tile adds its heads' slabs, so a step's scores are a chunk's
            # bit for bit (a tree over the sublanes is 4% of the call
            # faster and differs in the last place of one score in five).
            scores = dots[0:1]
            for h in range(1, heads):
                scores = scores + dots[h:h + 1]
        else:
            scores = dots.reshape(heads, tq, bk).sum(axis=0)
        kpos = j * bk + lax.broadcasted_iota(jnp.int32, (tq, bk), 1)
        qpos = first + lax.broadcasted_iota(jnp.int32, (tq, bk), 0)
        o_ref[...] = jnp.where((kpos <= qpos) & (kpos < limit), scores,
                               -jnp.inf)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)


def index_q_block(c: int, itemsize: int = 2) -> int:
    """Rows of a chunk's line in one tile of index queries: whole packed
    sublane tiles, 64 at the most (16 heads x 64 rows x a block of 1,024
    keys is 4 MiB of float32 products)."""
    tile = 32 // itemsize
    return min(64, -(-c // tile) * tile)


# Columns the selection takes at a time (``_select_kernel`` reads whole
# chunks of them up to a tile's last seen position), and so what the scores
# are written in: ``index_scores`` stops at the end of the chunk that holds
# the longest line's last seen position.
_SELECT_COLUMNS = 2048


def _select_chunk(s: int) -> int:
    return _divisor_block(s, _SELECT_COLUMNS)


def _index_scores_pallas(q, w, index_k, layer, slots, q0, limits):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, heads, c, di = q.shape
    s = index_k.shape[4]
    w = w.astype(jnp.float32)
    if c == 1:
        # A step's tile is its token's index heads (16 bfloat16 rows are
        # one packed sublane tile; a tile of the line's rows would be
        # padding but for one row a head) and its result the one row.
        # Blocks of 4,096 keys, or the grid's steps cost more than their
        # keys (0.35 us a step against 0.16 us a block of 1,024).
        tq, bk = 1, _divisor_block(s, 4096)
        q, q_block, w_block = q.reshape(n, heads, di), (heads, di), (heads, 1)

        def q_index(i, t, j, *_):
            return (i, 0, 0)
    else:
        tq, bk = index_q_block(c, q.dtype.itemsize), _divisor_block(s, 1024)
        pad = -c % tq
        if pad:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
            w = jnp.pad(w, ((0, 0), (0, 0), (0, pad)))
        w, q_block, w_block = w[..., None], (heads, tq, di), (heads, tq, 1)

        def q_index(i, t, j, *_):
            return (i, 0, t, 0)
    tiles = -(-c // tq)
    scalars = _line_scalars(layer, slots, q0, limits, s)
    # A run-time bound: past the chunk of the selection's columns that holds
    # the longest line's last seen position there is no grid step, and
    # nothing is written (a dead step stores a block of -inf: 0.003 ms and
    # 256 KB each, 70% of a chunk's steps over a prompt). One chunk at the
    # least, which an idle call's readers are given.
    ch = _select_chunk(s)
    seen = jnp.max(jnp.minimum(scalars[2] + c, scalars[3]))
    blocks = pl.cdiv(jnp.maximum(pl.cdiv(seen, ch), 1) * ch, bk)

    def k_index(i, t, j, lyr, slot, p0, lim):
        end = jnp.minimum(p0[i] + (t + 1) * tq, lim[i])
        last_live = jnp.maximum(pl.cdiv(end, bk) - 1, 0)
        return (lyr[0], slot[i], 0, 0, jnp.minimum(j, last_live))

    out = pl.pallas_call(
        functools.partial(_index_scores_kernel, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n, tiles, blocks),
            in_specs=[pl.BlockSpec((None, *q_block), q_index),
                      pl.BlockSpec((None, *w_block), q_index),
                      pl.BlockSpec((None, None, None, di, bk), k_index)],
            out_specs=pl.BlockSpec((None, tq, bk),
                                   lambda i, t, j, *_: (i, t, j))),
        out_shape=jax.ShapeDtypeStruct((n, tiles * tq, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 8 * heads * tq * bk * 4)),
        interpret=kernel_backend() == "interpret",
        name="index_scores",
    )(*scalars, q, w, index_k)
    return out[:, :c]


def index_scores(q, w, index_k, layer, slots, q0, limits):
    """q: [N, J, C, Di], the index queries of N lines, C rows each at
    positions ``q0[n] + arange(C)``; w: [N, J, C] float32, a weight a row
    and index head; index_k: [L, B, 1, Di, S], the rows' own keys already
    written; layer: a scalar; slots, q0, limits: int32 [N] (the line's slot,
    its first row's position, and the positions that exist: a row sees
    ``s <= its own`` and ``s < limit``). Returns float32 [N, C, S]:
    ``sum_j w relu(q . k)``, and ``-inf`` at a position the row does not
    see, *below the bound*: the longest line's last seen position,
    ``max_n min(q0[n] + C, limits[n])``, rounded up to whole chunks of the
    selection's columns (2,048 where they divide S), one chunk at the
    least. At and above the bound nothing is written and the values are
    unspecified: :func:`topk_threshold` under ``live`` and
    :func:`sparse_attention` read nothing there. (The jnp reference writes
    the whole array.)"""
    fn = (index_scores_reference if kernel_backend() == "reference"
          else _index_scores_pallas)
    return fn(q, w, index_k, _as_i32(layer), _as_i32(slots), _as_i32(q0),
              _as_i32(limits))


# --------------------------------------------------------------- selection

def kept(scores, thr, pcut):
    """The set the two numbers of :func:`topk_threshold` stand for: bool,
    the shape of ``scores`` [..., S]; thr and pcut [...]."""
    pos = jnp.arange(scores.shape[-1])
    return (scores > thr[..., None]) | (
        (scores == thr[..., None]) & (pos <= pcut[..., None]))


def topk_threshold_reference(scores, k: int):
    r, s = scores.shape
    if s <= k:
        return (jnp.full((r,), -jnp.inf, jnp.float32),
                jnp.full((r,), -1, jnp.int32))
    thr = lax.top_k(scores, k)[0][:, -1]
    need = k - jnp.sum(scores > thr[:, None], axis=1)
    equal = scores == thr[:, None]
    nth = equal & (jnp.cumsum(equal, axis=1) == need[:, None])
    pcut = jnp.where(thr > -jnp.inf, jnp.argmax(nth, axis=1), -1)
    return thr, pcut.astype(jnp.int32)


_SELECT_ROWS = 8


def _select_kernel(live_ref, s_ref, thr_ref, pcut_ref, key_ref, *, k: int,
                   ch: int, pos_bits: int):
    from jax.experimental import pallas as pl

    rows = s_ref.shape[0]
    nch = jnp.maximum(pl.cdiv(live_ref[pl.program_id(0)], ch), 1)

    def cols(c):
        return pl.ds(pl.multiple_of(c * ch, ch), ch)

    def make_keys(c, carry):
        bits = lax.bitcast_convert_type(s_ref[:, cols(c)], jnp.int32)
        # Order-preserving: a negative float's bits below the sign are
        # flipped, so int32 order is float order (-inf lowest of all); -0.0
        # is 0.0's equal (a sum of ``w * relu`` gives either), where
        # ``lax.top_k`` alone would put it below.
        bits = jnp.where(bits == _INT_MIN, 0, bits)
        key_ref[:, cols(c)] = bits ^ ((bits >> 31) & 0x7FFFFFFF)
        return carry

    lax.fori_loop(0, nch, make_keys, 0)

    def count(test):
        """float32 [rows, 1]: the columns of the live chunks where
        ``test(keys, first column)`` holds (exact: under 2^24). The hits
        are added up a lane, and the lanes once a count."""
        def add(c, acc):
            return acc + jnp.where(test(key_ref[:, cols(c)], c * ch), 1.0,
                                   0.0)
        acc = lax.fori_loop(0, nch, add, jnp.zeros((rows, ch), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    # The k-th largest key, bit by bit from the top: the largest value with
    # at least k keys at or above it. The sign first (keys at or above 0),
    # then bits 30 to 0, which order alike under either sign.
    want = jnp.float32(k)
    ans = jnp.where(count(lambda key, _: key >= 0) >= want, 0, _INT_MIN)

    def value_bit(i, ans):
        cand = ans | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(lambda key, _: key >= cand) >= want, cand, ans)

    ans = lax.fori_loop(0, 31, value_bit, ans.astype(jnp.int32))
    # Fewer than k positions seen (the rest hold -inf, and what lies past
    # the live chunks was never counted): everything seen is kept.
    ans = jnp.maximum(ans, _KEY_NEG_INF)
    open_row = ans == _KEY_NEG_INF
    need = want - count(lambda key, _: key > ans)
    equal = count(lambda key, _: key == ans)

    # The position of the ``need``-th key equal to the k-th: the largest p
    # with fewer than ``need`` equal keys before it, bit by bit. Where every
    # row keeps all its equal keys (no tie at the k-th place, which is the
    # rule) there is nothing to find: the cut is the line's end.
    def equal_before(p):
        def test(key, col0):
            pos = col0 + lax.broadcasted_iota(jnp.int32, key.shape, 1)
            return (key == ans) & (pos < p)
        return count(test)

    def position_bit(i, p):
        cand = p | jnp.left_shift(jnp.int32(1), pos_bits - 1 - i)
        return jnp.where(equal_before(cand) < need, cand, p)

    tied = jnp.max(jnp.where(open_row, 0.0, equal - need)) > 0.0
    pcut = lax.cond(
        tied,
        lambda: lax.fori_loop(0, pos_bits, position_bit,
                              jnp.zeros((rows, 1), jnp.int32)),
        lambda: jnp.full((rows, 1), s_ref.shape[1] - 1, jnp.int32))
    thr_ref[...] = lax.bitcast_convert_type(
        ans ^ ((ans >> 31) & 0x7FFFFFFF), jnp.float32)
    pcut_ref[...] = jnp.where(open_row, -1, pcut)


def _topk_threshold_pallas(scores, live, k: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, s = scores.shape
    if s <= k:
        return topk_threshold_reference(scores, k)
    rows = _SELECT_ROWS
    r_pad = -(-r // rows) * rows
    if r_pad != r:
        scores = jnp.pad(scores, ((0, r_pad - r), (0, 0)),
                         constant_values=-jnp.inf)
        live = jnp.pad(live, (0, r_pad - r))
    tiles = r_pad // rows
    live = jnp.clip(live.reshape(tiles, rows).max(axis=1), 0, s)
    ch = _select_chunk(s)
    row_spec = pl.BlockSpec((rows, 1), lambda i, *_: (i, 0))
    thr, pcut = pl.pallas_call(
        functools.partial(_select_kernel, k=k, ch=ch,
                          pos_bits=max(1, (s - 1).bit_length())),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[pl.BlockSpec((rows, s), lambda i, *_: (i, 0))],
            out_specs=[row_spec, row_spec],
            scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((r_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((r_pad, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(32 << 20, 6 * rows * s * 4)),
        interpret=kernel_backend() == "interpret",
        name="index_select",
    )(live.astype(jnp.int32), scores)
    return thr[:r, 0], pcut[:r, 0]


def topk_threshold(scores, k: int, live=None):
    """scores: float32 [R, S], ``-inf`` where a row sees nothing; ``live``
    int32 [R] (optional): one past the last position a row sees, so that
    nothing past it is looked at: the kernel reads whole chunks of 2,048
    columns up to the one that holds the last of a tile of 8 rows, which is
    below the bound :func:`index_scores` writes to, and nothing above (the
    jnp reference reads the whole array). Returns (thr float32 [R], pcut int32
    [R]): the ``k`` largest of each row, a tie at the k-th place going to
    the lower position as ``lax.top_k`` settles it, are exactly
    ``kept(scores, thr, pcut)``; a row that sees no more than ``k``
    positions keeps them all (``thr`` -inf, ``pcut`` -1)."""
    if kernel_backend() == "reference":
        return topk_threshold_reference(scores, k)
    if live is None:
        live = jnp.full((scores.shape[0],), scores.shape[1], jnp.int32)
    return _topk_threshold_pallas(scores, _as_i32(live), k)


# --------------------------------------------------------------- attention

def sparse_attention_reference(q, k_cache, v_cache, scores, thr, pcut, layer,
                               slots, sm_scale: float):
    n, h, c, d = q.shape
    hkv = k_cache.shape[2]

    def lines(stack):
        return lax.dynamic_index_in_dim(stack, layer, 0,
                                        keepdims=False)[slots]

    kl, vl = lines(k_cache), lines(v_cache)               # [N, Hkv, S, D]
    qg = q.reshape(n, hkv, (h // hkv) * c, d)
    logits = jnp.einsum("nhrd,nhsd->nhrs", qg, kl.astype(q.dtype),
                        preferred_element_type=jnp.float32) * sm_scale
    keep = jnp.tile(kept(scores, thr, pcut), (1, h // hkv, 1))[:, None]
    logits = jnp.where(keep, logits, NEG_INF)
    p = jnp.where(keep, jnp.exp(logits - logits.max(-1, keepdims=True)), 0.0)
    denom = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("nhrs,nhsd->nhrd", p.astype(q.dtype), vl.astype(q.dtype),
                     preferred_element_type=jnp.float32) / denom
    return out.astype(q.dtype).reshape(n, h, c, d)


# Where a running maximum starts, and not at NEG_INF: a masked logit under it
# gives exp(NEG_INF - floor) == 0 with no compare and no second select, so a
# row that has kept nothing yet (or never does) adds zeros; every real logit
# lies above it.
_MAX_FLOOR = 0.5 * NEG_INF
# Keys a grid step: a step of this body costs about 2 us before its first
# key (a chunk's tile took 2.99 us for 512 keys where its products take 1.36,
# and takes 4.0 for 1,024), and past 1,024 the tiles of logits outgrow what
# is saved (PERF.md section 6, PR 65).
_KEY_BLOCK = 1024


def _sparse_attention_kernel(layer_ref, slot_ref, q0_ref, lim_ref, q_ref,
                             k_ref, v_ref, s_ref, thr_ref, pcut_ref, o_ref,
                             m_ref, l_ref, acc_ref, *, block_k: int,
                             sm_scale: float):
    from jax.experimental import pallas as pl

    del layer_ref, slot_ref  # read by the index maps
    n, tile, blk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    # The tile: every KV head's ``heads`` x ``per`` rows under the tile's
    # ``tokens`` masks, a mask a token whatever the head. A chunk's is
    # (group, tokens): row g * tokens + t is query head g of token t. A
    # step's is (1, group): the one token's heads, all under its mask.
    hkv, heads, per, d = q_ref.shape
    rows = heads * per
    tokens = s_ref.shape[0]
    end = jnp.minimum(q0_ref[n] + (tile + 1) * tokens, lim_ref[n])

    @pl.when(blk == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MAX_FLOOR, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(blk * block_k < end)
    def _():
        index = s_ref[...]
        kpos = blk * block_k + lax.broadcasted_iota(
            jnp.int32, (tokens, block_k), 1)
        keep = (index > thr_ref[...]) | (
            (index == thr_ref[...]) & (kpos <= pcut_ref[...]))
        for g in range(hkv):
            at = pl.ds(g * rows, rows)
            s = lax.dot_general(q_ref[g].reshape(rows, d), k_ref[g],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.where(keep[None], s.reshape(heads, per, block_k),
                          NEG_INF).reshape(rows, block_k)
            # The maximum runs over the products as they are; the scale
            # goes into the exponent.
            m_prev = m_ref[at, :]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp((s - m_new) * sm_scale)
            alpha = jnp.exp((m_prev - m_new) * sm_scale)
            l_ref[at, :] = alpha * l_ref[at, :] + p.sum(axis=-1,
                                                        keepdims=True)
            acc_ref[at, :] = alpha * acc_ref[at, :] + jnp.dot(
                p.astype(v_ref.dtype), v_ref[g],
                preferred_element_type=jnp.float32)
            m_ref[at, :] = m_new

    @pl.when(blk == pl.num_programs(2) - 1)
    def _():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = o.reshape(o_ref.shape).astype(o_ref.dtype)


def _sparse_attention_pallas(q, k_cache, v_cache, scores, thr, pcut, layer,
                             slots, q0, limits, *, sm_scale: float):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, c, d = q.shape
    hkv, s = k_cache.shape[2:4]
    group = h // hkv
    block_k = _divisor_block(s, _KEY_BLOCK)
    if c == 1:
        # A step's tile is its token's heads under the token's one mask,
        # the scores as they come (a tile of tokens would be padding but
        # for one row a head, the scores padded to match). Whole float32
        # sublane tiles a KV head.
        tokens, heads, per = 1, 1, -(-group // 8) * 8
    else:
        tokens = prefill_q_block(c, group, q.dtype.itemsize)
        heads, per = group, tokens
    tiles = -(-c // tokens)
    # [N, Hkv, 1, group, D] for a step, [N, Hkv, group, C, D] for a chunk.
    qg = q.reshape(n, hkv, heads, -1, d)
    real = qg.shape[3]
    if per * tiles != real:
        qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, per * tiles - real), (0, 0)))
    if tokens * tiles != c:
        pad = tokens * tiles - c
        scores = jnp.pad(scores, ((0, 0), (0, pad), (0, 0)),
                         constant_values=-jnp.inf)
        thr = jnp.pad(thr, ((0, 0), (0, pad)))
        pcut = jnp.pad(pcut, ((0, 0), (0, pad)), constant_values=-1)
    scalars = _line_scalars(layer, slots, q0, limits, s)
    # A run-time bound: key blocks past every line's last row do not exist
    # as grid steps (0.35 us each, dead). One step at the least, which
    # writes an idle call's zeros.
    blocks = jnp.maximum(pl.cdiv(jnp.max(jnp.minimum(
        scalars[2] + c, scalars[3])), block_k), 1)

    def last_live(i, t, p0, lim):
        end = jnp.minimum(p0[i] + (t + 1) * tokens, lim[i])
        return jnp.maximum(pl.cdiv(end, block_k) - 1, 0)

    def kv_index(i, t, j, lyr, slot, p0, lim):
        return (lyr[0], slot[i], 0, jnp.minimum(j, last_live(i, t, p0, lim)),
                0)

    def q_index(i, t, j, *_):
        return (i, 0, 0, t, 0)

    def score_index(i, t, j, lyr, slot, p0, lim):
        return (i, t, jnp.minimum(j, last_live(i, t, p0, lim)))

    def row_index(i, t, j, *_):
        return (i, t, 0)

    rows = hkv * heads * per
    # Every KV head a grid step: the mask is built and the scores fetched
    # once for the four, and the steps are a quarter as many.
    kv_spec = pl.BlockSpec((None, None, hkv, block_k, d), kv_index)
    q_spec = pl.BlockSpec((None, hkv, heads, per, d), q_index)
    row_spec = pl.BlockSpec((None, tokens, 1), row_index)
    out = pl.pallas_call(
        functools.partial(_sparse_attention_kernel, block_k=block_k,
                          sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n, tiles, blocks),
            in_specs=[q_spec, kv_spec, kv_spec,
                      pl.BlockSpec((None, tokens, block_k), score_index),
                      row_spec, row_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # A head's float32 logits and what is made of them, and the
            # heads' key and value blocks twice over.
            vmem_limit_bytes=max(
                32 << 20, 12 * heads * per * block_k * 4,
                8 * hkv * block_k * d * k_cache.dtype.itemsize)),
        interpret=kernel_backend() == "interpret",
        # Two names for a trace to tell a decode step's calls (a row a line)
        # from a chunk's.
        name="sparse_decode_attention" if c == 1 else
        "sparse_prefill_attention",
    )(*scalars, qg, k_cache, v_cache, scores, thr[..., None],
      pcut[..., None])
    return out[:, :, :, :real].reshape(n, h, c, d)


def sparse_attention(q, k_cache, v_cache, scores, thr, pcut, layer, slots,
                     q0, limits, *, sm_scale: float | None = None):
    """q: [N, H, C, D], the queries of N lines (query head h of KV head
    ``h // (H // Hkv)``); k_cache, v_cache: [L, B, Hkv, S, D], the rows' own
    keys and values already written; scores [N, C, S] float32, thr [N, C],
    pcut [N, C]: each row's set as :func:`index_scores` and
    :func:`topk_threshold` give it (a position the row does not see scores
    ``-inf`` and is in no set); layer, slots, q0, limits as
    :func:`index_scores` takes them. Of the scores the kernel reads the
    blocks of 1,024 columns up to each tile's last seen position, all below
    the bound :func:`index_scores` writes to, and nothing above (the jnp
    reference reads the whole array). Returns [N, H, C, D]; a row with an
    empty set gives zeros."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if kernel_backend() == "reference":
        return sparse_attention_reference(
            q, k_cache, v_cache, scores, thr, pcut, _as_i32(layer),
            _as_i32(slots), scale)
    return _sparse_attention_pallas(
        q, k_cache, v_cache, scores, thr, pcut, layer, slots, q0, limits,
        sm_scale=scale)


# ------------------------------------------------------ the index key leaf

def _index_rows_write_kernel(pos_ref, layer_ref, new_ref, win_ref, out_ref):
    from jax.experimental import pallas as pl

    del layer_ref  # read by the index maps
    p = pos_ref[pl.program_id(0)]
    lane = lax.broadcasted_iota(jnp.int32, win_ref.shape, 1)
    # A masked slot's position is negative: no lane of window 0 is hit.
    out_ref[...] = jnp.where(lane == lax.rem(p, win_ref.shape[1]),
                             new_ref[...], win_ref[...])


def _index_rows_write_pallas(index_k, new, layer, positions0, write_mask):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, di = new.shape
    s = index_k.shape[4]
    window = 128 if s % 128 == 0 else s
    pos = jnp.where(write_mask, positions0, -1).astype(jnp.int32)

    def win_index(i, pos, lyr):
        return (lyr[0], i, 0, 0,
                jnp.clip(pos[i], 0, s - 1) // window)

    win_spec = pl.BlockSpec((None, None, None, di, window), win_index)
    return pl.pallas_call(
        _index_rows_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((None, di, 1), lambda i, *_: (i, 0, 0)),
                      win_spec],
            out_specs=win_spec),
        out_shape=jax.ShapeDtypeStruct(index_k.shape, index_k.dtype),
        # Operands count the scalar-prefetch arguments: the leaf comes after
        # them and the new keys, and is written in place.
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=kernel_backend() == "interpret",
        name="index_rows_write",
    )(pos, _as_i32(layer).reshape(1),
      new.astype(index_k.dtype)[:, :, None], index_k)


def index_rows_write(index_k, new, layer, positions0, write_mask):
    """A decode step's index keys, new [B, Di], to ``index_k[layer, b, 0, :,
    positions0[b]]`` where ``write_mask[b]``, in place (a kernel, for
    ``kv_row_write``'s reason: the leaf keeps the layout the score kernel
    reads)."""
    if kernel_backend() == "reference":
        b, s = new.shape[0], index_k.shape[4]
        pos = jnp.where(write_mask, positions0, s)     # out of bounds: dropped
        return index_k.at[layer, jnp.arange(b), 0, :, pos].set(
            new.astype(index_k.dtype), mode="drop")
    return _index_rows_write_pallas(index_k, new, layer, positions0,
                                    write_mask)


def index_chunk_write(index_k, new, layer, slot, kv_len):
    """A prefill chunk's index keys, new [C, Di], to ``index_k[layer, slot,
    0, :, kv_len : kv_len + C]``, in place (a dynamic_update_slice into the
    loop's carry, as ``prefill_kv_write``; the caller keeps ``kv_len + C``
    within the line)."""
    return lax.dynamic_update_slice(
        index_k, new.T.astype(index_k.dtype)[None, None, None],
        (layer, slot, 0, 0, kv_len))
