"""The chunk's side of latent attention (ops/latent_attention.py): the
kernel's body through the Pallas interpreter against the XLA reference and
against a dense float32 masked softmax over the whole line."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import latent_attention as la
from ray_tpu.ops.kernels import force_kernel_backend

RANK, DN, DR, DV, WIDTH = 32, 16, 8, 16, 128
LAYER, SLOT = 1, 2
SCALE = 0.2


def _inputs(heads: int, chunk: int, line: int, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q_n = jax.random.normal(ks[0], (chunk, heads, DN))
    q_r = jax.random.normal(ks[1], (chunk, heads, DR))
    cache = jax.random.normal(ks[2], (2, 3, line, WIDTH))
    w_kb = jax.random.normal(ks[3], (RANK, heads, DN)) * RANK ** -0.5
    w_vb = jax.random.normal(ks[4], (RANK, heads, DV)) * RANK ** -0.5
    return q_n, q_r, cache, w_kb, w_vb


def _dense(q_n, q_r, cache, w_kb, w_vb, kv_len, length):
    """Every row of the line up-projected, one softmax a query."""
    rows = cache[LAYER, SLOT]
    c, s = q_n.shape[0], rows.shape[0]
    kpos = jnp.arange(s)
    # A dead row may hold anything: it is masked out, never multiplied.
    rows = jnp.where((kpos < length)[:, None], rows, 0.0)
    ckv, kr = rows[:, :RANK], rows[:, RANK:RANK + DR]
    kn = jnp.einsum("sr,rhd->shd", ckv, w_kb)
    v = jnp.einsum("sr,rhd->shd", ckv, w_vb)
    sc = (jnp.einsum("chd,shd->hcs", q_n, kn)
          + jnp.einsum("chd,sd->hcs", q_r, kr)) * SCALE
    visible = ((kpos[None] <= kv_len + jnp.arange(c)[:, None])
               & (kpos[None] < length))[None]
    p = jnp.where(visible, jnp.exp(
        sc - jnp.where(visible, sc, -jnp.inf).max(-1, keepdims=True,
                                                  initial=-1e30)), 0.0)
    out = jnp.einsum("hcs,shd->hcd", p, v) / jnp.maximum(
        p.sum(-1, keepdims=True), 1e-30)
    return out.transpose(1, 0, 2)


# name: (chunk, line, block, kv_len, length); the line is blocks of 128
# unless it is shorter than one.
CASES = {
    "empty_line": (128, 512, 128, 0, 128),
    "kv_len_off_a_block_boundary": (128, 512, 128, 100, 228),
    "line_shorter_than_a_block": (32, 64, None, 16, 48),
    "dead_blocks_behind_live_ones": (128, 512, 128, 128, 256),
    "last_chunk_ends_before_its_rows": (128, 512, 128, 256, 300),
    "chunk_smaller_than_the_block": (32, 512, 128, 160, 192),
    "chunk_of_no_whole_tile": (24, 512, 128, 250, 274),
    "a_row_that_sees_nothing": (32, 512, 128, 0, 0),
}


@pytest.mark.parametrize("heads", [128, 64])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_reference_and_the_dense_softmax(heads, case,
                                                           monkeypatch):
    chunk, line, block, kv_len, length = CASES[case]
    # Several tiles of heads at these widths too, as the chip runs 128 and
    # 64 heads in 8 and 4 tiles.
    monkeypatch.setattr(la, "_PREFILL_VMEM", 6 << 20)
    assert la.latent_prefill_head_tile(
        heads, -(-chunk // 8) * 8, block or line, RANK, DN, DR, DV, WIDTH,
        4) <= heads // 2
    q_n, q_r, cache, w_kb, w_vb = _inputs(heads, chunk, line)
    live = min(kv_len + chunk, length)
    step = block or line
    dead_from = -(-live // step) * step
    # Blocks wholly past the live rows are neither fetched nor computed.
    cache = cache.at[:, :, dead_from:].set(jnp.nan)
    kw = dict(rope_dim=DR, sm_scale=SCALE, block=block)
    args = (q_n, q_r, cache, w_kb, w_vb, LAYER, SLOT, kv_len, length)
    with force_kernel_backend("reference"):
        want = la.latent_prefill_attention(*args, **kw)
    with force_kernel_backend("interpret"):
        got = jax.jit(lambda *a: la.latent_prefill_attention(*a, **kw))(*args)
    assert got.shape == (chunk, heads, DV)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_dense(*args[:5], kv_len, length)),
        atol=2e-5)
    if length == 0:
        assert not np.asarray(got).any()
    # The row's padding (lanes rank + Dr and beyond) is never read.
    noisy = cache.at[..., RANK + DR:].set(1e9)
    with force_kernel_backend("interpret"):
        again = la.latent_prefill_attention(q_n, q_r, noisy, *args[3:], **kw)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_the_head_tile_comes_from_the_shapes():
    """128 and 64 heads at the published widths, a chunk of 512 against
    blocks of 512 of rows of 640: the same tile, another count of tiles;
    a tile's outputs are whole lanes, or all heads."""
    widths = (512, 512, 512, 128, 64, 128, 640)
    assert la.latent_prefill_head_tile(128, *widths) == 16
    assert la.latent_prefill_head_tile(64, *widths) == 16
    assert la.latent_prefill_head_tile(6, 32, 64, RANK, DN, DR, DV, 128) == 6
    assert la._prefill_vmem(16, *widths, 2) <= la._PREFILL_VMEM < \
        la._prefill_vmem(32, *widths, 2)


def test_a_block_that_does_not_divide_the_line_is_refused():
    q_n, q_r, cache, w_kb, w_vb = _inputs(4, 16, 96)
    with force_kernel_backend("interpret"), pytest.raises(
            ValueError, match="does not divide"):
        la.latent_prefill_attention(q_n, q_r, cache, w_kb, w_vb, 0, 0, 0, 16,
                                    rope_dim=DR, sm_scale=SCALE, block=64)
