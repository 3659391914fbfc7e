"""Grouped matmul for a routed expert layer that keeps every token.

The rows of ``x`` are the (token, expert) picks that fell on experts held
here, sorted by expert and laid out in tiles of ``tm`` rows, every tile
within one expert (a group whose size is no multiple of ``tm`` ends in a
partly empty tile). ``tile_expert[t]`` names the expert of tile ``t`` and
``n_live`` how many tiles there are: both are run-time values, so nothing
has a capacity and nothing is dropped, and the grid's first dimension is
``n_live`` itself. An expert that got no row has no tile and is not read;
a tile's rows multiply ``w[layer, tile_expert[t]]``, taken from the whole
stack ``[L, E, K, N]`` by the block specs (a caller that handed in
``w[layer]`` would make XLA copy a layer of experts first).

With ``w2`` the kernel gives ``silu(x @ w) * (x @ w2)``, the first half of
a SwiGLU expert, in one pass over ``x``. Accumulation is float32. Output
rows past the last live tile are not written (whatever the buffer held);
the caller reads only rows it placed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.kernels import kernel_backend

# One weight block, in bytes: two matrices (SwiGLU) times two pipeline
# buffers of it must fit VMEM with room beside.
_W_BLOCK_BYTES = 3 << 20


def _divisor_block(dim: int, cap: int) -> int:
    """The largest multiple of 128 up to ``cap`` that divides ``dim``; the
    whole dimension where none does (tiny test widths)."""
    fits = [b for b in range(128, min(cap, dim) + 1, 128) if dim % b == 0]
    return fits[-1] if fits else dim


def grouped_matmul_reference(x, w, layer, tile_expert, n_live, *, tm: int,
                             w2=None):
    """Every expert's matmul over all rows, each row keeping its own
    expert's (small sizes only). Rows of dead tiles give zeros."""
    tiles = x.shape[0] // tm
    row_expert = jnp.repeat(tile_expert[:tiles], tm)
    row_live = jnp.repeat(jnp.arange(tiles) < n_live, tm)
    wl = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
    w2l = None if w2 is None else jax.lax.dynamic_index_in_dim(
        w2, layer, 0, keepdims=False)
    out = jnp.zeros((x.shape[0], w.shape[-1]), jnp.float32)
    for e in range(w.shape[1]):
        y = jnp.dot(x, wl[e], preferred_element_type=jnp.float32)
        if w2l is not None:
            y = jax.nn.silu(y) * jnp.dot(x, w2l[e],
                                         preferred_element_type=jnp.float32)
        out = jnp.where(((row_expert == e) & row_live)[:, None], y, out)
    return out.astype(x.dtype)


def _gmm_kernel(te_ref, layer_ref, x_ref, *refs, swiglu: bool):
    from jax.experimental import pallas as pl

    del te_ref, layer_ref  # read by the block specs' index maps
    if swiglu:
        w_ref, w2_ref, o_ref, acc_ref, acc2_ref = refs
    else:
        w_ref, o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        if swiglu:
            acc2_ref[...] = jnp.zeros(acc2_ref.shape, jnp.float32)

    x = x_ref[...]
    acc_ref[...] += jnp.dot(x, w_ref[...],
                            preferred_element_type=jnp.float32)
    if swiglu:
        acc2_ref[...] += jnp.dot(x, w2_ref[...],
                                 preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        y = acc_ref[...]
        if swiglu:
            y = jax.nn.silu(y) * acc2_ref[...]
        o_ref[...] = y.astype(o_ref.dtype)


def _grouped_matmul_pallas(x, w, layer, tile_expert, n_live, *, tm: int,
                           w2=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    mp, kdim = x.shape
    n = w.shape[-1]
    swiglu = w2 is not None
    tk = _divisor_block(kdim, 2048)
    tn = _divisor_block(n, max(128, _W_BLOCK_BYTES
                               // (tk * w.dtype.itemsize)))
    interpret = kernel_backend() == "interpret"

    def x_index(t, j, k, te, lyr):
        return (t, k)

    def w_index(t, j, k, te, lyr):
        return (lyr[0], te[t], k, j)

    def o_index(t, j, k, te, lyr):
        return (t, j)

    w_spec = pl.BlockSpec((None, None, tk, tn), w_index)
    scratch = [pltpu.VMEM((tm, tn), jnp.float32)] * (2 if swiglu else 1)
    weights = (w, w2) if swiglu else (w,)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, swiglu=swiglu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # The interpreter wants a static grid: every tile then, the
            # dead ones on the last live tile's expert (zero rows).
            grid=(mp // tm if interpret else n_live, n // tn, kdim // tk),
            in_specs=[pl.BlockSpec((tm, tk), x_index)]
            + [w_spec] * len(weights),
            out_specs=pl.BlockSpec((tm, tn), o_index),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((mp, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(
                32 << 20, 6 * len(weights) * tk * tn * w.dtype.itemsize)),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(tile_expert.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), x, *weights)


def grouped_matmul(x, w, layer, tile_expert, n_live, *, tm: int, w2=None):
    """x: [Mp, K], Mp a multiple of ``tm``; w (and w2): [L, E, K, N];
    layer: int32 scalar; tile_expert: [Mp // tm] int32, the expert of each
    tile (any held expert for a dead tile); n_live: int32 scalar, the tiles
    in use. Returns [Mp, N]: ``x @ w[layer, e]`` a tile, or with ``w2`` the
    gated product; rows of dead tiles are undefined."""
    if x.shape[0] % tm:
        raise ValueError(f"grouped_matmul: {x.shape[0]} rows are not whole "
                         f"tiles of {tm}")
    fn = (grouped_matmul_reference if kernel_backend() == "reference"
          else _grouped_matmul_pallas)
    return fn(x, w, jnp.asarray(layer, jnp.int32), tile_expert,
              jnp.asarray(n_live, jnp.int32), tm=tm, w2=w2)
