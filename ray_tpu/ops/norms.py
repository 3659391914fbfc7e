"""RMSNorm: Pallas fused kernel + reference implementation; LayerNorm in jnp.

The TPU framework owns its normalization kernels (the reference delegates to
torch). RMSNorm (no mean subtraction) is the transformer default (Llama-family).
The Pallas kernel fuses the reduction, rsqrt, and scale multiply in VMEM; the
jnp path is used off the TPU (ops/kernels.py decides) and for autodiff (XLA
fuses it into neighbors anyway — the kernel exists for the cases XLA's fusion
boundary splits, e.g. ahead of a sharded matmul).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.kernels import KernelMesh, kernel_backend


def rms_norm_reference(x, weight, eps: float = 1e-6):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rms_norm_pallas(x, weight, eps: float = 1e-6, block_rows: int = 256):
    from jax.experimental import pallas as pl

    orig_shape = x.shape
    d = orig_shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    # Fewer rows than a block: one block spanning the array. Otherwise the
    # grid rounds up and the last block runs partly out of bounds, which
    # Pallas pads on read and drops on write; rows are independent, so the
    # padding never reaches a kept row.
    block_rows = min(block_rows, rows)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=kernel_backend() == "interpret",
        name="rms_norm",
    )(x2, weight)
    return out.reshape(orig_shape)


def rms_norm(x, weight, eps: float = 1e-6, kmesh: KernelMesh | None = None):
    """x: [batch, ..., d]. The Pallas kernel forward where ops/kernels.py
    picks it, the reference elsewhere and for the gradient (custom_vjp
    recomputes through the reference). Under a mesh of several devices pass
    its ``kmesh``: the kernel then runs on each device's rows, the batch dim
    split over the data axes."""
    if kernel_backend() == "reference":
        return rms_norm_reference(x, weight, eps)
    return _rms_norm_cv(x, weight, eps, kmesh)


def _rms_fwd_kernel(x, weight, eps, kmesh):
    fwd = functools.partial(rms_norm_pallas, eps=eps)
    if kmesh is not None:
        rows = kmesh.rows_spec(x.ndim)
        fwd = kmesh.shard(fwd, in_specs=(rows, P()), out_specs=rows)
    return fwd(x, weight)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_norm_cv(x, weight, eps, kmesh):
    return _rms_fwd_kernel(x, weight, eps, kmesh)


def _rms_fwd(x, weight, eps, kmesh):
    return _rms_fwd_kernel(x, weight, eps, kmesh), (x, weight)


def _rms_bwd(eps, kmesh, res, g):
    x, weight = res
    _, vjp = jax.vjp(lambda x_, w_: rms_norm_reference(x_, w_, eps), x, weight)
    return vjp(g)


_rms_norm_cv.defvjp(_rms_fwd, _rms_bwd)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    """``(x - mean(x)) rsqrt(var(x) + eps) w + b`` over the last dimension
    (``nn.LayerNorm``), statistics in float32. Plain jnp: XLA fuses it into
    its neighbours."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    centred = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    y = centred * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)
