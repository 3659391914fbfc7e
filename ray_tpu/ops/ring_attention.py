"""Ring attention: exact attention over sequences sharded across the ``sp``
mesh axis, with K/V blocks rotating around the ICI ring via ``lax.ppermute``.

New work relative to the reference framework (reference: SURVEY.md §5 — Ray
has no sequence/context parallelism anywhere; its role stops at process-group
bring-up). Here long context is first-class: each device holds Sq/N of the
sequence; at every ring step it attends its local Q against the visiting K/V
chunk with online-softmax accumulation, then passes the chunk to its ICI
neighbor. Compute/communication overlap is XLA's job (the ppermute is
independent of the attention einsum in each step, so the scheduler pipelines
them).

Causality across chunks: positions are global (chunk_index · chunk_len +
local offset); a visiting chunk strictly in the future is fully masked and
contributes nothing (the online update with all-masked logits is a no-op).

Usage: inside ``shard_map`` over a mesh with an ``sp`` axis, with q/k/v
sharded on their sequence dim. ``ring_attention_sharded`` builds that
shard_map for a global array.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import NEG_INF, _repeat_kv
from ray_tpu.ops.kernels import kernel_backend


def _ring_step_combine(q, k, v, o, m, l, scale, causal, q_offset, kv_offset,
                       kv_block):
    """One online-softmax accumulation of local q against a visiting kv chunk."""
    b, h, sq, d = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(k.shape[2])[None, :] + kv_offset
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # Guard: fully-masked rows keep m at NEG_INF; exp underflows to 0 — fine.
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    return o_new, m_new, l_new


def ring_attention_local(q, k, v, axis_name: str, causal: bool = True,
                         sm_scale: float | None = None,
                         impl: str = "auto"):
    """Per-shard body (call inside shard_map). q/k/v: local [B, H, S/N, D].

    ``impl``: "flash" runs each ring step through the Pallas chunk kernel
    (ops/attention.py flash_attention_chunk — data-driven causal positions,
    differentiable lse) and combines chunks by (out, lse) log-sum-exp;
    "einsum" is the materialized-score XLA path; "auto" picks flash wherever
    ops/kernels.py runs Pallas kernels (on the TPU).
    """
    b, h, sq, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    chunk = sq
    perm = [(i, (i + 1) % n) for i in range(n)]

    if impl == "auto":
        impl = "einsum" if kernel_backend() == "reference" else "flash"

    if impl == "flash":
        from ray_tpu.ops.attention import flash_attention_chunk

        qpos = my * chunk + jnp.arange(sq, dtype=jnp.int32)

        def stepf(t, carry):
            o, lse_acc, kc, vc = carry
            src = (my - t) % n
            kpos = src * chunk + jnp.arange(chunk, dtype=jnp.int32)
            o_t, lse_t = flash_attention_chunk(q, kc, vc, qpos, kpos,
                                               causal, scale)
            # log-sum-exp combine of normalized per-chunk results; a fully
            # masked chunk arrives with lse ~ -inf and weight 0.
            lse_new = jnp.logaddexp(lse_acc, lse_t)
            w_old = jnp.exp(lse_acc - lse_new)[..., None]
            w_new = jnp.exp(lse_t - lse_new)[..., None]
            o = o * w_old + o_t.astype(jnp.float32) * w_new
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)
            return o, lse_new, kc, vc

        o0 = jnp.zeros((b, h, sq, d), jnp.float32)
        lse0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
        o, _, _, _ = lax.fori_loop(0, n, stepf, (o0, lse0, k, v))
        return o.astype(q.dtype)

    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)

    # Ring: at step t, this device holds the chunk originally owned by
    # (my - t) mod n; chunks travel to the next-higher index each step.

    def step(t, carry):
        o, m, l, kc, vc = carry
        src = (my - t) % n  # owner of the visiting chunk
        o, m, l = _ring_step_combine(
            q, kc, vc, o, m, l, scale, causal,
            q_offset=my * chunk, kv_offset=src * chunk, kv_block=chunk,
        )
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return o, m, l, kc, vc

    o, m, l, _, _ = lax.fori_loop(0, n, step, (o0, m0, l0, k, v))
    l = jnp.maximum(l, 1e-30)
    return (o / l[..., None]).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, axis: str = "sp",
                           causal: bool = True,
                           sm_scale: float | None = None,
                           batch_axes=None, impl: str = "auto"):
    """Global-array entry: shard seq dim over ``axis``, run the ring.

    ``batch_axes``: optional mesh axes to shard the batch dim over (e.g.
    ("dp", "fsdp") in a combined dp×sp mesh)."""
    spec = P(batch_axes, None, axis, None)
    fn = shard_map_ring(mesh, axis, causal, sm_scale, spec, impl)
    return fn(q, k, v)


@functools.lru_cache(maxsize=64)
def shard_map_ring(mesh: Mesh, axis: str, causal: bool, sm_scale, spec: P,
                   impl: str = "auto"):
    body = functools.partial(ring_attention_local, axis_name=axis,
                             causal=causal, sm_scale=sm_scale, impl=impl)

    @jax.jit
    def fn(q, k, v):
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    return fn
