"""Attention ops: naive reference, blockwise (memory-efficient, autodiff-able),
and a Pallas TPU flash-attention forward kernel.

This layer is new work relative to the reference framework — Ray delegates
intra-model compute to torch/vLLM (reference: SURVEY.md §5 "long-context ...
the reference has none"); a TPU-native framework owns its attention kernels.

Design:
- ``attention_reference``: O(S²) jnp softmax attention — ground truth in tests.
- ``blockwise_attention``: lax.scan over KV blocks with online softmax; O(S)
  activations, differentiable, runs anywhere. This is also the inner step of
  ring attention (ray_tpu/ops/ring_attention.py).
- ``flash_attention``: pl.pallas_call kernel (MXU-tiled, VMEM-resident online
  softmax, causal masking with block skipping); custom_vjp whose backward
  recomputes through ``blockwise_attention``.

Shapes: q [B, H, Sq, D], k/v [B, Hkv, Skv, D]; GQA when Hkv < H.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.kernels import (
    KernelMesh,
    kernel_backend,
    target_device_kind,
)

NEG_INF = -1e30
LOG2E = 1.4426950408889634  # log2(e): kernels run base-2 softmax (exp2 is
LN2 = 0.6931471805599453    # the VPU-native transcendental; exp = mul+exp2)

# Fused dq+dkv backward (one kernel, 5 matmuls per block pair instead of 7
# across the split kernels). RTPU_FLASH_FUSED_BWD=0 falls back to the split
# dq / dkv kernels.
import os as _os

FUSED_BWD = _os.environ.get("RTPU_FLASH_FUSED_BWD", "1") != "0"


def _env_int(name: str, default: int) -> int:
    v = _os.environ.get(name)
    if not v:
        return default
    try:
        n = int(v)
    except ValueError:
        return default
    return n if n > 0 else default


def flash_blocks(block_q: int | None = None,
                 block_k: int | None = None) -> tuple[int, int]:
    """Resolve flash-attention kernel block sizes: explicit argument wins,
    then the RTPU_FLASH_BLOCK_Q / RTPU_FLASH_BLOCK_K env overrides (the
    autotuner sets these per candidate before tracing — block size is a
    compile-time grid parameter, so each value is a separate compile), then
    the 512 default chip-measured best at the bench geometry. Values must
    divide the sequence length; the pallas wrappers assert that loudly."""
    return (block_q or _env_int("RTPU_FLASH_BLOCK_Q", 512),
            block_k or _env_int("RTPU_FLASH_BLOCK_K", 512))

# Scoped-VMEM ceiling for the flash kernels, by TPU generation. The v5e has
# 128 MB of VMEM per core, and the compiler's default 16 MB scoped limit is
# too tight for packed blocks. Only generations the kernels were compiled
# for are listed; any other device keeps the compiler default. Override with
# RTPU_FLASH_VMEM_LIMIT_MB (0 = force the compiler default).
_VMEM_LIMIT_MB_BY_GEN = {"v5": 96}


def _flash_vmem_limit_bytes() -> int | None:
    """vmem_limit_bytes for pltpu.CompilerParams, from the generation of the
    device the kernel compiles for; None leaves the compiler default."""
    env = _os.environ.get("RTPU_FLASH_VMEM_LIMIT_MB")
    if env is not None:
        mb = int(env)
        return mb * 1024 * 1024 if mb > 0 else None
    kind = target_device_kind().lower()  # e.g. "tpu v5 lite"
    for tok in kind.replace("tpu", " ").split():
        if tok.startswith("v") and tok[1:2].isdigit():
            mb = _VMEM_LIMIT_MB_BY_GEN.get(tok[:2])
            return mb * 1024 * 1024 if mb is not None else None
    return None


def _flash_compiler_params(pltpu):
    """Grid semantics of every flash kernel here, plus the scoped-VMEM
    ceiling where the generation has one."""
    limit = _flash_vmem_limit_bytes()
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        **({"vmem_limit_bytes": limit} if limit is not None else {}))


def _interpret() -> bool:
    return kernel_backend() == "interpret"


def _repeat_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """Expand KV heads to match query heads (GQA)."""
    b, hkv, s, d = k.shape
    if hkv == num_heads:
        return k
    rep = num_heads // hkv
    return jnp.repeat(k, rep, axis=1)


def attention_reference(q, k, v, causal: bool = True, sm_scale: float | None = None,
                        q_offset: int = 0):
    """O(S²) reference. q_offset: absolute position of q[0] (for ring/chunked)."""
    b, h, sq, d = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(k.shape[2])[None, :]
        logits = jnp.where(kpos <= qpos, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def blockwise_attention(q, k, v, causal: bool = True,
                        sm_scale: float | None = None,
                        kv_block: int = 512, q_offset: int = 0,
                        kv_offset: int = 0):
    """Online-softmax attention scanned over KV blocks.

    Activation memory is O(Sq · D) regardless of Skv. Differentiable (autodiff
    through the scan); combine with jax.checkpoint for long sequences.
    """
    b, h, sq, d = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    skv = k.shape[2]
    kv_block = min(kv_block, skv)
    nblocks = (skv + kv_block - 1) // kv_block
    pad = nblocks * kv_block - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)

    kb = k.reshape(b, h, nblocks, kv_block, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nblocks, kv_block, d).transpose(2, 0, 1, 3, 4)

    qpos = jnp.arange(sq) + q_offset

    def step(carry, inputs):
        o, m, l = carry
        blk_idx, kblk, vblk = inputs
        kpos = blk_idx * kv_block + jnp.arange(kv_block) + kv_offset
        # preferred_element_type (bf16 MXU inputs, f32 accumulate) rather
        # than a bf16 dot + astype: the cast form miscompiles under XLA
        # fusion in the scan's backward (NaN dq/dk on CPU and TPU for
        # multi-block bf16 inputs) and is lower-precision anyway.
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kblk,
                       preferred_element_type=jnp.float32) * scale
        valid = (kpos[None, :] - kv_offset) < skv  # mask zero-padding
        if causal:
            full_mask = (kpos[None, :] <= qpos[:, None]) & valid
        else:
            full_mask = valid
        s = jnp.where(full_mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    idxs = jnp.arange(nblocks)
    (o, m, l), _ = lax.scan(step, (o0, m0, l0), (idxs, kb, vb))
    l = jnp.maximum(l, 1e-30)
    return (o / l[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU flash-attention forward kernel
# ---------------------------------------------------------------------------

def _pick_pack(rep: int) -> int:
    """Q-heads packed per kernel invocation. Packing P heads that share one
    GQA kv head row-concatenates their q blocks into [P*block_q, d] tiles:
    every matmul and VPU softmax op becomes P× larger (amortizing per-op
    overheads that dominate at head_dim 64) while the causal block-skip
    granularity stays block_q. Chip-measured fwd at the bench geometry
    (B4 H32 KV8 S2048 D64): 36.5 → 48.0 TF/s with pack=4 + the inline
    diagonal (devbench/prof_flash_pack.py, r5)."""
    for p in (4, 2):
        if rep % p == 0:
            return p
    return 1


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, kv_seq_len: int,
                      block_k: int, sm_scale: float, causal: bool,
                      inline_diag: bool):
    """Grid: (batch*heads/pack, q_blocks). K/V stream through VMEM in
    block_k chunks; online softmax state lives in registers/VMEM. Emits the
    per-row logsumexp so the backward can recompute p = exp(s - lse)
    without a second online pass (FlashAttention-2 shape).

    Causal modes:
    - inline_diag (block_q == block_k, sq == skv): a mask-free fori_loop
      over the fully-visible kv blocks, then the single partial (diagonal)
      block unrolled as straight-line code with a LOCAL triangular mask
      (identical for every qi). Two fori_loops pipeline worse in Mosaic
      (r4 + r5 measurements); one loop + an unrolled tail does not.
    - generic: per-block global position mask with a traced upper bound.
    """
    from jax.experimental import pallas as pl  # local: TPU-only dependency

    qi = pl.program_id(1)
    # Keep q bf16: the MXU runs bf16×bf16 with f32 accumulation at full
    # rate; casting inputs to f32 would fall off the fast path (~6x
    # slower). The base-2 scale (p = exp2(s2 - m2)) is folded into q ONCE
    # per packed q tile instead of multiplying every [rows, bk] score
    # block on the VPU; the extra bf16 rounding of q·scale is ~0.4%
    # relative on the logit — inside flash-attention's bf16 error budget.
    q = q_ref[...]                       # [pack, bq, d]
    pack, bq, d = q.shape
    rows = pack * bq
    q2 = q.reshape(rows, d)
    scale2 = sm_scale * LOG2E
    qs = (q2.astype(jnp.float32) * scale2).astype(q2.dtype)

    nkv = kv_seq_len // block_k

    def body(j, carry, masked, local_tri=False):
        o, m, l = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jnp.dot(qs, k.T,
                    preferred_element_type=jnp.float32)  # [rows, bk]
        if masked:
            # Packed row r is query position qi*bq + (r mod bq).
            lq = lax.rem(lax.broadcasted_iota(jnp.int32, s.shape, 0), bq)
            lk = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if local_tri:
                # Diagonal block: same local triangular pattern for all qi.
                s = jnp.where(lk <= lq, s, NEG_INF)
            else:
                s = jnp.where(j * block_k + lk <= qi * bq + lq, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp2(s - m_new[:, None])
        alpha = jnp.exp2(m - m_new)
        # Fold the row-sum of p into the p@v matmul via a ones column
        # appended to v: the MXU (at ~30% utilization here) absorbs the
        # reduction the VPU would otherwise do across the lane dimension
        # (chip-measured fwd 2.35 -> 2.10 ms at the bench geometry). Note
        # l now sums the BF16-quantized p — the same p the o matmul uses —
        # so o/l stay mutually consistent, but lse shifts ~1e-3 relative
        # vs an f32-accumulated sum; the backward recomputes p from this
        # same lse, keeping gradients self-consistent.
        v1 = jnp.concatenate(
            [v, jnp.ones((v.shape[0], 1), v.dtype)], axis=1)
        ov = jnp.dot(p.astype(v.dtype), v1,
                     preferred_element_type=jnp.float32)
        l_new = l * alpha + lax.slice(ov, (0, d), (rows, d + 1))[:, 0]
        o_new = o * alpha[:, None] + lax.slice(ov, (0, 0), (rows, d))
        return o_new, m_new, l_new

    o0 = jnp.zeros((rows, d), jnp.float32)
    m0 = jnp.full((rows,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows,), jnp.float32)

    if causal and inline_diag:
        carry = lax.fori_loop(
            0, qi, functools.partial(body, masked=False), (o0, m0, l0))
        o, m, l = body(qi, carry, masked=True, local_tri=True)
    elif causal:
        upper = lax.div((qi + 1) * bq + block_k - 1, block_k)
        upper = jnp.minimum(upper, nkv)
        o, m, l = lax.fori_loop(
            0, upper, functools.partial(body, masked=True), (o0, m0, l0))
    else:
        o, m, l = lax.fori_loop(
            0, nkv, functools.partial(body, masked=False), (o0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (o / l[:, None]).astype(o_ref.dtype).reshape(pack, bq, d)
    lse_ref[...] = ((m + jnp.log2(l)) * LN2).reshape(pack, bq)  # natural-log


def _packed_qspecs(pack, block_q, d, kv_div, skv):
    """BlockSpecs for the packed-head layout: q-side arrays live as
    [b*h/pack, pack, sq, d] (adjacent heads grouped, so flat group index
    i // (rep/pack) is exactly the flat kv-head index), row statistics as
    [b*h/pack, pack, sq]."""
    from jax.experimental import pallas as pl

    return (
        pl.BlockSpec((None, pack, block_q, d), lambda i, j: (i, 0, j, 0)),
        pl.BlockSpec((None, skv, d), lambda i, j: (i // kv_div, 0, 0)),
        pl.BlockSpec((None, pack, block_q), lambda i, j: (i, 0, j)),
    )


def _flash_fwd_pallas(q, k, v, causal: bool, sm_scale: float,
                      block_q: int | None = None, block_k: int | None = None):
    """GQA-native: k/v stay [B, Hkv, S, D]; the BlockSpec index maps send
    each packed q-head group to its kv head — no materialized repeat."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_q, block_k = flash_blocks(block_q, block_k)
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    rep = h // hkv
    block_q = min(block_q, sq)
    # The inline-diagonal causal mode needs square blocks on the diagonal.
    inline_diag = causal and sq == skv and sq % block_q == 0
    block_k = block_q if inline_diag else min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (
        "flash_attention requires seq lengths divisible by block sizes"
    )
    pack = _pick_pack(rep)
    g = b * h // pack
    kv_div = rep // pack
    qf = q.reshape(g, pack, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    kernel = functools.partial(
        _flash_fwd_kernel, kv_seq_len=skv, block_k=block_k,
        sm_scale=sm_scale, causal=causal, inline_diag=inline_diag,
    )
    qspec, kvspec, rowspec = _packed_qspecs(pack, block_q, d, kv_div, skv)
    out, lse = pl.pallas_call(
        kernel,
        grid=(g, sq // block_q),
        in_specs=[qspec, kvspec, kvspec],
        out_specs=[qspec, rowspec],
        out_shape=[
            jax.ShapeDtypeStruct((g, pack, sq, d), q.dtype),
            # Row statistics as [g, pack, sq] blocks of (pack, block_q):
            # the sublane dim equals the array dim (TPU tiling requires the
            # last two block dims be (8k, 128k) or match the array), without
            # the official kernel's 128-lane broadcast copy of every row.
            jax.ShapeDtypeStruct((g, pack, sq), jnp.float32),
        ],
        compiler_params=_flash_compiler_params(pltpu),
        interpret=_interpret(),
        name="flash_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _rows_3d(x, bh, s):
    """[B, H, S] row-statistics → the [B*H, 1, S] kernel layout."""
    return x.reshape(bh, 1, s)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, kv_seq_len: int, block_k: int,
                         sm_scale: float, causal: bool, block_q: int):
    """dQ, one q block per grid step: dq = Σ_j (p ∘ (dO·Vᵀ − Δ))·K · scale
    with p recomputed from the saved logsumexp."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    q = q_ref[...]                       # [bq, d] bf16
    do = do_ref[...].astype(jnp.float32)
    lse2 = lse_ref[0, :] * LOG2E         # [bq] f32, base-2
    delta = delta_ref[0, :]              # [bq] f32
    nkv = kv_seq_len // block_k
    scale2 = sm_scale * LOG2E
    # Same bf16 q·scale folding as the forward — the saved lse encodes
    # logits computed from the ROUNDED qs, so the backward must recompute
    # s identically or exp2(s - lse) rows stop summing to 1.
    qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)

    def body(j, dq):
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jnp.dot(qs, k.T, preferred_element_type=jnp.float32)
        if causal:
            qpos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = j * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp2(s - lse2[:, None])  # [bq, bk]
        dp = jnp.dot(do.astype(v.dtype), v.T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        return dq + jnp.dot(ds.astype(k.dtype), k,
                            preferred_element_type=jnp.float32)

    if causal:
        upper = lax.div((qi + 1) * block_q + block_k - 1, block_k)
        upper = jnp.minimum(upper, nkv)
    else:
        upper = nkv
    d = q_ref.shape[-1]
    dq = lax.fori_loop(0, upper, body, jnp.zeros((q.shape[0], d), jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, q_seq_len: int, block_q: int,
                          sm_scale: float, causal: bool, block_k: int):
    """dK/dV, one kv block per grid step: dv = Σ_i pᵀ·dO,
    dk = Σ_i (p ∘ (dO·Vᵀ − Δ))ᵀ·Q · scale. Causal skips q blocks above
    the diagonal (they can't attend to this kv block)."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    k = k_ref[...]                       # [bk, d] bf16
    v = v_ref[...]
    nq = q_seq_len // block_q
    scale2 = sm_scale * LOG2E

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * block_q, block_q), :]
        do = do_ref[pl.ds(i * block_q, block_q), :]
        lse2 = lse_ref[0, pl.ds(i * block_q, block_q)] * LOG2E
        delta = delta_ref[0, pl.ds(i * block_q, block_q)]
        # Rounded q·scale fold matches the forward's lse (see dq kernel).
        qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)
        s = jnp.dot(qs, k.T, preferred_element_type=jnp.float32)
        if causal:
            qpos = i * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp2(s - lse2[:, None])  # [bq, bk]
        dv = dv + jnp.dot(p.astype(do.dtype).T, do,
                          preferred_element_type=jnp.float32)
        dp = jnp.dot(do.astype(v.dtype), v.T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk = dk + jnp.dot(ds.astype(q.dtype).T, q,
                          preferred_element_type=jnp.float32)
        return dk, dv

    lower = lax.div(ki * block_k, block_q) if causal else 0
    d = k_ref.shape[-1]
    z = jnp.zeros((k.shape[0], d), jnp.float32)
    dk, dv = lax.fori_loop(lower, nq, body, (z, z))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                            kv_seq_len: int, block_k: int, sm_scale: float,
                            causal: bool, inline_diag: bool):
    """Fused backward: ONE pass over (q block, kv block) pairs computes
    dq, dk and dv together — the split dq/dkv kernels each recompute
    s = q·kᵀ, p and dp = dO·vᵀ for every pair (7 matmuls/pair across the
    two kernels); fused needs 5 and reads q/k/v/dO/lse/Δ once.

    Grid: (batch*heads/pack, q_blocks). dq is written per q block. dk/dv
    accumulate in f32 VMEM scratch across the whole q sweep (scratch
    persists over the sequential inner grid dim) and flush ONCE to HBM in
    the kernel's native dtype at the last q block — the HBM buffers stay
    bf16-sized instead of the f32 accumulator layout.

    Head packing bonus: the packed heads share one kv head, so the
    dv += p_catᵀ·dO_cat and dk += ds_catᵀ·q_cat matmuls (contraction over
    the packed rows) compute the GQA head-group fold for free — dk/dv HBM
    outputs shrink by pack× and the external fold pass disappears when
    pack == rep. Causal modes as in _flash_fwd_kernel (inline_diag:
    mask-free loop + the single diagonal block unrolled straight-line)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    nq = pl.num_programs(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[...]                       # [pack, bq, d] bf16
    pack, bq, d = q.shape
    rows = pack * bq
    q2 = q.reshape(rows, d)
    do = do_ref[...].reshape(rows, d)    # bf16
    # Row stats stay [pack, bq]: Mosaic supports collapsing LEADING dims
    # (same lane layout) but not a 2D→1D shape cast, so per-row broadcasts
    # below go through a [pack, bq, bk] view.
    lse2 = lse_ref[...] * LOG2E          # [pack, bq] f32, base-2
    delta = delta_ref[...]               # [pack, bq] f32
    nkv = kv_seq_len // block_k
    scale2 = sm_scale * LOG2E
    # Scale folding (see _flash_fwd_kernel): the logit scale rides q into
    # the s matmul, and ds's sm_scale rides the [*, d]-shaped matmul
    # OPERANDS (q for dk, k for dq) — two fewer [rows, bk] VPU multiplies
    # per block pair, at one extra bf16 rounding (~0.4%) on the operand.
    qs = (q2.astype(jnp.float32) * scale2).astype(q2.dtype)
    q_sc = (q2.astype(jnp.float32) * sm_scale).astype(q2.dtype)

    def body(j, dq, masked, local_tri=False):
        kslc = pl.ds(j * block_k, block_k)
        k = k_ref[kslc, :]
        v = v_ref[kslc, :]
        s = jnp.dot(qs, k.T, preferred_element_type=jnp.float32)
        if masked:
            lq = lax.rem(lax.broadcasted_iota(jnp.int32, s.shape, 0), bq)
            lk = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if local_tri:
                s = jnp.where(lk <= lq, s, NEG_INF)
            else:
                s = jnp.where(j * block_k + lk <= qi * bq + lq, s, NEG_INF)
        bk = s.shape[1]
        p = jnp.exp2(
            (s.reshape(pack, bq, bk) - lse2[..., None]).reshape(rows, bk))
        dp = jnp.dot(do.astype(v.dtype), v.T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp.reshape(pack, bq, bk)
                  - delta[..., None]).reshape(rows, bk)  # unscaled;
        # the sm_scale rides the matmul operands below
        k_sc = (k.astype(jnp.float32) * sm_scale).astype(k.dtype)
        dv_acc[kslc, :] += jnp.dot(p.astype(do.dtype).T, do,
                                   preferred_element_type=jnp.float32)
        dk_acc[kslc, :] += jnp.dot(ds.astype(q2.dtype).T, q_sc,
                                   preferred_element_type=jnp.float32)
        return dq + jnp.dot(ds.astype(k.dtype), k_sc,
                            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((rows, d), jnp.float32)
    if causal and inline_diag:
        dq = lax.fori_loop(0, qi, functools.partial(body, masked=False), dq0)
        dq = body(qi, dq, masked=True, local_tri=True)
    elif causal:
        upper = lax.div((qi + 1) * bq + block_k - 1, block_k)
        upper = jnp.minimum(upper, nkv)
        dq = lax.fori_loop(0, upper, functools.partial(body, masked=True),
                           dq0)
    else:
        dq = lax.fori_loop(0, nkv, functools.partial(body, masked=False),
                           dq0)
    dq_ref[...] = dq.astype(dq_ref.dtype).reshape(pack, bq, d)

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_fused_pallas(q, k, v, out, lse, g, causal: bool,
                            sm_scale: float,
                            block_q: int | None = None,
                            block_k: int | None = None):
    """Single-kernel backward (see _flash_bwd_fused_kernel). dk/dv come
    back folded to kv heads [B, Hkv, S, D] — the pack-group fold happens
    inside the kernel's accumulation; any remaining rep/pack groups are
    folded here in f32. The f32 accumulation lives in VMEM scratch, not
    HBM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_q, block_k = flash_blocks(block_q, block_k)
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    rep = h // hkv
    block_q = min(block_q, sq)
    inline_diag = causal and sq == skv and sq % block_q == 0
    block_k = block_q if inline_diag else min(block_k, skv)
    pack = _pick_pack(rep)
    grp = b * h // pack
    kv_div = rep // pack
    qf = q.reshape(grp, pack, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)
    dof = g.reshape(grp, pack, sq, d).astype(q.dtype)
    lsef = lse.reshape(grp, pack, sq)
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    deltaf = delta.reshape(grp, pack, sq)

    qspec, kvspec, rowspec = _packed_qspecs(pack, block_q, d, kv_div, skv)
    dkvspec = pl.BlockSpec((None, skv, d), lambda i, j: (i, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel, kv_seq_len=skv,
                          block_k=block_k, sm_scale=sm_scale, causal=causal,
                          inline_diag=inline_diag),
        grid=(grp, sq // block_q),
        in_specs=[qspec, kvspec, kvspec, qspec, rowspec, rowspec],
        out_specs=[qspec, dkvspec, dkvspec],
        out_shape=[
            jax.ShapeDtypeStruct((grp, pack, sq, d), q.dtype),
            jax.ShapeDtypeStruct((grp, skv, d), q.dtype),
            jax.ShapeDtypeStruct((grp, skv, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((skv, d), jnp.float32),
            pltpu.VMEM((skv, d), jnp.float32),
        ],
        compiler_params=_flash_compiler_params(pltpu),
        interpret=_interpret(),
        name="flash_bwd",
    )(qf, kf, vf, dof, lsef, deltaf)
    dq = dq.reshape(b, h, sq, d)
    if kv_div > 1:  # fold the remaining head groups per kv head, in f32
        dk = dk.astype(jnp.float32).reshape(b, hkv, kv_div, skv, d).sum(2)
        dv = dv.astype(jnp.float32).reshape(b, hkv, kv_div, skv, d).sum(2)
    else:
        dk = dk.reshape(b, hkv, skv, d)
        dv = dv.reshape(b, hkv, skv, d)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, g, causal: bool, sm_scale: float,
                      block_q: int | None = None, block_k: int | None = None):
    """GQA-native like the forward: k/v stay [B, Hkv, S, D]; dk/dv come back
    per *query* head [B, H, S, D] (caller folds the group dimension)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_q, block_k = flash_blocks(block_q, block_k)
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    rep = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)
    dof = g.reshape(b * h, sq, d).astype(q.dtype)
    lsef = _rows_3d(lse, b * h, sq)
    # Δ_i = rowsum(dO ∘ O): the softmax-normalization term of ds.
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    deltaf = _rows_3d(delta, b * h, sq)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, kv_seq_len=skv,
                          block_k=block_k, sm_scale=sm_scale, causal=causal,
                          block_q=block_q),
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, skv, d), lambda i, j: (i // rep, 0, 0)),
            pl.BlockSpec((None, skv, d), lambda i, j: (i // rep, 0, 0)),
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, lsef, deltaf)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, q_seq_len=sq,
                          block_q=block_q, sm_scale=sm_scale, causal=causal,
                          block_k=block_k),
        grid=(b * h, skv // block_k),
        in_specs=[
            pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i // rep, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i // rep, j, 0)),
            pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, sq), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, sq), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, skv, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, skv, d), q.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(qf, kf, vf, dof, lsef, deltaf)

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, skv, d),
            dv.reshape(b, h, skv, d))


# ---------------------------------------------------------------------------
# Chunk kernels: flash attention between a LOCAL q block and a VISITING K/V
# chunk whose global positions are runtime values (ring attention rotates
# chunks with lax.ppermute, so offsets are traced axis_index products, not
# Python ints). Causality is data-driven via position-vector inputs, the
# kernel emits (out, lse), and the backward supports an lse cotangent —
# the online cross-chunk combiner differentiates through both.
#
# These deliberately DUPLICATE the static-causal kernels above rather than
# generalize them: the static path's compile-time diagonal skip (upper
# bound on the kv loop) is worth ~2x on long causal self-attention and
# cannot survive runtime positions. Optimization levers landed in one pair
# (ones-column row-sum, scale folding) must be mirrored in the other.


def _flash_chunk_fwd_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref,
                            o_ref, lse_ref, *, kv_seq_len: int, block_k: int,
                            sm_scale: float, causal: bool):
    from jax.experimental import pallas as pl

    q = q_ref[...]
    scale2 = sm_scale * LOG2E
    qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)
    qpos = qpos_ref[0, :]                # [bq] i32, GLOBAL positions
    nkv = kv_seq_len // block_k

    def body(j, carry):
        o, m, l = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jnp.dot(qs, k.T, preferred_element_type=jnp.float32)
        if causal:
            kpos = kpos_ref[0, pl.ds(j * block_k, block_k)]
            s = jnp.where(kpos[None, :] <= qpos[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp2(s - m_new[:, None])
        alpha = jnp.exp2(m - m_new)
        v1 = jnp.concatenate(
            [v, jnp.ones((v.shape[0], 1), v.dtype)], axis=1)
        ov = jnp.dot(p.astype(v.dtype), v1,
                     preferred_element_type=jnp.float32)
        d_ = v.shape[1]
        l_new = l * alpha + lax.slice(ov, (0, d_), (ov.shape[0], d_ + 1))[:, 0]
        o_new = o * alpha[:, None] + lax.slice(ov, (0, 0), (ov.shape[0], d_))
        return o_new, m_new, l_new

    d = q_ref.shape[-1]
    o0 = jnp.zeros((q.shape[0], d), jnp.float32)
    m0 = jnp.full((q.shape[0],), NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0],), jnp.float32)
    # No static diagonal skip: chunk visibility depends on runtime offsets,
    # and visiting chunks are all-visible or all-masked except the one
    # diagonal chunk per ring sweep — a full pass wastes ~(1/2n) of work.
    o, m, l = lax.fori_loop(0, nkv, body, (o0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (o / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, :] = (m + jnp.log2(l)) * LN2


def _flash_chunk_bwd_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, do_ref,
                            lse_ref, delta_ref, glse_ref,
                            dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                            kv_seq_len: int, block_k: int, sm_scale: float,
                            causal: bool):
    """Fused dq/dk/dv for one chunk pair, with the lse-cotangent term:
    ds = p ∘ (dO·vᵀ − Δ + g_lse) — lse depends on s with dlse/ds = p, so
    a cotangent on lse adds a per-row bias inside the p product."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    nq = pl.num_programs(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[...]
    do = do_ref[...]
    lse2 = lse_ref[0, :] * LOG2E
    rowbias = glse_ref[0, :] - delta_ref[0, :]  # (g_lse − Δ) per row
    qpos = qpos_ref[0, :]
    nkv = kv_seq_len // block_k
    scale2 = sm_scale * LOG2E
    qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)
    q_sc = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)

    def body(j, dq):
        kslc = pl.ds(j * block_k, block_k)
        k = k_ref[kslc, :]
        v = v_ref[kslc, :]
        s = jnp.dot(qs, k.T, preferred_element_type=jnp.float32)
        if causal:
            kpos = kpos_ref[0, kslc]
            s = jnp.where(kpos[None, :] <= qpos[:, None], s, NEG_INF)
        p = jnp.exp2(s - lse2[:, None])
        dp = jnp.dot(do.astype(v.dtype), v.T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp + rowbias[:, None])
        k_sc = (k.astype(jnp.float32) * sm_scale).astype(k.dtype)
        dv_acc[kslc, :] += jnp.dot(p.astype(do.dtype).T, do,
                                   preferred_element_type=jnp.float32)
        dk_acc[kslc, :] += jnp.dot(ds.astype(q.dtype).T, q_sc,
                                   preferred_element_type=jnp.float32)
        return dq + jnp.dot(ds.astype(k.dtype), k_sc,
                            preferred_element_type=jnp.float32)

    d = q_ref.shape[-1]
    dq = lax.fori_loop(0, nkv, body,
                       jnp.zeros((q.shape[0], d), jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _chunk_specs(b, h, hkv, sq, skv, d, block_q):
    from jax.experimental import pallas as pl

    rep = h // hkv
    return [
        pl.BlockSpec((None, 1, block_q), lambda i, j: (0, 0, j)),  # qpos
        pl.BlockSpec((None, 1, skv), lambda i, j: (0, 0, 0)),      # kpos
        pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),  # q
        pl.BlockSpec((None, skv, d), lambda i, j: (i // rep, 0, 0)),
        pl.BlockSpec((None, skv, d), lambda i, j: (i // rep, 0, 0)),
    ]


def _chunk_blocks(sq: int, skv: int, block_q: int, block_k: int):
    """POWER-OF-TWO block sizes that DIVIDE the chunk — ring shards can be
    any S/N, a floor-divided grid would silently drop the tail, and Mosaic
    tiling needs 8-aligned blocks (so an unaligned length must fail loudly
    here, not with an opaque TPU compile error)."""

    def pick(n: int, cap: int) -> int:
        b = min(cap, 1 << (n.bit_length() - 1))  # largest pow2 <= n
        while b > 8 and n % b:
            b //= 2
        return b

    block_q = pick(sq, block_q)
    block_k = pick(skv, block_k)
    if block_q < 8 or block_k < 8 or sq % block_q or skv % block_k:
        raise ValueError(
            f"flash_attention_chunk needs seq lengths with a power-of-two "
            f"block divisor >= 8 (got sq={sq}, skv={skv}); pad the ring "
            f"shard length or use impl='einsum'")
    return block_q, block_k


def _flash_chunk_fwd_pallas(q, k, v, qpos, kpos, causal, sm_scale,
                            block_q: int | None = None,
                            block_k: int | None = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_q, block_k = flash_blocks(block_q, block_k)
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    block_q, block_k = _chunk_blocks(sq, skv, block_q, block_k)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)
    qposf = qpos.astype(jnp.int32).reshape(1, 1, sq)
    kposf = kpos.astype(jnp.int32).reshape(1, 1, skv)

    out, lse = pl.pallas_call(
        functools.partial(_flash_chunk_fwd_kernel, kv_seq_len=skv,
                          block_k=block_k, sm_scale=sm_scale, causal=causal),
        grid=(b * h, sq // block_q),
        in_specs=_chunk_specs(b, h, hkv, sq, skv, d, block_q),
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            # f32 out: the cross-chunk log-sum-exp combiner accumulates in
            # f32 and casts ONCE at the end — a bf16 out here would add
            # one rounding per ring step (error growing with ring size).
            jax.ShapeDtypeStruct((b * h, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ],
        compiler_params=_flash_compiler_params(pltpu),
        interpret=_interpret(),
        name="flash_chunk_fwd",
    )(qposf, kposf, qf, kf, vf)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _flash_chunk_bwd_pallas(q, k, v, qpos, kpos, out, lse, g_out, g_lse,
                            causal, sm_scale,
                            block_q: int | None = None,
                            block_k: int | None = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_q, block_k = flash_blocks(block_q, block_k)
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    block_q, block_k = _chunk_blocks(sq, skv, block_q, block_k)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)
    dof = g_out.reshape(b * h, sq, d).astype(q.dtype)
    lsef = _rows_3d(lse, b * h, sq)
    delta = (g_out.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    deltaf = _rows_3d(delta, b * h, sq)
    glsef = _rows_3d(g_lse.astype(jnp.float32), b * h, sq)
    qposf = qpos.astype(jnp.int32).reshape(1, 1, sq)
    kposf = kpos.astype(jnp.int32).reshape(1, 1, skv)

    row = pl.BlockSpec((None, 1, block_q), lambda i, j: (i, 0, j))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_chunk_bwd_kernel, kv_seq_len=skv,
                          block_k=block_k, sm_scale=sm_scale, causal=causal),
        grid=(b * h, sq // block_q),
        in_specs=_chunk_specs(b, h, hkv, sq, skv, d, block_q) + [
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),  # dO
            row, row, row,                                   # lse, Δ, g_lse
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, skv, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, skv, d), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, skv, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, skv, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((skv, d), jnp.float32),
            pltpu.VMEM((skv, d), jnp.float32),
        ],
        compiler_params=_flash_compiler_params(pltpu),
        interpret=_interpret(),
        name="flash_chunk_bwd",
    )(qposf, kposf, qf, kf, vf, dof, lsef, deltaf, glsef)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, skv, d),
            dv.reshape(b, h, skv, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def flash_attention_chunk(q, k, v, qpos, kpos, causal: bool = True,
                          sm_scale: float | None = None):
    """(out, lse) for local q against one visiting K/V chunk, with
    GLOBAL positions supplied as arrays (qpos [Sq], kpos [Skv] — runtime
    values, e.g. ring-step offsets from lax.axis_index). lse is natural-log
    and differentiable, so cross-chunk online combiners (ring attention)
    backprop exactly. GQA-native like flash_attention."""
    return _chunk_fwd(q, k, v, qpos, kpos, causal, sm_scale)[0]


def _chunk_fwd(q, k, v, qpos, kpos, causal, sm_scale):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, lse = _flash_chunk_fwd_pallas(q, k, v, qpos, kpos, causal, scale)
    return (out, lse), (q, k, v, qpos, kpos, out, lse)


def _chunk_bwd(causal, sm_scale, res, cts):
    q, k, v, qpos, kpos, out, lse = res
    g_out, g_lse = cts
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    h, hkv = q.shape[1], k.shape[1]
    dq, dk, dv = _flash_chunk_bwd_pallas(
        q, k, v, qpos, kpos, out, lse, g_out, g_lse, causal, scale)
    if hkv != h:  # GQA fold
        b, _, skv, d = dk.shape
        rep = h // hkv
        dk = dk.astype(jnp.float32).reshape(b, hkv, rep, skv, d).sum(2)
        dv = dv.astype(jnp.float32).reshape(b, hkv, rep, skv, d).sum(2)
    import numpy as _np

    # Integer position inputs carry float0 cotangents (jax's convention
    # for non-differentiable array args under custom_vjp).
    zq = _np.zeros(qpos.shape, dtype=jax.dtypes.float0)
    zk = _np.zeros(kpos.shape, dtype=jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            zq, zk)


flash_attention_chunk.defvjp(_chunk_fwd, _chunk_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: float | None = None, use_pallas: bool = True,
                    kmesh: KernelMesh | None = None):
    """Flash attention: Pallas TPU kernels for forward AND backward
    (dq/dk/dv with p recomputed inside the kernel from the saved lse).

    Runs ``blockwise_attention`` where ops/kernels.py picks the reference
    implementation (off the TPU) or with ``use_pallas=False``. Under a mesh
    of several devices pass its ``kmesh``: the kernels then run per shard,
    batch over the data axes and heads over the head axis.
    """
    return _flash_fwd(q, k, v, causal, sm_scale, use_pallas, kmesh)[0]


def _flash_fwd(q, k, v, causal, sm_scale, use_pallas, kmesh):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if use_pallas and kernel_backend() != "reference":
        fwd = functools.partial(_flash_fwd_pallas, causal=causal,
                                sm_scale=scale)
        if kmesh is not None:
            # GQA: the kv heads split over the head axis like the query
            # heads, so each shard keeps whole query-head groups.
            s4, s3 = kmesh.heads_spec(4), kmesh.heads_spec(3)
            fwd = kmesh.shard(fwd, in_specs=(s4, s4, s4),
                              out_specs=(s4, s3))
        out, lse = fwd(q, k, v)
        out = out.astype(q.dtype)
        # Under jax.checkpoint, a policy that saves 'flash_resid' keeps these
        # residuals across the remat boundary so the backward pass does NOT
        # re-run the forward kernel (see models/llama.py _remat_wrap 'dots').
        out = checkpoint_name(out, "flash_resid")
        lse = checkpoint_name(lse, "flash_resid")
        return out, (q, k, v, out, lse)
    out = blockwise_attention(q, k, v, causal=causal, sm_scale=scale)
    return out, (q, k, v, None, None)


def _flash_bwd_kernels(q, k, v, out, lse, g, *, causal, scale):
    """dq, dk, dv with dk/dv folded to the kv heads."""
    if FUSED_BWD:
        # dk/dv come back already folded to kv heads (pack-group fold
        # inside the kernel, remainder inside the wrapper).
        return _flash_bwd_fused_pallas(q, k, v, out, lse, g, causal, scale)
    dq, dk, dv = _flash_bwd_pallas(q, k, v, out, lse, g, causal, scale)
    h, hkv = q.shape[1], k.shape[1]
    if hkv != h:  # GQA: fold the repeated query-head groups back
        b, _, skv, d = dk.shape
        rep = h // hkv
        dk = dk.astype(jnp.float32).reshape(b, hkv, rep, skv, d).sum(2)
        dv = dv.astype(jnp.float32).reshape(b, hkv, rep, skv, d).sum(2)
    return dq, dk, dv


def _flash_bwd(causal, sm_scale, use_pallas, kmesh, res, g):
    q, k, v, out, lse = res
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if lse is not None:
        bwd = functools.partial(_flash_bwd_kernels, causal=causal,
                                scale=scale)
        if kmesh is not None:
            s4, s3 = kmesh.heads_spec(4), kmesh.heads_spec(3)
            bwd = kmesh.shard(bwd, in_specs=(s4, s4, s4, s4, s3, s4),
                              out_specs=(s4, s4, s4))
        dq, dk, dv = bwd(q, k, v, out, lse, g)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
    # Reference path: differentiate through the blockwise implementation.
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(q_, k_, v_, causal=causal,
                                               sm_scale=sm_scale),
        q, k, v,
    )
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
