"""Attention / norm / rope kernels vs reference implementations.

Pallas kernels run in interpret mode on CPU via pltpu force_tpu_interpret_mode
where exercised; numerical ground truth is the O(S²) reference.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import (
    attention_reference,
    blockwise_attention,
    flash_attention,
)
from ray_tpu.ops.kernels import force_kernel_backend
from ray_tpu.ops.norms import rms_norm_reference
from ray_tpu.ops.ring_attention import ring_attention_sharded
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.parallel.mesh import MeshSpec, build_mesh


def _qkv(b=2, h=4, hkv=None, s=128, d=32, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv or h, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv or h, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_reference(causal):
    q, k, v = _qkv()
    ref = attention_reference(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, kv_block=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blockwise_unaligned_kv_block():
    q, k, v = _qkv(s=96)
    ref = attention_reference(q, k, v)
    out = blockwise_attention(q, k, v, kv_block=40)  # 96 = 2*40 + 16 pad
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_gqa_heads():
    q, k, v = _qkv(h=8, hkv=2)
    ref = attention_reference(q, k, v)
    out = blockwise_attention(q, k, v, kv_block=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_cpu_fallback_and_grad():
    q, k, v = _qkv(s=64)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, False).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_flash_pallas_interpret_matches_reference():
    q, k, v = _qkv(b=1, h=2, s=256, d=64)
    with pltpu.force_tpu_interpret_mode():
        from ray_tpu.ops.attention import _flash_fwd_pallas

        out, lse = _flash_fwd_pallas(q, k, v, causal=True, sm_scale=1.0 / 8.0,
                                     block_q=128, block_k=128)
    ref = attention_reference(q, k, v, causal=True, sm_scale=1.0 / 8.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2,
                               rtol=2e-2)
    # lse must reproduce softmax normalizers: exp(s - lse) rows sum to 1.
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) / 8.0
    mask = np.tril(np.ones((256, 256), bool))
    s = np.where(mask, s, -np.inf)
    ref_lse = np.log(np.exp(s).sum(-1))
    np.testing.assert_allclose(np.asarray(lse), ref_lse, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("h,hkv,causal", [(2, 2, True), (4, 2, True),
                                          (2, 2, False),
                                          # rep=4: pack=4 kernel path + the
                                          # kv_div>1 remainder fold — the
                                          # geometry production Llama uses.
                                          (8, 2, True), (8, 1, False)])
def test_flash_pallas_backward_matches_reference(h, hkv, causal, fused):
    """Gradient equivalence of the Pallas backward kernels (interpret mode)
    against autodiff through attention_reference — incl. the GQA fold —
    for BOTH the fused dq+dkv kernel and the split-kernel fallback."""
    import ray_tpu.ops.attention as attn_mod

    q, k, v = _qkv(b=1, h=h, hkv=hkv, s=256, d=64)
    w = jnp.asarray(
        np.linspace(0.5, 1.5, q.size).reshape(q.shape), jnp.float32)

    def loss(f):
        return lambda q, k, v: (f(q, k, v).astype(jnp.float32) * w).sum()

    old_fused = attn_mod.FUSED_BWD
    attn_mod.FUSED_BWD = fused
    try:
        with force_kernel_backend("interpret"):
            g = jax.grad(loss(lambda q, k, v: flash_attention(
                q, k, v, causal, None, True)), argnums=(0, 1, 2))(q, k, v)
    finally:
        attn_mod.FUSED_BWD = old_fused
    g_ref = jax.grad(loss(lambda q, k, v: attention_reference(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), g, g_ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        denom = max(np.abs(b).max(), 1e-9)
        assert np.abs(a - b).max() / denom < 2e-2, name


def test_ring_attention_matches_reference(cpu_mesh_devices):
    mesh = build_mesh(MeshSpec(sp=8), cpu_mesh_devices)
    q, k, v = _qkv(b=1, h=2, s=256, d=32)
    ref = attention_reference(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, mesh, axis="sp", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_ring_attention_noncausal(cpu_mesh_devices):
    mesh = build_mesh(MeshSpec(sp=4), cpu_mesh_devices[:4])
    q, k, v = _qkv(b=1, h=2, s=64, d=16)
    ref = attention_reference(q, k, v, causal=False)
    out = ring_attention_sharded(q, k, v, mesh, axis="sp", causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_ring_attention_differentiable(cpu_mesh_devices):
    mesh = build_mesh(MeshSpec(sp=4), cpu_mesh_devices[:4])
    q, k, v = _qkv(b=1, h=1, s=64, d=16)

    def ring_loss(q, k, v):
        return ring_attention_sharded(q, k, v, mesh, axis="sp").sum()

    def ref_loss(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_rms_norm_reference_properties():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 5 + 1
    w = jnp.ones(64)
    y = rms_norm_reference(x, w)
    rms = jnp.sqrt(jnp.mean(y * y, axis=-1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, atol=1e-3)


def test_rms_norm_pallas_interpret():
    from ray_tpu.ops.norms import rms_norm_pallas

    x = jax.random.normal(jax.random.PRNGKey(1), (256, 128))
    w = jax.random.normal(jax.random.PRNGKey(2), (128,))
    with pltpu.force_tpu_interpret_mode():
        out = rms_norm_pallas(x, w)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(rms_norm_reference(x, w)), atol=1e-5)


@pytest.mark.parametrize("rows", [8, 424, 1000])
def test_rms_norm_kernel_any_row_count(rows):
    """Row counts that no block divides go through the kernel too (a
    partial last block), never around it: 424 is a prefill chunk clamped to
    the cache tail, 1000 leaves a ragged block after three full ones."""
    from ray_tpu.ops.norms import rms_norm

    x = jax.random.normal(jax.random.PRNGKey(1), (1, rows, 128))
    w = jax.random.normal(jax.random.PRNGKey(2), (128,))
    with force_kernel_backend("interpret"):
        jaxpr = jax.make_jaxpr(rms_norm)(x, w)
        out = rms_norm(x, w)
    assert "pallas_call" in str(jaxpr)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(rms_norm_reference(x, w)), atol=1e-5)


def test_rope_rotation_preserves_norm():
    inv = rope_frequencies(64)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 16, 64))
    out = apply_rope(x, jnp.arange(16), inv)
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(out, axis=-1)),
        np.asarray(jnp.linalg.norm(x, axis=-1)), rtol=1e-5,
    )


def test_rope_relative_property():
    # <rope(q, m), rope(k, n)> depends only on m - n
    inv = rope_frequencies(32)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 32))

    def dot_at(m, n):
        qm = apply_rope(jnp.broadcast_to(q, (1, 1, 1, 32)), jnp.array([m]), inv)
        kn = apply_rope(jnp.broadcast_to(k, (1, 1, 1, 32)), jnp.array([n]), inv)
        return float(jnp.sum(qm * kn))

    assert abs(dot_at(5, 3) - dot_at(12, 10)) < 1e-3


def test_rope_llama3_scaling():
    inv_plain = rope_frequencies(64)
    inv_scaled = rope_frequencies(64, scaling={
        "factor": 8.0, "low_freq_factor": 1.0, "high_freq_factor": 4.0,
        "original_max_position": 8192,
    })
    # low-frequency components shrink; highest frequencies unchanged
    assert np.asarray(inv_scaled)[-1] < np.asarray(inv_plain)[-1]
    np.testing.assert_allclose(np.asarray(inv_scaled)[0],
                               np.asarray(inv_plain)[0])


# ---------------------------------------------------------------------------
# fused cross-entropy (ops/loss.py)
# ---------------------------------------------------------------------------

def _ce_reference(x, head, targets, mask):
    logits = (x.astype(jnp.float32) @ head.astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    m = (jnp.ones_like(nll) if mask is None else mask).astype(jnp.float32)
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_fused_cross_entropy_matches_reference(chunk):
    from ray_tpu.ops.loss import fused_cross_entropy

    key = jax.random.PRNGKey(0)
    b, s, h, v = 2, 16, 8, 32
    x = jax.random.normal(key, (b, s, h), jnp.float32)
    head = jax.random.normal(jax.random.PRNGKey(1), (h, v), jnp.float32) * 0.2
    targets = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, v)

    got = fused_cross_entropy(x, head, targets, None, chunk)
    want = _ce_reference(x, head, targets, None)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fused_cross_entropy_grads_match():
    from ray_tpu.ops.loss import fused_cross_entropy

    key = jax.random.PRNGKey(3)
    b, s, h, v = 2, 8, 8, 24
    x = jax.random.normal(key, (b, s, h), jnp.float32)
    head = jax.random.normal(jax.random.PRNGKey(4), (h, v), jnp.float32) * 0.2
    targets = jax.random.randint(jax.random.PRNGKey(5), (b, s), 0, v)
    mask = (jax.random.uniform(jax.random.PRNGKey(6), (b, s)) > 0.3)

    gx, gh = jax.grad(
        lambda x_, h_: fused_cross_entropy(x_, h_, targets, mask, 4),
        argnums=(0, 1))(x, head)
    rx, rh = jax.grad(
        lambda x_, h_: _ce_reference(x_, h_, targets, mask),
        argnums=(0, 1))(x, head)
    np.testing.assert_allclose(gx, rx, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gh, rh, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("s,chunk", [(13, 4), (7, 512), (24, 7), (17, 17)])
def test_fused_cross_entropy_odd_seq_nondivisible_chunk(s, chunk):
    """s % chunk != 0 falls back to a single chunk (ops/loss.py): the
    forward AND the custom-vjp backward must both take the fallback and
    agree with the reference — the backward recomputes chunk geometry
    independently, so a fwd/bwd disagreement would silently corrupt
    gradients rather than error."""
    from ray_tpu.ops.loss import fused_cross_entropy

    b, h, v = 2, 8, 24
    x = jax.random.normal(jax.random.PRNGKey(7), (b, s, h), jnp.float32)
    head = jax.random.normal(jax.random.PRNGKey(8), (h, v), jnp.float32) * 0.2
    targets = jax.random.randint(jax.random.PRNGKey(9), (b, s), 0, v)
    mask = (jax.random.uniform(jax.random.PRNGKey(10), (b, s)) > 0.25)

    got = fused_cross_entropy(x, head, targets, mask, chunk)
    want = _ce_reference(x, head, targets, mask)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    gx, gh = jax.grad(
        lambda x_, h_: fused_cross_entropy(x_, h_, targets, mask, chunk),
        argnums=(0, 1))(x, head)
    rx, rh = jax.grad(
        lambda x_, h_: _ce_reference(x_, h_, targets, mask),
        argnums=(0, 1))(x, head)
    np.testing.assert_allclose(gx, rx, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gh, rh, rtol=1e-4, atol=1e-5)


def test_fused_cross_entropy_divisible_multichunk_grads():
    """Companion boundary case: s % chunk == 0 with several chunks (the
    scan path, not the fallback) at an odd chunk count."""
    from ray_tpu.ops.loss import fused_cross_entropy

    b, s, h, v, chunk = 2, 15, 8, 24, 5
    x = jax.random.normal(jax.random.PRNGKey(11), (b, s, h), jnp.float32)
    head = jax.random.normal(jax.random.PRNGKey(12), (h, v),
                             jnp.float32) * 0.2
    targets = jax.random.randint(jax.random.PRNGKey(13), (b, s), 0, v)

    gx, gh = jax.grad(
        lambda x_, h_: fused_cross_entropy(x_, h_, targets, None, chunk),
        argnums=(0, 1))(x, head)
    rx, rh = jax.grad(
        lambda x_, h_: _ce_reference(x_, h_, targets, None),
        argnums=(0, 1))(x, head)
    np.testing.assert_allclose(gx, rx, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gh, rh, rtol=1e-4, atol=1e-5)


def test_llama_loss_fused_matches_unfused():
    from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn

    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    fused = loss_fn(cfg, params, tokens, targets, attn_impl="blockwise",
                    remat=False, fused_ce=True)
    plain = loss_fn(cfg, params, tokens, targets, attn_impl="blockwise",
                    remat=False, fused_ce=False)
    np.testing.assert_allclose(fused, plain, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_fused_backward_multiblock(causal):
    """Multi-q-block case (s > block_q): exercises the fused kernel's
    dk/dv revisiting accumulation across the sequential grid dimension
    (the s=256 cases above fit one block and never re-enter)."""
    import ray_tpu.ops.attention as attn_mod

    q, k, v = _qkv(b=1, h=1, hkv=1, s=1024, d=64)

    def loss(f):
        return lambda q, k, v: (f(q, k, v).astype(jnp.float32) ** 2).sum()

    old = attn_mod.FUSED_BWD
    attn_mod.FUSED_BWD = True
    try:
        with force_kernel_backend("interpret"):
            g = jax.grad(loss(lambda q, k, v: flash_attention(
                q, k, v, causal, None, True)), argnums=(0, 1, 2))(q, k, v)
    finally:
        attn_mod.FUSED_BWD = old
    g_ref = jax.grad(loss(lambda q, k, v: attention_reference(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), g, g_ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        denom = max(np.abs(b).max(), 1e-9)
        assert np.abs(a - b).max() / denom < 2e-2, name


def test_kernels_under_a_mesh_match_reference(cpu_mesh_devices):
    """With a KernelMesh the kernels run per shard (batch rows over dp, GQA
    head groups over tp) and must give what the whole arrays give."""
    from ray_tpu.ops.norms import rms_norm
    from ray_tpu.parallel.sharding import kernel_mesh

    mesh = build_mesh(MeshSpec(dp=2, tp=2), cpu_mesh_devices[:4])
    kmesh = kernel_mesh(mesh)
    assert kmesh.batch == ("dp", "fsdp") and kmesh.heads == "tp"
    q, k, v = _qkv(b=2, h=4, hkv=2, s=128, d=32)
    w = jnp.asarray(np.linspace(0.5, 1.5, 32), jnp.float32)
    weight = jnp.asarray(
        np.linspace(0.5, 1.5, q.size).reshape(q.shape), jnp.float32)

    def loss(attn, norm):
        def f(q, k, v, w):
            return (attn(norm(q, w), k, v).astype(jnp.float32)
                    * weight).sum()
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3)))

    with force_kernel_backend("interpret"):
        out, g = loss(
            lambda q, k, v: flash_attention(q, k, v, True, None, True, kmesh),
            lambda x, w: rms_norm(x, w, 1e-6, kmesh))(q, k, v, w)
    ref, g_ref = loss(lambda q, k, v: attention_reference(q, k, v),
                      rms_norm_reference)(q, k, v, w)
    np.testing.assert_allclose(float(out), float(ref), rtol=2e-2)
    for name, a, b in zip("dq dk dv dw".split(), g, g_ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() / np.abs(b).max() < 2e-2, name


class TestRingFlashChunk:
    """Ring attention over the Pallas chunk kernel (flash_attention_chunk:
    data-driven causal positions, differentiable lse) must match the
    reference exactly like the einsum path does. The interpret backend
    runs the real kernel code on CPU."""

    def _with_interpret(self, fn):
        with force_kernel_backend("interpret"):
            return fn()

    def test_forward_matches_reference(self, cpu_mesh_devices):
        mesh = build_mesh(MeshSpec(sp=4), cpu_mesh_devices[:4])
        q, k, v = _qkv(b=1, h=2, s=256, d=32)
        ref = attention_reference(q, k, v, causal=True)
        out = self._with_interpret(lambda: ring_attention_sharded(
            q, k, v, mesh, axis="sp", causal=True, impl="flash"))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=3e-2)

    def test_forward_gqa_noncausal(self, cpu_mesh_devices):
        mesh = build_mesh(MeshSpec(sp=4), cpu_mesh_devices[:4])
        q, k, v = _qkv(b=1, h=4, hkv=2, s=128, d=32)
        ref = attention_reference(q, k, v, causal=False)
        out = self._with_interpret(lambda: ring_attention_sharded(
            q, k, v, mesh, axis="sp", causal=False, impl="flash"))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=3e-2)

    def test_gradients_match_reference(self, cpu_mesh_devices):
        """The cross-chunk (out, lse) combiner backprops through the
        chunk kernel's lse cotangent (ds = p(dp - delta + g_lse))."""
        mesh = build_mesh(MeshSpec(sp=4), cpu_mesh_devices[:4])
        q, k, v = _qkv(b=1, h=2, s=128, d=32)
        w = jnp.asarray(
            np.linspace(0.5, 1.5, q.size).reshape(q.shape), jnp.float32)

        def ring_loss(q, k, v):
            out = ring_attention_sharded(q, k, v, mesh, axis="sp",
                                         causal=True, impl="flash")
            return (out.astype(jnp.float32) * w).sum()

        def ref_loss(q, k, v):
            return (attention_reference(q, k, v, causal=True)
                    .astype(jnp.float32) * w).sum()

        g = self._with_interpret(
            lambda: jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v))
        g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("dq dk dv".split(), g, g_ref):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            denom = max(np.abs(b).max(), 1e-9)
            assert np.abs(a - b).max() / denom < 3e-2, name


# ---------------------------------------------------------- decode attention

DECODE_BLOCK = 128


def _plain_decode_attention(q, kc, vc, layer, lengths, pos0):
    """float32 masked softmax over the full line, K/V repeated per query
    head: what the grouped, length-aware op has to equal."""
    b, h, k, d = q.shape
    hkv, s = kc.shape[2], kc.shape[3]
    kl = jnp.repeat(kc[layer].astype(jnp.float32), h // hkv, axis=1)
    vl = jnp.repeat(vc[layer].astype(jnp.float32), h // hkv, axis=1)
    scores = jnp.einsum("bhkd,bhsd->bhks", q.astype(jnp.float32), kl,
                        precision="highest") / np.sqrt(d)
    kpos = jnp.arange(s)[None, None, :]
    qpos = (pos0[:, None] + jnp.arange(k))[:, :, None]
    visible = ((kpos <= qpos) & (kpos < lengths[:, None, None]))[:, None]
    scores = jnp.where(visible, scores, -jnp.inf)
    top = jnp.max(scores, -1, keepdims=True)
    p = jnp.where(visible, jnp.exp(scores - jnp.where(
        jnp.isfinite(top), top, 0.0)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhks,bhsd->bhkd", p, vl, precision="highest")


def _decode_lengths(pattern: str, s: int, k_tokens: int) -> np.ndarray:
    """Seven slots: every length around a block edge beside an empty slot
    and a full one, or lines that leave the walk nothing, or one line at
    either end of it."""
    return np.array({
        "edges": [0, s, 1, DECODE_BLOCK - 1, DECODE_BLOCK, DECODE_BLOCK + 1,
                  k_tokens],
        "all_empty": [0] * 7,
        "first_only": [DECODE_BLOCK + 1] + [0] * 6,
        "last_only": [0] * 6 + [s]}[pattern], np.int32)


# Every backend, layer and line at the mixed lengths; the patterns that leave
# slots (or every slot) out of the kernel's walk through the kernel's body.
_DECODE_CASES = [
    (k, g, s, layer, backend, "edges")
    for k in (1, 5) for g in (1, 4) for s in (256, 384)  # 384: as 3,200
    for layer in (0, 1) for backend in ("interpret", "reference")
] + [(k, g, 384, 1, "interpret", pattern)
     for k in (1, 5) for g in (1, 4)
     for pattern in ("all_empty", "first_only", "last_only")]


@pytest.mark.parametrize("k_tokens,group,s,layer,backend,pattern",
                         _DECODE_CASES)
def test_decode_attention_matches_plain_softmax(k_tokens, group, s, layer,
                                                backend, pattern):
    from ray_tpu.ops.decode_attention import decode_attention

    layers, hkv, d = 2, 2, 64
    lengths = _decode_lengths(pattern, s, k_tokens)
    b = len(lengths)
    keys = jax.random.split(jax.random.PRNGKey(k_tokens * 7 + group), 3)
    q = jax.random.normal(keys[0], (b, hkv * group, k_tokens, d),
                          jnp.bfloat16)
    live = (np.arange(s)[None, :] < lengths[:, None])[None, :, None, :, None]
    # Rows past the length hold large garbage: a block read by mistake, or
    # the wrong layer of the stack, shows.
    kc = jnp.where(live, jax.random.normal(
        keys[1], (layers, b, hkv, s, d)), 3e4).astype(jnp.bfloat16)
    vc = jnp.where(live, jax.random.normal(
        keys[2], (layers, b, hkv, s, d)), -3e4).astype(jnp.bfloat16)
    pos0 = jnp.asarray(lengths - k_tokens)
    want = _plain_decode_attention(q, kc, vc, layer, jnp.asarray(lengths),
                                   pos0)
    with force_kernel_backend(backend):
        got = decode_attention(q, kc, vc, layer, jnp.asarray(lengths), pos0,
                               block=DECODE_BLOCK)
    assert got.shape == q.shape and got.dtype == q.dtype
    # bf16 probabilities and output: 2^-8 relative on values of order one.
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2)
    # A slot that holds nothing gives zeros, visited or not.
    assert not np.asarray(got, np.float32)[lengths == 0].any()


@pytest.mark.parametrize("lengths", [
    [0, 1, 128, 129, 512],       # empty, one row, a block, a block + 1, whole
    [0, 0, 0, 0, 0],
    [300, 0, 0, 0, 0],
    [0, 0, 0, 0, 300],
    [512, 512, 512, 512, 512],   # the static worst case, no step to spare
    [600, 0, 129, 0, 7],         # past the line's end: clamped to it
], ids=["edges", "all_empty", "first_only", "last_only", "all_whole",
        "clamped"])
def test_decode_plan_is_the_live_blocks_in_slot_order(lengths):
    from ray_tpu.ops.decode_attention import decode_plan

    block, max_seq = 128, 512
    plan = jax.tree.map(np.asarray, decode_plan(
        jnp.asarray(lengths, jnp.int32), block, max_seq))
    blocks_of = [-(-min(n, max_seq) // block) for n in lengths]
    want = [(i, j) for i, n in enumerate(blocks_of) for j in range(n)]
    n_live = int(plan.n_live[0])
    assert plan.n_live.shape == (1,) and n_live == sum(blocks_of)
    steps = len(lengths) * (max_seq // block)
    for field in plan[1:]:
        assert field.shape == (steps,) and field.dtype == np.int32
    assert list(zip(plan.slot[:n_live].tolist(),
                    plan.block[:n_live].tolist())) == want
    assert plan.first[:n_live].tolist() == [int(j == 0) for _, j in want]
    assert plan.last[:n_live].tolist() == [
        int(j == blocks_of[i] - 1) for i, j in want]
    # What lies past the walk starts and ends nothing, and indexes the cache.
    assert not plan.first[n_live:].any() and not plan.last[n_live:].any()
    assert (plan.slot >= 0).all() and (plan.slot < len(lengths)).all()
    assert (plan.block >= 0).all() and (plan.block < max_seq // block).all()


def test_decode_attention_takes_its_callers_plan():
    """A caller with many layers at the same lengths plans once: the result
    is the call's own, and a plan for another block is refused."""
    from ray_tpu.ops.decode_attention import decode_attention, decode_plan

    layers, b, hkv, s, d = 2, 3, 2, 256, 64
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (b, hkv * 4, 1, d), jnp.bfloat16)
    kc = jax.random.normal(keys[1], (layers, b, hkv, s, d), jnp.bfloat16)
    vc = jax.random.normal(keys[2], (layers, b, hkv, s, d), jnp.bfloat16)
    lengths = jnp.asarray([200, 0, 129], jnp.int32)
    pos0 = lengths - 1

    def two_layers(plan):
        return [decode_attention(q, kc, vc, layer, lengths, pos0, plan=plan,
                                 block=DECODE_BLOCK) for layer in range(2)]

    with force_kernel_backend("interpret"):
        own = jax.jit(lambda: two_layers(None))()
        planned = jax.jit(lambda: two_layers(
            decode_plan(lengths, DECODE_BLOCK, s)))()
        for a, c in zip(own, planned):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(c, np.float32))
        with pytest.raises(ValueError, match="a plan of 3 steps"):
            decode_attention(q, kc, vc, 0, lengths, pos0,
                             plan=decode_plan(lengths, s, s),
                             block=DECODE_BLOCK)


def test_decode_attention_plans_each_devices_own_slots_under_a_mesh():
    """Slots over one mesh axis, KV heads over another: the plan is the
    shards' own plans side by side, and each device walks its own."""
    from jax.sharding import Mesh

    from ray_tpu.ops.decode_attention import decode_attention, decode_plan
    from ray_tpu.ops.kernels import KernelMesh

    kmesh = KernelMesh(Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                            ("dp", "tp")), batch=("dp",), heads="tp")
    layers, b, hkv, s, d = 2, 4, 2, 256, 64
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (b, hkv * 4, 1, d), jnp.bfloat16)
    kc = jax.random.normal(keys[1], (layers, b, hkv, s, d), jnp.bfloat16)
    vc = jax.random.normal(keys[2], (layers, b, hkv, s, d), jnp.bfloat16)
    lengths = jnp.asarray([0, 200, 129, 0], jnp.int32)
    pos0 = jnp.maximum(lengths - 1, 0)
    want = _plain_decode_attention(q, kc, vc, 1, lengths, pos0)
    with force_kernel_backend("interpret"):
        plan = jax.jit(lambda n: decode_plan(n, DECODE_BLOCK, s,
                                             kmesh=kmesh))(lengths)
        got = jax.jit(lambda plan: decode_attention(
            q, kc, vc, 1, lengths, pos0, plan=plan, block=DECODE_BLOCK,
            kmesh=kmesh))(plan)
    # Two slots a device, in its own numbering.
    assert plan.n_live.tolist() == [2, 2]
    assert plan.slot[:2].tolist() == [1, 1]
    assert plan.slot[4:6].tolist() == [0, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2)
    assert not np.asarray(got, np.float32)[np.asarray(lengths) == 0].any()


@pytest.mark.parametrize("backend", ["interpret", "reference"])
@pytest.mark.parametrize("k_tokens", [1, 5])
def test_kv_row_write_touches_only_its_rows(k_tokens, backend):
    from ray_tpu.ops.decode_attention import kv_row_write

    layers, hkv, s, d = 2, 2, 64, 64
    # Window edges (16 rows), the line's end, and a masked slot.
    pos = np.array([0, 13, 15, 16, s - k_tokens, 30], np.int32)
    mask = np.array([True, True, True, True, True, False])
    b = len(pos)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    kc = jax.random.normal(keys[0], (layers, b, hkv, s, d), jnp.bfloat16)
    vc = jax.random.normal(keys[1], (layers, b, hkv, s, d), jnp.bfloat16)
    nk = jax.random.normal(keys[2], (b, hkv, k_tokens, d), jnp.bfloat16)
    nv = jax.random.normal(keys[3], (b, hkv, k_tokens, d), jnp.bfloat16)
    want_k, want_v = np.array(kc), np.array(vc)
    for i in range(b):
        if mask[i]:
            want_k[1, i, :, pos[i]:pos[i] + k_tokens] = np.asarray(nk[i])
            want_v[1, i, :, pos[i]:pos[i] + k_tokens] = np.asarray(nv[i])
    with force_kernel_backend(backend):
        got_k, got_v = jax.jit(kv_row_write)(
            kc, vc, nk, nv, 1, jnp.asarray(pos), jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)


def test_decode_kv_block_divides_the_serving_lines():
    from ray_tpu.ops.decode_attention import (
        decode_kv_block,
        kv_positions_read,
    )

    for s in (2048, 3200, 4096, 256, 384):
        block = decode_kv_block(s, 128)
        assert s % block == 0 and block % 128 == 0, (s, block)
    assert decode_kv_block(64, 16, 4) == 64  # no multiple of 128: one block
    got = kv_positions_read(np.array([0, 1, 512, 513, 2048]), 512)
    assert got.tolist() == [0, 512, 512, 1024, 2048]


# --------------------------------------------------------- prefill attention

def _plain_prefill_attention(q, kc, vc, layer, slot, kv_len, length):
    """float32 masked softmax over the slot's full line, K/V repeated per
    query head: what the grouped, length-aware op has to equal."""
    h, c, d = q.shape
    hkv, s = kc.shape[2], kc.shape[3]
    kl = jnp.repeat(kc[layer, slot].astype(jnp.float32), h // hkv, axis=0)
    vl = jnp.repeat(vc[layer, slot].astype(jnp.float32), h // hkv, axis=0)
    scores = jnp.einsum("hcd,hsd->hcs", q.astype(jnp.float32), kl,
                        precision="highest") / np.sqrt(d)
    kpos = jnp.arange(s)[None, :]
    qpos = kv_len + jnp.arange(c)[:, None]
    visible = ((kpos <= qpos) & (kpos < length))[None]
    p = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hcs,hsd->hcd", p, vl, precision="highest")


# Buckets: the smallest, a chunk clamped to the cache's tail (no power of
# two), the largest. Cached rows: none, unaligned (a prefill that starts
# where an adopted prefix ends), several blocks deep.
PREFILL_CASES = [(c, kv) for c in (16, 40, 512) for kv in (0, 37, 300)]


@pytest.mark.parametrize("backend", ["interpret", "reference"])
@pytest.mark.parametrize("pad", [0, 5])  # 5: a padded final chunk
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("chunk,kv_len", PREFILL_CASES)
def test_prefill_attention_matches_plain_softmax(chunk, kv_len, group, pad,
                                                 backend):
    from ray_tpu.ops.prefill_attention import prefill_attention

    layers, slots, hkv, s, d = 2, 3, 2, 1024, 64
    layer, slot, length = 1, 2, kv_len + chunk - pad
    keys = jax.random.split(jax.random.PRNGKey(chunk + kv_len + group), 3)
    q = jax.random.normal(keys[0], (hkv * group, chunk, d), jnp.bfloat16)
    # Every row but the prompt's own in its own layer and slot holds large
    # garbage: a block read by mistake, or the wrong line, shows.
    live = np.zeros((layers, slots, 1, s, 1), bool)
    live[layer, slot, :, :length] = True
    kc = jnp.where(live, jax.random.normal(
        keys[1], (layers, slots, hkv, s, d)), 3e4).astype(jnp.bfloat16)
    vc = jnp.where(live, jax.random.normal(
        keys[2], (layers, slots, hkv, s, d)), -3e4).astype(jnp.bfloat16)
    want = _plain_prefill_attention(q, kc, vc, layer, slot, kv_len, length)
    with force_kernel_backend(backend):
        got = jax.jit(partial(prefill_attention, block_k=DECODE_BLOCK))(
            q, kc, vc, layer, slot, kv_len, length)
    assert got.shape == q.shape and got.dtype == q.dtype
    # bf16 probabilities and output: 2^-8 relative on values of order one.
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2)


@pytest.mark.parametrize("backend", ["interpret", "reference"])
def test_prefill_attention_tiles_of_the_chunk_agree(backend):
    """Several tiles of queries (the serving shape has two of 256 tokens),
    each with its own last live block, against one tile of the whole
    chunk."""
    from ray_tpu.ops.prefill_attention import (
        prefill_attention,
        prefill_q_block,
    )

    assert prefill_q_block(512, 4) == 256 and prefill_q_block(40, 4) == 48
    assert prefill_q_block(16, 1) == 16 and prefill_q_block(424, 4) == 256
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (8, 128, 64), jnp.bfloat16)
    kc = jax.random.normal(keys[1], (1, 2, 2, 512, 64), jnp.bfloat16)
    vc = jax.random.normal(keys[2], (1, 2, 2, 512, 64), jnp.bfloat16)
    with force_kernel_backend(backend):
        one, four = (prefill_attention(q, kc, vc, 0, 1, 201, 329,
                                       block_q=bq, block_k=DECODE_BLOCK)
                     for bq in (128, 32))
    np.testing.assert_allclose(np.asarray(one, np.float32),
                               np.asarray(four, np.float32), atol=2e-2)


@pytest.mark.parametrize("chunk,kv_len", PREFILL_CASES)
def test_prefill_kv_write_touches_only_its_rows(chunk, kv_len):
    from ray_tpu.ops.prefill_attention import prefill_kv_write

    layers, slots, hkv, s, d = 2, 3, 2, 1024, 64
    keys = jax.random.split(jax.random.PRNGKey(chunk), 4)
    kc = jax.random.normal(keys[0], (layers, slots, hkv, s, d), jnp.bfloat16)
    vc = jax.random.normal(keys[1], (layers, slots, hkv, s, d), jnp.bfloat16)
    nk = jax.random.normal(keys[2], (hkv, chunk, d), jnp.bfloat16)
    nv = jax.random.normal(keys[3], (hkv, chunk, d), jnp.bfloat16)
    want_k, want_v = np.array(kc), np.array(vc)
    want_k[1, 2, :, kv_len:kv_len + chunk] = np.asarray(nk)
    want_v[1, 2, :, kv_len:kv_len + chunk] = np.asarray(nv)
    got_k, got_v = jax.jit(prefill_kv_write)(kc, vc, nk, nv, 1, 2, kv_len)
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)


def test_prefill_attention_under_a_mesh_runs_on_each_shards_heads(
        cpu_mesh_devices):
    """tensor_parallel_size > 1 shards the KV heads: the kernel runs per
    shard on its heads of the stack and gives what the whole arrays give."""
    from ray_tpu.ops.prefill_attention import prefill_attention
    from ray_tpu.parallel.sharding import kernel_mesh

    kmesh = kernel_mesh(build_mesh(MeshSpec(tp=2), cpu_mesh_devices[:2]))
    assert kmesh.heads == "tp"
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (8, 40, 64), jnp.bfloat16)
    kc = jax.random.normal(keys[1], (2, 2, 4, 256, 64), jnp.bfloat16)
    vc = jax.random.normal(keys[2], (2, 2, 4, 256, 64), jnp.bfloat16)
    want = _plain_prefill_attention(q, kc, vc, 1, 1, 100, 137)
    with force_kernel_backend("interpret"):
        got = jax.jit(partial(prefill_attention, kmesh=kmesh))(
            q, kc, vc, 1, 1, 100, 137)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2)


# ------------------------------------------------- packed stacks (heads of 64)

def _packed_case(seed, layers, slots, hkv, s, d):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    kc = jax.random.normal(keys[0], (layers, slots, hkv, s, d), jnp.bfloat16)
    vc = jax.random.normal(keys[1], (layers, slots, hkv, s, d), jnp.bfloat16)
    return kc, vc, jnp.concatenate([kc, vc], axis=-1)


@pytest.mark.parametrize("backend", ["interpret", "reference"])
@pytest.mark.parametrize("k_tokens,group", [(1, 4), (5, 1)])
def test_decode_attention_reads_a_packed_stack_as_keys_and_values(
        k_tokens, group, backend):
    """Keys and values of a head side by side in one row of 128 (a head of
    64 alone fills half a lane row) give what the two stacks give."""
    from ray_tpu.ops.decode_attention import decode_attention

    hkv, s, d = 2, 384, 64
    lengths = _decode_lengths("edges", s, k_tokens)
    kc, vc, packed = _packed_case(11, 2, len(lengths), hkv, s, d)
    q = jax.random.normal(jax.random.PRNGKey(12),
                          (len(lengths), hkv * group, k_tokens, d),
                          jnp.bfloat16)
    pos0 = jnp.asarray(lengths - k_tokens)
    want = _plain_decode_attention(q, kc, vc, 1, jnp.asarray(lengths), pos0)
    with force_kernel_backend(backend):
        got = decode_attention(q, packed, None, 1, jnp.asarray(lengths), pos0,
                               block=DECODE_BLOCK)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2)
    assert not np.asarray(got, np.float32)[lengths == 0].any()


def _softmax_rows(q, kc, vc, lengths, limits, sink=None):
    """float64 softmax a row over one layer's lines: row (b, h, j) sees the
    keys at positions ``<= limits[b, j]`` and ``< lengths[b]``; ``sink``
    [H] joins each head's denominator with no row of values."""
    b, h, k, d = q.shape
    hkv, s = kc.shape[1], kc.shape[2]
    q, kc, vc = (np.asarray(a, np.float64) for a in (q, kc, vc))
    kl, vl = (np.repeat(a, h // hkv, axis=1) for a in (kc, vc))
    scores = np.einsum("bhkd,bhsd->bhks", q, kl) / np.sqrt(d)
    kpos = np.arange(s)[None, None, :]
    visible = ((kpos <= limits[:, :, None])
               & (kpos < lengths[:, None, None]))[:, None]
    scores = np.where(visible, scores, -np.inf)
    top = scores.max(-1, keepdims=True)
    if sink is not None:
        sunk = np.asarray(sink, np.float64)[None, :, None, None]
        top = np.maximum(top, sunk)
    top = np.where(np.isfinite(top), top, 0.0)
    p = np.where(visible, np.exp(scores - top), 0.0)
    denom = p.sum(-1, keepdims=True)
    if sink is not None:
        denom = denom + np.exp(sunk - top)
    return np.einsum("bhks,bhsd->bhkd", p / np.maximum(denom, 1e-30), vl)


@pytest.mark.parametrize("backend", ["interpret", "reference"])
@pytest.mark.parametrize("stack", ["two_stacks", "packed", "sink"])
def test_decode_attention_s_rows_of_a_block_share_its_limit(stack, backend):
    """``rows_a_limit``: 8 new rows a line in two blocks of 4, the mask's
    position at the first block's last. The first 4 rows see the line up to
    the second block's start, the last 4 through its end (the line's
    length), whatever a row's place in its block; lines that end inside a
    block of the walk, at its edge and one past it, a line of the 8 rows
    alone, an empty slot; two stacks, the packed one, and a sink. The
    kernel's body gives what the reference gives, and both what a dense
    softmax under that mask does. At 1 (the default) the rows' limits are
    a row apart, as ever."""
    from ray_tpu.ops.decode_attention import (
        decode_attention,
        decode_attention_reference,
    )

    hkv, group, s, d, k, g = 2, 4, 384, 64, 8, 4
    lengths = np.array([0, 8, 127, 128, 130, 133, 200, s], np.int32)
    b = len(lengths)
    kc, vc, packed = _packed_case(21, 2, b, hkv, s, d)
    live = (np.arange(s)[None, :] < lengths[:, None])[None, :, None, :, None]
    # Rows past a line's length hold garbage a wrong limit would show.
    kc, vc = (jnp.where(live, a, fill).astype(jnp.bfloat16)
              for a, fill in ((kc, 3e4), (vc, -3e4)))
    if stack == "packed":
        caches = (jnp.concatenate([kc, vc], axis=-1), None)
    else:
        caches = (kc, vc)
    keys = jax.random.split(jax.random.PRNGKey(22), 2)
    q = jax.random.normal(keys[0], (b, hkv * group, k, d), jnp.bfloat16)
    sink = (2.0 * jax.random.normal(keys[1], (hkv * group,))
            if stack == "sink" else None)
    start = lengths - k
    pos0 = jnp.asarray(start + (g - 1))
    limits = start[:, None] + (g - 1) + np.arange(k)[None, :] // g * g
    want = _softmax_rows(q, kc[1], vc[1], lengths, limits, sink)
    with force_kernel_backend(backend):
        got = decode_attention(q, *caches, 1, jnp.asarray(lengths), pos0,
                               block=DECODE_BLOCK, sink=sink, rows_a_limit=g)
        one = decode_attention(q, *caches, 1, jnp.asarray(lengths), pos0,
                               block=DECODE_BLOCK, sink=sink, rows_a_limit=1)
        default = decode_attention(q, *caches, 1, jnp.asarray(lengths), pos0,
                                   block=DECODE_BLOCK, sink=sink)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=3e-2)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(decode_attention_reference(
            q, *caches, 1, jnp.asarray(lengths), pos0, sink=sink,
            rows_a_limit=g), np.float32), atol=3e-2)
    assert not np.asarray(got, np.float32)[0].any()
    # the limit a row apart, bit for bit the call that names no grouping;
    # rows 1 to 3 of the first block then see keys of the second
    np.testing.assert_array_equal(np.asarray(one), np.asarray(default))
    np.testing.assert_allclose(
        np.asarray(default, np.float32),
        _softmax_rows(q, kc[1], vc[1], lengths,
                      start[:, None] + (g - 1) + np.arange(k)[None, :], sink),
        atol=3e-2)
    assert np.abs(np.asarray(default, np.float32)[1:, :, 1:g]
                  - np.asarray(got, np.float32)[1:, :, 1:g]).max() > 0.1
    with pytest.raises(ValueError, match="does not divide"):
        decode_attention(q, *caches, 1, jnp.asarray(lengths), pos0,
                         rows_a_limit=3)


@pytest.mark.parametrize("backend", ["interpret", "reference"])
@pytest.mark.parametrize("k_tokens", [1, 5])
def test_kv_row_write_packs_a_heads_key_and_value_into_one_row(k_tokens,
                                                                backend):
    from ray_tpu.ops.decode_attention import kv_row_write

    hkv, s, d = 2, 64, 64
    pos = np.array([0, 13, 15, 16, s - k_tokens, 30], np.int32)
    mask = np.array([True, True, True, True, True, False])
    _, _, packed = _packed_case(13, 2, len(pos), hkv, s, d)
    keys = jax.random.split(jax.random.PRNGKey(14), 2)
    nk = jax.random.normal(keys[0], (len(pos), hkv, k_tokens, d),
                           jnp.bfloat16)
    nv = jax.random.normal(keys[1], (len(pos), hkv, k_tokens, d),
                           jnp.bfloat16)
    want = np.array(packed)
    for i in range(len(pos)):
        if mask[i]:
            want[1, i, :, pos[i]:pos[i] + k_tokens, :d] = np.asarray(nk[i])
            want[1, i, :, pos[i]:pos[i] + k_tokens, d:] = np.asarray(nv[i])
    with force_kernel_backend(backend):
        got, none = jax.jit(kv_row_write)(
            packed, None, nk, nv, 1, jnp.asarray(pos), jnp.asarray(mask))
    assert none is None
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("backend", ["interpret", "reference"])
@pytest.mark.parametrize("chunk,kv_len,pad", [(16, 0, 0), (40, 37, 5),
                                              (512, 300, 0)])
def test_prefill_attention_reads_a_packed_stack_as_keys_and_values(
        chunk, kv_len, pad, backend):
    from ray_tpu.ops.prefill_attention import (
        prefill_attention,
        prefill_kv_write,
    )

    hkv, group, s, d = 2, 4, 1024, 64
    length = kv_len + chunk - pad
    kc, vc, packed = _packed_case(15, 2, 3, hkv, s, d)
    keys = jax.random.split(jax.random.PRNGKey(chunk), 3)
    q = jax.random.normal(keys[0], (hkv * group, chunk, d), jnp.bfloat16)
    nk = jax.random.normal(keys[1], (hkv, chunk, d), jnp.bfloat16)
    nv = jax.random.normal(keys[2], (hkv, chunk, d), jnp.bfloat16)
    kc, vc = prefill_kv_write(kc, vc, nk, nv, 1, 2, kv_len)
    packed, none = jax.jit(prefill_kv_write)(packed, None, nk, nv, 1, 2,
                                             kv_len)
    assert none is None
    np.testing.assert_array_equal(
        np.asarray(packed), np.asarray(jnp.concatenate([kc, vc], axis=-1)))
    want = _plain_prefill_attention(q, kc, vc, 1, 2, kv_len, length)
    with force_kernel_backend(backend):
        got = jax.jit(partial(prefill_attention, block_k=DECODE_BLOCK))(
            q, packed, None, 1, 2, kv_len, length)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2)
