"""Rotary position embeddings (RoPE), Llama-3 style with NTK frequency
scaling or YaRN's ramp between kept and interpolated frequencies, and the
adjacent-pair rotation of the latent-attention family.
Pure jnp — XLA fuses the elementwise rotation into the surrounding
projections, so no kernel is needed.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_ramp_bounds(head_dim: int, theta: float, scaling: dict
                     ) -> tuple[int, int]:
    """(low, high): the pair indices between which YaRN's ramp runs. Pair i
    turns ``original_max_position_embeddings * f_i / (2 pi)`` times over the
    trained positions; ``d(n)`` is the (real) index of the pair that turns
    n times. Pairs up to ``floor(d(beta_fast))`` keep their frequency, pairs
    from ``ceil(d(beta_slow))`` on are divided by ``factor``."""
    orig = scaling["original_max_position_embeddings"]

    def d(turns):
        return (head_dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(d(scaling.get("beta_fast", 32))), 0),
            min(math.ceil(d(scaling.get("beta_slow", 1))), head_dim - 1))


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``
    (1 without scaling). A model whose ``rope_scaling`` has
    ``mscale_all_dim`` multiplies its softmax scale by the square of this
    at ``mscale_all_dim``; the factor on cos and sin is the ratio of this
    at ``mscale`` and at ``mscale_all_dim``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(head_dim: int, theta: float = 500000.0,
                     scaling: dict | None = None) -> jnp.ndarray:
    """Inverse frequencies [head_dim/2]. ``scaling`` follows Llama-3:
    {"factor", "low_freq_factor", "high_freq_factor", "original_max_position"},
    or, with ``"type": "yarn"``, YaRN: {"factor", "beta_fast", "beta_slow",
    "original_max_position_embeddings"}, a linear ramp over the pairs'
    indices from the published frequency to that divided by ``factor``.
    """
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling and scaling.get("type") == "yarn":
        low, high = yarn_ramp_bounds(head_dim, theta, scaling)
        ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                        / max(high - low, 0.001), 0.0, 1.0)
        return inv * (1 - ramp) + inv / scaling["factor"] * ramp
    if scaling:
        factor = scaling["factor"]
        low = scaling.get("low_freq_factor", 1.0)
        high = scaling.get("high_freq_factor", 4.0)
        orig = scaling.get("original_max_position", 8192)
        wavelen = 2 * jnp.pi / inv
        ratio = orig / wavelen
        smooth = jnp.clip((ratio - low) / (high - low), 0.0, 1.0)
        inv = jnp.where(
            wavelen > orig / low,  # low-frequency: fully scale
            inv / factor,
            jnp.where(
                wavelen < orig / high,  # high-frequency: keep
                inv,
                (1 - smooth) * inv / factor + smooth * inv,
            ),
        )
    return inv


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               inv_freq: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs. x: [B, H, S, D]; positions: [S] or [B, S] absolute."""
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[:, :, None].astype(jnp.float32) * inv_freq[None, None, :]
    cos = jnp.cos(angles)[:, None, :, :]  # [B, 1, S, D/2]
    sin = jnp.sin(angles)[:, None, :, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                           inv_freq: jnp.ndarray) -> jnp.ndarray:
    """Rotate adjacent pairs (2i, 2i + 1), the convention of the latent
    (MLA) attention family, where :func:`apply_rope` rotates (i, i + D/2).
    x: [B, H, S, D]; positions: [S] or [B, S] absolute. The result keeps the
    interleaved layout, so a dot product of two vectors rotated here equals
    that of the same vectors permuted to halves and rotated there."""
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[:, :, None].astype(jnp.float32) * inv_freq[None, None, :]
    cos = jnp.cos(angles)[:, None, :, :]  # [B, 1, S, D/2]
    sin = jnp.sin(angles)[:, None, :, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def apply_rope_partial(x: jnp.ndarray, positions: jnp.ndarray,
                       inv_freq: jnp.ndarray) -> jnp.ndarray:
    """A rotary that turns part of a head: the first ``2 * len(inv_freq)``
    lanes as :func:`apply_rope` turns a whole head of that size (lane i with
    lane ``i + len(inv_freq)``), the lanes after them left as they are.
    x: [B, H, S, D]; positions: [S] or [B, S] absolute."""
    rot = 2 * inv_freq.shape[0]
    return jnp.concatenate(
        [apply_rope(x[..., :rot], positions, inv_freq), x[..., rot:]],
        axis=-1)
