"""Rotary position embeddings (RoPE), Llama-3 style with NTK frequency
scaling, and the adjacent-pair rotation of the latent-attention family.
Pure jnp — XLA fuses the elementwise rotation into the surrounding
projections, so no kernel is needed.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float = 500000.0,
                     scaling: dict | None = None) -> jnp.ndarray:
    """Inverse frequencies [head_dim/2]. ``scaling`` follows Llama-3:
    {"factor", "low_freq_factor", "high_freq_factor", "original_max_position"}.
    """
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
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
