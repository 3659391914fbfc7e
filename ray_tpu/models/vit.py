"""ViT: vision transformer classifier family, TPU-first.

New work relative to the reference framework (Ray delegates model code to
torch; a TPU-native framework ships its model families — SURVEY.md §2.3
"model family" axis). Same idiom as models/llama.py: stacked-layer params
scanned with lax.scan, logical-axis table consumed by
parallel/sharding.py, flash attention (non-causal) from ops/attention.py
on the MXU, jax.checkpoint remat modes.

Patchify is a reshape (not a conv): [B, H, W, C] -> [B, (H/p)(W/p), p*p*C]
then one matmul — exactly what XLA lowers a stride-p conv to, minus the
conv. Pairs with data.read_images(size=...) for multimodal ingest.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.norms import rms_norm


@dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_channels: int = 3
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    num_classes: int = 1000
    norm_eps: float = 1e-6
    dtype: str = "float32"

    @staticmethod
    def tiny() -> "ViTConfig":
        return ViTConfig(image_size=16, patch_size=4, hidden_size=32,
                         intermediate_size=64, num_layers=2, num_heads=2,
                         num_classes=10)

    @staticmethod
    def base16() -> "ViTConfig":
        return ViTConfig()  # ViT-B/16

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def num_params(self) -> int:
        h, i, L = self.hidden_size, self.intermediate_size, self.num_layers
        patch_in = self.patch_size**2 * self.num_channels
        per_layer = 4 * h * h + 2 * h * i + 2 * h
        return (patch_in * h + (self.num_patches + 1) * h + h
                + L * per_layer + h + h * self.num_classes)


def param_logical_axes(cfg: ViTConfig) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules):
    attention projections shard over heads (tp), MLP over mlp (tp),
    layers stack on the pp-able leading axis — the same table shape the
    generic make_train_step consumes for llama."""
    return {
        "patch_embed": ("patch_in", "embed"),
        "pos_embed": (None, "embed"),
        "cls_token": ("embed",),
        "final_norm": ("embed",),
        "head": ("embed", "classes"),
        "layers": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "heads"),
            "wv": ("layers", "embed", "heads"),
            "wo": ("layers", "heads", "embed"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
            "attn_norm": ("layers", "embed"),
            "mlp_norm": ("layers", "embed"),
        },
    }


def init_params(cfg: ViTConfig, key: jax.Array) -> dict:
    h, L = cfg.hidden_size, cfg.num_layers
    i = cfg.intermediate_size
    patch_in = cfg.patch_size**2 * cfg.num_channels
    dt = cfg.jnp_dtype
    keys = jax.random.split(key, 9)

    def norm_init(k, *shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    return {
        "patch_embed": norm_init(keys[0], patch_in, h),
        "pos_embed": (jax.random.normal(
            keys[1], (cfg.num_patches + 1, h), jnp.float32) * 0.02
        ).astype(dt),
        "cls_token": jnp.zeros((h,), dt),
        "final_norm": jnp.ones((h,), dt),
        "head": norm_init(keys[2], h, cfg.num_classes,
                          scale=1.0 / math.sqrt(h)),
        "layers": {
            "wq": norm_init(keys[3], L, h, h),
            "wk": norm_init(keys[4], L, h, h),
            "wv": norm_init(keys[5], L, h, h),
            "wo": norm_init(keys[6], L, h, h,
                            scale=1.0 / math.sqrt(h * 2 * L)),
            "w_up": norm_init(keys[7], L, h, i),
            "w_down": norm_init(keys[8], L, i, h,
                                scale=1.0 / math.sqrt(i * 2 * L)),
            "attn_norm": jnp.ones((L, h), dt),
            "mlp_norm": jnp.ones((L, h), dt),
        },
    }


def patchify(cfg: ViTConfig, images: jax.Array) -> jax.Array:
    """[B, H, W, C] -> [B, N, p*p*C] patch rows (pure reshape/transpose)."""
    b, hh, ww, c = images.shape
    p = cfg.patch_size
    x = images.reshape(b, hh // p, p, ww // p, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (hh // p) * (ww // p), p * p * c)


def _layer(cfg: ViTConfig, x, lp, attn_impl: str, kmesh=None):
    b, s, h = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim
    xn = rms_norm(x, lp["attn_norm"], cfg.norm_eps, kmesh)
    q = (xn @ lp["wq"]).reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    k = (xn @ lp["wk"]).reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    v = (xn @ lp["wv"]).reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    use_pallas = attn_impl == "flash"
    attn = flash_attention(q, k, v, False, None, use_pallas,
                           kmesh)  # bidirectional
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    x = x + attn @ lp["wo"]
    xn = rms_norm(x, lp["mlp_norm"], cfg.norm_eps, kmesh)
    return x + (jax.nn.gelu(xn @ lp["w_up"]) @ lp["w_down"])


def forward(cfg: ViTConfig, params: dict, images: jax.Array,
            attn_impl: str = "flash", remat: bool | str = False,
            kmesh=None) -> jax.Array:
    """[B, H, W, C] images (float in [0, 1]) -> [B, num_classes] logits.
    ``kmesh``: the caller's mesh for the Pallas kernels (ops/kernels.py)."""
    dt = cfg.jnp_dtype
    x = patchify(cfg, images.astype(dt)) @ params["patch_embed"]
    cls = jnp.broadcast_to(params["cls_token"], (x.shape[0], 1,
                                                 cfg.hidden_size))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"][None]

    # Same remat policy machinery as llama ('dots'/'dots+' save matmul
    # outputs + flash residuals; True/'full' recomputes everything).
    from ray_tpu.models.llama import _remat_wrap

    layer_fn = _remat_wrap(
        partial(_layer, cfg, attn_impl=attn_impl, kmesh=kmesh), remat)

    def scan_body(x, lp):
        return layer_fn(x, lp), None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)
    return (x[:, 0, :] @ params["head"]).astype(jnp.float32)  # cls token


def loss_fn(cfg: ViTConfig, params: dict, images: jax.Array,
            labels: jax.Array, attn_impl: str = "flash",
            remat: bool | str = False, kmesh=None) -> jax.Array:
    logits = forward(cfg, params, images, attn_impl=attn_impl, remat=remat,
                     kmesh=kmesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def make_vit_train_step(*args, **kwargs):
    """Moved to train/spmd.py beside the llama/mixtral factories."""
    from ray_tpu.train.spmd import make_vit_train_step as factory

    return factory(*args, **kwargs)
