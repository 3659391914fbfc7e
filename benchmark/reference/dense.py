"""Plain reference: a dense decoder (Mistral-7B) forward pass and loss.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision
(on a TPU a float32 matmul runs in lower precision without it): no
kernels, no cache, no batching, one layer's weights cast at a time. It
follows the published description (Hugging Face ``modeling_mistral.py``):
pre-norm residual blocks, RMSNorm with the statistics in float32, rotary
embeddings applied to the two halves of each head (``rotate_half``),
grouped-query causal attention scaled by 1/sqrt(head_dim), SwiGLU, untied
head. It shares no code with the program.

Weights come as a dict (see ``adapters/llama.reference_weights``): matrices
are [in, out]; ``layers`` leaves carry the layer on their leading axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rotary(x, theta):
    """x: [S, heads, D]; position p rotates pair (i, i + D/2) by
    p * theta^(-2i/D)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(c: dict, x, w):
    """x: [S, hidden] (already normed) -> [S, hidden]."""
    s = x.shape[0]
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    q = rotary((x @ w["q"]).reshape(s, hq, d), c["rope_theta"])
    k = rotary((x @ w["k"]).reshape(s, hkv, d), c["rope_theta"])
    v = (x @ w["v"]).reshape(s, hkv, d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = hq // hkv
    outs = []
    for g in range(hkv):  # one KV head and its query heads at a time
        qg = q[:, g * group:(g + 1) * group]                  # [S, G, D]
        scores = jnp.einsum("sgd,td->gst", qg, k[:, g]) / jnp.sqrt(F32(d))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("gst,td->sgd", probs, v[:, g]))
    out = jnp.concatenate(outs, axis=1).reshape(s, hq * d)
    return out @ w["o"]


def mlp(x, w):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


@functools.partial(jax.jit, static_argnames=("c",))
def _layer(c, x, w):
    cd = dict(c)
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        x = x + attention(cd, rms_norm(x, w["attn_norm"], cd["rms_norm_eps"]), w)
        return x + mlp(rms_norm(x, w["mlp_norm"], cd["rms_norm_eps"]), w)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def _static(c: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "rms_norm_eps")
    return tuple((k, c[k]) for k in keys)


def logits(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> logits [S, V] in float32."""
    x = weights["embed"][tokens].astype(F32)
    n_layers = weights["layers"]["q"].shape[0]
    for l in range(n_layers):
        w = jax.tree.map(lambda a: a[l], weights["layers"])
        x = _layer(_static(c), x, w)
    return _head(x, weights["final_norm"], weights["head"],
                 c["rms_norm_eps"])


def loss(c: dict, weights: dict, tokens, targets) -> float:
    """Mean next-token cross-entropy over [B, S], one sequence at a time."""
    total, count = 0.0, 0
    for b in range(tokens.shape[0]):
        lg = logits(c, weights, tokens[b])
        logp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[b][:, None], axis=-1)
        total += float(nll.sum())
        count += int(targets[b].shape[0])
    return total / count
