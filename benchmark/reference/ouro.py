"""Plain reference: a looped decoder (Ouro-2.6B) forward pass.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision
(on a TPU a float32 matmul runs in lower precision without it): no kernels,
no cache, no batching, one layer's weights cast at a time, the whole
sequence through the stack ``total_ut_steps`` times. It follows the model's
own ``modeling_ouro.py`` (huggingface.co/ByteDance/Ouro-2.6B) as the
configuration file's ``assumed`` states it:

    h = E[tokens]
    for t in 0 .. T-1:                    # the same layers, the same weights
        for l in 0 .. L-1:
            h = h + RMSNorm(Attn_l(RMSNorm(h; n1_l)); n2_l)   # sandwich
            h = h + RMSNorm(MLP_l(RMSNorm(h; n3_l)); n4_l)
        h = RMSNorm(h; n_final)           # what pass t+1 starts from
        g_t = sigmoid(w_exit . h + b_exit)
    logits = W_head h                     # of the pass the exit rule picks

Attention is plain multi-head (grouped where the configuration has fewer KV
heads), causal, 1/sqrt(head_dim), rotary on the two halves of each head at
the token's absolute position, and every pass attends to the keys and values
of *that pass* only (computed here from the pass's own input, which is what
a cache line for every (pass, layer) holds). SwiGLU with ``silu``, no
biases in the block, untied head. The exit rule: pass t (1-based) is left
with probability ``p_t = g_t * prod_{j<t}(1 - g_j)``, the last with what
remains; the head reads the first pass whose summed probability reaches
``early_exit_threshold``, the last where none does (at the published
threshold of 1: the last). It shares no code with the program.

Weights come as a dict (see ``adapters/ouro.reference_weights``): matrices
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
    k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(F32(d))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    out = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(s, hq * d) @ w["o"]


def mlp(x, w):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


@functools.partial(jax.jit, static_argnames=("c",))
def _layer(c, x, w):
    cd = dict(c)
    eps = cd["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        a = attention(cd, rms_norm(x, w["attn_norm"], eps), w)
        x = x + rms_norm(a, w["attn_post_norm"], eps)
        m = mlp(rms_norm(x, w["mlp_norm"], eps), w)
        return x + rms_norm(m, w["mlp_post_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _between_passes(x, final_norm, gate_w, gate_b, eps):
    """(the normed state, the gate's g [S])."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm.astype(F32), eps)
        return x, jax.nn.sigmoid(x @ gate_w.astype(F32) + gate_b.astype(F32))


@jax.jit
def _head(x, head):
    with jax.default_matmul_precision("highest"):
        return x @ head.astype(F32)


def _static(c: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "rms_norm_eps")
    return tuple((k, c[k]) for k in keys)


def passes(c: dict, weights: dict, tokens):
    """tokens [S] -> (each pass's normed state, a list of [S, hidden], and
    each pass's gate, a list of [S])."""
    x = weights["embed"][tokens].astype(F32)
    n_layers = weights["layers"]["q"].shape[0]
    states, gates = [], []
    for _ in range(c["total_ut_steps"]):
        for l in range(n_layers):
            w = jax.tree.map(lambda a: a[l], weights["layers"])
            x = _layer(_static(c), x, w)
        x, g = _between_passes(x, weights["final_norm"], weights["gate_w"],
                               weights["gate_b"], c["rms_norm_eps"])
        states.append(x)
        gates.append(g)
    return states, gates


def _distribution(gates: list):
    """[S, T]: p_t = g_t * prod_{j<t}(1 - g_j), the last what remains."""
    left = jnp.ones_like(gates[0])
    ps = []
    for g in gates[:-1]:
        ps.append(g * left)
        left = left * (1.0 - g)
    return jnp.stack(ps + [left], axis=1)


def exit_distribution(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> [S, total_ut_steps] in float32, rows summing to 1."""
    return _distribution(passes(c, weights, tokens)[1])


def logits(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> logits [S, V] in float32, of the pass the exit rule
    picks for each token."""
    states, gates = passes(c, weights, tokens)
    reached = jnp.cumsum(_distribution(gates), axis=1)
    # the first pass that reaches the threshold, the last where none does
    hit = jnp.concatenate(
        [reached[:, :-1] >= c["early_exit_threshold"],
         jnp.ones((reached.shape[0], 1), bool)], axis=1)
    pick = jnp.argmax(hit, axis=1)
    x = jnp.take_along_axis(jnp.stack(states, axis=1),
                            pick[:, None, None], axis=1)[:, 0]
    return _head(x, weights["head"])
