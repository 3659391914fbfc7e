"""Plain reference: the LFM2-MoE forward pass (gated short convolutions
beside a few attentions, dense SwiGLUs in the leading layers, routed
experts in the rest).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no state, no batching, no sorting of tokens by expert. It
follows the equations of the family's ``modeling_lfm2_moe.py`` as
``benchmark/configs/lfm2-24b-a2b.json`` states them under ``assumed``; it
shares no code with the program and is never given the program's choices.

One layer, input ``h``, ``N`` an RMSNorm with float32 statistics::

    h = h + op(N(h; operator_norm))        # by layer_types[l]
    h = h + ffn(N(h; ffn_norm))            # dense for l < num_dense_layers

``conv``: ``[B, C, x] = split3(u W_in)``; ``z = B * x``; ``v_t = w[0]
z_{t-2} + w[1] z_{t-1} + w[2] z_t`` with ``z`` zero before position 0,
written as three shifted products over the whole sequence; ``y = (C * v)
W_out``. ``full_attention``: ``q, k, v`` without bias; an RMSNorm over each
head's values of ``q`` and of ``k`` before the rotation of the two halves
(``rotate_half``); causal softmax of ``q . k / sqrt(head_dim)``, query head
h on KV head ``h // group``; the heads' outputs concatenated into ``W_o``.
Routed ``ffn``: ``s = sigmoid(u W_g)``, the chosen set ``top_k(s + b)``,
weights ``s`` at the chosen divided by ``(their sum + 1e-6)`` and times
``routed_scaling_factor``; an expert is a SwiGLU at the expert width. After
the last layer ``N(h; embedding_norm)`` and the head, which is the
embedding's transpose unless the weights bring a ``head`` of their own.

Departures from a literal transcription, none of which changes a value in
exact arithmetic: weights stay in their stored dtype and are cast a
sub-block at a time (an operator, a dense SwiGLU, one expert: 9.8 GiB in
float32 do not fit beside themselves); attention runs in query blocks of
512 (the scores of 8,192 x 8,192 x 32 heads do not fit); an expert is
applied to every token and weighted by zero where it was not chosen.

Weights come as a dict (see ``adapters/lfm2.reference_weights``): matrices
are [in, out]; ``layers`` leaves are stacked over the layers that have
them, in layer order: the two norms over all layers, the convolution's over
the ``conv`` layers, the attention's over the ``full_attention`` layers,
the dense SwiGLU's over the dense layers, the router's and the experts'
over the routed layers (the experts' next axis is the expert).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rotary_halves(x, theta):
    """x: [S, heads, D]; position p rotates the pair (i, i + D/2) by
    p * theta^(-2i/D)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _static(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "norm_eps", "conv_L_cache", "num_experts", "num_experts_per_tok",
            "use_expert_bias", "norm_topk_prob", "routed_scaling_factor")
    if c["conv_bias"]:
        raise ValueError("conv_bias: the reference has no bias to add")
    return tuple((k, c[k]) for k in keys) + (
        ("rope_theta", float(c["rope_parameters"]["rope_theta"])),)


@functools.partial(jax.jit, static_argnames=("c",))
def _conv(c, x, norm_w, w):
    """x: [S, hidden] -> x + ShortConv(N(x))."""
    cd = dict(c)
    taps = cd["conv_L_cache"]
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        u = rms_norm(x, norm_w.astype(F32), cd["norm_eps"])
        gate_in, gate_out, xs = jnp.split(u @ w["in"], 3, axis=-1)
        z = gate_in * xs
        s = z.shape[0]
        # Tap j meets z shifted down by (taps - 1 - j) positions.
        v = sum(w["taps"][j] * jnp.pad(z, ((taps - 1 - j, 0), (0, 0)))[:s]
                for j in range(taps))
        return x + (gate_out * v) @ w["out"]


@functools.partial(jax.jit, static_argnames=("c",))
def _attention(c, x, norm_w, w):
    """x: [S, hidden] -> x + Attention(N(x))."""
    cd = dict(c)
    nh, nkv, eps = (cd["num_attention_heads"], cd["num_key_value_heads"],
                    cd["norm_eps"])
    d = cd["hidden_size"] // nh
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        u = rms_norm(x, norm_w.astype(F32), eps)
        s = u.shape[0]
        q = rms_norm((u @ w["q"]).reshape(s, nh, d), w["q_norm"], eps)
        k = rms_norm((u @ w["k"]).reshape(s, nkv, d), w["k_norm"], eps)
        v = (u @ w["v"]).reshape(s, nkv, d)
        q = rotary_halves(q, cd["rope_theta"])
        k = rotary_halves(k, cd["rope_theta"])
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        outs = []
        for q0 in range(0, s, QUERY_BLOCK):
            q1 = min(q0 + QUERY_BLOCK, s)
            scores = jnp.einsum("qhd,khd->hqk", q[q0:q1], k) / math.sqrt(d)
            causal = (jnp.arange(s)[None, :]
                      <= jnp.arange(q0, q1)[:, None])[None]
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
        out = jnp.concatenate(outs, axis=0).reshape(s, nh * d)
        return x + out @ w["o"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rms_norm(x, w.astype(F32), eps)


@jax.jit
def _swiglu(x, gate, up, down):
    """x: [S, in] (already normed) -> [S, in]."""
    with jax.default_matmul_precision("highest"):
        gate, up, down = (a.astype(F32) for a in (gate, up, down))
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnames=("c",))
def _route(c, u, router, bias):
    """[S, experts] float32: an expert's weight where it was chosen, 0
    elsewhere."""
    cd = dict(c)
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(u @ router.astype(F32))
    by = s + bias.astype(F32) if cd["use_expert_bias"] else s
    chosen = jnp.argsort(-by, axis=-1)[:, :cd["num_experts_per_tok"]]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cd["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-6)
    picked = picked * cd["routed_scaling_factor"]
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def moe(c: tuple, u, w, layer: int):
    """The routed layer on u [S, hidden]; ``layer`` counts routed layers."""
    weights = _route(c, u, w["router"][layer], w["expert_bias"][layer])
    out = jnp.zeros_like(u)
    for e in range(w["e_gate"].shape[1]):
        y = _swiglu(u, w["e_gate"][layer, e], w["e_up"][layer, e],
                    w["e_down"][layer, e])
        out = out + weights[:, e][:, None] * y
    return out


@functools.partial(jax.jit, static_argnames=("eps", "tied"))
def _head(x, final_norm, head, eps, tied):
    """``head`` is [hidden, vocab], or the embedding [vocab, hidden] when
    the two are ``tied``."""
    with jax.default_matmul_precision("highest"):
        head = head.astype(F32).T if tied else head.astype(F32)
        return rms_norm(x, final_norm.astype(F32), eps) @ head


def logits(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> logits [S, V] in float32."""
    st = _static(c)
    w = weights["layers"]
    eps = c["norm_eps"]
    x = weights["embed"][tokens].astype(F32)
    convs = attentions = 0
    for layer, kind in enumerate(c["layer_types"]):
        if kind == "conv":
            x = _conv(st, x, w["operator_norm"][layer],
                      {"in": w["conv_in"][convs], "taps": w["conv_w"][convs],
                       "out": w["conv_out"][convs]})
            convs += 1
        elif kind == "full_attention":
            x = _attention(st, x, w["operator_norm"][layer],
                           {k: w[k][attentions] for k in
                            ("q", "k", "v", "o", "q_norm", "k_norm")})
            attentions += 1
        else:
            raise ValueError(f"layer_types[{layer}] = {kind!r}")
        u = _norm(x, w["ffn_norm"][layer], eps)
        if layer < c["num_dense_layers"]:
            x = x + _swiglu(u, w["gate"][layer], w["up"][layer],
                            w["down"][layer])
        else:
            x = x + moe(st, u, w, layer - c["num_dense_layers"])
    tied = "head" not in weights
    return _head(x, weights["final_norm"],
                 weights["embed" if tied else "head"], eps, tied)
