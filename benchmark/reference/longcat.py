"""Plain reference: the LongCat-Flash forward pass (latent attention, double
layers with a shortcut-connected expert branch, zero-compute experts).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no batching, no sorting of tokens by expert. It follows
the equations of the published ``modeling_longcat_flash.py`` and technical
report (arXiv:2509.01322) as ``benchmark/configs/longcat-flash-chat.json``
states them under ``assumed``; it shares no code with the program.

One double layer, input ``h``, ``N`` an RMSNorm with float32 statistics::

    a1  = h  + MLA_0(N(h))          u = N(a1)
    m   = MoE(u)                    # the shortcut branch leaves here
    f1  = a1 + FFN_0(u)
    a2  = f1 + MLA_1(N(f1))
    out = a2 + FFN_1(N(a2)) + m     # and rejoins here

``MLA`` is written un-absorbed, as published: ``c_q = N(x W_qa) *
sqrt(hidden / q_lora_rank)``, ``q = c_q W_qb`` split per head into ``q_n``
and ``q_r``; ``[c, k_r] = x W_kva``, ``c_kv = N(c) * sqrt(hidden /
kv_lora_rank)``; ``q_r`` and the one shared ``k_r`` rotated by adjacent
pairs; ``[k_n, v] = c_kv W_kvb`` per head; causal softmax of ``(q_n . k_n +
q_r . k_r) / sqrt(Dn + Dr)``; the heads' outputs concatenated into ``W_o``.

``MoE``: ``p = softmax(u W_r)`` over routed + zero experts, the chosen set
``top_k(p + b)``, weights ``routed_scaling_factor * p`` not renormalised;
a routed expert is a SwiGLU at the expert width, a zero expert the
identity. The share: the configuration says which routed experts are held
(``expert_shard`` of ``expert_shards``); the others' terms are left out,
as in the program (there is no exchange to bring them), and the zero
experts' terms are all kept.

Departures from a literal transcription, none of which changes a value in
exact arithmetic: weights stay in their stored dtype and are cast a
sub-block at a time (an attention, an FFN, one expert: a whole double layer
in float32 does not fit beside them); attention runs in query blocks of
512 (the scores of 8,192 x 8,192 x 64 heads do not fit); an expert is
applied to every token and weighted by zero where it was not chosen.

Weights come as a dict (see ``adapters/longcat.reference_weights``):
matrices are [in, out]; ``layers`` leaves carry a layer on their leading
axis: the sub-layer for an attention's or a dense FFN's (double layer l is
sub-layers 2l and 2l + 1), the double layer for the router's and the
experts', whose next axis is the expert.
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


def rotary_pairs(x, theta):
    """x: [S, heads, D]; position p rotates the adjacent pair (2i, 2i + 1)
    by p * theta^(-2i/D)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def _static(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "rms_norm_eps", "mla_scale_q_lora",
            "mla_scale_kv_lora", "routed_scaling_factor", "moe_topk",
            "zero_expert_num", "n_routed_experts")
    held = c["n_routed_experts"]
    total = c.get("published", {}).get("n_routed_experts", held)
    shard = c.get("expert_shard", 0)
    if held * c.get("expert_shards", 1) != total:
        raise ValueError(f"{held} experts held x {c.get('expert_shards', 1)}"
                         f" shards is not the model's {total}")
    return tuple((k, c[k]) for k in keys) + (
        ("routed_total", total), ("held_from", shard * held))


@functools.partial(jax.jit, static_argnames=("c",))
def _attention(c, x, norm_w, w):
    """x: [S, hidden] -> x + MLA(N(x))."""
    cd = dict(c)
    nh, dn, dr, dv = (cd["num_attention_heads"], cd["qk_nope_head_dim"],
                      cd["qk_rope_head_dim"], cd["v_head_dim"])
    rank, eps = cd["kv_lora_rank"], cd["rms_norm_eps"]
    hidden = cd["hidden_size"]
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        xn = rms_norm(x, norm_w.astype(F32), eps)
        s = xn.shape[0]
        cq = rms_norm(xn @ w["q_a"], w["q_a_norm"], eps)
        if cd["mla_scale_q_lora"]:
            cq = cq * math.sqrt(hidden / cd["q_lora_rank"])
        q = (cq @ w["q_b"]).reshape(s, nh, dn + dr)
        q_n, q_r = q[..., :dn], rotary_pairs(q[..., dn:], cd["rope_theta"])
        kv = xn @ w["kv_a"]
        ckv = rms_norm(kv[:, :rank], w["kv_a_norm"], eps)
        if cd["mla_scale_kv_lora"]:
            ckv = ckv * math.sqrt(hidden / rank)
        k_r = rotary_pairs(kv[:, None, rank:], cd["rope_theta"])[:, 0]
        kv_up = (ckv @ w["kv_b"]).reshape(s, nh, dn + dv)
        k_n, v = kv_up[..., :dn], kv_up[..., dn:]
        outs = []
        for q0 in range(0, s, QUERY_BLOCK):
            q1 = min(q0 + QUERY_BLOCK, s)
            scores = (jnp.einsum("qhd,khd->hqk", q_n[q0:q1], k_n)
                      + jnp.einsum("qhd,kd->hqk", q_r[q0:q1], k_r))
            scores = scores / math.sqrt(dn + dr)
            causal = (jnp.arange(s)[None, :]
                      <= jnp.arange(q0, q1)[:, None])[None]
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
        out = jnp.concatenate(outs, axis=0).reshape(s, nh * dv)
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
    """[S, routed + zero] float32: ``scaling * p`` where an expert was
    chosen, 0 elsewhere."""
    cd = dict(c)
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(u @ router.astype(F32), axis=-1)
    order = jnp.argsort(-(p + bias.astype(F32)), axis=-1)
    chosen = order[:, :cd["moe_topk"]]
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, chosen].set(
        cd["routed_scaling_factor"] * picked)


def moe(c: tuple, u, w, layer: int):
    """The routed layer's share on u [S, hidden]: the held experts' terms
    and every zero expert's. ``w`` is the whole ``layers`` dict."""
    cd = dict(c)
    weights = _route(c, u, w["router"][layer], w["router_bias"][layer])
    total, lo = cd["routed_total"], cd["held_from"]
    out = weights[:, total:].sum(axis=-1, keepdims=True) * u  # identity
    for e in range(w["e_gate"].shape[1]):                     # held experts
        y = _swiglu(u, w["e_gate"][layer, e], w["e_up"][layer, e],
                    w["e_down"][layer, e])
        out = out + weights[:, lo + e][:, None] * y
    return out


def double_layer(c: tuple, h, w, layer: int):
    eps = dict(c)["rms_norm_eps"]

    def att(i):
        return {k: w[k][2 * layer + i] for k in
                ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o")}

    def ffn(x, i):
        return _swiglu(x, w["gate"][2 * layer + i], w["up"][2 * layer + i],
                       w["down"][2 * layer + i])

    def norm(x, name, i):
        return _norm(x, w[name][2 * layer + i], eps)

    a1 = _attention(c, h, w["attn_norm"][2 * layer], att(0))
    u = norm(a1, "post_norm", 0)
    m = moe(c, u, w, layer)
    f1 = a1 + ffn(u, 0)
    a2 = _attention(c, f1, w["attn_norm"][2 * layer + 1], att(1))
    return a2 + ffn(norm(a2, "post_norm", 1), 1) + m


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def logits(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> logits [S, V] in float32."""
    st = _static(c)
    x = weights["embed"][tokens].astype(F32)
    for layer in range(weights["layers"]["router"].shape[0]):
        x = double_layer(st, x, weights["layers"], layer)
    return _head(x, weights["final_norm"], weights["head"],
                 c["rms_norm_eps"])
