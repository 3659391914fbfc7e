"""Plain reference: the DeepSeek-V2 forward pass (latent attention with YaRN
rotary, a leading dense layer, then shared experts beside routed experts
chosen by the group-limited rule).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no batching, no sorting of tokens by expert. It follows
the equations of the published ``modeling_deepseek.py`` (DeepSeek-V2,
arXiv:2405.04434) as ``benchmark/configs/deepseek-v2.json`` states them
under ``assumed``; it shares no code with the program: the grouped rule,
YaRN's frequencies and scale, and the shared experts are written here again.

Layer ``l``, input ``h``, ``N`` an RMSNorm with float32 statistics::

    a   = h + MLA(N(h))             u = N(a)
    out = a + F_l(u)

``F_l`` is a SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` layers; after them ``Shared(u) + sum_e w_e
E_e(u)``, ``Shared`` one SwiGLU of ``n_shared_experts x
moe_intermediate_size``.

``MLA``, un-absorbed, as published: ``c_q = N(x W_qa)``, ``q = c_q W_qb``
split per head into ``q_n`` and ``q_r``; ``[c, k_r] = x W_kva``, ``c_kv =
N(c)``; ``q_r`` and the one shared ``k_r`` rotated by adjacent pairs at
YaRN's inverse frequencies; ``[k_n, v] = c_kv W_kvb`` per head; causal
softmax of ``(q_n . k_n + q_r . k_r) * m^2 / sqrt(Dn + Dr)`` with ``m = 0.1
* mscale_all_dim * ln(factor) + 1``; the heads' outputs concatenated into
``W_o``. YaRN: pair i of Dr/2 has ``f_i = theta^(-2i/Dr)``; ``d(n) = Dr
ln(orig / (2 pi n)) / (2 ln theta)``; ``low = floor(d(beta_fast))``, ``high
= ceil(d(beta_slow))``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``;
``inv_freq_i = f_i (1 - ramp_i) + f_i / factor * ramp_i``. The factor on
cos and sin, ``m(mscale) / m(mscale_all_dim)``, is applied (1 as
published).

The gate: ``s = softmax(u W_g)`` over the routed experts; a group (of
``n_group`` consecutive, equal groups) scores as its largest ``s``; the
``topk_group`` best groups are kept and every other expert's score set to
0; the ``num_experts_per_tok`` largest of what is left are the choice;
weights ``routed_scaling_factor * s`` at the chosen (with ``norm_topk_prob``
instead ``s / (sum + 1e-20)``, unscaled). The share: the configuration says
which routed experts are held (``expert_shard`` of ``expert_shards``); the
others' terms are left out, as in the program (there is no exchange to
bring them); the shared experts are whole.

Departures from a literal transcription, none of which changes a value in
exact arithmetic: weights stay in their stored dtype and are cast a
sub-block at a time; attention runs a group of ``HEAD_BLOCK`` heads at a
time, each head group's outputs multiplied into its own rows of ``W_o`` and
summed, in query blocks of ``QUERY_BLOCK`` (the scores of 16,384 x 16,384 x
128 heads are 137 GB); an expert is applied to every token and weighted by
zero where it was not chosen.

Weights come as a dict (see ``adapters/deepseek.reference_weights``):
matrices are [in, out]; a leaf of ``layers`` is stacked over the layers
that have it, in layer order (attention and norms: all; ``gate``, ``up``,
``down``: the dense layers; ``router``, ``s_*``, ``e_*``: the routed
layers, the experts' next axis the expert).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
HEAD_BLOCK = 16


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def yarn_temperature(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: dict | None):
    """[dim / 2] inverse frequencies and (low, high), the ramp's ends."""
    idx = jnp.arange(0, dim, 2, dtype=F32)
    freq = 1.0 / (theta ** (idx / dim))
    if not rs or rs.get("factor", 1) <= 1:
        return freq, (0, 0)
    orig = rs["original_max_position_embeddings"]

    def pair_that_turns(n):
        return dim * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(rs["beta_slow"])), dim - 1)
    span = (high - low) or 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / span, 0, 1)
    return freq * (1 - ramp) + freq / rs["factor"] * ramp, (low, high)


def rotary_pairs(x, inv_freq, scale: float):
    """x: [S, heads, D]; position p rotates the adjacent pair (2i, 2i + 1)
    by p * inv_freq[i]; cos and sin times ``scale``."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[:, None, :] * scale
    sin = jnp.sin(ang)[:, None, :] * scale
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def _static(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "rms_norm_eps",
            "routed_scaling_factor", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "first_k_dense_replace")
    held = c["n_routed_experts"]
    total = c.get("published", {}).get("n_routed_experts", held)
    shard = c.get("expert_shard", 0)
    if held * c.get("expert_shards", 1) != total:
        raise ValueError(f"{held} experts held x {c.get('expert_shards', 1)}"
                         f" shards is not the model's {total}")
    rs = c.get("rope_scaling")
    return tuple((k, c[k]) for k in keys) + (
        ("rope_scaling", tuple(sorted(rs.items())) if rs else None),
        ("routed_total", total), ("held_from", shard * held))


@functools.partial(jax.jit, static_argnames=("c",))
def _latents(c, x, norm_w, w):
    """x: [S, hidden] -> (c_q [S, q_rank], c_kv [S, rank], k_r [S, Dr]
    rotated): what every head shares."""
    cd = dict(c)
    rank, eps = cd["kv_lora_rank"], cd["rms_norm_eps"]
    rs = dict(cd["rope_scaling"]) if cd["rope_scaling"] else None
    with jax.default_matmul_precision("highest"):
        xn = rms_norm(x, norm_w.astype(F32), eps)
        cq = rms_norm(xn @ w["q_a"].astype(F32), w["q_a_norm"].astype(F32),
                      eps)
        kv = xn @ w["kv_a"].astype(F32)
        ckv = rms_norm(kv[:, :rank], w["kv_a_norm"].astype(F32), eps)
    inv_freq, _ = yarn_inv_freq(cd["qk_rope_head_dim"], cd["rope_theta"], rs)
    k_r = rotary_pairs(kv[:, None, rank:], inv_freq, _rotary_scale(rs))[:, 0]
    return cq, ckv, k_r


def _rotary_scale(rs: dict | None) -> float:
    if not rs:
        return 1.0
    return (yarn_temperature(rs["factor"], rs.get("mscale", 1.0))
            / yarn_temperature(rs["factor"], rs.get("mscale_all_dim", 0.0)))


def softmax_scale(cd: dict) -> float:
    rs = dict(cd["rope_scaling"]) if cd["rope_scaling"] else None
    scale = 1.0 / math.sqrt(cd["qk_nope_head_dim"] + cd["qk_rope_head_dim"])
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_temperature(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


@functools.partial(jax.jit, static_argnames=("c",))
def _heads(c, cq, ckv, k_r, q_b, kv_b, o):
    """The attention of one group of heads, through its rows of ``W_o``:
    q_b [q_rank, G * (Dn + Dr)], kv_b [rank, G * (Dn + Dv)], o [G * Dv,
    hidden] -> [S, hidden]."""
    cd = dict(c)
    dn, dr, dv = (cd["qk_nope_head_dim"], cd["qk_rope_head_dim"],
                  cd["v_head_dim"])
    rs = dict(cd["rope_scaling"]) if cd["rope_scaling"] else None
    s = cq.shape[0]
    g = q_b.shape[1] // (dn + dr)
    inv_freq, _ = yarn_inv_freq(dr, cd["rope_theta"], rs)
    scale = softmax_scale(cd)
    with jax.default_matmul_precision("highest"):
        q = (cq @ q_b.astype(F32)).reshape(s, g, dn + dr)
        q_n = q[..., :dn]
        q_r = rotary_pairs(q[..., dn:], inv_freq, _rotary_scale(rs))
        kv_up = (ckv @ kv_b.astype(F32)).reshape(s, g, dn + dv)
        k_n, v = kv_up[..., :dn], kv_up[..., dn:]
        block = min(QUERY_BLOCK, s)
        if s % block:
            raise ValueError(f"{s} positions are no multiple of {block}")

        def one_block(args):
            qn, qr, q0 = args
            scores = (jnp.einsum("qhd,khd->hqk", qn, k_n)
                      + jnp.einsum("qhd,kd->hqk", qr, k_r)) * scale
            causal = (jnp.arange(s)[None, :]
                      <= (q0 + jnp.arange(block))[:, None])[None]
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        out = jax.lax.map(one_block, (
            q_n.reshape(s // block, block, g, dn),
            q_r.reshape(s // block, block, g, dr),
            jnp.arange(0, s, block)))
        return out.reshape(s, g * dv) @ o.astype(F32)


def attention(c: tuple, x, norm_w, w):
    """x: [S, hidden] -> x + MLA(N(x))."""
    cd = dict(c)
    nh, dn, dr, dv = (cd["num_attention_heads"], cd["qk_nope_head_dim"],
                      cd["qk_rope_head_dim"], cd["v_head_dim"])
    cq, ckv, k_r = _latents(c, x, norm_w, w)
    out = x
    for h0 in range(0, nh, HEAD_BLOCK):
        h1 = min(h0 + HEAD_BLOCK, nh)
        out = out + _heads(
            c, cq, ckv, k_r, w["q_b"][:, h0 * (dn + dr):h1 * (dn + dr)],
            w["kv_b"][:, h0 * (dn + dv):h1 * (dn + dv)],
            w["o"][h0 * dv:h1 * dv])
    return out


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
def gate_weights(c, u, router):
    """[S, routed] float32: the weight of every routed expert for every
    token, 0 where it was not chosen."""
    cd = dict(c)
    groups, keep = cd["n_group"], cd["topk_group"]
    k = cd["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        s = jax.nn.softmax(u @ router.astype(F32), axis=-1)
    n, e = s.shape
    rows = jnp.arange(n)[:, None]
    group_score = s.reshape(n, groups, e // groups).max(axis=-1)
    kept = jnp.argsort(-group_score, axis=-1)[:, :keep]
    group_kept = jnp.zeros((n, groups), bool).at[rows, kept].set(True)
    allowed = jnp.repeat(group_kept, e // groups, axis=1)
    chosen = jnp.argsort(-jnp.where(allowed, s, 0.0), axis=-1)[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cd["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    else:
        picked = picked * cd["routed_scaling_factor"]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def routed_experts(c: tuple, u, w, r: int):
    """The held experts' terms of routed layer ``r`` on u [S, hidden]."""
    lo = dict(c)["held_from"]
    weights = gate_weights(c, u, w["router"][r])
    out = jnp.zeros_like(u)
    for e in range(w["e_gate"].shape[1]):                     # held experts
        y = _swiglu(u, w["e_gate"][r, e], w["e_up"][r, e], w["e_down"][r, e])
        out = out + weights[:, lo + e][:, None] * y
    return out


def shared_experts(u, w, r: int):
    return _swiglu(u, w["s_gate"][r], w["s_up"][r], w["s_down"][r])


def layer(c: tuple, h, w, l: int):
    cd = dict(c)
    eps, dense = cd["rms_norm_eps"], cd["first_k_dense_replace"]
    att = {k: w[k][l] for k in ("q_a", "q_a_norm", "q_b", "kv_a",
                                "kv_a_norm", "kv_b", "o")}
    a = attention(c, h, w["attn_norm"][l], att)
    u = _norm(a, w["post_norm"][l], eps)
    if l < dense:
        return a + _swiglu(u, w["gate"][l], w["up"][l], w["down"][l])
    r = l - dense
    return a + shared_experts(u, w, r) + routed_experts(c, u, w, r)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def logits(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> logits [S, V] in float32."""
    st = _static(c)
    x = weights["embed"][tokens].astype(F32)
    for l in range(weights["layers"]["attn_norm"].shape[0]):
        x = layer(st, x, weights["layers"], l)
    return _head(x, weights["final_norm"], weights["head"],
                 c["rms_norm_eps"])
