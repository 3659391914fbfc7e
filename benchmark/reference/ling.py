"""Plain reference: the forward pass of Ling-3.0-flash-VL's language model
(Kimi Delta Attention layers, a gated latent attention every
``layer_group_size`` layers, two leading dense layers, then a shared expert
beside routed experts chosen by a grouped rule with a selection bias).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no state handed between calls, no batching, no sorting
of tokens by expert, and the delta rule as the token-by-token recurrence (a
``lax.scan`` over the positions), never the chunked form the program runs.
It follows the equations of the Kimi Linear report (arXiv 2510.26692), of
``fla``'s ``KimiDeltaAttention`` and of the family's
``modeling_bailing_moe_v2.py`` as ``benchmark/configs/ling-3.0-flash-vl.json``
states them under ``assumed``; it shares no code with the program and is
never given the program's choices.

Layer ``l``, input ``h``, ``N(x; w) = x rsqrt(mean x^2 + eps) w``::

    a  = h + Mix_l(N(h))
    h' = a + F_l(N(a))

``Mix_l`` is the latent attention where ``(l + 1) % layer_group_size == 0``
and Kimi Delta Attention (KDA) otherwise.

KDA, ``heads`` heads of ``D`` for keys and values alike, no bias: ``x W_q``,
``x W_k``, ``x W_v`` each pass a depthwise causal convolution (``[channels,
taps]``, zeros before position 0: ``y_t = sum_j w[:, j] x_{t - taps + 1 +
j}``) and ``silu``; ``q`` and ``k`` are L2-normalised a head (``x rsqrt(sum
x^2 + 1e-6)``), ``q`` scaled by ``D^-1/2``; no rotary. ``beta = sigmoid(x
W_b)`` a head. ``g = kda_lower_bound sigmoid(exp(A_log[head]) (x W_f +
dt_bias))``: a number in ``(kda_lower_bound, 0)`` a head, key channel and
token. A head, from ``S_0 = 0`` (``S`` is D keys x D values)::

    S' = Diag(exp(g_t)) S_{t-1};  d_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t d_t^T;         o_t = S_t^T q_t

The output a head is ``w o rsqrt(mean o^2 + eps) sigmoid(x W_z)`` (the norm
first, then the gate a channel), the heads side by side through ``W_o``.

Latent attention, no low-rank query: ``q = x W_q`` split a head into
``q_n`` and ``q_r``; ``[c, k_r] = x W_kva``, ``c_kv = N(c)``; ``q_r`` and
the one shared ``k_r`` rotated a half against the other (pair ``(i, i +
Dr/2)`` by ``p theta^(-2i/Dr)``), unscaled; ``[k_n, v] = c_kv W_kvb`` a
head; causal softmax of ``(q_n . k_n + q_r . k_r) / sqrt(Dn + Dr)``; a
head's output times ``sigmoid(x W_a)[head]``; the heads through ``W_o``.

``F_l`` is a SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` layers; after them ``Shared(u) + sum_e w_e
E_e(u)``. The gate: ``s = sigmoid(u W_g)`` over the routed experts, ``c = s
+ b``; a group (of ``n_group`` consecutive, equal groups) scores as the sum
of its two largest ``c``; the ``topk_group`` best groups are kept and every
other expert is out of the choice; the ``num_experts_per_tok`` largest
``c`` left are the picks; their weights are ``s`` at the picks, divided by
their sum + 1e-20 (``norm_topk_prob``), times ``routed_scaling_factor``.
The share: the configuration says which routed experts are held
(``expert_shard`` of ``expert_shards``); the others' terms are left out, as
in the program (there is no exchange to bring them); the shared expert is
whole. After the last layer ``N`` and an untied head.

Departures from a literal transcription, none of which changes a value in
exact arithmetic: weights stay in their stored dtype and are cast a
sub-block at a time; attention runs in query blocks of ``QUERY_BLOCK``; an
expert is applied to every token and weighted by zero where it was not
chosen; the head runs in blocks of positions; a sequence longer than one
query block is padded to a multiple of ``PAD_TO`` positions, which no
earlier position sees; the three convolutions (of ``q``, ``k`` and ``v``)
are one over their channels side by side.

Weights come as a dict (see ``adapters/ling.reference_weights``): matrices
are [in, out]; a leaf of ``layers`` is stacked over the layers that have
it, in layer order (the norms: all layers; ``kda_*``: the KDA layers;
``q`` to ``o``: the latent layers; ``gate``, ``up``, ``down``: the dense
layers; ``router`` to ``e_down``: the routed layers, the experts' next axis
the expert).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256
HEAD_ROWS = 4096
# A sequence is padded to a multiple of this many positions (causal: the
# tail is inert and its rows are dropped), so that the requests of a check,
# which differ in length, meet a few compiled shapes and not one each.
PAD_TO = 2048

KDA_LEAVES = ("kda_q", "kda_k", "kda_v", "kda_f", "kda_b", "kda_z",
              "kda_conv", "kda_a_log", "kda_dt_bias", "kda_norm", "kda_out")
LATENT_LEAVES = ("q", "kv_a", "kv_a_norm", "kv_b", "attn_gate", "o")


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _static(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "head_dim", "rope_theta", "rms_norm_eps", "layer_group_size",
            "short_conv_kernel_size", "kda_lower_bound",
            "first_k_dense_replace", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "routed_scaling_factor")
    if c.get("q_lora_rank") is not None or c.get("rope_scaling"):
        raise ValueError("the reference has one query matrix and rotates "
                         "unscaled")
    depth = c["num_hidden_layers"]
    if any(c["expert_swiglu_limit_list"][:depth]) \
            or any(c["share_expert_swiglu_limit_list"][:depth]):
        raise ValueError("a clamped SwiGLU is not in the reference")
    held = c["num_experts"]
    total = c.get("published", {}).get("num_experts", held)
    if held * c.get("expert_shards", 1) != total:
        raise ValueError(f"{held} experts held x {c.get('expert_shards', 1)}"
                         f" shards is not the model's {total}")
    return tuple((k, c[k]) for k in keys) + (
        ("routed_total", total),
        ("held_from", int(c.get("expert_shard", 0)) * held))


def delta_rule(q, k, v, g, beta):
    """q, k, v, g: [S, heads, D]; beta: [S, heads]. The recurrence of the
    module's docstring from a zero state; o [S, heads, D]."""
    def token(state, row):
        q_t, k_t, v_t, g_t, b_t = row
        state = jnp.exp(g_t)[:, :, None] * state        # a row of S its own
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        d = b_t[:, None] * (v_t - seen)
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, zero, (q, k, v, g, beta))[1]


@functools.partial(jax.jit, static_argnames=("c",))
def _kda(c, x, norm_w, w):
    """x: [S, hidden] -> x + KDA(N(x))."""
    cd = dict(c)
    d, taps, eps = (cd["head_dim"], cd["short_conv_kernel_size"],
                    cd["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        u = rms_norm(x, norm_w.astype(F32), eps)
        s = u.shape[0]
        heads = w["kda_q"].shape[1] // d
        mixed = jnp.concatenate(
            [u @ w["kda_q"], u @ w["kda_k"], u @ w["kda_v"]], axis=-1)
        # Tap j meets the input shifted down by (taps - 1 - j) positions.
        mixed = jax.nn.silu(sum(
            w["kda_conv"][:, j]
            * jnp.pad(mixed, ((taps - 1 - j, 0), (0, 0)))[:s]
            for j in range(taps)))
        q, k, v = (t.reshape(s, heads, d) for t in jnp.split(mixed, 3, -1))
        beta = jax.nn.sigmoid(u @ w["kda_b"])
        amount = jnp.repeat(jnp.exp(w["kda_a_log"]), d)
        g = cd["kda_lower_bound"] * jax.nn.sigmoid(
            amount * (u @ w["kda_f"] + w["kda_dt_bias"]))

        def unit(t):
            return t * jax.lax.rsqrt(
                jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

        o = delta_rule(unit(q) / math.sqrt(d), unit(k), v,
                       g.reshape(s, heads, d), beta)
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        o = w["kda_norm"] * (o * jax.lax.rsqrt(var + eps))
        o = o.reshape(s, heads * d) * jax.nn.sigmoid(u @ w["kda_z"])
        return x + o @ w["kda_out"]


def rotary_halves(x, theta, dim: int):
    """x: [S, heads, dim]; position p rotates the pair (i, i + dim/2) by
    p * theta^(-2i/dim)."""
    s = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("c",))
def _latent_attention(c, x, norm_w, w):
    """x: [S, hidden] -> x + GatedMLA(N(x))."""
    cd = dict(c)
    nh, rank = cd["num_attention_heads"], cd["kv_lora_rank"]
    dn, dr, dv = (cd["qk_nope_head_dim"], cd["qk_rope_head_dim"],
                  cd["v_head_dim"])
    eps, theta = cd["rms_norm_eps"], float(cd["rope_theta"])
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        u = rms_norm(x, norm_w.astype(F32), eps)
        s = u.shape[0]
        q = (u @ w["q"]).reshape(s, nh, dn + dr)
        q_n, q_r = q[..., :dn], rotary_halves(q[..., dn:], theta, dr)
        kv = u @ w["kv_a"]
        ckv = rms_norm(kv[:, :rank], w["kv_a_norm"], eps)
        k_r = rotary_halves(kv[:, None, rank:], theta, dr)[:, 0]
        kv_up = (ckv @ w["kv_b"]).reshape(s, nh, dn + dv)
        k_n, v = kv_up[..., :dn], kv_up[..., dn:]
        block = min(QUERY_BLOCK, s)
        if s % block:
            raise ValueError(f"{s} positions are no multiple of {block}")

        def one_block(args):
            qn, qr, q0 = args
            scores = (jnp.einsum("qhd,khd->hqk", qn, k_n)
                      + jnp.einsum("qhd,kd->hqk", qr, k_r)) \
                / math.sqrt(dn + dr)
            causal = (jnp.arange(s)[None, :]
                      <= (q0 + jnp.arange(block))[:, None])[None]
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        out = jax.lax.map(one_block, (
            q_n.reshape(s // block, block, nh, dn),
            q_r.reshape(s // block, block, nh, dr),
            jnp.arange(0, s, block)))
        out = out.reshape(s, nh, dv) \
            * jax.nn.sigmoid(u @ w["attn_gate"])[:, :, None]
        return x + out.reshape(s, nh * dv) @ w["o"]


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
def gate_weights(c, u, router, bias):
    """[S, routed] float32: the weight of every routed expert for every
    token, 0 where it was not chosen."""
    cd = dict(c)
    groups, keep = cd["n_group"], cd["topk_group"]
    k = cd["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(u @ router.astype(F32))
    n, e = s.shape
    rows = jnp.arange(n)[:, None]
    choice = s + bias.astype(F32)
    in_groups = choice.reshape(n, groups, e // groups)
    group_score = jnp.sort(in_groups, axis=-1)[..., -2:].sum(axis=-1)
    kept = jnp.argsort(-group_score, axis=-1)[:, :keep]
    group_kept = jnp.zeros((n, groups), bool).at[rows, kept].set(True)
    allowed = jnp.repeat(group_kept, e // groups, axis=1)
    chosen = jnp.argsort(-jnp.where(allowed, choice, -jnp.inf),
                         axis=-1)[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cd["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    picked = picked * cd["routed_scaling_factor"]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def routed_experts(c: tuple, u, w, r: int):
    """The held experts' terms of routed layer ``r`` on u [S, hidden]."""
    lo = dict(c)["held_from"]
    weights = gate_weights(c, u, w["router"][r], w["router_bias"][r])
    out = jnp.zeros_like(u)
    for e in range(w["e_gate"].shape[1]):                     # held experts
        y = _swiglu(u, w["e_gate"][r, e], w["e_up"][r, e], w["e_down"][r, e])
        out = out + weights[:, lo + e][:, None] * y
    return out


def shared_expert(u, w, r: int):
    return _swiglu(u, w["s_gate"][r], w["s_up"][r], w["s_down"][r])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def layer(c: tuple, h, w, l: int):
    """Layer ``l`` on h [S, hidden]. The latent layers before it are ``l //
    group`` and the KDA ones the rest: its place in the stacks of its
    kind."""
    cd = dict(c)
    group, dense = cd["layer_group_size"], cd["first_k_dense_replace"]
    latent = l // group
    if (l + 1) % group:
        a = _kda(c, h, w["input_norm"][l],
                 {k: w[k][l - latent] for k in KDA_LEAVES})
    else:
        a = _latent_attention(c, h, w["input_norm"][l],
                              {k: w[k][latent] for k in LATENT_LEAVES})
    u = _norm(a, w["post_norm"][l], cd["rms_norm_eps"])
    if l < dense:
        return a + _swiglu(u, w["gate"][l], w["up"][l], w["down"][l])
    r = l - dense
    return a + shared_expert(u, w, r) + routed_experts(c, u, w, r)


def logits(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> logits [S, V] in float32."""
    st = _static(c)
    s = tokens.shape[0]
    if s > QUERY_BLOCK:
        tokens = jnp.pad(tokens, (0, -s % PAD_TO))
    x = weights["embed"][tokens].astype(F32)
    for l in range(weights["layers"]["input_norm"].shape[0]):
        x = layer(st, x, weights["layers"], l)
    return jnp.concatenate(
        [_head(x[r0:min(r0 + HEAD_ROWS, s)], weights["final_norm"],
               weights["head"], c["rms_norm_eps"])
         for r0 in range(0, s, HEAD_ROWS)], axis=0)
