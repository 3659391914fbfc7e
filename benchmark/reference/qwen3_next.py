"""Plain reference: the Qwen3-Next forward pass (Gated DeltaNet layers, a
gated full attention every ``full_attention_interval`` layers, routed
experts with a gated shared expert in every layer).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no state handed between calls, no batching, no sorting
of tokens by expert, and the gated delta rule as the token-by-token
recurrence (a ``lax.scan`` over the positions), never the chunked form the
program runs. It follows the equations of the family's
``modeling_qwen3_next.py`` as ``benchmark/configs/qwen3-next-80b-a3b.json``
states them under ``assumed``; it shares no code with the program and is
never given the program's choices.

Layer ``l``, input ``h``, ``N(x; w) = x rsqrt(mean x^2 + eps) (1 + w)``::

    a  = h + Mix_l(N(h; input_layernorm))
    h' = a + F(N(a; post_attention_layernorm))

``Mix_l`` is the gated attention where ``(l + 1) % full_attention_interval
== 0`` and Gated DeltaNet otherwise.

Gated DeltaNet (``Qwen3NextGatedDeltaNet``): ``x W_qkvz`` is laid out a key
head, ``[q Dk | k Dk | v r Dv | z r Dv]`` with ``r`` value heads a key head;
``x W_ba`` a key head ``[b r | a r]``. All heads' ``q``, then ``k``, then
``v`` side by side pass a depthwise causal convolution (``conv1d`` [channels,
taps], no bias, zeros before position 0: ``y_t = sum_j w[:, j] x_{t - taps +
1 + j}``) and ``silu``. ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
dt_bias)``. ``q`` and ``k`` are repeated for their ``r`` value heads
(``repeat_interleave``), L2-normalised a head (``x rsqrt(sum x^2 + 1e-6)``),
``q`` scaled by ``Dk^-1/2``. Per value head from ``S_0 = 0``::

    S' = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t d_t^T;   o_t = S_t^T q_t

The output a head is ``w o rsqrt(mean o^2 + eps) silu(z)`` (the norm first,
then the gate; a weight of its own kind, not ``1 + w``), the heads side by
side through ``W_out``.

Gated attention (``Qwen3NextAttention``): ``x W_q`` gives a head ``[q D |
gate D]``; ``N`` over each head's ``q`` and ``k``; ``rotate_half`` over the
first ``partial_rotary_factor D`` values of a head, the rest unrotated;
causal softmax of ``q . k / sqrt(D)``, query head h on KV head ``h //
group``; the output times ``sigmoid(gate)``, then ``W_o``.

``F`` (``Qwen3NextSparseMoeBlock``): ``s = softmax(u W_g)`` over all
experts, the ``num_experts_per_tok`` largest chosen, their weights divided
by their sum (``norm_topk_prob``); beside them ``sigmoid(u w_sg)
Shared(u)``. The share: the configuration says which routed experts are
held (``expert_shard`` of ``expert_shards``); the others' terms are left
out, as in the program (there is no exchange to bring them); the shared
expert is whole. After the last layer ``N`` and an untied head.

Departures from a literal transcription, none of which changes a value in
exact arithmetic: weights stay in their stored dtype and are cast a
sub-block at a time; attention runs in query blocks of ``QUERY_BLOCK`` (the
scores of 31,744 x 31,744 x 16 heads are 64 GB); an expert is applied to
every token and weighted by zero where it was not chosen; the head runs in
blocks of positions; a sequence longer than one query block is padded to a
multiple of ``PAD_TO`` positions, which no earlier position sees.

Weights come as a dict (see ``adapters/qwen3_next.reference_weights``):
matrices are [in, out]; a leaf of ``layers`` is stacked over the layers that
have it, in layer order (the norms, the router, the shared and the routed
experts: all layers; ``qkvz`` to ``out``: the linear layers; ``q`` to
``k_norm``: the full-attention layers; the experts' next axis the expert).
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
# tail is inert and its rows are dropped), so that the four requests of a
# check, which differ in length, meet one or two compiled shapes and not
# four: compiling the layers anew for every length took most of the 221 s
# the first check did (my chip run, PR 48).
PAD_TO = 4096


def zero_centred_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _static(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "partial_rotary_factor", "rope_theta",
            "rms_norm_eps", "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "full_attention_interval",
            "num_experts_per_tok", "norm_topk_prob")
    if c.get("rope_scaling"):
        raise ValueError("rope_scaling: the reference rotates unscaled")
    held = c["num_experts"]
    return tuple((k, c[k]) for k in keys) + (
        ("held_from", int(c.get("expert_shard", 0)) * held),)


def delta_rule(q, k, v, g, beta):
    """q, k: [S, heads, Dk]; v: [S, heads, Dv]; g, beta: [S, heads]. The
    recurrence of the module's docstring from a zero state; o [S, heads,
    Dv]."""
    def token(state, row):
        q_t, k_t, v_t, g_t, b_t = row
        state = jnp.exp(g_t)[:, None, None] * state
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        d = b_t[:, None] * (v_t - seen)
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, zero, (q, k, v, g, beta))[1]


@functools.partial(jax.jit, static_argnames=("c",))
def _gated_delta_net(c, x, norm_w, w):
    """x: [S, hidden] -> x + GatedDeltaNet(N(x))."""
    cd = dict(c)
    nk, nv = cd["linear_num_key_heads"], cd["linear_num_value_heads"]
    dk, dv = cd["linear_key_head_dim"], cd["linear_value_head_dim"]
    taps, eps, r = cd["linear_conv_kernel_dim"], cd["rms_norm_eps"], nv // nk
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        u = zero_centred_norm(x, norm_w.astype(F32), eps)
        s = u.shape[0]
        qkvz = (u @ w["qkvz"]).reshape(s, nk, 2 * dk + 2 * r * dv)
        q, k, v, z = jnp.split(qkvz, (dk, 2 * dk, 2 * dk + r * dv), axis=-1)
        ba = (u @ w["ba"]).reshape(s, nk, 2 * r)
        b, a = ba[..., :r].reshape(s, nv), ba[..., r:].reshape(s, nv)
        mixed = jnp.concatenate(
            [q.reshape(s, -1), k.reshape(s, -1), v.reshape(s, -1)], axis=-1)
        # Tap j meets the input shifted down by (taps - 1 - j) positions.
        mixed = jax.nn.silu(sum(
            w["conv"][:, j] * jnp.pad(mixed, ((taps - 1 - j, 0), (0, 0)))[:s]
            for j in range(taps)))
        q, k, v = jnp.split(mixed, (nk * dk, 2 * nk * dk), axis=-1)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(w["a_log"]) * jax.nn.softplus(a + w["dt_bias"])

        def unit(t):
            t = jnp.repeat(t.reshape(s, nk, dk), r, axis=1)
            return t * jax.lax.rsqrt(
                jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

        o = delta_rule(unit(q) / math.sqrt(dk), unit(k),
                       v.reshape(s, nv, dv), g, beta)
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        o = w["norm"] * (o * jax.lax.rsqrt(var + eps))
        o = o * jax.nn.silu(z.reshape(s, nv, dv))
        return x + o.reshape(s, nv * dv) @ w["out"]


def rotary_part(x, theta, part: int):
    """x: [S, heads, D]; the first ``part`` values of a head rotated by
    halves of that part (pair (i, i + part/2) by p * theta^(-2i/part)), the
    rest as they are."""
    s = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, part, 2, dtype=F32) / part))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :part // 2], x[..., part // 2:part]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., part:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("c",))
def _attention(c, x, norm_w, w):
    """x: [S, hidden] -> x + GatedAttention(N(x))."""
    cd = dict(c)
    nh, nkv, d = (cd["num_attention_heads"], cd["num_key_value_heads"],
                  cd["head_dim"])
    eps, theta = cd["rms_norm_eps"], float(cd["rope_theta"])
    part = int(d * cd["partial_rotary_factor"])
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        u = zero_centred_norm(x, norm_w.astype(F32), eps)
        s = u.shape[0]
        qg = (u @ w["q"]).reshape(s, nh, 2 * d)
        q, gate = qg[..., :d], qg[..., d:].reshape(s, nh * d)
        q = zero_centred_norm(q, w["q_norm"], eps)
        k = zero_centred_norm((u @ w["k"]).reshape(s, nkv, d), w["k_norm"],
                              eps)
        v = (u @ w["v"]).reshape(s, nkv, d)
        q, k = rotary_part(q, theta, part), rotary_part(k, theta, part)
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        block = min(QUERY_BLOCK, s)
        if s % block:
            raise ValueError(f"{s} positions are no multiple of {block}")

        def one_block(args):
            qb, q0 = args
            scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
            causal = (jnp.arange(s)[None, :]
                      <= (q0 + jnp.arange(block))[:, None])[None]
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        out = jax.lax.map(one_block, (q.reshape(s // block, block, nh, d),
                                      jnp.arange(0, s, block)))
        out = out.reshape(s, nh * d) * jax.nn.sigmoid(gate)
        return x + out @ w["o"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return zero_centred_norm(x, w.astype(F32), eps)


@jax.jit
def _swiglu(x, gate, up, down):
    """x: [S, in] (already normed) -> [S, in]."""
    with jax.default_matmul_precision("highest"):
        gate, up, down = (a.astype(F32) for a in (gate, up, down))
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnames=("c",))
def gate_weights(c, u, router):
    """[S, experts] float32: an expert's weight where it was chosen, 0
    elsewhere."""
    cd = dict(c)
    with jax.default_matmul_precision("highest"):
        s = jax.nn.softmax(u @ router.astype(F32), axis=-1)
    chosen = jnp.argsort(-s, axis=-1)[:, :cd["num_experts_per_tok"]]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cd["norm_topk_prob"]:
        picked = picked / picked.sum(axis=-1, keepdims=True)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def routed_experts(c: tuple, u, w, layer: int):
    """The held experts' terms of layer ``layer`` on u [S, hidden]."""
    lo = dict(c)["held_from"]
    weights = gate_weights(c, u, w["router"][layer])
    out = jnp.zeros_like(u)
    for e in range(w["e_gate"].shape[1]):                     # held experts
        y = _swiglu(u, w["e_gate"][layer, e], w["e_up"][layer, e],
                    w["e_down"][layer, e])
        out = out + weights[:, lo + e][:, None] * y
    return out


@jax.jit
def _shared_gate(u, w_sg):
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(u @ w_sg.astype(F32))[:, None]


def shared_expert(u, w, layer: int):
    return _shared_gate(u, w["shared_gate"][layer]) * _swiglu(
        u, w["s_gate"][layer], w["s_up"][layer], w["s_down"][layer])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return zero_centred_norm(x, final_norm.astype(F32), eps) @ \
            head.astype(F32)


def layer(c: tuple, h, w, l: int):
    """Layer ``l`` on h [S, hidden]. The full-attention layers before it are
    ``l // interval`` and the linear ones the rest: its place in the stacks
    of its kind."""
    cd = dict(c)
    full = l // cd["full_attention_interval"]
    if (l + 1) % cd["full_attention_interval"]:
        a = _gated_delta_net(
            c, h, w["input_norm"][l],
            {k: w[k][l - full] for k in ("qkvz", "ba", "conv", "a_log",
                                         "dt_bias", "norm", "out")})
    else:
        a = _attention(c, h, w["input_norm"][l],
                       {k: w[k][full] for k in
                        ("q", "k", "v", "o", "q_norm", "k_norm")})
    u = _norm(a, w["post_norm"][l], cd["rms_norm_eps"])
    return a + shared_expert(u, w, l) + routed_experts(c, u, w, l)


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
