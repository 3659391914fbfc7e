"""Plain reference: the Olmo-Hybrid forward pass and loss (Gated DeltaNet
layers beside a full attention without positions, a dense SwiGLU in every
layer, norms after the operator).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no batching, and the gated delta rule as the
token-by-token recurrence (a ``lax.scan`` over the positions), never the
chunked form the program runs. It follows the equations of the family's
``Olmo3DecoderLayer`` / ``Olmo3Attention`` / ``Olmo3RMSNorm`` and of the
Gated DeltaNet as ``modeling_qwen3_next.py`` carries it, as
``benchmark/configs/olmo-hybrid-7b.json`` states them under ``assumed``; it
shares no code with the program and is never given the program's choices.
Every function is differentiable: ``jax.grad`` of :func:`loss_array` is what
the program's gradients are held to (tests/test_olmo_hybrid.py,
devbench/olmo_hybrid_bench.py).

``N(x; w) = w x rsqrt(mean(x^2) + eps)``. Layer ``l``, input ``h``, no norm
before an operator::

    a  = h + N(Mix_l(h); post_attention_layernorm)
    h' = a + N(F(a); post_feedforward_layernorm)

``F(u) = down(silu(gate(u)) up(u))``. ``Mix_l`` by ``layer_types[l]``.

Gated DeltaNet: ``q = silu(conv(x W_q))``, ``k = silu(conv(x W_k))``, ``v =
silu(conv(x W_v))``, ``conv`` depthwise causal over ``linear_conv_kernel_dim``
taps, no bias, zeros before position 0 (``y_t = sum_j w[j] x_{t - taps + 1 +
j}``); ``beta = 2 sigmoid(x W_b)`` where ``linear_allow_neg_eigval``
(``sigmoid`` otherwise); ``g = -exp(A_log) softplus(x W_a + dt_bias)``; ``q``
and ``k`` L2-normalised a head (``x rsqrt(sum x^2 + 1e-6)``), ``q`` scaled by
``Dk^-1/2``. Per head from ``S_0 = 0``::

    S' = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t d_t^T;   o_t = S_t^T q_t

The output a head is ``N(o; o_norm) silu(z)`` with ``z = x W_g`` (the norm
first, then the gate), the heads side by side through ``W_o``.

Full attention: ``q = N(x W_q; q_norm)``, ``k = N(x W_k; k_norm)``, each norm
over all heads' values at once; ``v = x W_v``; no rotary; causal softmax of
``q . k / sqrt(head_dim)``; ``W_o``. After the last layer ``N(h; norm)`` and
an untied head.

Departures from a literal transcription, none of which changes a value in
exact arithmetic: weights stay in their stored dtype and are cast a layer at
a time; attention runs a head at a time; the recurrence's scan is cut in
blocks of ``SCAN_BLOCK`` positions under ``jax.checkpoint``, so that its
gradient keeps a state a block and not a state a token (8,192 states of 30 x
96 x 192 float32 are 18 GB); the head runs in blocks of positions.

Weights come as a dict (see ``adapters/olmo_hybrid.reference_weights``):
matrices are [in, out], taps [taps, channels]; a leaf of ``layers`` is
stacked over the layers that have it, in layer order (``lin_*``, ``conv_*``,
``a_log``, ``dt_bias``, ``o_norm``: the linear layers; ``q`` to ``k_norm``:
the full-attention layers; the norms and the SwiGLU: all layers).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
SCAN_BLOCK = 64
HEAD_ROWS = 4096
LINEAR, ATTENTION = "linear_attention", "full_attention"

LINEAR_KEYS = ("lin_q", "lin_k", "lin_v", "lin_g", "lin_a", "lin_b",
               "conv_q", "conv_k", "conv_v", "a_log", "dt_bias", "o_norm",
               "lin_o")
ATTENTION_KEYS = ("q", "k", "v", "o", "q_norm", "k_norm")
LAYER_KEYS = ("post_attn_norm", "post_ffn_norm", "gate", "up", "down")


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return w * (x * jax.lax.rsqrt(var + eps))


def layer_types(c: dict, layers: int) -> tuple:
    return tuple(c["layer_types"][:layers])


def _static(c: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_allow_neg_eigval")
    if c["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("rope_theta: the reference rotates nothing")
    if c["linear_num_key_heads"] != c["linear_num_value_heads"]:
        raise ValueError("the reference takes a key head a value head")
    return tuple((k, c[k]) for k in keys)


def delta_rule(q, k, v, g, beta):
    """q, k: [S, heads, Dk]; v: [S, heads, Dv]; g, beta: [S, heads]. The
    recurrence of the module's docstring from a zero state; o [S, heads,
    Dv]. S a multiple of ``SCAN_BLOCK`` or under it."""
    def token(state, row):
        q_t, k_t, v_t, g_t, b_t = row
        state = jnp.exp(g_t)[:, None, None] * state
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        d = b_t[:, None] * (v_t - seen)
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def block(state, rows):
        return jax.lax.scan(token, state, rows)

    s = q.shape[0]
    n = max(1, s // SCAN_BLOCK)
    if s % n:
        raise ValueError(f"{s} positions are no multiple of {SCAN_BLOCK}")
    rows = tuple(a.reshape(n, s // n, *a.shape[1:])
                 for a in (q, k, v, g, beta))
    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(block, zero, rows)[1].reshape(v.shape)


def causal_conv(x, taps_w):
    """x [S, channels], taps_w [taps, channels]: tap j meets the input
    shifted down by (taps - 1 - j) positions."""
    taps, s = taps_w.shape[0], x.shape[0]
    return sum(taps_w[j] * jnp.pad(x, ((taps - 1 - j, 0), (0, 0)))[:s]
               for j in range(taps))


def gated_delta_net(cd: dict, x, w):
    """x: [S, hidden] -> GatedDeltaNet(x) [S, hidden]."""
    nh = cd["linear_num_value_heads"]
    dk, dv = cd["linear_key_head_dim"], cd["linear_value_head_dim"]
    s = x.shape[0]
    q, k, v = (jax.nn.silu(causal_conv(x @ w["lin_" + n], w["conv_" + n]))
               for n in ("q", "k", "v"))
    beta = jax.nn.sigmoid(x @ w["lin_b"])
    if cd["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(x @ w["lin_a"] + w["dt_bias"])

    def unit(t):
        t = t.reshape(s, nh, dk)
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                 + 1e-6)

    o = delta_rule(unit(q) / math.sqrt(dk), unit(k), v.reshape(s, nh, dv),
                   g, beta)
    o = rms_norm(o, w["o_norm"], cd["rms_norm_eps"])
    o = o * jax.nn.silu((x @ w["lin_g"]).reshape(s, nh, dv))
    return o.reshape(s, nh * dv) @ w["lin_o"]


def full_attention(cd: dict, x, w):
    """x: [S, hidden] -> Attention(x) [S, hidden]; no positions."""
    nh, nkv, d = (cd["num_attention_heads"], cd["num_key_value_heads"],
                  cd["head_dim"])
    s, eps = x.shape[0], cd["rms_norm_eps"]
    q = rms_norm(x @ w["q"], w["q_norm"], eps).reshape(s, nh, d)
    k = rms_norm(x @ w["k"], w["k_norm"], eps).reshape(s, nkv, d)
    v = (x @ w["v"]).reshape(s, nkv, d)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(args):
        q_h, k_h, v_h = args                                  # [S, D]
        scores = jnp.where(causal, q_h @ k_h.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_h

    out = jax.lax.map(head, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    return jnp.moveaxis(out, 0, 1).reshape(s, nh * d) @ w["o"]


def swiglu(x, w):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


@functools.partial(jax.jit, static_argnames=("c", "kind"))
def layer(c: tuple, kind: str, h, w):
    """One layer on h [S, hidden]; ``w`` the operator's leaves of its kind
    and the layer's own, whatever their dtype."""
    cd = dict(c)
    eps = cd["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        mix = gated_delta_net if kind == LINEAR else full_attention
        a = h + rms_norm(mix(cd, h, w), w["post_attn_norm"], eps)
        return a + rms_norm(swiglu(a, w), w["post_ffn_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def layer_weights(c: dict, weights: dict, l: int) -> tuple[str, dict]:
    """(kind, leaves) of layer ``l``: its place in the stacks of its kind is
    the count of that kind before it."""
    kinds = c["layer_types"]
    kind = kinds[l]
    at = sum(1 for x in kinds[:l] if x == kind)
    lay = weights["layers"]
    own = {k: lay[k][at] for k in
           (LINEAR_KEYS if kind == LINEAR else ATTENTION_KEYS)}
    return kind, {**own, **{k: lay[k][l] for k in LAYER_KEYS}}


def hidden(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> the last layer's output [S, hidden] in float32."""
    st = _static(c)
    x = weights["embed"][tokens].astype(F32)
    for l in range(weights["layers"]["post_attn_norm"].shape[0]):
        kind, w = layer_weights(c, weights, l)
        x = layer(st, kind, x, w)
    return x


def logits(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> logits [S, V] in float32."""
    x = hidden(c, weights, tokens)
    s = x.shape[0]
    return jnp.concatenate(
        [head_logits(x[r0:min(r0 + HEAD_ROWS, s)], weights["final_norm"],
                     weights["head"], c["rms_norm_eps"])
         for r0 in range(0, s, HEAD_ROWS)], axis=0)


def nll_sum(lg, targets) -> jax.Array:
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()


def loss_array(c: dict, weights: dict, tokens, targets) -> jax.Array:
    """Mean next-token cross-entropy over [B, S], one sequence at a time: a
    float32 scalar, differentiable in ``weights``."""
    total = sum(nll_sum(logits(c, weights, tokens[b]), targets[b])
                for b in range(tokens.shape[0]))
    return total / (tokens.shape[0] * tokens.shape[1])


def loss(c: dict, weights: dict, tokens, targets) -> float:
    return float(loss_array(c, weights, tokens, targets))
