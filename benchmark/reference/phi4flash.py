"""Plain reference: the Phi-4-mini-flash forward pass (SambaY: a self-decoder
of selective-scan and differential-attention layers, a cross-decoder of
gated memory units and cross attentions).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no state handed between calls, no batching, every layer
at every position (the program's prefill leaves the cross-decoder out of
all but a prompt's last position), the selective scan as the token-by-token
recurrence (a ``lax.scan`` over the positions), the two softmaxes of the
differential attention computed apart on unpacked heads of ``head_dim`` (the
program packs a pair into one head of twice the width). It follows the
equations of the family's ``modeling_phi4flash.py`` as
``benchmark/configs/phi-4-mini-flash-reasoning.json`` states them under
``assumed``; it shares no code with the program and is never given the
program's choices.

Layer ``l`` of ``L``, input ``h``, ``LN(x; w, b) = (x - mean x) rsqrt(var x
+ eps) w + b``::

    a  = h + Mix_l(LN(h; input_layernorm))
    h' = a + F(LN(a; post_attention_layernorm))
    F(u) = W_down (silu(g) * v),  [g | v] = W_gate_up u

``Mix_l`` (``mb_per_layer`` 2): ``l`` even and ``<= L/2`` the scan operator;
``l`` odd and ``< L/2`` differential attention over a window; ``l = L/2 +
1`` differential attention, causal and full; ``l`` even and ``>= L/2 + 2``
the gated memory unit; ``l`` odd and ``>= L/2 + 3`` cross differential
attention on layer ``L/2 + 1``'s keys and values.

Scan operator (``Phi3Mamba``): ``[x | z] = W_in u``; ``x = silu(conv(x) +
b_c)`` (``conv1d`` [channels, taps], zeros before position 0: ``y_t = sum_j
w[:, j] x_{t - taps + 1 + j}``); ``[dt_r | B | C] = W_x x``; ``dt =
softplus(W_dt dt_r + b_dt)``; ``A = -exp(A_log)`` [channels, states]. From
``h_0 = 0``::

    h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t^T
    y_t = h_t C_t + D x_t

The operator gives ``W_out (y * silu(z))``; layer ``L/2``'s ``y`` is the
memory ``m``. Gated memory unit: ``W_out (silu(W_in u) * m)``.

Differential attention (``SambaYFlashAttention2``): ``[q | k | v] = W_qkv u
+ b``; heads alternate within a pair (head ``2i`` is ``q1_i``, ``2i + 1``
``q2_i``; keys and values alike); query pair ``i`` attends KV pair ``i //
group``. ``S1 = softmax(q1 k1^T / sqrt(d) + mask)``, ``S2`` alike on ``q2,
k2``; ``o = (S1 - lambda S2) [v1 | v2]``; ``o = o rsqrt(mean o^2 + eps) w (1
- lambda_init)`` over the pair's ``2 d``; the pairs side by side through
``W_o`` with bias. ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``. No positional term.
The window's mask: position ``t`` sees ``t - window + 1 .. t``. The cross
attention has ``q = W_q u + b_q`` alone and reads layer ``L/2 + 1``'s ``k``
and ``v``.

After the last layer ``LN`` and the embedding transposed (tied, no bias).

Departures from a literal transcription, none of which changes a value in
exact arithmetic: the fused ``W_qkv`` and ``W_gate_up`` are taken as their
column blocks (``q``, ``k``, ``v``; ``gate``, ``up``), as the program stores
them, so that no second copy of 3.4 GB of weights is made beside the first;
weights stay in their stored dtype and are cast a layer's
leaf at a time (float32 whole is 15.4 GB); attention runs in query blocks of
``QUERY_BLOCK`` (the scores of 12,288 x 12,288 x 20 pairs x 2 are 24 GB); the
head runs in blocks of ``HEAD_ROWS`` positions and each block goes to the
host as it is made (``[S, 200,064]`` float32 is 9.8 GB at 12,288 positions),
so ``logits`` returns a NumPy array; a sequence longer than one query block
is padded to a multiple of ``PAD_TO`` positions, which no earlier position
sees (every program here is compiled a length: about 70 s for a new one), and
the head runs on the rows that were asked for.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 512
HEAD_ROWS = 1024
PAD_TO = 2048


def _mm(x, w):
    return x @ w.astype(F32)


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def selective_scan(x, dt, a, b, c, d):
    """x, dt [S, channels]; a [channels, states]; b, c [S, states]; d
    [channels] -> y [S, channels], from a zero state, token by token."""
    def token(h, row):
        x_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t[None]
        return h, h @ c_t + d * x_t

    _, y = lax.scan(token, jnp.zeros(a.shape, F32), (x, dt, b, c))
    return y


@functools.partial(jax.jit, static_argnames=("states",))
def scan_operator(u, w, states: int):
    """The scan operator on normed u [S, hidden] -> (its output before
    ``W_out`` and the gate: y [S, channels]; z [S, channels])."""
    with jax.default_matmul_precision("highest"):
        x, z = jnp.split(_mm(u, w["in_proj"]), 2, axis=-1)
        taps = w["conv"].astype(F32)                       # [channels, taps]
        n = taps.shape[1]
        padded = jnp.pad(x, ((n - 1, 0), (0, 0)))
        x = jax.nn.silu(w["conv_bias"].astype(F32) + sum(
            taps[:, j] * padded[j:j + x.shape[0]] for j in range(n)))
        low = _mm(x, w["x_proj"])
        rank = low.shape[1] - 2 * states
        dt = jax.nn.softplus(_mm(low[:, :rank], w["dt_proj"])
                             + w["dt_bias"].astype(F32))
        y = selective_scan(x, dt, -jnp.exp(w["a_log"].astype(F32)),
                           low[:, rank:rank + states],
                           low[:, rank + states:], w["d"].astype(F32))
        return y, z


@jax.jit
def scan_output(y, z, out_proj):
    with jax.default_matmul_precision("highest"):
        return _mm(y * jax.nn.silu(z), out_proj)


@jax.jit
def gated_memory_unit(u, m, w_in, w_out):
    with jax.default_matmul_precision("highest"):
        return _mm(jax.nn.silu(_mm(u, w_in)) * m, w_out)


def split_pairs(x, heads: int):
    """x [S, heads * d] -> (x1, x2) [heads / 2, S, d]: head ``2i`` and head
    ``2i + 1`` of every pair."""
    s = x.shape[0]
    x = x.reshape(s, heads // 2, 2, -1)
    return x[:, :, 0].transpose(1, 0, 2), x[:, :, 1].transpose(1, 0, 2)


@functools.partial(jax.jit, static_argnames=("window",))
def _attend_block(q, k, v, q0, window: int):
    """One softmax: q [pairs, Q, d] at positions ``q0 + arange(Q)`` against
    k [kv pairs, S, d], v [kv pairs, S, 2 d] -> [pairs, Q, 2 d]. ``window``
    0: causal and full."""
    with jax.default_matmul_precision("highest"):
        pairs, rows, d = q.shape
        group = pairs // k.shape[0]
        k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
        scores = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(d)
        qpos = q0 + jnp.arange(rows)[:, None]
        kpos = jnp.arange(k.shape[1])[None, :]
        seen = kpos <= qpos
        if window:
            seen = seen & (kpos > qpos - window)
        p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v)


def _softmax_attention(q, k, v, window: int):
    return jnp.concatenate(
        [_attend_block(q[:, r0:r0 + QUERY_BLOCK], k, v, r0, window)
         for r0 in range(0, q.shape[1], QUERY_BLOCK)], axis=1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _differential(o1, o2, lam, subln, w_o, b_o, init, eps: float):
    """``init`` is the layer's ``lambda_init``, a run-time scalar: one
    compiled program serves every layer (static, it was 16 compilations of
    18 s each for every new length)."""
    with jax.default_matmul_precision("highest"):
        lam = lam.astype(F32)
        full = (jnp.exp(jnp.sum(lam[0] * lam[1]))
                - jnp.exp(jnp.sum(lam[2] * lam[3])) + init)
        o = o1 - full * o2                                 # [pairs, S, 2 d]
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
            * subln.astype(F32) * (1.0 - init)
        o = o.transpose(1, 0, 2).reshape(o.shape[1], -1)
        return _mm(o, w_o) + b_o.astype(F32)


def differential_attention(c, q, k1, k2, v, w, layer: int, window: int):
    """q [S, hidden] as projected; k1, k2 [kv pairs, S, d]; v [kv pairs, S,
    2 d] -> the attention's output [S, hidden]."""
    q1, q2 = split_pairs(q, c["num_attention_heads"])
    return _differential(
        _softmax_attention(q1, k1, v, window),
        _softmax_attention(q2, k2, v, window),
        w["lam"], w["subln"], w["o"], w["o_bias"],
        jnp.float32(lambda_init(layer)), eps=c["layer_norm_eps"])


@jax.jit
def _linear(u, w, b):
    with jax.default_matmul_precision("highest"):
        return _mm(u, w) + b.astype(F32)


def self_attention(c, u, w, layer: int, window: int):
    """Differential self attention on normed u [S, hidden] -> (output, (k1,
    k2, v)): the keys and values are what a cross attention reads."""
    q, k, v = (_linear(u, w[n], w[n + "_bias"]) for n in ("q", "k", "v"))
    k1, k2 = split_pairs(k, c["num_key_value_heads"])
    v = jnp.concatenate(split_pairs(v, c["num_key_value_heads"]), axis=-1)
    return (differential_attention(c, q, k1, k2, v, w, layer, window),
            (k1, k2, v))


@jax.jit
def _swiglu(u, gate, up, down):
    with jax.default_matmul_precision("highest"):
        return _mm(jax.nn.silu(_mm(u, gate)) * _mm(u, up), down)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, b, eps):
    return layer_norm(x, w, b, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, w, b, embed, eps):
    with jax.default_matmul_precision("highest"):
        return layer_norm(x, w, b, eps) @ embed.astype(F32).T


def _of(stack: dict, names, at: int) -> dict:
    return {k: stack[k][at] for k in names}


def logits(c: dict, weights: dict, tokens) -> np.ndarray:
    """tokens [S] -> logits [S, V] in float32, on the host."""
    lw, eps = weights["layers"], c["layer_norm_eps"]
    layers, half = c["num_hidden_layers"], c["num_hidden_layers"] // 2
    states = lw["a_log"].shape[2]
    s = tokens.shape[0]
    if s > QUERY_BLOCK:
        tokens = jnp.pad(tokens, (0, -s % PAD_TO))
    x = weights["embed"][tokens].astype(F32)
    memory = kv = None
    for l in range(layers):
        u = _norm(x, lw["norm1"][l], lw["norm1_bias"][l], eps)
        if l % 2 == 0 and l <= half:
            w = _of(lw, ("in_proj", "conv", "conv_bias", "x_proj", "dt_proj",
                         "dt_bias", "a_log", "d"), l // 2)
            y, z = scan_operator(u, w, states)
            if l == half:
                memory = y
            mixed = scan_output(y, z, lw["ssm_out"][l // 2])
        elif l <= half + 1:
            w = _of(lw, ("q", "q_bias", "k", "k_bias", "v", "v_bias", "o",
                         "o_bias", "lam", "subln"), l // 2)
            mixed, held = self_attention(
                c, u, w, l, c["sliding_window"] if l < half else 0)
            if l == half + 1:
                kv = held
        elif l % 2 == 0:
            at = (l - half - 2) // 2
            mixed = gated_memory_unit(u, memory, lw["gmu_in"][at],
                                      lw["gmu_out"][at])
        else:
            at = (l - half - 3) // 2
            w = {"lam": lw["cross_lam"][at], "subln": lw["cross_subln"][at],
                 "o": lw["cross_o"][at], "o_bias": lw["cross_o_bias"][at]}
            q = _linear(u, lw["cross_q"][at], lw["cross_q_bias"][at])
            mixed = differential_attention(c, q, *kv, w, l, 0)
        a = x + mixed
        x = a + _swiglu(_norm(a, lw["norm2"][l], lw["norm2_bias"][l], eps),
                        lw["gate"][l], lw["up"][l], lw["down"][l])
    return np.concatenate(
        [np.asarray(_head(x[r0:min(r0 + HEAD_ROWS, s)], weights["final_norm"],
                          weights["final_norm_bias"], weights["embed"], eps))
         for r0 in range(0, s, HEAD_ROWS)], axis=0)
