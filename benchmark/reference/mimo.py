"""Plain reference: the MiMo-V2 forward pass (window and full attention
mixed, a learned sink in the window layers' softmax, keys wider than values,
a rotary over part of a head at a base a kind of layer, a leading dense
layer, then routed experts chosen by a sigmoid rule with a selection bias).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no batching, no sorting of tokens by expert. It follows
the equations of the family's ``modeling_mimo_v2_flash.py`` as
``benchmark/configs/mimo-v2.5.json`` states them under ``assumed``; it
shares no code with the program: the rotary, the window's mask, the sink
and the rule are written here again.

Layer ``l``, input ``h``, ``N`` an RMSNorm with float32 statistics::

    a   = h + Attn_l(N(h))          u = N(a)
    out = a + F_l(u)

``F_l`` is a SwiGLU of ``intermediate_size`` where ``moe_layer_freq[l]`` is
0 and ``sum_e w_e E_e(u)`` elsewhere, ``E_e`` a SwiGLU of
``moe_intermediate_size``; no shared expert.

``Attn_l``: ``hybrid_layer_pattern[l]`` 0 is a full layer
(``num_key_value_heads`` KV heads, base ``rope_theta``, causal), 1 a window
layer (``swa_num_key_value_heads``, ``swa_rope_theta``; query ``p`` sees keys
``p - sliding_window + 1 .. p``). ``q = x W_q`` (heads x ``head_dim``), ``k =
x W_k`` (KV heads x ``head_dim``), ``v = attention_value_scale x W_v`` (KV
heads x ``v_head_dim``), the three column blocks of the stored ``qkv``
matrix. Rotary on the first ``R = int(head_dim x partial_rotary_factor)``
lanes of every head of ``q`` and ``k``: lane ``i < R/2`` with lane ``i +
R/2`` by ``p x theta^(-2i/R)``; the lanes from ``R`` on as they are. Scores
``q . k / sqrt(head_dim)``, softmax in float32; query head ``h`` reads KV
head ``h // (heads / KV heads)``. A window layer with
``add_swa_attention_sink_bias`` has a learned ``sink[h]`` a query head:
``out = sum_j e^(s_j - m) v_j / (sum_j e^(s_j - m) + e^(sink[h] - m))``,
``m`` the largest of the scores and the sink. The heads' outputs
concatenated into ``W_o``.

The gate: ``s = sigmoid(u W_g)`` over all routed experts; the choice is the
``num_experts_per_tok`` largest of ``s + b`` (``topk_method`` ``noaux_tc``
with one group); weights ``s`` at the chosen, over their sum + 1e-20 with
``norm_topk_prob``, times ``routed_scaling_factor`` (null: 1). The share:
the configuration says which routed experts are held (``expert_shard`` of
``expert_shards``); the others' terms are left out, as in the program
(there is no exchange to bring them).

Departures from a literal transcription, none of which changes a value in
exact arithmetic: weights stay in their stored dtype and are cast a
sub-block at a time; what is done a token at a time runs ``ROWS`` rows at a
time; attention runs one KV head's query heads at a time, each group's
outputs multiplied into its own rows of ``W_o`` and summed, in query blocks
of ``QUERY_BLOCK``, and a window layer's block is given the keys its mask
can show and no others (the scores of 32,768 x 32,768 x 64 heads are 275
GB); an expert is applied to every token and weighted by zero where it was
not chosen; a sequence longer than ``QUERY_BLOCK`` is padded to whole
blocks, which no earlier position sees; the logits go to the host
``HEAD_ROWS`` rows at a time.

Weights come as a dict (see ``adapters/mimo.reference_weights``): matrices
are [in, out]; a leaf of ``layers`` is stacked over the layers that have
it, in layer order (norms and ``o``: all; ``qkv_full``: the full layers;
``qkv_window`` and ``sink``: the window layers; ``gate``, ``up``, ``down``:
the dense layers; ``router``, ``router_bias``, ``e_*``: the routed layers,
the experts' next axis the expert).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 4096
QUERY_BLOCK = 256
HEAD_ROWS = 4096


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rotary_part(x, first, theta: float, rot: int):
    """x: [S, heads, D] at positions ``first ..``; lane i < rot/2 turns with
    lane i + rot/2 by p * theta^(-2i/rot), the lanes from ``rot`` on stay."""
    s = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = (first + jnp.arange(s)).astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def _kind(c: dict, window: bool) -> tuple:
    """What one kind of attention layer is made of, hashable."""
    d = c["swa_head_dim" if window else "head_dim"]
    return (("heads", c["swa_num_attention_heads" if window
                        else "num_attention_heads"]),
            ("kv_heads", c["swa_num_key_value_heads" if window
                           else "num_key_value_heads"]),
            ("d", d),
            ("dv", c["swa_v_head_dim" if window else "v_head_dim"]),
            ("rot", int(d * c["partial_rotary_factor"])),
            ("theta", float(c["swa_rope_theta" if window else "rope_theta"])),
            ("window", c["sliding_window"] if window else 0),
            ("value_scale", float(c["attention_value_scale"])),
            ("eps", float(c["layernorm_epsilon"])))


def _by_rows(fn, x):
    """``fn`` on ``ROWS`` rows of x at a time."""
    return jnp.concatenate([fn(x[r0:r0 + ROWS])
                            for r0 in range(0, x.shape[0], ROWS)], axis=0)


@functools.partial(jax.jit, static_argnames=("k",))
def _keys_values(k, x, first, norm_w, w_k, w_v):
    """x: [rows, hidden] at positions ``first ..`` -> the rotated keys
    [rows, KV heads, D] and the scaled values [rows, KV heads, Dv]."""
    kd = dict(k)
    with jax.default_matmul_precision("highest"):
        xn = rms_norm(x, norm_w.astype(F32), kd["eps"])
        keys = (xn @ w_k.astype(F32)).reshape(-1, kd["kv_heads"], kd["d"])
        values = kd["value_scale"] * (xn @ w_v.astype(F32))
    return (rotary_part(keys, first, kd["theta"], kd["rot"]),
            values.reshape(-1, kd["kv_heads"], kd["dv"]))


@functools.partial(jax.jit, static_argnames=("k",))
def _group(k, x, norm_w, w_q, keys, values, w_o, sink):
    """The attention of one KV head's query heads, through their rows of
    ``W_o``: x [S, hidden], w_q [hidden, G * D], keys [S, D], values [S,
    Dv], w_o [G * Dv, hidden], sink [G] or None -> [S, hidden]."""
    kd = dict(k)
    d, dv, window = kd["d"], kd["dv"], kd["window"]
    s = x.shape[0]
    g = w_q.shape[1] // d
    block = min(QUERY_BLOCK, s)
    # A window layer's block of queries sees at most the ``window - 1``
    # positions before it and its own: those keys, laid behind ``window``
    # rows that are nobody's.
    span = block + window if window else s
    if window:
        keys, values = (jnp.pad(a, ((window, 0), (0, 0)))
                        for a in (keys, values))

    def one_block(q0):
        with jax.default_matmul_precision("highest"):
            xn = rms_norm(jax.lax.dynamic_slice_in_dim(x, q0, block),
                          norm_w.astype(F32), kd["eps"])
            q = rotary_part((xn @ w_q.astype(F32)).reshape(block, g, d), q0,
                            kd["theta"], kd["rot"])
            qpos = (q0 + jnp.arange(block))[:, None]
            if window:
                kb = jax.lax.dynamic_slice_in_dim(keys, q0, span)
                vb = jax.lax.dynamic_slice_in_dim(values, q0, span)
                kpos = (q0 - window + jnp.arange(span))[None, :]
                seen = (kpos <= qpos) & (kpos > qpos - window) & (kpos >= 0)
            else:
                kb, vb = keys, values
                seen = jnp.arange(s)[None, :] <= qpos
            scores = jnp.einsum("qhd,kd->hqk", q, kb) / math.sqrt(d)
            scores = jnp.where(seen[None], scores, -jnp.inf)
            if sink is None:
                probs = jax.nn.softmax(scores, axis=-1)
            else:
                at = sink.astype(F32)[:, None, None]
                top = jnp.maximum(scores.max(axis=-1, keepdims=True), at)
                e = jnp.exp(scores - top)
                probs = e / (e.sum(axis=-1, keepdims=True)
                             + jnp.exp(at - top))
            out = jnp.einsum("hqk,kd->qhd", probs, vb)
            return out.reshape(block, g * dv) @ w_o.astype(F32)

    return jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, -1)


def attention(c: dict, window: bool, x, norm_w, w_qkv, w_o, sink):
    """x: [S, hidden] -> x + Attn(N(x)) for one layer of a kind."""
    k = _kind(c, window)
    kd = dict(k)
    nh, nkv, d, dv = kd["heads"], kd["kv_heads"], kd["d"], kd["dv"]
    g = nh // nkv
    w_q, w_k, w_v = (w_qkv[:, :nh * d], w_qkv[:, nh * d:(nh + nkv) * d],
                     w_qkv[:, (nh + nkv) * d:])
    parts = [_keys_values(k, x[r0:r0 + ROWS], r0, norm_w, w_k, w_v)
             for r0 in range(0, x.shape[0], ROWS)]
    keys, values = (jnp.concatenate(p, axis=0) for p in zip(*parts))
    out = x
    for h in range(nkv):
        out = out + _group(
            k, x, norm_w, w_q[:, h * g * d:(h + 1) * g * d], keys[:, h],
            values[:, h], w_o[h * g * dv:(h + 1) * g * dv],
            None if sink is None else sink[h * g:(h + 1) * g])
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


@functools.partial(jax.jit, static_argnames=("k", "renormalize", "factor"))
def gate_weights(u, router, bias, k: int, renormalize: bool, factor: float):
    """[S, routed] float32: the weight of every routed expert for every
    token, 0 where it was not chosen."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(u @ router.astype(F32))
    rows = jnp.arange(s.shape[0])[:, None]
    chosen = jnp.argsort(-(s + bias.astype(F32)), axis=-1)[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if renormalize:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(s).at[rows, chosen].set(picked * factor)


def held_experts(c: dict) -> tuple[int, int]:
    """(the first held expert, the routed experts in the whole model)."""
    held = c["n_routed_experts"]
    total = c.get("published", {}).get("n_routed_experts", held)
    shards = c.get("expert_shards", 1)
    if held * shards != total:
        raise ValueError(f"{held} experts held x {shards} shards is not "
                         f"the model's {total}")
    return c.get("expert_shard", 0) * held, total


def routed_experts(c: dict, u, w, r: int):
    """The held experts' terms of routed layer ``r`` on u [rows, hidden]."""
    lo, _ = held_experts(c)
    factor = c.get("routed_scaling_factor")
    weights = gate_weights(u, w["router"][r], w["router_bias"][r],
                           c["num_experts_per_tok"],
                           bool(c["norm_topk_prob"]),
                           1.0 if factor is None else float(factor))
    out = jnp.zeros_like(u)
    for e in range(w["e_gate"].shape[1]):                     # held experts
        y = _swiglu(u, w["e_gate"][r, e], w["e_up"][r, e], w["e_down"][r, e])
        out = out + weights[:, lo + e][:, None] * y
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def logits(c: dict, weights: dict, tokens) -> np.ndarray:
    """tokens [S] -> logits [S, V] in float32, on the host."""
    lw, eps = weights["layers"], float(c["layernorm_epsilon"])
    s = tokens.shape[0]
    if s > QUERY_BLOCK:
        tokens = jnp.pad(tokens, (0, -s % QUERY_BLOCK))
    x = weights["embed"][tokens].astype(F32)
    seen = {"full": 0, "window": 0, "dense": 0, "routed": 0}
    for l in range(c["num_hidden_layers"]):
        window = bool(c["hybrid_layer_pattern"][l])
        routed = bool(c["moe_layer_freq"][l])
        kind, ffn = "window" if window else "full", \
            "routed" if routed else "dense"
        at, r = seen[kind], seen[ffn]
        sink = (lw["sink"][at] if c["add_swa_attention_sink_bias" if window
                                    else "add_full_attention_sink_bias"]
                else None)
        a = attention(c, window, x, lw["attn_norm"][l], lw["qkv_" + kind][at],
                      lw["o"][l], sink)

        def ffn_rows(rows):
            u = _norm(rows, lw["post_norm"][l], eps)
            if routed:
                return rows + routed_experts(c, u, lw, r)
            return rows + _swiglu(u, lw["gate"][r], lw["up"][r],
                                  lw["down"][r])

        x = _by_rows(ffn_rows, a)
        seen[kind] += 1
        seen[ffn] += 1
    return np.concatenate(
        [np.asarray(_head(x[r0:min(r0 + HEAD_ROWS, s)], weights["final_norm"],
                          weights["head"], eps))
         for r0 in range(0, s, HEAD_ROWS)], axis=0)
