"""Plain reference: Keye-VL-2.0's language model, a Qwen3-MoE decoder whose
attention reads the positions a learned indexer chooses (DeepSeek sparse
attention).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no batching, no threshold in the sort's place, no sorting
of tokens by expert. It follows the equations of ``modeling_qwen3_moe.py``
and of the DeepSeek-V3.2-Exp report's indexer as
``benchmark/configs/keye-vl-2.0-30b-a3b.json`` states them under
``assumed``; it shares no code with the program and is never given the
program's choices.

One layer, input ``h``, ``N`` an RMSNorm with float32 statistics::

    h = h + attn(N(h; input_layernorm))
    h = h + moe(N(h; post_attention_layernorm))

``attn``: ``q, k, v`` without bias; an RMSNorm over each head's values of
``q`` and of ``k`` before the rotation of the two halves (``rotate_half``).
**The indexer**, from the same normed input ``x``: ``qI = x W_qI``
(``indexer_num_heads`` heads of ``indexer_head_dim``), ``kI = LayerNorm(x
W_kI)`` (one key a position, weight and bias), ``w = x W_w *
indexer_num_heads^-1/2 * indexer_head_dim^-1/2``; the first half of each
index head and of ``kI`` is rotated by the position (halves within that
half), the rest is not; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
for ``s <= t``. **The selection**: query ``t`` attends all ``s <= t`` where
they are no more than ``topk``, else the ``topk`` positions of largest
``I[t, s]`` as ``jax.lax.top_k`` returns them (equal scores: the lower
position first), the same set for every head. Softmax of ``q . k /
sqrt(head_dim)`` over the set, query head h on KV head ``h // group``; the
heads' outputs concatenated into ``W_o``. ``moe``: ``p = softmax(u W_g)``
over all the router's outputs, the ``num_experts_per_tok`` largest chosen,
their weights divided by their sum (``norm_topk_prob``); an expert is
``down(silu(gate u) * (up u))``; the experts the weights hold are
``expert_shard`` of ``expert_shards`` equal shares of the router's outputs,
and a pick that falls on an expert held elsewhere adds nothing here. After
the last layer ``N(h; norm)`` and the head, a matrix of its own.

Departures from a literal transcription, none of which changes a value in
exact arithmetic: weights stay in their stored dtype and are cast a
sub-block at a time; the index scores, the selection and the attention run
in query blocks of 512 (``[512, S]`` a block), the attention a KV head at a
time; an expert is applied to every token and weighted by zero where it was
not chosen.

Weights come as a dict (see ``adapters/keye.reference_weights``): matrices
are [in, out]; every leaf of ``layers`` is stacked over the layers (the
experts' next axis is the held expert).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
HEAD_ROWS = 4096


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def rotary_halves(x, theta, positions):
    """x: [S, heads, D]; position p rotates the pair (i, i + D/2) by
    p * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _static(c: dict) -> tuple:
    if c.get("attention_bias") or c.get("use_sliding_window"):
        raise ValueError("attention_bias / use_sliding_window: the "
                         "reference has neither")
    if c.get("mlp_only_layers") or c.get("decoder_sparse_step", 1) != 1:
        raise ValueError("a layer that is not routed: the reference has "
                         "none")
    sa = c["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the reference's indexer has one key a position")
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "num_experts_per_tok",
            "norm_topk_prob")
    return tuple((k, c[k]) for k in keys) + (
        ("rope_theta", float(c["rope_theta"])),
        ("index_heads", sa["indexer_num_heads"]),
        ("index_dim", sa["indexer_head_dim"]), ("topk", sa["topk"]),
        ("expert_shard", int(c.get("expert_shard", 0))),
        ("router_outputs", int(c.get("published", c)["num_experts"])))


@functools.partial(jax.jit, static_argnames=("c",))
def _projections(c, x, norm_w, w):
    """x [S, hidden] -> q [S, nh, D], k and v [S, nkv, D] (q and k normed a
    head and rotated), qI [S, J, Di], kI [S, Di] (their first halves
    rotated), wI [S, J]."""
    cd = dict(c)
    nh, nkv, d, eps, theta = (
        cd["num_attention_heads"], cd["num_key_value_heads"], cd["head_dim"],
        cd["rms_norm_eps"], cd["rope_theta"])
    heads, di = cd["index_heads"], cd["index_dim"]
    s = x.shape[0]
    at = jnp.arange(s)
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        u = rms_norm(x, norm_w.astype(F32), eps)
        q = rms_norm((u @ w["q"]).reshape(s, nh, d), w["q_norm"], eps)
        k = rms_norm((u @ w["k"]).reshape(s, nkv, d), w["k_norm"], eps)
        v = (u @ w["v"]).reshape(s, nkv, d)
        qi = (u @ w["index_q"]).reshape(s, heads, di)
        ki = layer_norm(u @ w["index_k"], w["index_k_norm"],
                        w["index_k_bias"], eps)[:, None, :]
        wi = (u @ w["index_w"]) * (heads ** -0.5 * di ** -0.5)
    rot = di // 2
    qi = jnp.concatenate(
        [rotary_halves(qi[..., :rot], theta, at), qi[..., rot:]], axis=-1)
    ki = jnp.concatenate(
        [rotary_halves(ki[..., :rot], theta, at), ki[..., rot:]], axis=-1)
    return (rotary_halves(q, theta, at), rotary_halves(k, theta, at), v,
            qi, ki[:, 0], wi)


@functools.partial(jax.jit, static_argnames=("c",))
def _selected(c, qi, wi, ki, q0):
    """A block of queries at positions ``q0 + arange(R)`` against every key:
    bool [R, S], each row's set."""
    cd = dict(c)
    r, s = qi.shape[0], ki.shape[0]
    with jax.default_matmul_precision("highest"):
        scores = jnp.zeros((r, s), F32)
        for j in range(cd["index_heads"]):
            scores = scores + wi[:, j][:, None] * jax.nn.relu(
                qi[:, j] @ ki.T)
    causal = jnp.arange(s)[None, :] <= (q0 + jnp.arange(r))[:, None]
    topk = cd["topk"]
    if s <= topk:
        return causal
    # A tie is between equal numbers: 0.0 and -0.0 (a sum of w relu(.)
    # gives either) are one, where top_k's total order would tell them apart.
    scores = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, chosen = jax.lax.top_k(scores, topk)
    picked = jnp.zeros((r, s), bool).at[
        jnp.arange(r)[:, None], chosen].set(True)
    # A row that sees fewer than topk positions is handed some it does not
    # see to fill the count: it attends what it sees.
    return picked & causal


@functools.partial(jax.jit, static_argnames=("c",))
def _attend(c, q, k, v, keep):
    """softmax(q . k / sqrt(D)) v over each row's set; q [R, nh, D], k and
    v [S, nkv, D], keep [R, S] -> [R, nh * D]. A KV head at a time."""
    cd = dict(c)
    group = cd["num_attention_heads"] // cd["num_key_value_heads"]
    outs = []
    with jax.default_matmul_precision("highest"):
        for g in range(cd["num_key_value_heads"]):
            qs = q[:, g * group:(g + 1) * group]
            scores = jnp.einsum("qhd,kd->hqk", qs, k[:, g]) \
                / math.sqrt(cd["head_dim"])
            probs = jax.nn.softmax(
                jnp.where(keep[None], scores, -jnp.inf), -1)
            outs.append(jnp.einsum("hqk,kd->qhd", probs, v[:, g]))
    return jnp.concatenate(outs, axis=1).reshape(q.shape[0], -1)


@jax.jit
def _out(x, o, wo):
    with jax.default_matmul_precision("highest"):
        return x + o @ wo.astype(F32)


def attention(c: tuple, x, norm_w, w, sets: list | None = None):
    """x: [S, hidden] -> x + Attention(N(x)). ``sets``, when given, collects
    the blocks' sets, bool [R, S] each."""
    q, k, v, qi, ki, wi = _projections(
        c, x, norm_w, {n: w[n] for n in w if n != "o"})
    outs = []
    for q0 in range(0, x.shape[0], QUERY_BLOCK):
        rows = slice(q0, q0 + QUERY_BLOCK)
        keep = _selected(c, qi[rows], wi[rows], ki, q0)
        if sets is not None:
            sets.append(keep)
        outs.append(_attend(c, q[rows], k, v, keep))
    return _out(x, jnp.concatenate(outs), w["o"])


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
def _route(c, u, router):
    """[S, router outputs] float32: an expert's weight where it was chosen,
    0 elsewhere."""
    cd = dict(c)
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(u @ router.astype(F32), axis=-1)
    chosen = jnp.argsort(-p, axis=-1)[:, :cd["num_experts_per_tok"]]
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    if cd["norm_topk_prob"]:
        picked = picked / picked.sum(axis=-1, keepdims=True)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, chosen].set(picked)


def moe(c: tuple, u, w, layer: int):
    """The routed layer on u [S, hidden]: the held experts' part."""
    weights = _route(c, u, w["router"][layer])
    held = w["e_gate"].shape[1]
    first = dict(c)["expert_shard"] * held
    out = jnp.zeros_like(u)
    for e in range(held):
        y = _swiglu(u, w["e_gate"][layer, e], w["e_up"][layer, e],
                    w["e_down"][layer, e])
        out = out + weights[:, first + e][:, None] * y
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


ATTENTION_LEAVES = ("q", "k", "v", "o", "q_norm", "k_norm", "index_q",
                    "index_k", "index_w", "index_k_norm", "index_k_bias")


def logits(c: dict, weights: dict, tokens, sets: list | None = None
           ) -> jax.Array:
    """tokens [S] -> float32 logits [S, V]: row ``p`` chooses token
    ``p + 1``. ``sets``, when given, collects every layer's list of blocks'
    sets. Tokens appended after a sequence are inert: the model is causal."""
    st, w, eps = _static(c), weights["layers"], c["rms_norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    x = weights["embed"][tokens].astype(F32)
    for layer in range(w["input_norm"].shape[0]):
        kept = None if sets is None else []
        x = attention(st, x, w["input_norm"][layer],
                      {k: w[k][layer] for k in ATTENTION_LEAVES}, kept)
        if sets is not None:
            sets.append(kept)
        x = x + moe(st, _norm(x, w["post_attention_norm"][layer], eps), w,
                    layer)
    s = tokens.shape[0]
    return jnp.concatenate(
        [_head(x[r0:r0 + HEAD_ROWS], weights["final_norm"], weights["head"],
               eps) for r0 in range(0, s, HEAD_ROWS)], axis=0)
