"""Plain reference: SDAR-MoE (a Qwen3-MoE decoder under a block-causal mask)
and its generation by diffusion over blocks.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no batching, no sorting of tokens by expert. It follows
the equations of the family's ``modeling_sdar_moe.py`` and ``generate.py``
as ``benchmark/configs/sdar-30b-a3b-chat.json`` states them under
``assumed``; it shares no code with the program and is never given the
program's choices.

One layer, input ``h``, ``N`` an RMSNorm with float32 statistics::

    h = h + attn(N(h; input_layernorm))
    h = h + moe(N(h; post_attention_layernorm))

``attn``: ``q, k, v`` without bias; an RMSNorm over each head's values of
``q`` and of ``k`` before the rotation of the two halves (``rotate_half``);
softmax of ``q . k / sqrt(head_dim)`` over the keys a query may see, query
head h on KV head ``h // group``; the heads' outputs concatenated into
``W_o``. **The mask is block-causal**: with blocks of ``block_length``
positions, query ``i`` sees key ``j`` iff ``j // block_length <= i //
block_length``. ``moe``: ``p = softmax(u W_g)`` over all experts, the
``num_experts_per_tok`` largest chosen, their weights ``p`` at the chosen
divided by their sum (``norm_topk_prob``; nothing is added to the sum); an
expert is ``down(silu(gate u) * (up u))``. After the last layer ``N(h;
norm)`` and the head, a matrix of its own.

Generation (:func:`generate`): a prompt's whole blocks are context; then
block after block, each starting as the prompt's tokens past its last whole
block (first block only) and the mask token at every open position. A
denoising pass runs the sequence so far, and **the logits at an open
position choose that position's token** (no shift by one); the rule opens
``block_length / denoising_steps`` positions a pass; when none is open the
block is final. (The published procedure runs the final block once more to
store its K/V; with no cache there is nothing to store.)

:func:`logits` has the harness's signature and meaning (row ``p`` holds the
logits that chose token ``p + 1``) for the ``sequential`` rule, under
which the state that chose position ``i`` follows from the final sequence
alone: earlier blocks final, in ``i``'s block the positions before ``i``
final and ``i`` and those after it the mask token. So it runs one clean
pass that keeps every layer's K/V and ``block_length`` partly masked passes
(pass ``s``: position ``i`` is the mask token iff ``i % block_length >=
s``; a block's queries see the clean K/V of the blocks before it and their
own block's K/V of that pass), takes from pass ``s`` the rows with ``i %
block_length == s`` and puts row ``i`` at ``i - 1``. Tokens appended after
the sequence (the harness pads to a multiple of 512) are inert: in the pass
that a row is taken from, every position after it in its block is the mask
token whatever was there, and later blocks are never seen.

Departures from a literal transcription, none of which changes a value in
exact arithmetic: weights stay in their stored dtype and are cast a
sub-block at a time (an attention, one expert); attention runs in query
blocks of 512 (the scores of a few thousand positions against twice as many
keys do not fit beside the weights); an expert is applied to every token
and weighted by zero where it was not chosen.

Weights come as a dict (see ``adapters/sdar.reference_weights``): matrices
are [in, out]; every leaf of ``layers`` is stacked over the layers (the
experts' next axis is the expert).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
RULES = ("sequential", "low_confidence_static", "low_confidence_dynamic")


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
    if c.get("attention_bias") or c.get("use_sliding_window"):
        raise ValueError("attention_bias / use_sliding_window: the "
                         "reference has neither")
    if c.get("mlp_only_layers") or c.get("decoder_sparse_step", 1) != 1:
        raise ValueError("a layer that is not routed: the reference has "
                         "none")
    if c.get("rope_scaling"):
        raise ValueError("rope_scaling: the reference rotates unscaled")
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "block_length")
    return tuple((k, c[k]) for k in keys) + (
        ("rope_theta", float(c["rope_theta"])),)


def _heads(cd, u, w):
    """u [S, hidden] (normed) -> q [S, nh, D], k and v [S, nkv, D], q and k
    normed a head and rotated."""
    nh, nkv, d, eps = (cd["num_attention_heads"], cd["num_key_value_heads"],
                       cd["head_dim"], cd["rms_norm_eps"])
    s = u.shape[0]
    q = rms_norm((u @ w["q"]).reshape(s, nh, d), w["q_norm"], eps)
    k = rms_norm((u @ w["k"]).reshape(s, nkv, d), w["k_norm"], eps)
    v = (u @ w["v"]).reshape(s, nkv, d)
    return (rotary_halves(q, cd["rope_theta"]),
            rotary_halves(k, cd["rope_theta"]), v)


def _attend(cd, q, k, v, visible):
    """softmax(q . k / sqrt(D)) v over the keys ``visible`` [S, K] lets a
    query see; q [S, nh, D], k and v [K, nkv, D] -> [S, nh * D]."""
    group = cd["num_attention_heads"] // cd["num_key_value_heads"]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    outs = []
    for q0 in range(0, q.shape[0], QUERY_BLOCK):
        rows = slice(q0, q0 + QUERY_BLOCK)
        scores = jnp.einsum("qhd,khd->hqk", q[rows], k) \
            / math.sqrt(cd["head_dim"])
        probs = jax.nn.softmax(
            jnp.where(visible[rows][None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
    return jnp.concatenate(outs).reshape(q.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("c",))
def _attention(c, x, norm_w, w, clean=None):
    """x: [S, hidden] -> (x + Attention(N(x)), this pass's (k, v)). With
    ``clean``, another pass's (k, v) over the same positions, a query sees
    *those* rows of the blocks before its own and this pass's rows of its
    own block; without, this pass's rows throughout (the same mask)."""
    cd = dict(c)
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        u = rms_norm(x, norm_w.astype(F32), cd["rms_norm_eps"])
        q, k, v = _heads(cd, u, w)
        at = jnp.arange(u.shape[0]) // cd["block_length"]
        own, before = at[None, :] == at[:, None], at[None, :] < at[:, None]
        if clean is None:
            out = _attend(cd, q, k, v, own | before)
        else:
            # Two sets of keys side by side: the clean rows where the key's
            # block lies before the query's, this pass's inside the block.
            out = _attend(cd, q, jnp.concatenate([clean[0], k]),
                          jnp.concatenate([clean[1], v]),
                          jnp.concatenate([before, own], axis=1))
        return x + out @ w["o"], (k, v)


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
    """[S, experts] float32: an expert's weight where it was chosen, 0
    elsewhere."""
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
    """The routed layer on u [S, hidden]."""
    weights = _route(c, u, w["router"][layer])
    out = jnp.zeros_like(u)
    for e in range(w["e_gate"].shape[1]):
        y = _swiglu(u, w["e_gate"][layer, e], w["e_up"][layer, e],
                    w["e_down"][layer, e])
        out = out + weights[:, e][:, None] * y
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def _pass(c: dict, weights: dict, tokens, clean=None):
    """One pass of the stack over tokens [S]. Returns (logits [S, V], every
    layer's (k, v)). ``clean``: a clean pass's K/V, which this pass's
    queries see in the blocks before their own (see :func:`_attention`)."""
    st, w, eps = _static(c), weights["layers"], c["rms_norm_eps"]
    x = weights["embed"][tokens].astype(F32)
    kept = []
    for layer in range(c["num_hidden_layers"]):
        x, kv = _attention(
            st, x, w["input_norm"][layer],
            {k: w[k][layer] for k in ("q", "k", "v", "o", "q_norm",
                                      "k_norm")},
            None if clean is None else clean[layer])
        kept.append(kv)
        x = x + moe(st, _norm(x, w["post_attention_norm"][layer], eps), w,
                    layer)
    return _head(x, weights["final_norm"], weights["head"], eps), kept


def forward(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> float32 logits [S, V] of the clean block-causal pass:
    row ``i`` is the logits *at* position ``i``."""
    return _pass(c, weights, jnp.asarray(tokens, jnp.int32))[0]


def _open_now(rule: str, n: int, threshold: float, confidence, is_open):
    """Which open positions of the block take their token after a pass
    (plain Python over a block's few positions): ``n`` of them, or all that
    are open where fewer are."""
    open_at = [i for i, o in enumerate(is_open) if o]
    if rule == "sequential":
        return open_at[:n]
    best = sorted(open_at, key=lambda i: (-confidence[i], i))[:n]
    if rule == "low_confidence_static":
        return best
    high = [i for i in open_at if confidence[i] > threshold]
    return high if len(high) >= n else best


def generate(c: dict, weights: dict, prompt: list[int], n: int,
             strategy: str | None = None, trace: list | None = None
             ) -> list[int]:
    """``n`` tokens after ``prompt``, greedily, block by block (the module's
    docstring). Each pass runs the whole sequence so far, clean blocks and
    the current partly masked one: under the block-causal mask that is what
    a pass of the block alone against stored K/V computes. ``trace``, when
    given, collects (position, the logits row that chose its token)."""
    strategy = strategy or c["remasking_strategy"]
    if strategy not in RULES:
        raise ValueError(f"remasking_strategy {strategy!r}")
    k, mask_id = c["block_length"], c["mask_token_id"]
    per_pass = k // c["denoising_steps"]
    seq = list(prompt)
    while len(seq) < len(prompt) + n:
        start = len(seq) - len(seq) % k
        given = len(seq) - start
        block = seq[start:] + [mask_id] * (k - given)
        is_open = [False] * given + [True] * (k - given)
        while any(is_open):
            rows = forward(c, weights, seq[:start] + block)[start:]
            x0 = jnp.argmax(rows, axis=-1)
            confidence = jax.nn.softmax(rows, axis=-1)[jnp.arange(k), x0]
            for i in _open_now(strategy, per_pass,
                               c.get("confidence_threshold", 0.9),
                               [float(p) for p in confidence], is_open):
                block[i], is_open[i] = int(x0[i]), False
                if trace is not None:
                    trace.append((start + i, rows[i]))
        seq = seq[:start] + block
    return seq[len(prompt):len(prompt) + n]


def logits(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> float32 [S, V] whose row ``p`` holds the logits that
    chose token ``p + 1`` under the ``sequential`` rule (the module's
    docstring); the last row is zeros (nothing was chosen after it)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    k = c["block_length"]
    at = jnp.arange(tokens.shape[0]) % k
    _, clean = _pass(c, weights, tokens)
    chose = None
    for s in range(k):
        masked = jnp.where(at >= s, c["mask_token_id"], tokens)
        rows, _ = _pass(c, weights, masked, clean)
        chose = rows if chose is None else jnp.where(
            (at == s)[:, None], rows, chose)
    return jnp.concatenate([chose[1:], jnp.zeros_like(chose[:1])])
