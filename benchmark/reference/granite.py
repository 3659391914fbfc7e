"""Plain reference: the Granite 4.0-H forward pass (Mamba-2 layers, an
attention without positions where ``layer_types`` says so, routed experts
beside a shared SwiGLU in every layer, four multipliers).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no state handed between calls, no batching, no sorting of
tokens by expert, and Mamba-2's rule as the token-by-token recurrence (a
``lax.scan`` over the positions), never the chunked form the program runs.
It follows the equations of the family's ``modeling_granitemoehybrid.py``
(the mixer is Bamba's and ``mamba_ssm``'s ``Mamba2``) as
``benchmark/configs/granite-4.0-h-small.json`` states them under
``assumed``; it shares no code with the program and is never given the
program's choices.

``h_0 = embedding_multiplier E[token]``. Layer ``l``, input ``h``, ``N(x; w)
= x rsqrt(mean x^2 + eps) w``, ``r = residual_multiplier``::

    a  = h + r Mix_l(N(h; input_layernorm))
    h' = a + r F(N(a; post_attention_layernorm))

``Mix_l`` is the attention where ``layer_types[l] == "attention"`` and the
Mamba-2 mixer where it is ``"mamba"``.

Mamba-2 (``GraniteMoeHybridMambaLayer``): ``[z | xBC | dt] = u W_in`` (``z``
``d_inner`` = heads x head_dim wide, ``xBC`` ``d_inner + 2 n_groups
d_state``, ``dt`` a number a head; no bias). ``xBC`` passes a depthwise
causal convolution (``conv1d`` [channels, taps] **with a bias**, zeros
before position 0: ``y_t = b + sum_j w[:, j] x_{t - taps + 1 + j}``) and
``silu``, and splits into ``x`` (heads x P), ``B`` and ``C`` (``d_state``
each, one group: every head's). ``dt = softplus(dt + dt_bias)`` (no
clamp: ``time_step_limit`` is (0, inf)), ``A = -exp(A_log)``. Per head from
``S_0 = 0`` (``S`` is ``[d_state, P]``)::

    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T
    y_t = S_t^T C_t + D x_t

The output is ``W_out N(y silu(z); w)``: the gate first, then an RMSNorm
over all ``d_inner`` channels at once (one group).

Attention (``GraniteMoeHybridAttention``): no bias, no rotary and no other
positional term (``position_embedding_type`` "nope"), causal softmax of ``q
. k attention_multiplier``, query head h on KV head ``h // group``, ``W_o``.

``F`` (``GraniteMoeHybridMoE`` beside ``shared_mlp``): ``s = u W_g`` over all
experts, the ``num_experts_per_tok`` largest chosen, their weights the
softmax over the chosen logits; an expert is ``W_out (silu(g) x v)`` with
``[g | v] = W_in u`` (the gate the first half); beside the routed sum the
shared SwiGLU of the same form, added as it is. The share: the configuration
says which routed experts are held (``expert_shard`` of ``expert_shards``);
the others' terms are left out, as in the program (there is no exchange to
bring them); the shared SwiGLU is whole. After the last layer ``N``;
``logits = N(h) E^T / logits_scaling`` (the embedding tied).

Departures from a literal transcription, none of which changes a value in
exact arithmetic: weights stay in their stored dtype and are cast a
sub-block at a time; attention runs in query blocks of ``QUERY_BLOCK``; an
expert is applied to every token and weighted by zero where it was not
chosen; the head runs in blocks of positions; a sequence longer than one
query block is padded to a multiple of ``PAD_TO`` positions, which no
earlier position sees.

Weights come as a dict (see ``adapters/granite.reference_weights``):
matrices are [in, out]; a leaf of ``layers`` is stacked over the layers that
have it, in layer order (the norms, the router, the shared SwiGLU and the
routed experts: all layers; ``in_proj`` to ``out``: the Mamba layers; ``q``
to ``o``: the attention layers; the experts' next axis the expert).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256
HEAD_ROWS = 4096
# A sequence is padded to a multiple of this many positions (causal: the
# tail is inert and its rows are dropped), so that the requests of a check,
# which differ in length, meet one or two compiled shapes and not four
# (reference/qwen3_next.py's reason).
PAD_TO = 1024


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _static(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_n_groups", "mamba_d_conv", "num_experts_per_tok",
            "embedding_multiplier", "residual_multiplier",
            "attention_multiplier", "logits_scaling")
    if c["position_embedding_type"] != "nope" or c.get("rope_scaling"):
        raise ValueError("the reference has no positional term")
    if c["mamba_n_groups"] != 1:
        raise ValueError("the reference has one B and one C for all heads")
    if c["mamba_proj_bias"] or c["attention_bias"] \
            or not c["mamba_conv_bias"] or not c["tie_word_embeddings"]:
        raise ValueError("the reference has a bias on the convolution alone "
                         "and a tied head")
    held = c["num_local_experts"]
    return tuple((k, c[k]) for k in keys) + (
        ("held_from", int(c.get("expert_shard", 0)) * held),)


def ssd_rule(x, dt, a, b, c):
    """x: [S, heads, P]; dt: [S, heads]; a: [heads]; b, c: [S, N]. The
    recurrence of the module's docstring from a zero state, without the
    skip; y [S, heads, P]."""
    def token(state, row):                         # state [heads, N, P]
        x_t, dt_t, b_t, c_t = row
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + b_t[None, :, None] * (dt_t[:, None] * x_t)[:, None, :]
        return state, jnp.einsum("hnp,n->hp", state, c_t)

    zero = jnp.zeros((x.shape[1], b.shape[1], x.shape[2]), F32)
    return jax.lax.scan(token, zero, (x, dt, b, c))[1]


@functools.partial(jax.jit, static_argnames=("c",))
def _mamba(c, x, norm_w, w):
    """x: [S, hidden] -> x + r Mamba2(N(x))."""
    cd = dict(c)
    nh, p, n = cd["mamba_n_heads"], cd["mamba_d_head"], cd["mamba_d_state"]
    taps, eps = cd["mamba_d_conv"], cd["rms_norm_eps"]
    di = nh * p
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        u = rms_norm(x, norm_w.astype(F32), eps)
        s = u.shape[0]
        z, xbc, dt = jnp.split(u @ w["in_proj"], (di, 2 * di + 2 * n),
                               axis=-1)
        # Tap j meets the input shifted down by (taps - 1 - j) positions.
        xbc = jax.nn.silu(w["conv_bias"] + sum(
            w["conv"][:, j] * jnp.pad(xbc, ((taps - 1 - j, 0), (0, 0)))[:s]
            for j in range(taps)))
        xs, b, cc = jnp.split(xbc, (di, di + n), axis=-1)
        xs = xs.reshape(s, nh, p)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        y = ssd_rule(xs, dt, -jnp.exp(w["a_log"]), b, cc) \
            + w["d"][:, None] * xs
        y = y.reshape(s, di) * jax.nn.silu(z)
        y = rms_norm(y, w["norm"], eps)
        return x + cd["residual_multiplier"] * (y @ w["out"])


@functools.partial(jax.jit, static_argnames=("c",))
def _attention(c, x, norm_w, w):
    """x: [S, hidden] -> x + r Attention(N(x))."""
    cd = dict(c)
    nh, nkv = cd["num_attention_heads"], cd["num_key_value_heads"]
    d = cd["hidden_size"] // nh
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        u = rms_norm(x, norm_w.astype(F32), cd["rms_norm_eps"])
        s = u.shape[0]
        q = (u @ w["q"]).reshape(s, nh, d)
        k = jnp.repeat((u @ w["k"]).reshape(s, nkv, d), nh // nkv, axis=1)
        v = jnp.repeat((u @ w["v"]).reshape(s, nkv, d), nh // nkv, axis=1)
        block = min(QUERY_BLOCK, s)
        if s % block:
            raise ValueError(f"{s} positions are no multiple of {block}")

        def one_block(args):
            qb, q0 = args
            scores = jnp.einsum("qhd,khd->hqk", qb, k) \
                * cd["attention_multiplier"]
            causal = (jnp.arange(s)[None, :]
                      <= (q0 + jnp.arange(block))[:, None])[None]
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        out = jax.lax.map(one_block, (q.reshape(s // block, block, nh, d),
                                      jnp.arange(0, s, block)))
        return x + cd["residual_multiplier"] * (
            out.reshape(s, nh * d) @ w["o"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rms_norm(x, w.astype(F32), eps)


@jax.jit
def _glu(x, gate, up, down):
    """x: [S, in] (already normed) -> [S, in]: ``W_out (silu(g) v)`` with
    ``[g | v] = W_in x``; the published ``input_linear`` is ``gate`` and then
    ``up``, which the weights keep apart (an expert stack is not copied to
    lay them side by side)."""
    with jax.default_matmul_precision("highest"):
        gate, up, down = (a.astype(F32) for a in (gate, up, down))
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnames=("c",))
def gate_weights(c, u, router):
    """[S, experts] float32: an expert's weight where it was chosen, 0
    elsewhere."""
    cd = dict(c)
    with jax.default_matmul_precision("highest"):
        s = u @ router.astype(F32)
    chosen = jnp.argsort(-s, axis=-1)[:, :cd["num_experts_per_tok"]]
    picked = jax.nn.softmax(jnp.take_along_axis(s, chosen, axis=-1), axis=-1)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def routed_experts(c: tuple, u, w, layer: int):
    """The held experts' terms of layer ``layer`` on u [S, hidden]."""
    lo = dict(c)["held_from"]
    weights = gate_weights(c, u, w["router"][layer])
    out = jnp.zeros_like(u)
    for e in range(w["e_gate"].shape[1]):                     # held experts
        y = _glu(u, w["e_gate"][layer, e], w["e_up"][layer, e],
                 w["e_down"][layer, e])
        out = out + weights[:, lo + e][:, None] * y
    return out


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _head(x, final_norm, embed, eps, scaling):
    with jax.default_matmul_precision("highest"):
        return (rms_norm(x, final_norm.astype(F32), eps)
                @ embed.astype(F32).T) / scaling


def layer(c: tuple, kinds: tuple, h, w, l: int):
    """Layer ``l`` on h [S, hidden]: its place in the stacks of its kind is
    the number of layers of that kind before it."""
    cd = dict(c)
    at = kinds[:l].count(kinds[l])
    if kinds[l] == "mamba":
        a = _mamba(c, h, w["input_norm"][l],
                   {k: w[k][at] for k in (
                       "in_proj", "conv", "conv_bias", "dt_bias", "a_log",
                       "d", "norm", "out")})
    elif kinds[l] == "attention":
        a = _attention(c, h, w["input_norm"][l],
                       {k: w[k][at] for k in ("q", "k", "v", "o")})
    else:
        raise ValueError(f"layer_types[{l}] = {kinds[l]!r}")
    u = _norm(a, w["post_norm"][l], cd["rms_norm_eps"])
    f = _glu(u, w["s_gate"][l], w["s_up"][l], w["s_down"][l]) \
        + routed_experts(c, u, w, l)
    return a + cd["residual_multiplier"] * f


def logits(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> logits [S, V] in float32."""
    st = _static(c)
    kinds = tuple(c["layer_types"])
    s = tokens.shape[0]
    if s > QUERY_BLOCK:
        tokens = jnp.pad(tokens, (0, -s % PAD_TO))
    x = c["embedding_multiplier"] * weights["embed"][tokens].astype(F32)
    if len(kinds) != weights["layers"]["input_norm"].shape[0]:
        raise ValueError(f"{len(kinds)} layer_types for "
                         f"{weights['layers']['input_norm'].shape[0]} layers")
    for l in range(len(kinds)):
        x = layer(st, kinds, x, weights["layers"], l)
    return jnp.concatenate(
        [_head(x[r0:min(r0 + HEAD_ROWS, s)], weights["final_norm"],
               weights["embed"], c["rms_norm_eps"],
               float(c["logits_scaling"]))
         for r0 in range(0, s, HEAD_ROWS)], axis=0)
