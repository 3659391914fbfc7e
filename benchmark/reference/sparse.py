"""Plain reference: a sparse-expert decoder (Mixtral-8x7B) forward pass
and loss, in the manner of ``reference/dense.py`` (float32, ``highest``
precision, no kernels, one layer's weights at a time).

The attention block is the dense one. The expert block follows the
published model (``modeling_mixtral.py``): a linear router over the
hidden state, softmax, the top ``num_experts_per_tok`` experts, their
gates renormalised to sum to 1, each expert a SwiGLU MLP. Every expert is
applied to every token and the result weighted (zero where not routed):
plain, at four times the arithmetic.

Two departures of the *program* from the published model are mirrored
here, because the comparison is of the program's loss (the configuration
file lists them under ``departures``):

- capacity routing: each expert takes at most
  ceil(capacity_factor * top_k * T / E) claims of the T tokens of the
  whole batch, in token order (first choice before second); later claims
  are dropped and the token keeps its residual;
- the auxiliary load-balancing loss, E * sum_e(share of kept claims on e *
  mean router probability of e), averaged over layers and added with
  ``router_aux_loss_coef``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference import dense

F32 = jnp.float32


def routing(c: dict, router_logits, capacity: int):
    """[T, E] logits -> (weights [T, E] with the renormalised gate of each
    kept claim, aux loss)."""
    t, e = router_logits.shape
    k = c["num_experts_per_tok"]
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                       # [T, K]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    claims = jax.nn.one_hot(idx, e, dtype=jnp.int32).reshape(t * k, e)
    before = jnp.cumsum(claims, axis=0) - claims              # earlier claims
    position = (before * claims).sum(-1).reshape(t, k)
    kept = position < capacity
    onehot = jax.nn.one_hot(idx, e, dtype=F32)                # [T, K, E]
    weights = jnp.einsum("tk,tke->te", gate * kept, onehot)
    kept_per_expert = jnp.einsum("tk,tke->e", kept.astype(F32), onehot)
    share = kept_per_expert / jnp.maximum(kept_per_expert.sum(), 1.0)
    aux = e * jnp.sum(share * probs.mean(0))
    return weights, aux


@functools.partial(jax.jit, static_argnames=("c",))
def _attn_block(c, x, w):
    """x: [B, S, hidden]; attention one sequence at a time."""
    cd = dict(c)
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        rows = [xb + dense.attention(
            cd, dense.rms_norm(xb, w["attn_norm"], cd["rms_norm_eps"]), w)
            for xb in x]
        return jnp.stack(rows)


@functools.partial(jax.jit, static_argnames=("c", "capacity"))
def _route(c, capacity, xn, router):
    cd = dict(c)
    with jax.default_matmul_precision("highest"):
        return routing(cd, xn @ router.astype(F32), capacity)


@jax.jit
def _expert(xn, weight, gate, up, down):
    with jax.default_matmul_precision("highest"):
        gate, up, down = (a.astype(F32) for a in (gate, up, down))
        y = (jax.nn.silu(xn @ gate) * (xn @ up)) @ down
        return y * weight[:, None]


def _static(c: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "rms_norm_eps", "num_experts_per_tok")
    return tuple((k, c[k]) for k in keys)


def capacity(c: dict, n_tokens: int) -> int:
    factor = c["departures"]["capacity_factor"]["value"]
    return max(1, int(math.ceil(
        factor * c["num_experts_per_tok"] * n_tokens
        / c["num_local_experts"])))


def hidden(c: dict, weights: dict, tokens):
    """tokens [B, S] -> (final hidden states [B, S, hidden] before the last
    norm, mean aux loss)."""
    b, s = tokens.shape
    x = weights["embed"][tokens].astype(F32)
    lay = weights["layers"]
    n_layers, n_experts = lay["gate"].shape[:2]
    cap = capacity(c, b * s)
    aux_total = 0.0
    for l in range(n_layers):
        attn_w = {k: lay[k][l] for k in ("q", "k", "v", "o", "attn_norm")}
        x = _attn_block(_static(c), x, attn_w)
        xn = dense.rms_norm(x, lay["mlp_norm"][l].astype(F32),
                            c["rms_norm_eps"]).reshape(b * s, -1)
        weight, aux = _route(_static(c), cap, xn, lay["router"][l])
        y = jnp.zeros_like(xn)
        for e in range(n_experts):
            y = y + _expert(xn, weight[:, e], lay["gate"][l, e],
                            lay["up"][l, e], lay["down"][l, e])
        x = x + y.reshape(b, s, -1)
        aux_total += float(aux)
    return x, aux_total / n_layers


def logits(c: dict, weights: dict, tokens) -> jax.Array:
    """tokens [S] -> logits [S, V]: one sequence as a batch of one."""
    x, _aux = hidden(c, weights, tokens[None])
    return dense._head(x[0], weights["final_norm"], weights["head"],
                       c["rms_norm_eps"])


def loss(c: dict, weights: dict, tokens, targets) -> float:
    x, aux = hidden(c, weights, tokens)
    total, count = 0.0, 0
    for b in range(tokens.shape[0]):
        lg = dense._head(x[b], weights["final_norm"], weights["head"],
                         c["rms_norm_eps"])
        logp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[b][:, None], axis=-1)
        total += float(nll.sum())
        count += int(targets[b].shape[0])
    return total / count + c["router_aux_loss_coef"] * aux
