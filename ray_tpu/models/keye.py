"""Keye-VL-2.0's language model: a Qwen3-MoE decoder whose attention reads
the positions a learned indexer chooses.

The family of ``model_type: "KeyeVL2"`` (huggingface.co/Kwai-Keye/
Keye-VL-2.0-30B-A3B), text alone: the vision tower is not here (the
published configuration gives it no size), and with text alone the three
components of its multimodal rotary are equal, so the rotary is the plain
one. The block is models/sdar.py's (:func:`sdar.layer`, causal and a token a
step where SDAR is block-causal): ``h = h + attn(N(h))``, ``h = h +
moe(N(h))``, GQA without bias whose queries and keys are RMS-normed a head
before the half-rotated RoPE, a routed layer in every layer (softmax over
all experts, the 8 largest, renormalised, nothing dropped; this program
computes the part of the held experts, ``expert_shard`` of
``expert_shards``), one RMSNorm after the last layer and a head of its own.

What is Keye's is ``sa_config``, the DeepSeek sparse attention indexer, in
every layer (:func:`indexer`): from the layer's normed input ``x``

- ``qI = x W_qI``, ``index_heads`` heads of ``index_head_dim``;
- ``kI = LayerNorm(x W_kI)``, one key of ``index_head_dim`` a position;
- ``w = x W_w * index_heads^-1/2 * index_head_dim^-1/2``, a weight a head;
- the first ``index_rope_dim`` values of ``qI`` (each head) and of ``kI``
  rotated by the position (half-split within them), the rest not;

``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` in float32 for ``s <= t``,
and query ``t`` attends, in all its heads alike, the ``index_topk``
positions of largest ``I[t, s]`` (a tie to the lower position; all it sees
where they are no more). ops/sparse_attention.py computes the three steps
against a slot cache and llm/keye_serving.py keeps ``kI`` in a cache leaf of
its own; :func:`forward` here is the pass over whole sequences with no
cache.

Params are a flat pytree; every leaf of ``layers`` is stacked over the
layers, which are all alike.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import sdar
from ray_tpu.models.lfm2 import attention_heads
from ray_tpu.models.routed import RouterRule, layer_of
from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.rope import apply_rope_partial, rope_frequencies
from ray_tpu.util import tracing

INDEXER_LEAVES = ("wi_q", "wi_k", "wi_w", "ik_norm", "ik_bias")
# What the programs count of the selection, beside the routed layers'
# counts: rows that scored (a row a layer), the positions they saw, and the
# positions they kept, ``min(seen, index_topk)`` each; then the last two
# again for a decode step's rows alone, each of which reads its line for
# itself (a chunk's rows share theirs).
INDEX_COUNTERS = ("index_rows", "index_positions_scored",
                  "index_positions_selected", "index_step_positions_scored",
                  "index_step_positions_selected")
# Seeded weights only: the embedding's deviation (:func:`init_params`).
EMBED_SIZE = 1.0


@dataclass(frozen=True)
class KeyeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768       # one expert
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    # What this program holds of the routed experts (models/routed.py).
    expert_shard: int = 0
    expert_shards: int = 1
    # sa_config: the indexer.
    index_heads: int = 16
    index_head_dim: int = 64
    index_rope_dim: int = 32               # rotated values of an index head
    index_topk: int = 2048
    max_seq_len: int = 262144
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not 0 < self.index_rope_dim <= self.index_head_dim \
                or self.index_rope_dim % 2:
            raise ValueError(
                f"index_rope_dim {self.index_rope_dim}: an even number up "
                f"to index_head_dim {self.index_head_dim}")
        if self.index_topk < 1:
            raise ValueError(f"index_topk {self.index_topk}: at least 1")
        self.router_rule     # a share that does not divide is said here

    @staticmethod
    def tiny(**kw) -> "KeyeConfig":
        """Test-size config: 3 layers, 8 experts of 32 with 2 a token, 4
        index heads of 16 and the 8 best positions, fewer than a test's
        context."""
        base = dict(vocab_size=512, hidden_size=64, moe_intermediate_size=32,
                    num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16,
                    num_experts=8, num_experts_per_tok=2, index_heads=4,
                    index_head_dim=16, index_rope_dim=8, index_topk=8,
                    max_seq_len=256, dtype="float32")
        base.update(kw)
        return KeyeConfig(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def experts_held(self) -> int:
        return self.router_rule.held

    @property
    def router_rule(self) -> RouterRule:
        return RouterRule(
            experts=self.num_experts, topk=self.num_experts_per_tok,
            score="softmax", use_bias=False,
            renormalize=self.norm_topk_prob, renorm_eps=0.0,
            expert_shard=self.expert_shard,
            expert_shards=self.expert_shards)

    def indexer_params(self) -> int:
        h, di = self.hidden_size, self.index_head_dim
        return h * (self.index_heads * di + di + self.index_heads) + 2 * di

    def num_params(self) -> int:
        """Parameters held here (this shard's experts)."""
        h, d = self.hidden_size, self.head_dim
        attn = (2 * h * self.num_heads * d + 2 * h * self.num_kv_heads * d
                + 2 * d)
        routed = (h * self.num_experts
                  + self.experts_held * 3 * h * self.moe_intermediate_size)
        return (self.num_layers * (attn + self.indexer_params() + routed
                                   + 2 * h)
                + 2 * self.vocab_size * h + h)


def param_logical_axes(cfg: KeyeConfig) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules)."""
    axes = sdar.param_logical_axes(cfg)
    axes["layers"].update({
        "wi_q": ("layers", "embed", None),
        "wi_k": ("layers", "embed", None),
        "wi_w": ("layers", "embed", None),
        "ik_norm": ("layers", None),
        "ik_bias": ("layers", None),
    })
    return axes


def init_params(cfg: KeyeConfig, key: jax.Array) -> dict:
    """models/sdar.init_params (every projection's output at unit variance,
    the norms near 1, the experts' down-projections scaled down by depth)
    with this shard's experts, the indexer's leaves, and an embedding of
    unit size.

    **The embedding.** models/sdar.py draws it at 0.02, which the first
    norm undoes; the residual stream keeps the 0.02, and from the second
    layer on it is made of what the attentions and the experts added. With
    seeded weights an attention over thousands of positions is a mean of
    unrelated values: what the positions have in common survives the mean,
    what tells them apart is divided by ``sqrt(n)``, and neighbouring
    queries read nearly the same. A stream made of such means is the same
    at every position: the first seeding of this model (the values'
    projection 16 times larger, so that the selected set would show) chose
    one token at every position, and the fp8 control and the selection left
    out both read exactly 0 on some seeds (PERF.md section 6). At
    ``EMBED_SIZE`` a token's own row carries the stream as a trained
    model's does, the logits differ from position to position, and the
    attentions' part (a tenth of the stream over twelve layers) is what a
    wrong set moves.

    The indexer's projections are of unit output variance; its key's
    LayerNorm has a weight near 1 and a bias near 0, both drawn."""
    kb, ki = jax.random.split(key)
    # The expert stacks models/sdar.py draws are this shard's; the router,
    # drawn below, scores every expert of the model whatever is held here.
    params = sdar.init_params(
        replace(cfg, num_experts=cfg.experts_held, expert_shards=1,
                expert_shard=0), kb)
    lay = params["layers"]
    h, L = cfg.hidden_size, cfg.num_layers
    keys = iter(jax.random.split(ki, 8))
    dt = cfg.jnp_dtype

    def matrix(*shape, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    lay["router"] = matrix(L, h, cfg.num_experts, dtype=jnp.float32)
    params["embed_tokens"] = (params["embed_tokens"].astype(jnp.float32)
                              * (EMBED_SIZE / 0.02)).astype(dt)
    di = cfg.index_head_dim
    lay["wi_q"] = matrix(L, h, cfg.index_heads * di)
    lay["wi_k"] = matrix(L, h, di)
    lay["wi_w"] = matrix(L, h, cfg.index_heads)
    lay["ik_norm"] = (1.0 + 0.1 * jax.random.normal(
        next(keys), (L, di), jnp.float32)).astype(dt)
    lay["ik_bias"] = (0.1 * jax.random.normal(
        next(keys), (L, di), jnp.float32)).astype(dt)
    return params


# ---------------------------------------------------------------- blocks

def indexer(cfg: KeyeConfig, ip: dict, xn, positions):
    """xn [B, S, H] (the layer's normed input) at ``positions`` ([S] or
    [B, S]) -> the index queries [B, J, S, Di] and key [B, S, Di], rotated
    in their first ``index_rope_dim`` values, in xn's dtype, and the heads'
    weights [B, J, S] float32."""
    b, s, _ = xn.shape
    heads, di = cfg.index_heads, cfg.index_head_dim
    q, k, w = lax.optimization_barrier(
        (xn @ ip["wi_q"], xn @ ip["wi_k"], xn @ ip["wi_w"]))
    q = q.reshape(b, s, heads, di).transpose(0, 2, 1, 3)
    kf = k.astype(jnp.float32)
    mean = kf.mean(axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(kf - mean), axis=-1, keepdims=True)
    k = ((kf - mean) * lax.rsqrt(var + cfg.norm_eps)
         * ip["ik_norm"].astype(jnp.float32)
         + ip["ik_bias"].astype(jnp.float32)).astype(xn.dtype)
    inv_freq = rope_frequencies(cfg.index_rope_dim, cfg.rope_theta)
    q = apply_rope_partial(q, positions, inv_freq)
    k = apply_rope_partial(k[:, None], positions, inv_freq)[:, 0]
    w = (w.astype(jnp.float32).transpose(0, 2, 1)
         * (heads ** -0.5 * di ** -0.5))
    return q, k, w


def indexer_leaves(layers: dict, index) -> dict:
    """Layer ``index``'s indexer leaves of the stacked ``params["layers"]``."""
    with tracing.part("stack"):
        return {k: layer_of(layers[k], index) for k in INDEXER_LEAVES}


def index_counts(cfg: KeyeConfig, seen, step: bool):
    """int32[5] in the order of INDEX_COUNTERS for ONE layer: ``seen`` int32
    [...], the positions each scoring row saw (0: a row that did not
    score); ``step``: whether the rows are a decode step's."""
    seen = seen.reshape(-1)
    kept = jnp.minimum(seen, cfg.index_topk).sum()
    return jnp.stack([(seen > 0).sum(), seen.sum(), kept,
                      seen.sum() * step, kept * step]).astype(jnp.int32)


def forward(cfg: KeyeConfig, params: dict, tokens, *,
            kmesh: KernelMesh | None = None, picks: list | None = None):
    """tokens [B, S] -> (float32 logits [B, S, V], router counts int32[6]).
    Whole causal sequences, no cache: the index scores of every pair, each
    row's set by :func:`sa.topk_threshold_reference`, a softmax under the
    set's mask. ``picks``, when given (and the call is not traced), collects
    every layer's sets, bool [B, S, S]."""
    b, s = tokens.shape
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    with tracing.part("attn"):
        positions = jnp.arange(s)
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)
        causal = positions[None, :] <= positions[:, None]
    group = cfg.num_heads // cfg.num_kv_heads

    def attention(index, ap, xn, state):
        q, k, v = attention_heads(cfg, ap, xn, positions, inv_freq)
        qi, ki, w = indexer(cfg, indexer_leaves(params["layers"], index), xn,
                            positions)
        dots = jnp.einsum("bjtd,bsd->bjts", qi, ki,
                          preferred_element_type=jnp.float32)
        scores = jnp.sum(w[..., None] * jnp.maximum(dots, 0.0), axis=1)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        thr, pcut = sa.topk_threshold_reference(
            scores.reshape(b * s, s), cfg.index_topk)
        keep = sa.kept(scores, thr.reshape(b, s), pcut.reshape(b, s))
        if state is not None:
            state = state.at[index].set(keep)
        k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        logits = jnp.where(keep[:, None], logits / math.sqrt(cfg.head_dim),
                           -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(xn.dtype)
        o = jnp.einsum("bhqk,bhkd->bqhd", probs, v).reshape(b, s, -1)
        return (o @ ap["wo"]).astype(xn.dtype), state

    state = (None if picks is None
             else jnp.zeros((cfg.num_layers, b, s, s), bool))
    x, state, counts = sdar.run_layers(cfg, params, x, attention, state,
                                       jnp.ones(tokens.shape, bool), kmesh)
    if picks is not None:
        picks.extend(state)
    return sdar.lm_head(cfg, params, x, kmesh), counts
