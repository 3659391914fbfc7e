"""SDAR-MoE family: a Qwen3-MoE decoder that generates by diffusion over
blocks.

The family of ``model_type: "sdar_moe"`` (huggingface.co/JetLM/
SDAR-30B-A3B-Chat). The backbone is the Qwen3-MoE block: a layer is ``h = h
+ attn(N(h))`` then ``h = h + moe(N(h))`` with RMSNorms N; the attention is
GQA without bias whose queries and keys are RMS-normed a head (weights of
``head_dim``) before the half-rotated RoPE (``lfm2.attention_heads`` at
heads of 128); every layer's feed-forward is the routed layer of
models/routed.py: softmax over all experts, the largest ``num_experts_per_
tok`` chosen with no bias, their weights divided by their sum (nothing
added to it), no shared expert, nothing dropped. After the last layer one
RMSNorm, then a head of its own.

What is SDAR's is the mask and the way it generates. Positions are cut into
blocks of ``block_length``; position ``i`` sees position ``j`` iff ``j //
block_length <= i // block_length``: inside a block attention goes both
ways, across blocks it is causal. A block is generated whole: it starts as
the mask token at every open position, a *denoising forward* runs its rows
against the stored K/V of the blocks before it and its own rows' K/V (of its
current, partly masked content), the logits **at** an open position choose
that position's token (no shift by one), and a rule
(:func:`open_positions`) says which of the open positions take their token
now; when none is open the *commit* runs the clean block once more and its
K/V are the ones later blocks see. llm/sdar_serving.py runs that against
the engine's slot cache, the commit's rows beside the next block's first
denoising forward's; :func:`forward` here is the clean block-causal pass
over whole sequences.

Params are a flat pytree; every leaf of ``layers`` is stacked over the
layers, which are all alike.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.lfm2 import attention_heads
from ray_tpu.models.routed import MOE_COUNTERS, RouterRule, layer_of, moe_block
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import rope_frequencies
from ray_tpu.util import tracing

RULES = ("sequential", "low_confidence_static", "low_confidence_dynamic")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")


@dataclass(frozen=True)
class SdarConfig:
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
    max_seq_len: int = 32768
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    # Generation (the family's generate.py; none is a key of config.json).
    block_length: int = 4
    denoising_steps: int = 4               # forwards a block
    remasking_strategy: str = "sequential"
    confidence_threshold: float = 0.9      # the dynamic rule's
    mask_token_id: int = 151669
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.remasking_strategy not in RULES:
            raise ValueError(f"remasking_strategy "
                             f"{self.remasking_strategy!r}: one of {RULES}")
        if self.block_length % self.denoising_steps:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} do not divide a "
                f"block of {self.block_length}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} outside "
                             f"the vocabulary of {self.vocab_size}")

    @staticmethod
    def tiny(**kw) -> "SdarConfig":
        """Test-size config: 3 layers, 8 experts of 32 with 2 a token,
        blocks of 4 opened one position a forward."""
        base = dict(vocab_size=512, hidden_size=64, moe_intermediate_size=32,
                    num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16,
                    num_experts=8, num_experts_per_tok=2, max_seq_len=256,
                    mask_token_id=300, dtype="float32")
        base.update(kw)
        return SdarConfig(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def opened_a_forward(self) -> int:
        """Positions a denoising forward opens at the least."""
        return self.block_length // self.denoising_steps

    @property
    def reads_confidence(self) -> bool:
        """Whether the rule looks at a position's confidence at all."""
        return self.remasking_strategy != "sequential"

    @property
    def read_a_forward(self) -> int:
        """Rows of a block whose logits a denoising forward's rule can read:
        under ``sequential`` the leftmost open positions it opens, known
        before the forward runs; under the confidence rules every open
        position's confidence decides, so all of them."""
        return (self.block_length if self.reads_confidence
                else self.opened_a_forward)

    @property
    def router_rule(self) -> RouterRule:
        return RouterRule(
            experts=self.num_experts, topk=self.num_experts_per_tok,
            score="softmax", use_bias=False,
            renormalize=self.norm_topk_prob, renorm_eps=0.0)

    def num_params(self) -> int:
        h, d = self.hidden_size, self.head_dim
        attn = (2 * h * self.num_heads * d + 2 * h * self.num_kv_heads * d
                + 2 * d)
        routed = (h * self.num_experts
                  + self.num_experts * 3 * h * self.moe_intermediate_size)
        return (self.num_layers * (attn + routed + 2 * h)
                + 2 * self.vocab_size * h + h)


def param_logical_axes(cfg: SdarConfig) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules)."""
    return {
        "embed_tokens": ("vocab", "embed"),
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "ffn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "q_norm": ("layers", None),
            "k_norm": ("layers", None),
            "router": ("layers", "embed", None),
            "we_gate": ("layers", "expert", "embed", "mlp"),
            "we_up": ("layers", "expert", "embed", "mlp"),
            "we_down": ("layers", "expert", "mlp", "embed"),
        },
    }


def init_params(cfg: SdarConfig, key: jax.Array) -> dict:
    """Scaled-normal init that keeps every projection's output at unit
    variance; the norms' weights (``q_norm`` and ``k_norm`` among them) near
    1, as a trained checkpoint has them and an all-ones init would hide.

    The experts' down-projections are scaled down by 1 / sqrt(2 x layers),
    as models/llama.init_params scales its ``w_down`` and models/lfm2.py its
    experts', for LFM2's reason: the router's eighth place of 128 is a
    discrete choice between two softmax scores that are nearly equal, a
    near-tie falls differently in bfloat16 and in float32, and each such
    swap exchanges an eighth of the layer's output; at full size that reads
    as a computation one precision lower would, scaled it is of rounding's
    size. The attention's output projection is not scaled: the residual
    stream grows along the layers as a trained one does."""
    h, d, L = cfg.hidden_size, cfg.head_dim, cfg.num_layers
    fe, E = cfg.moe_intermediate_size, cfg.num_experts
    qd, kvd = cfg.num_heads * d, cfg.num_kv_heads * d
    dt = cfg.jnp_dtype
    keys = iter(jax.random.split(key, 16))

    def matrix(*shape, dtype=dt, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def norm(*shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dt)

    return {
        "embed_tokens": matrix(cfg.vocab_size, h, scale=0.02),
        "final_norm": norm(h),
        "lm_head": matrix(h, cfg.vocab_size),
        "layers": {
            "attn_norm": norm(L, h),
            "ffn_norm": norm(L, h),
            "wq": matrix(L, h, qd),
            "wk": matrix(L, h, kvd),
            "wv": matrix(L, h, kvd),
            "wo": matrix(L, qd, h),
            "q_norm": norm(L, d),
            "k_norm": norm(L, d),
            # The router stays float32: its top-k is a discrete choice.
            "router": matrix(L, h, E, dtype=jnp.float32),
            "we_gate": matrix(L, E, h, fe),
            "we_up": matrix(L, E, h, fe),
            "we_down": matrix(L, E, fe, h,
                              scale=1.0 / math.sqrt(2 * L * fe)),
        },
    }


# ---------------------------------------------------------------- blocks

def layer(cfg: SdarConfig, layers: dict, index, x, attention, state, valid,
          kmesh=None):
    """Layer ``index`` (a run-time value) on x [B, S, H]. ``layers`` is the
    whole stacked ``params["layers"]``: every leaf is indexed where it is
    used, the experts' stacks read in place. ``attention(index, ap, xn,
    state) -> (y, state)`` runs the attention on normed input with the
    layer's own params and threads whatever it keeps (a cache, or None); a
    row with ``valid`` false is routed nowhere. Returns (x, state,
    counts)."""
    b, s, hid = x.shape
    with tracing.part("stack"):
        ap = {k: layer_of(layers[k], index) for k in ATTENTION_LEAVES}
        attn_norm = layer_of(layers["attn_norm"], index)
        ffn_norm = layer_of(layers["ffn_norm"], index)
    with tracing.part("attn"):
        y, state = attention(
            index, ap, rms_norm(x, attn_norm, cfg.norm_eps, kmesh), state)
        x = x + y
    with tracing.part("moe_route"):
        u = rms_norm(x, ffn_norm, cfg.norm_eps, kmesh)
    m, counts = moe_block(cfg.router_rule, layers, index,
                          u.reshape(b * s, hid), valid.reshape(b * s))
    with tracing.part("moe_combine"):
        return x + m.reshape(b, s, hid), state, counts


def run_layers(cfg: SdarConfig, params, x, attention, state, valid,
               kmesh=None):
    """Every layer over x [B, S, H], ``state`` as the loop's carry. Returns
    (x, state, counts int32[6] summed over the layers)."""
    def body(carry, index):
        x, state, counts = carry
        x, state, c = layer(cfg, params["layers"], index, x, attention,
                            state, valid, kmesh)
        with tracing.part("moe_combine"):
            counts = counts + c
        return (x, state, counts), None

    zero = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    with tracing.part("stack"):
        (x, state, counts), _ = lax.scan(
            body, (x, state, zero), jnp.arange(cfg.num_layers))
    return x, state, counts


@tracing.part("head")
def lm_head(cfg: SdarConfig, params, x, kmesh=None):
    """x: [..., H] -> float32 logits [..., V]."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)
    return jnp.dot(x, params["lm_head"], preferred_element_type=jnp.float32)


def block_causal_mask(s: int, block: int):
    """[S, S] bool: query i sees key j iff ``j // block <= i // block``."""
    at = jnp.arange(s) // block
    return at[None, :] <= at[:, None]


def forward(cfg: SdarConfig, params: dict, tokens, *,
            kmesh: KernelMesh | None = None):
    """tokens [B, S] -> (float32 logits [B, S, V], router counts int32[6]).
    Whole clean sequences under the block-causal mask, no cache: row ``i``
    holds the logits at position ``i`` (which, in generation, choose that
    position's own token while it is open)."""
    b, s = tokens.shape
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    with tracing.part("attn"):
        positions = jnp.arange(s)
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)
        mask = block_causal_mask(s, cfg.block_length)[None, None]
    group = cfg.num_heads // cfg.num_kv_heads

    def attention(index, ap, xn, state):
        q, k, v = attention_heads(cfg, ap, xn, positions, inv_freq)
        k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(mask, scores / math.sqrt(cfg.head_dim), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(xn.dtype)
        o = jnp.einsum("bhqk,bhkd->bqhd", probs, v).reshape(b, s, -1)
        return (o @ ap["wo"]).astype(xn.dtype), state

    x, _, counts = run_layers(cfg, params, x, attention, None,
                              jnp.ones(tokens.shape, bool), kmesh)
    return lm_head(cfg, params, x, kmesh), counts


def open_rank(is_open):
    """is_open [B, K] bool -> int32 [B, K]: the open positions up to and
    with each one, so an open position's rank among its line's open ones,
    from 1."""
    return jnp.cumsum(is_open, axis=-1, dtype=jnp.int32)


def read_positions(cfg: SdarConfig, is_open):
    """The positions of each line whose logits this forward's rule can
    read: int32 [B, ``read_a_forward``], the leftmost open positions in
    order. Where a line has fewer open the rest name position 0, whose
    token nothing takes. ``None`` when the rule reads every row of the
    block."""
    r, k = cfg.read_a_forward, is_open.shape[-1]
    if r == k:
        return None
    nth = is_open[:, None, :] & (open_rank(is_open)[:, None, :]
                                 == jnp.arange(1, r + 1)[None, :, None])
    return jnp.argmax(nth, axis=-1)


def at_positions(is_open, rows):
    """rows [B, r], a value for each position of ``read_positions`` ->
    [B, K]: each of those positions holds its row's value (any other
    position some row's, which nothing takes). ``rows`` itself when every
    row was read."""
    r = rows.shape[-1]
    if r == is_open.shape[-1]:
        return rows
    return jnp.take_along_axis(
        rows, jnp.clip(open_rank(is_open) - 1, 0, r - 1), axis=-1)


def open_positions(cfg: SdarConfig, confidence, is_open):
    """Which open positions of each block take their token after this
    denoising forward. confidence [B, K] float32 (the probability of the
    token chosen at each position; ``None`` under a rule that does not
    read it), is_open [B, K] bool. Returns [B, K]
    bool, a subset of ``is_open`` with ``opened_a_forward`` positions a
    line (all that are open, where fewer are):

    - ``sequential``: the leftmost open positions;
    - ``low_confidence_static``: the open positions of highest confidence;
    - ``low_confidence_dynamic``: every open position whose confidence is
      over ``confidence_threshold``, the static choice where fewer than
      ``opened_a_forward`` are."""
    n = cfg.opened_a_forward
    k = is_open.shape[-1]
    if not cfg.reads_confidence:
        return is_open & (open_rank(is_open) <= n)
    score = jnp.where(is_open, confidence, -jnp.inf)
    # The n best, the earlier position where two are equal: a position's
    # rank is how many stand before it.
    ahead = ((score[:, None, :] > score[:, :, None])
             | ((score[:, None, :] == score[:, :, None])
                & (jnp.arange(k)[None, None, :] < jnp.arange(k)[None, :, None])))
    static = is_open & (ahead.sum(axis=-1) < n)
    if cfg.remasking_strategy == "low_confidence_static":
        return static
    high = is_open & (confidence > cfg.confidence_threshold)
    return jnp.where(high.sum(axis=-1, keepdims=True) >= n, high, static)
