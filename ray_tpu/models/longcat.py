"""LongCat-Flash family: latent (MLA) attention, double layers with a
shortcut-connected expert branch, zero-compute experts.

One published "layer" is a double layer: two latent attentions, two dense
SwiGLU FFNs and one routed expert layer whose branch leaves after the first
attention and rejoins after the second FFN::

    a1  = h  + MLA_0(N(h))            u = N(a1)
    m   = MoE(u)                      # leaves here
    f1  = a1 + FFN_0(u)
    a2  = f1 + MLA_1(N(f1))
    out = a2 + FFN_1(N(a2)) + m       # rejoins here

The router has one output per routed expert and per zero-compute expert
(512 + 256), scores by softmax in float32, picks ``moe_topk`` of
``p + bias`` and weighs by ``routed_scaling_factor * p`` without
renormalising. A zero-compute expert is the identity: a weighted add of the
layer's input, no matmul. The routed layer is told which experts it holds
(``expert_shard`` of ``expert_shards``), routes over all of them, keeps
every pick that falls on a held expert (no capacity, no drop: the picks are
sorted by expert and multiplied in groups, ops/grouped_matmul.py) and
computes the part of the result its own experts give, plus the zero
experts' part, which every shard computes for its own tokens. What the
absent shards' experts would add is an expert-parallel exchange this module
does not have.

Latent attention is models/mla.py's, with this family's two norm factors
(``mla_scale_q_lora``, ``mla_scale_kv_lora``): per position it keeps
``kv_lora_rank + qk_rope_head_dim`` values for all heads. ``forward``
(training-shaped, no cache) up-projects keys and values from them
(``mla_full``); the serving programs (llm/longcat_serving.py) attend in the
absorbed form against the cached rows.

Params follow models/llama.py: a flat pytree with layers stacked on the
leading axis. The leaves of the attentions and dense FFNs are stacked by
sub-layer (double layer l holds sub-layers 2l and 2l + 1), the router's and
the experts' by double layer. A layer loop indexes every stack by the loop's
counter and scans over none: a scanned slice that two matmuls share (the
pair of a double layer) is copied out of the stack first, 1.2 GB a layer at
the published widths.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.mla import mla_full, mla_scales
# MOE_COUNTERS, MOE_TILE and dispatch_plan are this module's names too.
from ray_tpu.models.routed import (  # noqa: F401
    MOE_COUNTERS,
    MOE_TILE,
    RouterRule,
    dispatch_plan,
    layer_of as _layer_of,
    moe_block,
)
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm
from ray_tpu.util import tracing

@dataclass(frozen=True)
class LongcatConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28               # double layers
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 512        # in the whole model, all shards
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    max_seq_len: int = 131072
    rope_theta: float = 1e7
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # What this program holds of the routed experts: shard ``expert_shard``
    # of ``expert_shards`` equal shares, experts [shard * held, (shard + 1)
    # * held). One shard holds them all.
    expert_shard: int = 0
    expert_shards: int = 1

    # models/mla.mla_project's rotary turns adjacent pairs.
    mla_rope_interleaved: ClassVar[bool] = True

    def __post_init__(self):
        self.router_rule  # refuses a share the experts do not divide into

    @staticmethod
    def tiny(**kw) -> "LongcatConfig":
        """Test-size config with every mechanism: 16 routed + 8 zero
        experts, 4 a token, 2 double layers."""
        base = dict(vocab_size=512, hidden_size=128, ffn_hidden_size=256,
                    expert_ffn_hidden_size=64, num_layers=2, num_heads=4,
                    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
                    zero_expert_num=8, moe_topk=4, max_seq_len=256,
                    dtype="float32")
        base.update(kw)
        return LongcatConfig(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.expert_shards

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def router_rule(self) -> RouterRule:
        """Softmax over routed and zero experts, the choice by score +
        bias, the weights ``routed_scaling_factor * p`` as they are."""
        return RouterRule(
            experts=self.n_routed_experts, topk=self.moe_topk,
            score="softmax", use_bias=True, renormalize=False,
            scaling_factor=self.routed_scaling_factor,
            zero_experts=self.zero_expert_num,
            expert_shard=self.expert_shard,
            expert_shards=self.expert_shards)

    @property
    def latent_dim(self) -> int:
        """Values cached per position and attention."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Width of a cached row: ``latent_dim`` rounded up to whole
        128-lane tiles (576 -> 640, the rest zeros). A TPU lays an array
        whose last dimension is no multiple of 128 out with another
        dimension innermost, here the positions, and a kernel that wants
        rows then has the whole cache copied into row order and back
        around every call."""
        return -(-self.latent_dim // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_attention_layers(self) -> int:
        return 2 * self.num_layers

    @property
    def sm_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_head_dim)

    @property
    def rope_scaling(self) -> None:
        """The published configuration has none (models/deepseek.py, which
        shares the latent projections below, has YaRN's)."""
        return None

    def num_params(self) -> int:
        """Parameters held here (this shard's experts)."""
        h, nh = self.hidden_size, self.num_heads
        mla = (h * self.q_lora_rank + self.q_lora_rank * nh * self.qk_head_dim
               + h * self.latent_dim + self.kv_lora_rank * nh
               * (self.qk_nope_head_dim + self.v_head_dim)
               + nh * self.v_head_dim * h + self.q_lora_rank
               + self.kv_lora_rank)
        ffn = 3 * h * self.ffn_hidden_size
        router = h * self.router_outputs + self.router_outputs
        experts = self.experts_held * 3 * h * self.expert_ffn_hidden_size
        layer = 2 * (mla + ffn + 2 * h) + router + experts
        return self.num_layers * layer + 2 * self.vocab_size * h + h


def param_logical_axes(cfg: LongcatConfig) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules).
    ``layers`` is the stacked axis, sub-layers or double layers alike."""
    return {
        "embed_tokens": ("vocab", "embed"),
        "lm_head": ("embed", "vocab"),
        "final_norm": ("embed",),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "post_norm": ("layers", "embed"),
            "wq_a": ("layers", "embed", None),
            "q_a_norm": ("layers", None),
            "wq_b": ("layers", None, "heads"),
            "wkv_a": ("layers", "embed", None),
            "kv_a_norm": ("layers", None),
            "wkv_b": ("layers", None, "heads"),
            "wo": ("layers", "heads", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
            "router": ("layers", "embed", None),
            "router_bias": ("layers", None),
            "we_gate": ("layers", "expert", "embed", "mlp"),
            "we_up": ("layers", "expert", "embed", "mlp"),
            "we_down": ("layers", "expert", "mlp", "embed"),
        },
    }


def init_params(cfg: LongcatConfig, key: jax.Array) -> dict:
    """Scaled-normal init that keeps every projection's output at unit
    variance; ``router_bias`` (a buffer the published model tunes by a
    controller, not by gradients) is drawn at a small scale so that the
    selection it shifts is exercised.

    The two up-projections out of the latent norms count the fixed factor
    their input carries (``mla_scales``: 2 and 3.46 at the published
    widths). Drawn at 1/sqrt(fan_in) alone they gave attention scores of
    standard deviation 5.8, a softmax that is one position's argmax, and a
    model that turns bfloat16's rounding into whole logits (2.1 of 5.6
    against the float32 reference, my chip run, PR 27); a trained
    checkpoint has learnt weights for those factors.

    The output projections are not scaled down by depth (models/llama.py
    divides its by sqrt(2 * layers)): every branch adds unit variance and
    the residual stream grows along the layers as a trained one does. What
    rides on it is the weight of one routed term against the stream: a
    near-tie at the router's 12th place falls differently in bfloat16 and
    in float32 for one token and routed layer in six, and each swap of a
    zero expert adds or drops about 0.07 of the unit-variance input. Against
    a stream of variance 1 over the whole depth that read as 0.28 to 0.31
    of a logit in the benchmark's comparison, against this one as 0.15,
    beside 0.03 to 0.08 without a swap (my chip runs, PR 27)."""
    h, L, nh = cfg.hidden_size, cfg.num_layers, cfg.num_heads
    S = 2 * L  # sub-layers: the attentions and dense FFNs
    f, fe, E = (cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size,
                cfg.experts_held)
    od = nh * cfg.v_head_dim
    dt = cfg.jnp_dtype
    sq, skv = mla_scales(cfg)
    keys = jax.random.split(key, 16)

    def normal(k, *shape, scale=None, dtype=dt):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(
            dtype)

    return {
        "embed_tokens": (jax.random.normal(keys[0], (cfg.vocab_size, h),
                                           jnp.float32) * 0.02).astype(dt),
        "lm_head": normal(keys[1], h, cfg.vocab_size),
        "final_norm": jnp.ones((h,), dt),
        "layers": {
            "attn_norm": jnp.ones((S, h), dt),
            "post_norm": jnp.ones((S, h), dt),
            "wq_a": normal(keys[2], S, h, cfg.q_lora_rank),
            "q_a_norm": jnp.ones((S, cfg.q_lora_rank), dt),
            "wq_b": normal(keys[3], S, cfg.q_lora_rank, nh * cfg.qk_head_dim,
                           scale=1.0 / (sq * math.sqrt(cfg.q_lora_rank))),
            "wkv_a": normal(keys[4], S, h, cfg.latent_dim),
            "kv_a_norm": jnp.ones((S, cfg.kv_lora_rank), dt),
            "wkv_b": normal(keys[5], S, cfg.kv_lora_rank,
                            nh * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                            scale=1.0 / (skv * math.sqrt(cfg.kv_lora_rank))),
            "wo": normal(keys[6], S, od, h),
            "w_gate": normal(keys[7], S, h, f),
            "w_up": normal(keys[8], S, h, f),
            "w_down": normal(keys[9], S, f, h),
            # The router stays float32: its top-k is a discrete choice.
            "router": normal(keys[10], L, h, cfg.router_outputs,
                             dtype=jnp.float32),
            "router_bias": (jax.random.normal(
                keys[11], (L, cfg.router_outputs), jnp.float32)
                * 0.1 / cfg.router_outputs),
            "we_gate": normal(keys[12], L, E, h, fe),
            "we_up": normal(keys[13], L, E, h, fe),
            "we_down": normal(keys[14], L, E, fe, h),
        },
    }


# ---------------------------------------------------------------- blocks

def swiglu(x, w_gate, w_up, w_down):
    dt = x.dtype
    gate = jax.nn.silu((x @ w_gate).astype(jnp.float32)).astype(dt)
    return ((gate * (x @ w_up)) @ w_down).astype(dt)


SUBLAYER_LEAVES = ("attn_norm", "post_norm", "wq_a", "q_a_norm", "wq_b",
                   "wkv_a", "kv_a_norm", "wkv_b", "wo", "w_gate", "w_up",
                   "w_down")


def double_layer(cfg: LongcatConfig, layers: dict, layer, h, attn, state,
                 valid, kmesh=None):
    """Double layer ``layer`` (a run-time index) on h [B, S, H]. ``layers``
    is the whole stacked ``params["layers"]``: every leaf is indexed where
    it is used. ``attn(i, ap, xn, state) -> (out, state)`` is attention i of
    the pair on normed input with its own params ``ap``; ``state`` is
    whatever it threads (a cache). ``valid`` [B, S] marks real tokens for
    the router's counters. Returns (h, state, counts)."""
    b, s, hid = h.shape
    with tracing.part("stack"):
        p0, p1 = ({k: _layer_of(layers[k], 2 * layer + i)
                   for k in SUBLAYER_LEAVES} for i in (0, 1))
    with tracing.part("attn"):
        o, state = attn(0, p0, rms_norm(h, p0["attn_norm"], cfg.norm_eps,
                                        kmesh), state)
        a1 = h + o
    with tracing.part("mlp"):
        u = rms_norm(a1, p0["post_norm"], cfg.norm_eps, kmesh)
    m, counts = moe_block(cfg.router_rule, layers, layer,
                          u.reshape(b * s, hid), valid.reshape(b * s))
    with tracing.part("mlp"):
        f1 = a1 + swiglu(u, p0["w_gate"], p0["w_up"], p0["w_down"])
    with tracing.part("attn"):
        o, state = attn(1, p1, rms_norm(f1, p1["attn_norm"], cfg.norm_eps,
                                        kmesh), state)
        a2 = f1 + o
    with tracing.part("mlp"):
        x = rms_norm(a2, p1["post_norm"], cfg.norm_eps, kmesh)
        out = a2 + swiglu(x, p1["w_gate"], p1["w_up"], p1["w_down"])
    with tracing.part("moe_combine"):
        out = out + m.reshape(b, s, hid)
    return out, state, counts


@tracing.part("head")
def lm_head(cfg: LongcatConfig, params, x, kmesh=None):
    """x: [..., H] -> float32 logits [..., V] (untied head)."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)
    return x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)


def forward(cfg: LongcatConfig, params: dict, tokens, *,
            kmesh: KernelMesh | None = None):
    """tokens [B, S] -> (float32 logits [B, S, V], router counts int32[6]).
    Whole sequences, no cache: the shape of a training forward pass and of
    the parity tests."""
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    valid = jnp.ones(tokens.shape, bool)

    def attn(i, ap, xn, state):
        return mla_full(cfg, ap, xn, kmesh), state

    def body(carry, layer):
        x, counts = carry
        x, _, c = double_layer(cfg, params["layers"], layer, x, attn, None,
                               valid, kmesh)
        with tracing.part("moe_combine"):
            return (x, counts + c), None

    with tracing.part("stack"):
        (x, counts), _ = lax.scan(
            body, (x, jnp.zeros((len(MOE_COUNTERS),), jnp.int32)),
            jnp.arange(cfg.num_layers))
    return lm_head(cfg, params, x, kmesh), counts
