"""DeepSeek-V2 family: latent (MLA) attention with YaRN rotary, a leading
dense layer, then routed layers in which shared experts run on every token
beside experts chosen by a group-limited rule.

Layer ``l``, input ``h``, ``N`` an RMSNorm with float32 statistics::

    a   = h + MLA(N(h))               u = N(a)
    out = a + F_l(u)

``F_l`` is a dense SwiGLU in the first ``first_k_dense_replace`` layers and
``Shared(u) + sum_e w_e E_e(u)`` after them: ``Shared`` is one SwiGLU of
width ``n_shared_experts * moe_intermediate_size``, the sum runs over the
``num_experts_per_tok`` routed experts the rule chose. The rule
(``group_limited_greedy``): ``s = softmax(u W_g)`` in float32 over all
routed experts; the experts lie in ``n_group`` consecutive groups, a group
scores as its best expert, the ``topk_group`` best groups are kept and the
choice is the top of what they hold; the weights are
``routed_scaling_factor * s`` at the chosen, not renormalised, no selection
bias. With a group a device, a token's experts lie on at most
``topk_group`` devices: the family's device-limited routing.

The routed layer is models/routed.py's: told which experts it holds
(``expert_shard`` of ``expert_shards``), it routes over all of them, keeps
every pick that falls on a held expert and computes their part of the sum.
The shared experts are replicated in such a deployment: every shard
computes them whole for its own tokens, so where shards' results are summed
the shared part counts once (tests/test_deepseek.py).

Latent attention is models/mla.py's (``mla_project``, ``mla_full``;
``kv_up_projections`` is this module's own, for a ``wkv_b`` stored a head
at a time) without LongCat's two norm factors, with
YaRN's inverse frequencies (ops/rope.py) and the softmax scale multiplied
by ``yarn_mscale(factor, mscale_all_dim) ** 2``; the factor on cos and sin
is ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``,
1 in every published configuration of the family, and a configuration
where it is not is refused.

Params: a flat pytree, every leaf stacked over the layers that have it (the
attention's and the norms' over all layers, the dense SwiGLU's over the
dense layers, the router's, the shared and the routed experts' over the
routed layers) and indexed by the loop's counter where it is used
(the LongCat model's finding on scanned slices).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.lfm2 import swiglu
from ray_tpu.models.mla import mla_full
from ray_tpu.models.routed import (
    MOE_COUNTERS,
    RouterRule,
    layer_of,
    moe_block_picks,
)
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import yarn_mscale
from ray_tpu.util import tracing

# What a routed layer counts: the shared rule's counters and, of this
# family's own, the (token, routed layer) pairs with at least one pick on a
# held expert: what the grouped rule bounds (``topk_group`` of ``n_group``
# where a shard holds a group).
COUNTERS = MOE_COUNTERS + ("moe_tokens_local",)


@dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288         # the leading dense SwiGLU
    moe_intermediate_size: int = 1536      # one expert, routed or shared
    num_layers: int = 60
    first_k_dense_replace: int = 1
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 160            # in the whole model, all shards
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    max_seq_len: int = 163840
    rope_theta: float = 1e4
    # rope_scaling (YaRN); a factor of 1 is no scaling.
    rope_factor: float = 40.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_max_position: int = 4096
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    # What this program holds of the routed experts (as LongCat's).
    expert_shard: int = 0
    expert_shards: int = 1

    # models/mla.mla_project's two norm factors: this family has none.
    mla_scale_q_lora: ClassVar[bool] = False
    mla_scale_kv_lora: ClassVar[bool] = False
    mla_rope_interleaved: ClassVar[bool] = True

    def __post_init__(self):
        self.router_rule  # refuses shares and groups that do not divide
        if not 0 <= self.first_k_dense_replace <= self.num_layers:
            raise ValueError(f"first_k_dense_replace "
                             f"{self.first_k_dense_replace} of "
                             f"{self.num_layers} layers")
        if yarn_mscale(self.rope_factor, self.rope_mscale) != yarn_mscale(
                self.rope_factor, self.rope_mscale_all_dim):
            raise ValueError("rope_scaling with mscale != mscale_all_dim "
                             "scales cos and sin, which this model does not")

    @staticmethod
    def tiny(**kw) -> "DeepseekV2Config":
        """Test-size config with every mechanism: one dense layer and two
        routed ones, 16 experts in 4 groups of which 2 are kept, 3 a token,
        2 shared experts, YaRN over 32 trained positions."""
        base = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                    moe_intermediate_size=64, num_layers=3, num_heads=4,
                    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
                    num_experts_per_tok=3, n_group=4, topk_group=2,
                    rope_factor=8.0, rope_beta_fast=4.0,
                    rope_original_max_position=32, max_seq_len=256,
                    dtype="float32")
        base.update(kw)
        return DeepseekV2Config(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def num_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def num_routed_layers(self) -> int:
        return self.num_layers - self.first_k_dense_replace

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.expert_shards

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def router_rule(self) -> RouterRule:
        """Softmax over the routed experts, the choice among the best
        groups, the weights ``routed_scaling_factor * s`` as they are (the
        family's gate renormalises *or* scales: with ``norm_topk_prob`` the
        weights are ``s / (sum + 1e-20)`` and the factor is not applied)."""
        return RouterRule(
            experts=self.n_routed_experts, topk=self.num_experts_per_tok,
            score="softmax", use_bias=False,
            renormalize=self.norm_topk_prob, renorm_eps=1e-20,
            scaling_factor=(1.0 if self.norm_topk_prob
                            else self.routed_scaling_factor),
            groups=self.n_group, topk_groups=self.topk_group,
            expert_shard=self.expert_shard,
            expert_shards=self.expert_shards)

    @property
    def rope_scaling(self) -> dict | None:
        """ops/rope.rope_frequencies' ``scaling``."""
        if self.rope_factor <= 1:
            return None
        return {"type": "yarn", "factor": self.rope_factor,
                "beta_fast": self.rope_beta_fast,
                "beta_slow": self.rope_beta_slow,
                "original_max_position_embeddings":
                    self.rope_original_max_position}

    @property
    def latent_dim(self) -> int:
        """Values cached per position and layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Width of a cached row: whole 128-lane tiles
        (as LongcatConfig.latent_row)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def sm_scale(self) -> float:
        """1 / sqrt(head) times the square of YaRN's temperature."""
        return (yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2
                / math.sqrt(self.qk_head_dim))

    def num_params(self) -> int:
        """Parameters held here (this shard's experts)."""
        h, nh = self.hidden_size, self.num_heads
        mla = (h * self.q_lora_rank + self.q_lora_rank * nh * self.qk_head_dim
               + h * self.latent_dim + self.kv_lora_rank * nh
               * (self.qk_nope_head_dim + self.v_head_dim)
               + nh * self.v_head_dim * h + self.q_lora_rank
               + self.kv_lora_rank + 2 * h)
        dense = 3 * h * self.intermediate_size
        routed = (h * self.n_routed_experts + 3 * h * self.shared_width
                  + self.experts_held * 3 * h * self.moe_intermediate_size)
        return (self.num_layers * mla + self.num_dense_layers * dense
                + self.num_routed_layers * routed
                + 2 * self.vocab_size * h + h)


ATTENTION_LEAVES = ("attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a",
                    "kv_a_norm", "wkv_b", "wo")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
SHARED_LEAVES = ("ws_gate", "ws_up", "ws_down")


def param_logical_axes(cfg: DeepseekV2Config) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules).
    ``layers`` is the stacked axis, whichever layers a leaf is stacked
    over."""
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
            "wkv_b": ("layers", "heads", None, None),
            "wo": ("layers", "heads", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
            "router": ("layers", "embed", None),
            "ws_gate": ("layers", "embed", "mlp"),
            "ws_up": ("layers", "embed", "mlp"),
            "ws_down": ("layers", "mlp", "embed"),
            "we_gate": ("layers", "expert", "embed", "mlp"),
            "we_up": ("layers", "expert", "embed", "mlp"),
            "we_down": ("layers", "expert", "mlp", "embed"),
        },
    }


def init_params(cfg: DeepseekV2Config, key: jax.Array) -> dict:
    """Scaled-normal init that keeps every projection's output at unit
    variance, the norms' weights near 1 (models/lfm2.py). The attention's,
    the dense SwiGLU's and the shared experts' output projections are not
    scaled down by depth: every branch adds about unit variance and the
    residual stream grows along the layers as a trained one does
    (LongCat's ``init_params``). The routed experts' down-projections are, by
    1 / sqrt(8 x routed layers): models/lfm2.py's and models/sdar.py's
    1 / sqrt(2 x routed layers), for their reason, and half of it again.
    The sixth place of the rule, and here the third place among the groups
    too, is a discrete choice between scores that are nearly equal, which
    falls differently in bfloat16 and in float32 for some tokens, and a
    chosen expert's weight is 16 x a softmax score over 160, about 0.3 to
    0.8. Over 1,536 decoded positions against the float32 reference the
    programs' worst margin read 0.34 and 0.32 at 1 / sqrt(2 x 7), 0.13 and
    0.13 at half that and 0.07 and 0.07 with the routed experts at zero
    (``devbench/deepseek_bench.py margins``, my chip run, PR 45): at the
    larger scale a sound run's reading was the swaps' and reached 0.53 in
    13 runs, within a factor 2.6 of the same reference on fp8 weights; at
    this one it is mostly rounding's. What a swap costs a trained model is
    its own affair; here it must not pass for, or hide, a computation one
    precision lower."""
    h, L, nh = cfg.hidden_size, cfg.num_layers, cfg.num_heads
    nd, nm = cfg.num_dense_layers, cfg.num_routed_layers
    f, fe, fs, E = (cfg.intermediate_size, cfg.moe_intermediate_size,
                    cfg.shared_width, cfg.experts_held)
    dt = cfg.jnp_dtype
    keys = iter(jax.random.split(key, 32))

    def matrix(*shape, dtype=dt, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def norm(*shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dt)

    return {
        "embed_tokens": matrix(cfg.vocab_size, h, scale=0.02),
        "lm_head": matrix(h, cfg.vocab_size),
        "final_norm": norm(h),
        "layers": {
            "attn_norm": norm(L, h),
            "post_norm": norm(L, h),
            "wq_a": matrix(L, h, cfg.q_lora_rank),
            "q_a_norm": norm(L, cfg.q_lora_rank),
            "wq_b": matrix(L, cfg.q_lora_rank, nh * cfg.qk_head_dim),
            "wkv_a": matrix(L, h, cfg.latent_dim),
            "kv_a_norm": norm(L, cfg.kv_lora_rank),
            "wkv_b": matrix(L, nh, cfg.kv_lora_rank,
                            cfg.qk_nope_head_dim + cfg.v_head_dim),
            "wo": matrix(L, nh * cfg.v_head_dim, h),
            "w_gate": matrix(nd, h, f),
            "w_up": matrix(nd, h, f),
            "w_down": matrix(nd, f, h),
            # The router stays float32: its choices are discrete.
            "router": matrix(nm, h, cfg.n_routed_experts,
                             dtype=jnp.float32),
            "ws_gate": matrix(nm, h, fs),
            "ws_up": matrix(nm, h, fs),
            "ws_down": matrix(nm, fs, h),
            "we_gate": matrix(nm, E, h, fe),
            "we_up": matrix(nm, E, h, fe),
            "we_down": matrix(nm, E, fe, h,
                              scale=1.0 / math.sqrt(8 * max(nm, 1) * fe)),
        },
    }


# ---------------------------------------------------------------- blocks

def kv_up_projections(cfg: DeepseekV2Config, wkv_b):
    """wkv_b [nh, rank, Dn + Dv] -> the key half [rank, nh, Dn] and the
    value half [rank, nh, Dv], as models/mla.kv_up_projections gives
    them. The leaf is stored a head at a time (the published matrix is
    [rank, nh * (Dn + Dv)], a head's keys then its values): every product
    with it runs over heads as a batch, the absorbed decode step's two
    (``q_n W_kb^T`` and the value up-projection of the mix) and the
    chunk's up-projection alike, and XLA, given the published order,
    copied the whole stack head-major at the top of every decode program
    (0.25 GiB at 8 layers; the AOT compile of PR 45)."""
    w = wkv_b.transpose(1, 0, 2)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def routed_ffn(cfg: DeepseekV2Config, layers: dict, routed_layer, u, valid):
    """Routed layer ``routed_layer`` (its rank among the routed layers, a
    run-time index) on u [T, H] (normed). Returns (shared [T, H], the
    shared experts' SwiGLU, whole; routed [T, H], this shard's experts'
    part of the weighted sum; counts int32[7] in the order of COUNTERS)."""
    routed, counts, local = moe_block_picks(cfg.router_rule, layers,
                                            routed_layer, u, valid)
    with tracing.part("mlp"), tracing.part("moe_shared"):
        shared = swiglu(u, *(layer_of(layers[k], routed_layer)
                             for k in SHARED_LEAVES))
    with tracing.part("moe_combine"):
        counts = jnp.concatenate(
            [counts, jnp.sum(jnp.any(local, axis=1), dtype=jnp.int32)[None]])
    return shared, routed, counts


def layer(cfg: DeepseekV2Config, layers: dict, index, routed: bool, h, attn,
          state, valid, kmesh=None):
    """Layer ``index`` (a run-time index; ``routed`` says statically which
    kind it is) on h [B, S, H]. ``layers`` is the whole stacked
    ``params["layers"]``: every leaf is indexed where it is used.
    ``attn(index, ap, xn, state) -> (out, state)`` is the layer's attention
    on normed input with its own params ``ap``; ``state`` is whatever it
    threads (a cache). ``valid`` [B, S] marks real tokens for the router's
    counters. Returns (h, state, counts)."""
    b, s, hid = h.shape
    with tracing.part("stack"):
        ap = {k: layer_of(layers[k], index) for k in ATTENTION_LEAVES}
        post_norm = layer_of(layers["post_norm"], index)
    with tracing.part("attn"):
        o, state = attn(index, ap, rms_norm(h, ap["attn_norm"], cfg.norm_eps,
                                            kmesh), state)
        a = h + o
    with tracing.part("mlp"):
        u = rms_norm(a, post_norm, cfg.norm_eps, kmesh)
    if not routed:
        with tracing.part("mlp"):
            out = a + swiglu(u, *(layer_of(layers[k], index)
                                  for k in DENSE_LEAVES))
        return out, state, jnp.zeros((len(COUNTERS),), jnp.int32)
    shared, m, counts = routed_ffn(
        cfg, layers, index - cfg.num_dense_layers, u.reshape(b * s, hid),
        valid.reshape(b * s))
    with tracing.part("moe_combine"):
        out = a + (shared + m).reshape(b, s, hid)
    return out, state, counts


def run_layers(cfg: DeepseekV2Config, params, x, attn, state, valid,
               kmesh=None):
    """Every layer over x [B, S, H], ``state`` as carry: one scan over the
    dense layers and one over the routed. Returns (x, state, counts)."""
    counts = jnp.zeros((len(COUNTERS),), jnp.int32)
    nd = cfg.num_dense_layers
    for routed, first, n in ((False, 0, nd), (True, nd,
                                              cfg.num_routed_layers)):
        if not n:
            continue

        def body(carry, index, routed=routed):
            x, state, counts = carry
            x, state, c = layer(cfg, params["layers"], index, routed, x,
                                attn, state, valid, kmesh)
            with tracing.part("moe_combine"):
                return (x, state, counts + c), None

        with tracing.part("stack"):
            (x, state, counts), _ = lax.scan(
                body, (x, state, counts), first + jnp.arange(n))
    return x, state, counts


@tracing.part("head")
def lm_head(cfg: DeepseekV2Config, params, x, kmesh=None):
    """x: [..., H] -> float32 logits [..., V] (untied head)."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)
    return jnp.dot(x, params["lm_head"], preferred_element_type=jnp.float32)


def forward(cfg: DeepseekV2Config, params: dict, tokens, *,
            kmesh: KernelMesh | None = None):
    """tokens [B, S] -> (float32 logits [B, S, V], counts int32[7]). Whole
    sequences, no cache: the shape of a training forward pass and of the
    parity tests."""
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    valid = jnp.ones(tokens.shape, bool)

    def attn(index, ap, xn, state):
        return mla_full(cfg, ap, xn, kmesh, kv_up_projections), state

    x, _, counts = run_layers(cfg, params, x, attn, None, valid, kmesh)
    return lm_head(cfg, params, x, kmesh), counts
