"""Ling-3.0 family (the language model of Ling-3.0-flash-VL): Kimi Delta
Attention layers beside a gated latent attention every few layers, two
leading dense layers, then routed experts under a grouped rule with a
selection bias beside one shared expert.

The family of huggingface.co/inclusionAI/Ling-3.0-flash-VL (text alone: the
vision tower, the multi-token-prediction module and the clamped SwiGLU of
the last layers are not here, and a configuration that asks for the last
is refused). Layer ``l``, input ``h``, ``N`` an RMSNorm with a plain weight
(statistics in float32)::

    a  = h + Mix_l(N(h))
    h' = a + F_l(N(a))

``Mix_l`` is the latent attention where ``(l + 1) % layer_group_size == 0``
and Kimi Delta Attention otherwise:

- **Kimi Delta Attention (KDA).** ``linear_num_heads`` heads of
  ``linear_head_dim`` for keys and values alike. ``x W_q``, ``x W_k``, ``x
  W_v`` pass a depthwise causal convolution of ``short_conv_kernel_size``
  taps (zeros before position 0), then ``silu``; ``q`` and ``k`` are
  L2-normalised a head and ``q`` scaled by ``D^-1/2``; no rotary. ``beta =
  sigmoid(x W_b)`` a head. The decay is a number a head, key channel and
  token: ``g = kda_lower_bound sigmoid(exp(A_log[head]) (x W_f + dt_bias))``
  in float32, in ``(kda_lower_bound, 0)``. Then the gated delta rule with a
  decay a channel (ops/gated_delta.py), from a zero state. Its output a
  head is normed and gated, ``w o rsqrt(mean o^2 + eps) sigmoid(x W_z)``,
  the heads side by side through ``W_o``. What a token leaves behind is the
  state of every head and the last ``taps - 1`` rows of ``[q | k | v]``
  before the convolution.
- **Latent attention** (models/mla.py) without a low-rank query (``wq``
  alone), its rotary a half against the other; a head's output times
  ``sigmoid(x W_a)[head]`` before ``W_o``.

``F_l`` is a dense SwiGLU in the first ``first_k_dense_replace`` layers and
``Shared(u) + sum_e w_e E_e(u)`` after them: the routed layer of
models/routed.py under the grouped rule with a bias (sigmoid scores; a
group scores as the sum of its two largest ``s + b``; the ``topk_group``
best of ``n_group`` groups are kept; the picks are the largest ``s + b``
inside them; the weights ``s`` at the picks, divided by their sum, times
``routed_scaling_factor``), told which experts it holds (``expert_shard``
of ``expert_shards``), and one shared SwiGLU added as it is. After the last
layer ``N``, then an untied head.

Params: a flat pytree, every leaf stacked over the layers that have it (the
norms over all layers, KDA's leaves over the KDA layers, the attention's
over the latent ones, the dense SwiGLU's over the dense layers, the
router's and the experts' over the routed) and indexed where it is used.
Stored in another order than the published matrices, the same numbers:
``in_qkvz`` holds ``W_q | W_k | W_v | W_z`` side by side (published: four
matrices), so that ``[q | k | v]`` is one product and one slice, and
``wkv_b`` a head at a time (models/deepseek.kv_up_projections)
(benchmark/rtbench/adapters/ling.reference_weights puts them back).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.deepseek import kv_up_projections
from ray_tpu.models.lfm2 import swiglu
from ray_tpu.models.mla import mla_attend_full
from ray_tpu.models.qwen3_next import (
    conv_window,
    short_conv_silu,
    unit_heads,
)
from ray_tpu.models.routed import (
    MOE_COUNTERS,
    RouterRule,
    layer_of,
    moe_block,
)
from ray_tpu.ops.gated_delta import gated_delta_chunk
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm, rms_norm_reference
from ray_tpu.util import tracing

KDA, LATENT = "kda", "latent"

KDA_LEAVES = ("in_qkvz", "in_f", "in_b", "conv_w", "dt_bias", "a_log",
              "kda_norm", "out_proj")
LATENT_LEAVES = ("wq", "wkv_a", "kv_a_norm", "wkv_b", "wg", "wo")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
SHARED_LEAVES = ("ws_gate", "ws_up", "ws_down")


@dataclass(frozen=True)
class LingConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144           # the leading dense SwiGLUs
    moe_intermediate_size: int = 768
    shared_expert_intermediate_size: int = 768
    num_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6               # KDA x 5, then a latent layer
    num_heads: int = 32                     # the latent attention's
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    linear_num_heads: int = 32              # KDA's, keys and values alike
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0           # the floor of a token's decay
    num_experts: int = 512                  # in the whole model, all shards
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # The clamp of a SwiGLU, a layer: non-zero in the published model's last
    # layers only, and not computed here.
    expert_swiglu_limits: tuple = ()
    shared_swiglu_limits: tuple = ()
    # What this program holds of the routed experts (models/routed.py).
    expert_shard: int = 0
    expert_shards: int = 1
    max_seq_len: int = 131072
    rope_theta: float = 6e6
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    # models/mla.mla_project: no low-rank query, no norm factors, the
    # rotary a half against the other, unscaled.
    q_lora_rank: ClassVar[None] = None
    mla_scale_q_lora: ClassVar[bool] = False
    mla_scale_kv_lora: ClassVar[bool] = False
    mla_rope_interleaved: ClassVar[bool] = False
    rope_scaling: ClassVar[None] = None

    def __post_init__(self):
        if self.num_layers % self.layer_group_size:
            raise ValueError(
                f"{self.num_layers} layers are not whole groups of "
                f"{self.layer_group_size}")
        if not 0 <= self.first_k_dense_replace <= self.num_layers:
            raise ValueError(f"first_k_dense_replace "
                             f"{self.first_k_dense_replace} of "
                             f"{self.num_layers} layers")
        if self.short_conv_kernel_size < 2:
            raise ValueError("short_conv_kernel_size under 2 leaves no "
                             "window")
        if not self.kda_lower_bound < 0:
            raise ValueError(f"kda_lower_bound {self.kda_lower_bound}: the "
                             "decay's floor is under 0")
        for name in ("expert_swiglu_limits", "shared_swiglu_limits"):
            if any(getattr(self, name)[:self.num_layers]):
                raise ValueError(
                    f"{name} {getattr(self, name)[:self.num_layers]}: a "
                    "clamped SwiGLU is not computed here (the published "
                    "model has one from layer 34 on)")
        self.router_rule  # refuses shares and groups that do not divide

    @staticmethod
    def tiny(**kw) -> "LingConfig":
        """Test-size config with every kind of layer: a group of 2 (KDA,
        latent) three times, the first group dense, so a dense and a routed
        feed-forward under both kinds of mixer; 16 experts in 4 groups of
        which 2 are kept, 4 a token, a shared expert."""
        base = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                    moe_intermediate_size=32,
                    shared_expert_intermediate_size=32, num_layers=6,
                    first_k_dense_replace=2, layer_group_size=2,
                    num_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, linear_num_heads=4,
                    linear_head_dim=16, num_experts=16,
                    num_experts_per_tok=4, n_group=4, topk_group=2,
                    max_seq_len=256, dtype="float32")
        base.update(kw)
        return LingConfig(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def groups(self) -> int:
        return self.num_layers // self.layer_group_size

    @property
    def written_groups(self) -> int:
        """Groups that hold a dense layer: ``run_layers`` writes them out,
        a layer's feed-forward known statically, and scans the rest."""
        return -(-self.num_dense_layers // self.layer_group_size)

    @property
    def latent_lines(self) -> int:
        """Layers that leave a latent row a position."""
        return self.groups

    @property
    def linear_lines(self) -> int:
        """Layers that leave a state and a convolution window a slot."""
        return self.num_layers - self.groups

    @property
    def num_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def num_routed_layers(self) -> int:
        return self.num_layers - self.first_k_dense_replace

    @property
    def linear_dim(self) -> int:
        """All KDA heads' keys (or values) side by side."""
        return self.linear_num_heads * self.linear_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: all heads' q, k and v."""
        return 3 * self.linear_dim

    @property
    def linear_state_bytes(self) -> int:
        """One slot's state in one KDA layer (float32)."""
        return self.linear_num_heads * self.linear_head_dim ** 2 * 4

    @property
    def latent_dim(self) -> int:
        """Values cached per position and latent layer."""
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
        return 1.0 / math.sqrt(self.qk_head_dim)

    @property
    def experts_held(self) -> int:
        return self.router_rule.held

    @property
    def router_rule(self) -> RouterRule:
        """Sigmoid scores, the choice by ``s + b`` among the best groups
        (a group by its two largest), the weights ``s / (sum + 1e-20)``
        times the factor."""
        return RouterRule(
            experts=self.num_experts, topk=self.num_experts_per_tok,
            score="sigmoid", use_bias=True,
            renormalize=self.norm_topk_prob, renorm_eps=1e-20,
            scaling_factor=self.routed_scaling_factor,
            groups=self.n_group, topk_groups=self.topk_group,
            expert_shard=self.expert_shard,
            expert_shards=self.expert_shards)

    def kind(self, layer: int) -> str:
        return (LATENT if (layer + 1) % self.layer_group_size == 0
                else KDA)

    def num_params(self) -> int:
        """Parameters held here (this shard's experts)."""
        h, ld, nh = self.hidden_size, self.linear_dim, self.num_heads
        kda = (h * 5 * ld + h * self.linear_num_heads
               + self.conv_dim * self.short_conv_kernel_size + ld
               + self.linear_num_heads + self.linear_head_dim + ld * h)
        latent = (h * nh * self.qk_head_dim + h * self.latent_dim
                  + self.kv_lora_rank + self.kv_lora_rank * nh
                  * (self.qk_nope_head_dim + self.v_head_dim) + h * nh
                  + nh * self.v_head_dim * h)
        dense = 3 * h * self.intermediate_size
        routed = (h * self.num_experts + self.num_experts
                  + 3 * h * self.shared_expert_intermediate_size
                  + self.experts_held * 3 * h * self.moe_intermediate_size)
        return (self.linear_lines * kda + self.latent_lines * latent
                + self.num_dense_layers * dense
                + self.num_routed_layers * routed
                + self.num_layers * 2 * h + 2 * self.vocab_size * h + h)


def param_logical_axes(cfg: LingConfig) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules).
    ``layers`` is the stacked axis, over whichever layers have the leaf."""
    return {
        "embed_tokens": ("vocab", "embed"),
        "lm_head": ("embed", "vocab"),
        "final_norm": ("embed",),
        "layers": {
            "input_norm": ("layers", "embed"),
            "post_norm": ("layers", "embed"),
            "in_qkvz": ("layers", "embed", None),
            "in_f": ("layers", "embed", None),
            "in_b": ("layers", "embed", None),
            "conv_w": ("layers", None, None),
            "dt_bias": ("layers", None),
            "a_log": ("layers", None),
            "kda_norm": ("layers", None),
            "out_proj": ("layers", None, "embed"),
            "wq": ("layers", "embed", "heads"),
            "wkv_a": ("layers", "embed", None),
            "kv_a_norm": ("layers", None),
            "wkv_b": ("layers", "heads", None, None),
            "wg": ("layers", "embed", None),
            "wo": ("layers", "heads", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
            "router": ("layers", "embed", None),
            "router_bias": ("layers", None),
            "ws_gate": ("layers", "embed", "mlp"),
            "ws_up": ("layers", "embed", "mlp"),
            "ws_down": ("layers", "mlp", "embed"),
            "we_gate": ("layers", "expert", "embed", "mlp"),
            "we_up": ("layers", "expert", "embed", "mlp"),
            "we_down": ("layers", "expert", "mlp", "embed"),
        },
    }


# The per-token decay rates a seeded key channel is centred on: ``-g`` from
# DECAY_RATES[0] to DECAY_RATES[1], log-uniform over the channels, so that
# ``exp(g)`` spreads over 0.01 (a channel that forgets in a token, ``g``
# near the floor: what the chunked rule's blocks of 16 exist for) to 0.999.
DECAY_RATES = (1e-3, 4.6)
# The decay projection's seeded scale, in units of a unit-variance output:
# a token moves a channel's gate about its centre and not across the range.
DECAY_INPUT_SCALE = 0.5


def init_params(cfg: LingConfig, key: jax.Array) -> dict:
    """Scaled-normal init that keeps every projection's output at unit
    variance, the norms' weights near 1 (models/qwen3_next.py's, for its
    reasons): the mixers', the dense SwiGLUs' and the shared expert's output
    projections are not scaled down by depth; the routed experts'
    down-projections are, by 1 / sqrt(2 x routed layers), so that a swapped
    eighth pick (a discrete choice between two ``s + b`` that are nearly
    equal, which falls differently in bfloat16 and in float32) is of
    rounding's size. The convolution's taps are at 1/sqrt(taps); the
    selection bias is small and of both signs (0.02 x normal beside
    sigmoid scores about 0.5 +- 0.2); the decay: ``A = exp(a_log)`` uniform
    over 0.5 to 2 a head, ``dt_bias`` a channel such that ``kda_lower_bound
    sigmoid(A dt_bias)`` is minus the channel's rate of DECAY_RATES, and the
    decay's projection at DECAY_INPUT_SCALE of a unit-variance output."""
    h, L = cfg.hidden_size, cfg.num_layers
    nl, na = cfg.linear_lines, cfg.latent_lines
    nd, nm = cfg.num_dense_layers, cfg.num_routed_layers
    nh, lh, ld = cfg.num_heads, cfg.linear_num_heads, cfg.linear_dim
    f, fe, fs, E = (cfg.intermediate_size, cfg.moe_intermediate_size,
                    cfg.shared_expert_intermediate_size, cfg.experts_held)
    dt = cfg.jnp_dtype
    keys = iter(jax.random.split(key, 40))

    def matrix(*shape, dtype=dt, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def norm(*shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dt)

    amount = jax.random.uniform(next(keys), (nl, lh), jnp.float32, 0.5, 2.0)
    rate = jnp.exp(jax.random.uniform(
        next(keys), (nl, lh, cfg.linear_head_dim), jnp.float32,
        math.log(DECAY_RATES[0]), math.log(DECAY_RATES[1])))
    share = rate / -cfg.kda_lower_bound            # sigmoid's value
    centre = (jnp.log(share) - jnp.log1p(-share)) / amount[..., None]
    return {
        "embed_tokens": matrix(cfg.vocab_size, h, scale=0.02),
        "lm_head": matrix(h, cfg.vocab_size),
        "final_norm": norm(h),
        "layers": {
            "input_norm": norm(L, h),
            "post_norm": norm(L, h),
            "in_qkvz": matrix(nl, h, cfg.conv_dim + ld),
            "in_f": matrix(nl, h, ld,
                           scale=DECAY_INPUT_SCALE / math.sqrt(h)),
            "in_b": matrix(nl, h, lh),
            "conv_w": matrix(nl, cfg.short_conv_kernel_size, cfg.conv_dim,
                             scale=1.0 / math.sqrt(
                                 cfg.short_conv_kernel_size)),
            "dt_bias": centre.reshape(nl, ld),
            "a_log": jnp.log(amount),
            "kda_norm": norm(nl, cfg.linear_head_dim),
            "out_proj": matrix(nl, ld, h),
            "wq": matrix(na, h, nh * cfg.qk_head_dim),
            "wkv_a": matrix(na, h, cfg.latent_dim),
            "kv_a_norm": norm(na, cfg.kv_lora_rank),
            "wkv_b": matrix(na, nh, cfg.kv_lora_rank,
                            cfg.qk_nope_head_dim + cfg.v_head_dim),
            "wg": matrix(na, h, nh),
            "wo": matrix(na, nh * cfg.v_head_dim, h),
            "w_gate": matrix(nd, h, f),
            "w_up": matrix(nd, h, f),
            "w_down": matrix(nd, f, h),
            # The router stays float32: its choices are discrete.
            "router": matrix(nm, h, cfg.num_experts, dtype=jnp.float32),
            "router_bias": matrix(nm, cfg.num_experts, dtype=jnp.float32,
                                  scale=0.02),
            "ws_gate": matrix(nm, h, fs),
            "ws_up": matrix(nm, h, fs),
            "ws_down": matrix(nm, fs, h),
            "we_gate": matrix(nm, E, h, fe),
            "we_up": matrix(nm, E, h, fe),
            "we_down": matrix(nm, E, fe, h,
                              scale=1.0 / math.sqrt(2 * max(nm, 1) * fe)),
        },
    }


# ---------------------------------------------------------------- blocks

def kda_inputs(cfg: LingConfig, lp: dict, xn):
    """KDA's projections in, on xn [..., H] (normed) -> (mixed [...,
    conv_dim], the convolution's input, whose last rows a sequence keeps;
    z [..., linear_dim], the output's gate; g [..., heads, D] float32, the
    decay a key channel, in ``(kda_lower_bound, 0)``; beta [..., heads]
    float32)."""
    with tracing.part("linear_attn"):
        qkvz = xn @ lp["in_qkvz"]
        beta = jax.nn.sigmoid((xn @ lp["in_b"]).astype(jnp.float32))
        with tracing.part("kda_gate"):
            # An array of its own before it is laid along the state's rows
            # (models/mla.mla_project's ``keep_product``: XLA otherwise
            # folds that layout into the product and copies the whole
            # stacked ``in_f`` transposed at the top of a decode program).
            f = lax.optimization_barrier(jnp.dot(
                xn, lp["in_f"], preferred_element_type=jnp.float32))
            amount = jnp.repeat(jnp.exp(lp["a_log"]), cfg.linear_head_dim)
            g = cfg.kda_lower_bound * jax.nn.sigmoid(
                amount * (f + lp["dt_bias"]))
            g = g.reshape(*g.shape[:-1], cfg.linear_num_heads,
                          cfg.linear_head_dim)
        return qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:], g, beta


def kda_heads(cfg: LingConfig, lp: dict, window, s: int):
    """The depthwise causal convolution over ``window`` [B, taps - 1 + S,
    conv_dim] at its last ``s`` positions, ``silu``, and the split into
    heads: q, k (L2-normalised, the query scaled) and v, [B, S, heads, D]
    float32 each."""
    with tracing.part("linear_attn"):
        with tracing.part("conv"):
            mixed = short_conv_silu(lp["conv_w"], window, s)
        q, k, v = jnp.split(mixed, 3, axis=-1)
        nh = cfg.linear_num_heads
        return (unit_heads(q, nh) * cfg.linear_head_dim ** -0.5,
                unit_heads(k, nh), v.reshape(*v.shape[:2], nh, -1))


def kda_output(cfg: LingConfig, lp: dict, o, z, dtype):
    """The rule's output o [B, S, heads, D] float32 normed a head, gated by
    ``sigmoid(z)`` a channel and projected out."""
    with tracing.part("linear_attn"):
        b, s = o.shape[:2]
        o = rms_norm_reference(o, lp["kda_norm"], cfg.norm_eps)
        o = o * jax.nn.sigmoid(z.astype(jnp.float32).reshape(o.shape))
        return (o.astype(dtype).reshape(b, s, -1) @ lp["out_proj"]).astype(
            dtype)


def latent_output(cfg: LingConfig, ap: dict, xn, o, dtype):
    """The latent attention's output o [B, S, nh * Dv] times the head-wise
    gate ``sigmoid(xn W_a)``, projected out."""
    b, s, _ = o.shape
    gate = jax.nn.sigmoid((xn @ ap["wg"]).astype(jnp.float32))
    o = o.reshape(b, s, cfg.num_heads, -1) * gate[..., None].astype(o.dtype)
    return (o.reshape(b, s, -1) @ ap["wo"]).astype(dtype)


def shared_expert(layers: dict, index, u):
    """``Shared(u)`` on u [T, H]: the expert every token passes, added as
    it is."""
    with tracing.part("mlp"), tracing.part("moe_shared"):
        return swiglu(u, *(layer_of(layers[k], index) for k in SHARED_LEAVES))


def layer(cfg: LingConfig, layers: dict, at: int, repeat, routed: bool, x,
          operators: dict, state, valid, kmesh=None):
    """Layer ``repeat * layer_group_size + at`` on x [B, S, H]: ``at`` is
    the layer's place in its group (static), ``repeat`` the group's index (a
    run-time value, or an int in a group written out), ``routed`` says
    statically which feed-forward it has. ``layers`` is the whole stacked
    ``params["layers"]``: every leaf is indexed where it is used.
    ``operators[kind](repeat, at, p, xn, state) -> (y, state)`` runs the
    layer's mixer on normed input with its own params ``p``; ``repeat`` and
    ``at`` say which layer it is (a latent layer's cache line is ``repeat``,
    a KDA layer's ``repeat * (layer_group_size - 1) + at``) and ``state`` is
    whatever the operators thread. Returns (x, state, counts)."""
    b, s, hid = x.shape
    group = cfg.layer_group_size
    index = repeat * group + at
    kind = cfg.kind(at)
    line = repeat if kind == LATENT else repeat * (group - 1) + at
    with tracing.part("stack"):
        p = {k: layer_of(layers[k], line)
             for k in (LATENT_LEAVES if kind == LATENT else KDA_LEAVES)}
        input_norm = layer_of(layers["input_norm"], index)
        post_norm = layer_of(layers["post_norm"], index)
    # The mixer's place is ``attn`` for either kind; KDA opens
    # ``linear_attn``, ``kda_gate``, ``conv``, ``kda_rule`` and
    # ``linear_state`` inside it (tracing.SUBPARTS).
    with tracing.part("attn"):
        y, state = operators[kind](
            repeat, at, p, rms_norm(x, input_norm, cfg.norm_eps, kmesh),
            state)
        x = x + y
    if not routed:
        with tracing.part("mlp"):
            u = rms_norm(x, post_norm, cfg.norm_eps, kmesh)
            x = x + swiglu(u, *(layer_of(layers[k], index)
                                for k in DENSE_LEAVES))
        return x, state, jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    with tracing.part("moe_route"):
        u = rms_norm(x, post_norm, cfg.norm_eps, kmesh).reshape(b * s, hid)
    at_routed = index - cfg.num_dense_layers
    m, counts = moe_block(cfg.router_rule, layers, at_routed, u,
                          valid.reshape(b * s))
    shared = shared_expert(layers, at_routed, u)
    with tracing.part("moe_combine"):
        x = x + (m + shared).reshape(b, s, hid)
    return x, state, counts


def run_layers(cfg: LingConfig, params, x, operators: dict, state, valid,
               kmesh=None):
    """Every layer over x [B, S, H], ``state`` as carry. The groups that
    hold a dense layer (the first, at the published sizes) are written out,
    each layer's feed-forward known statically; the groups after them, all
    routed, are one scan over the group's index. Returns (x, state, counts
    int32[6] summed over the layers)."""
    group, mixed = cfg.layer_group_size, cfg.written_groups
    counts = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)

    def one_group(x, state, counts, repeat, routed_from: int):
        for at in range(group):
            x, state, c = layer(cfg, params["layers"], at, repeat,
                                at >= routed_from, x, operators, state,
                                valid, kmesh)
            with tracing.part("moe_combine"):
                counts = counts + c
        return x, state, counts

    for repeat in range(mixed):
        x, state, counts = one_group(
            x, state, counts, repeat, cfg.num_dense_layers - repeat * group)
    if cfg.groups > mixed:
        def body(carry, repeat):
            return one_group(*carry, repeat, 0), None

        with tracing.part("stack"):
            (x, state, counts), _ = lax.scan(
                body, (x, state, counts), jnp.arange(mixed, cfg.groups))
    return x, state, counts


@tracing.part("head")
def lm_head(cfg: LingConfig, params, x, kmesh=None):
    """x: [..., H] -> float32 logits [..., V] (untied head)."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)
    return jnp.dot(x, params["lm_head"], preferred_element_type=jnp.float32)


def forward(cfg: LingConfig, params: dict, tokens, *,
            kmesh: KernelMesh | None = None):
    """tokens [B, S] -> (float32 logits [B, S, V], router counts int32[6]).
    Whole sequences, no cache and no state: the convolution and the rule
    start from zeros, the attention is causal over the sequence."""
    b, s = tokens.shape
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    valid = jnp.ones(tokens.shape, bool)

    def kda(repeat, at, lp, xn, state):
        mixed, z, g, beta = kda_inputs(cfg, lp, xn)
        prior = jnp.zeros((b, cfg.short_conv_kernel_size - 1, cfg.conv_dim),
                          xn.dtype)
        q, k, v = kda_heads(cfg, lp, conv_window(prior, mixed), s)
        zero = jnp.zeros((cfg.linear_num_heads, cfg.linear_head_dim,
                          cfg.linear_head_dim), jnp.float32)
        with tracing.part("linear_attn"), tracing.part("kda_rule"):
            o = jax.vmap(lambda *a: gated_delta_chunk(
                *a, zero, g_floor=cfg.kda_lower_bound)[0])(q, k, v, g, beta)
        return kda_output(cfg, lp, o, z, xn.dtype), state

    def latent(repeat, at, ap, xn, state):
        o = mla_attend_full(cfg, ap, xn, kmesh, kv_up_projections)
        return latent_output(cfg, ap, xn, o, xn.dtype), state

    x, _, counts = run_layers(cfg, params, x, {KDA: kda, LATENT: latent},
                              None, valid, kmesh)
    return lm_head(cfg, params, x, kmesh), counts
