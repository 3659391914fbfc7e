"""Qwen3-Next family: Gated DeltaNet layers beside a gated full attention
every few layers, routed experts with a gated shared expert in every layer.

The family of ``model_type: "qwen3_next"`` (huggingface.co/Qwen/
Qwen3-Next-80B-A3B-Instruct). Layer ``l``, input ``h``, ``N`` an RMSNorm
whose weight is ``1 + w`` (statistics in float32)::

    a  = h + Mix_l(N(h))
    h' = a + F(N(a))

``Mix_l`` is the gated attention where ``(l + 1) % full_attention_interval
== 0`` and Gated DeltaNet otherwise:

- **Gated DeltaNet.** ``x W_qkvz`` gives, for ``linear_num_key_heads`` key
  heads of ``linear_key_head_dim`` and ``linear_num_value_heads`` value
  heads of ``linear_value_head_dim``, queries, keys, values and an output
  gate ``z``; ``x W_ba`` a step ``b`` and a decay input ``a`` a value head.
  ``[q | k | v]`` passes a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps (zeros before position 0), then ``silu``.
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` in
  float32. A key head's ``q`` and ``k`` serve its ``value heads / key
  heads`` value heads; both are L2-normalised a head (``x rsqrt(sum x^2 +
  1e-6)``) and ``q`` scaled by ``Dk^-1/2``. Then the gated delta rule
  (ops/gated_delta.py) a value head, from a zero state. Its output a head is
  normed and gated, ``w o rsqrt(mean o^2 + eps) silu(z)``, the heads side by
  side through ``W_out``. What a token leaves behind is the state of every
  head and the last ``taps - 1`` rows of ``[q | k | v]`` before the
  convolution: a state of fixed size, whatever the length.
- **Gated attention.** ``x W_q`` gives a head its query and its gate side
  by side; queries and keys are normed a head (``1 + w``), the first
  ``partial_rotary_factor`` of a head's values rotated (``rotate_half``
  over that part alone), causal GQA at ``head_dim^-1/2``; the output is
  multiplied by ``sigmoid(gate)`` before ``W_o``.

``F`` is ``sigmoid(u w_sg) Shared(u) + sum_e w_e E_e(u)``: the routed layer
of models/routed.py under the Qwen3-MoE rule (softmax over all experts, the
``num_experts_per_tok`` largest, their weights divided by their sum with
nothing added) and beside it one shared SwiGLU under a sigmoid gate of its
own. The routed layer is told which experts it holds (``expert_shard`` of
``expert_shards``) and computes their part of the sum; the shared expert is
replicated in such a deployment, so where shards' results are summed it
counts once (tests/test_qwen3_next.py). After the last layer ``N``, then an
untied head. The family's multi-token-prediction module is left out.

Params: a flat pytree, every leaf stacked over the layers that have it (the
norms, the router, the shared and the routed experts over all layers, the
rule's leaves over the linear layers, the attention's over the full ones)
and indexed by the loop's counter where it is used. Two leaves are stored in
another order than the published matrices, the same numbers: ``in_qkvz``
holds all heads' ``q``, then ``k``, then ``v``, then ``z`` (published: a key
head's ``q | k | v | z`` side by side), so that ``[q | k | v]`` is one
slice, and ``in_ba`` all heads' ``b`` then ``a``
(benchmark/rtbench/adapters/qwen3_next.reference_weights puts them back).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.lfm2 import swiglu
from ray_tpu.models.routed import (
    MOE_COUNTERS,
    RouterRule,
    layer_of,
    moe_block,
)
from ray_tpu.ops.attention import blockwise_attention
from ray_tpu.ops.gated_delta import gated_delta_chunk
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm, rms_norm_reference
from ray_tpu.ops.rope import apply_rope_partial, rope_frequencies
from ray_tpu.util import tracing

LINEAR, ATTENTION = "linear_attention", "full_attention"
L2_EPS = 1e-6

LINEAR_LEAVES = ("in_qkvz", "in_ba", "conv_w", "dt_bias", "a_log",
                 "gdn_norm", "out_proj")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
SHARED_LEAVES = ("ws_gate", "ws_up", "ws_down")


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512                 # in the whole model, all shards
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    # What this program holds of the routed experts (models/routed.py).
    expert_shard: int = 0
    expert_shards: int = 1
    max_seq_len: int = 262144
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.num_layers % self.full_attention_interval:
            raise ValueError(
                f"{self.num_layers} layers are not whole periods of "
                f"{self.full_attention_interval}")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"{self.linear_num_value_heads} value heads do not divide "
                f"over {self.linear_num_key_heads} key heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"partial_rotary_factor "
                             f"{self.partial_rotary_factor} of a head of "
                             f"{self.head_dim}")
        if self.linear_conv_kernel_dim < 2:
            raise ValueError("linear_conv_kernel_dim under 2 leaves no "
                             "window")
        self.router_rule  # refuses a share the experts do not divide into

    @staticmethod
    def tiny(**kw) -> "Qwen3NextConfig":
        """Test-size config with every mechanism: both layer kinds, a
        period (of 2) that repeats, 2 value heads a key head, a rotary over
        a quarter of a head, 8 experts of 32 with 2 a token, a shared
        expert."""
        base = dict(vocab_size=512, hidden_size=64, num_layers=4,
                    full_attention_interval=2, num_heads=4, num_kv_heads=2,
                    head_dim=16, linear_num_key_heads=2,
                    linear_num_value_heads=4, linear_key_head_dim=16,
                    linear_value_head_dim=8, num_experts=8,
                    num_experts_per_tok=2, moe_intermediate_size=32,
                    shared_expert_intermediate_size=32, max_seq_len=256,
                    dtype="float32")
        base.update(kw)
        return Qwen3NextConfig(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def periods(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def attention_lines(self) -> int:
        """Layers that leave keys and values a position."""
        return self.periods

    @property
    def linear_lines(self) -> int:
        """Layers that leave a state and a convolution window a slot."""
        return self.num_layers - self.periods

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: all heads' q, k and v."""
        return 2 * self.key_dim + self.value_dim

    @property
    def linear_state_bytes(self) -> int:
        """One slot's state in one linear layer (float32)."""
        return (self.linear_num_value_heads * self.linear_key_head_dim
                * self.linear_value_head_dim * 4)

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

    def kind(self, layer: int) -> str:
        return (ATTENTION if (layer + 1) % self.full_attention_interval == 0
                else LINEAR)

    def num_params(self) -> int:
        """Parameters held here (this shard's experts)."""
        h = self.hidden_size
        nv = self.linear_num_value_heads
        linear = (h * (self.conv_dim + self.value_dim) + h * 2 * nv
                  + self.conv_dim * self.linear_conv_kernel_dim + 2 * nv
                  + self.linear_value_head_dim + self.value_dim * h)
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        attn = 2 * h * qd + 2 * h * kvd + qd * h + 2 * self.head_dim
        ffn = (h * self.num_experts + 3 * h
               * self.shared_expert_intermediate_size + h
               + self.experts_held * 3 * h * self.moe_intermediate_size)
        return (self.linear_lines * linear + self.attention_lines * attn
                + self.num_layers * (ffn + 2 * h)
                + 2 * self.vocab_size * h + h)


def param_logical_axes(cfg: Qwen3NextConfig) -> dict:
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
            "in_ba": ("layers", "embed", None),
            "conv_w": ("layers", None, None),
            "dt_bias": ("layers", None, None),
            "a_log": ("layers", None, None),
            "gdn_norm": ("layers", None),
            "out_proj": ("layers", None, "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "q_norm": ("layers", None),
            "k_norm": ("layers", None),
            "router": ("layers", "embed", None),
            "shared_gate": ("layers", "embed"),
            "ws_gate": ("layers", "embed", "mlp"),
            "ws_up": ("layers", "embed", "mlp"),
            "ws_down": ("layers", "mlp", "embed"),
            "we_gate": ("layers", "expert", "embed", "mlp"),
            "we_up": ("layers", "expert", "embed", "mlp"),
            "we_down": ("layers", "expert", "mlp", "embed"),
        },
    }


# The per-token decay rates a seeded head is centred on: ``-g`` from
# DECAY_RATES[0] to DECAY_RATES[1], log-uniform over the heads, so that
# ``exp(g)`` spreads over about 0.5 to 0.999: heads that forget in a few
# tokens beside heads that remember a thousand, not all at one end.
DECAY_RATES = (1e-3, 0.7)


def init_params(cfg: Qwen3NextConfig, key: jax.Array) -> dict:
    """Scaled-normal init that keeps every projection's output at unit
    variance (models/lfm2.py). What a trained checkpoint has and an init at
    the published defaults would hide is drawn too: the ``1 + w`` norms'
    ``w`` (published 0) at 0.1, the rule's output norm (published 1) near
    1, the convolution's taps at 1/sqrt(taps), the shared expert's gate at
    1/sqrt(hidden) (a sigmoid of unit-variance input: 0.27 to 0.73), and the
    rule's decay: ``A = exp(a_log)`` uniform over 1 to 16 as the family
    draws it, ``dt_bias`` such that ``A softplus(dt_bias)`` is a head's rate
    of DECAY_RATES (the input's ``a`` moves it about that).

    The operators' and the shared expert's output projections are not scaled
    down by depth (models/longcat.py: the residual stream grows along the
    layers as a trained one does). The routed experts' down-projections are,
    by 1 / sqrt(2 x layers), as models/lfm2.py's and for its reason: the
    tenth place of the rule is a discrete choice between two softmax scores
    that are nearly equal, which falls differently in bfloat16 and in the
    float32 reference for some tokens; scaled, such a swap is of rounding's
    size and a sound run reads what rounding leaves."""
    h, d, L = cfg.hidden_size, cfg.head_dim, cfg.num_layers
    nl, na = cfg.linear_lines, cfg.attention_lines
    nv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    fe, fs, E = (cfg.moe_intermediate_size,
                 cfg.shared_expert_intermediate_size, cfg.experts_held)
    qd, kvd = cfg.num_heads * d, cfg.num_kv_heads * d
    dt = cfg.jnp_dtype
    keys = iter(jax.random.split(key, 32))

    def matrix(*shape, dtype=dt, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def norm(*shape, centre=0.0):
        return (centre + 0.1 * jax.random.normal(next(keys), shape,
                                                 jnp.float32)).astype(dt)

    # A value head's two numbers, by its key head: [layers, key heads,
    # value heads a key head].
    by_key = (nl, cfg.linear_num_key_heads, nv // cfg.linear_num_key_heads)
    amount = jax.random.uniform(next(keys), by_key, jnp.float32, 1.0, 16.0)
    rate = jnp.exp(jax.random.uniform(
        next(keys), by_key, jnp.float32, math.log(DECAY_RATES[0]),
        math.log(DECAY_RATES[1])))
    step = rate / amount
    return {
        "embed_tokens": matrix(cfg.vocab_size, h, scale=0.02),
        "lm_head": matrix(h, cfg.vocab_size),
        "final_norm": norm(h),
        "layers": {
            "input_norm": norm(L, h),
            "post_norm": norm(L, h),
            "in_qkvz": matrix(nl, h, cfg.conv_dim + cfg.value_dim),
            "in_ba": matrix(nl, h, 2 * nv),
            "conv_w": matrix(nl, cfg.linear_conv_kernel_dim, cfg.conv_dim,
                             scale=1.0 / math.sqrt(
                                 cfg.linear_conv_kernel_dim)),
            # softplus^-1(step): step + log(1 - exp(-step)).
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(amount),
            "gdn_norm": norm(nl, dv, centre=1.0),
            "out_proj": matrix(nl, cfg.value_dim, h),
            "wq": matrix(na, h, 2 * qd),
            "wk": matrix(na, h, kvd),
            "wv": matrix(na, h, kvd),
            "wo": matrix(na, qd, h),
            "q_norm": norm(na, d),
            "k_norm": norm(na, d),
            # The router stays float32: its top-k is a discrete choice.
            "router": matrix(L, h, cfg.num_experts, dtype=jnp.float32),
            "shared_gate": matrix(L, h, scale=1.0 / math.sqrt(h)),
            "ws_gate": matrix(L, h, fs),
            "ws_up": matrix(L, h, fs),
            "ws_down": matrix(L, fs, h),
            "we_gate": matrix(L, E, h, fe),
            "we_up": matrix(L, E, h, fe),
            "we_down": matrix(L, E, fe, h,
                              scale=1.0 / math.sqrt(2 * L * fe)),
        },
    }


# ---------------------------------------------------------------- blocks

def unit_offset(w):
    """A zero-centred norm's weight: ``1 + w`` in float32 (in the stored
    dtype the sum would keep 8 bits of ``w``)."""
    return 1.0 + w.astype(jnp.float32)


def linear_inputs(cfg: Qwen3NextConfig, lp: dict, xn):
    """The rule's projections in, on xn [..., H] (normed) -> (mixed [...,
    conv_dim], the convolution's input, whose last rows a sequence keeps;
    z [..., value_dim], the output's gate; g and beta [..., value heads]
    float32)."""
    with tracing.part("linear_attn"):
        qkvz = xn @ lp["in_qkvz"]
        b, a = jnp.split((xn @ lp["in_ba"]).astype(jnp.float32), 2, axis=-1)
        g = -jnp.exp(lp["a_log"].reshape(-1)) * jax.nn.softplus(
            a + lp["dt_bias"].reshape(-1))
        return (qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:], g,
                jax.nn.sigmoid(b))


def conv_window(prior, mixed):
    """``prior`` [B, taps - 1, conv_dim] and then the rows ``mixed`` [B, S,
    conv_dim] of this call: what the taps slide over, and what the window
    kept is cut from."""
    with tracing.part("linear_state"):
        return jnp.concatenate([prior.astype(mixed.dtype), mixed], axis=1)


def short_conv_silu(conv_w, window, s: int, bias=None):
    """A depthwise causal convolution, then ``silu``: ``conv_w`` [taps,
    channels] over ``window`` [B, taps - 1 + S, channels] at its last ``s``
    positions, float32 (models/ling.py's too), plus ``bias`` [channels]
    where the family has one (models/granite.py's)."""
    taps = conv_w.astype(jnp.float32)
    mixed = sum(taps[j] * window[:, j:j + s].astype(jnp.float32)
                for j in range(taps.shape[0]))
    return jax.nn.silu(
        mixed if bias is None else mixed + bias.astype(jnp.float32))


def unit_heads(x, heads: int):
    """x [B, S, heads * D] -> [B, S, heads, D], L2-normalised a head."""
    x = x.reshape(*x.shape[:2], heads, -1)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def linear_key_heads(cfg: Qwen3NextConfig, lp: dict, window, s: int):
    """The depthwise causal convolution over ``window`` [B, taps - 1 + S,
    conv_dim] at its last ``s`` positions, ``silu``, and the split into
    heads: q, k [B, S, key heads, Dk] float32 (L2-normalised, the query
    scaled), v [B, S, value heads, Dv]. A key head serves ``value heads //
    key heads`` value heads in a row."""
    with tracing.part("linear_attn"):
        b = window.shape[0]
        mixed = short_conv_silu(lp["conv_w"], window, s)
        q, k, v = jnp.split(mixed, (cfg.key_dim, 2 * cfg.key_dim), axis=-1)
        nk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        return (unit_heads(q, nk) * dk ** -0.5, unit_heads(k, nk),
                v.reshape(b, s, cfg.linear_num_value_heads,
                          cfg.linear_value_head_dim))


def linear_heads(cfg: Qwen3NextConfig, lp: dict, window, s: int):
    """:func:`linear_key_heads` with a key head's q and k repeated for its
    value heads, [B, S, value heads, Dk]: what the one-token step takes."""
    q, k, v = linear_key_heads(cfg, lp, window, s)
    with tracing.part("linear_attn"):
        rep = cfg.linear_num_value_heads // cfg.linear_num_key_heads
        return jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2), v


def linear_output(cfg: Qwen3NextConfig, lp: dict, o, z, dtype):
    """The rule's output o [B, S, value heads, Dv] float32 normed a head,
    gated by ``silu(z)`` and projected out."""
    with tracing.part("linear_attn"):
        b, s = o.shape[:2]
        o = rms_norm_reference(o, lp["gdn_norm"], cfg.norm_eps)
        o = o * jax.nn.silu(z.astype(jnp.float32).reshape(o.shape))
        return (o.astype(dtype).reshape(b, s, -1) @ lp["out_proj"]).astype(
            dtype)


def attention_heads(cfg: Qwen3NextConfig, ap: dict, xn, positions, inv_freq):
    """xn [B, S, H] (normed) -> the normed, partly rotated queries [B, nh,
    S, D] and keys [B, nkv, S, D], the values [B, nkv, S, D] and the
    output's gate [B, S, nh * D]."""
    b, s, _ = xn.shape
    nh, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # Arrays of their own before they are split into heads
    # (models/lfm2.attention_heads).
    qg, k, v = lax.optimization_barrier(
        (xn @ ap["wq"], xn @ ap["wk"], xn @ ap["wv"]))
    qg = qg.reshape(b, s, nh, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(b, s, nh * d)
    q = q.transpose(0, 2, 1, 3)
    k = k.reshape(b, s, nkv, d).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, nkv, d).transpose(0, 2, 1, 3)
    q = rms_norm_reference(q, unit_offset(ap["q_norm"]), cfg.norm_eps)
    k = rms_norm_reference(k, unit_offset(ap["k_norm"]), cfg.norm_eps)

    return (apply_rope_partial(q, positions, inv_freq),
            apply_rope_partial(k, positions, inv_freq), v, gate)


def attention_output(ap: dict, o, gate, dtype):
    """The attention's output o [B, S, nh * D] under its gate's sigmoid,
    projected out."""
    gated = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
    return (gated @ ap["wo"]).astype(dtype)


def shared_expert(layers: dict, index, u):
    """``sigmoid(u w_sg) Shared(u)`` on u [T, H]: the expert every token
    passes, under a gate of its own."""
    with tracing.part("mlp"), tracing.part("moe_shared"):
        gate = jax.nn.sigmoid(jnp.dot(
            u, layer_of(layers["shared_gate"], index),
            preferred_element_type=jnp.float32))
        y = swiglu(u, *(layer_of(layers[k], index) for k in SHARED_LEAVES))
        return (gate[:, None] * y.astype(jnp.float32)).astype(u.dtype)


def layer(cfg: Qwen3NextConfig, layers: dict, at: int, repeat, x,
          operators: dict, state, valid, kmesh=None):
    """Layer ``repeat * full_attention_interval + at`` on x [B, S, H]:
    ``at`` is the layer's place in the period (static), ``repeat`` the
    period's index (a run-time value). ``layers`` is the whole stacked
    ``params["layers"]``: every leaf is indexed where it is used.
    ``operators[kind](line, p, xn, state) -> (y, state)`` runs the layer's
    operator on normed input with its own params ``p``; ``line`` is the
    layer's rank among the layers of its kind (its cache line) and ``state``
    whatever the operators thread. Returns (x, state, counts)."""
    b, s, hid = x.shape
    period = cfg.full_attention_interval
    index = repeat * period + at
    kind = cfg.kind(at)
    line = repeat if kind == ATTENTION else repeat * (period - 1) + at
    with tracing.part("stack"):
        p = {k: layer_of(layers[k], line)
             for k in (ATTENTION_LEAVES if kind == ATTENTION
                       else LINEAR_LEAVES)}
        input_norm = unit_offset(layer_of(layers["input_norm"], index))
        post_norm = unit_offset(layer_of(layers["post_norm"], index))
    # The operator's place is ``attn`` for either kind; the rule opens
    # ``linear_attn``, ``delta_rule`` and ``linear_state`` inside it
    # (tracing.SUBPARTS).
    with tracing.part("attn"):
        y, state = operators[kind](
            line, p, rms_norm(x, input_norm, cfg.norm_eps, kmesh), state)
        x = x + y
    with tracing.part("moe_route"):
        u = rms_norm(x, post_norm, cfg.norm_eps, kmesh).reshape(b * s, hid)
    m, counts = moe_block(cfg.router_rule, layers, index, u,
                          valid.reshape(b * s))
    shared = shared_expert(layers, index, u)
    with tracing.part("moe_combine"):
        x = x + (m + shared).reshape(b, s, hid)
    return x, state, counts


def run_layers(cfg: Qwen3NextConfig, params, x, operators: dict, state,
               valid, kmesh=None):
    """Every layer over x [B, S, H], ``state`` as carry: one scan over the
    repeats of the period. Returns (x, state, counts int32[6] summed over
    the layers)."""
    def body(carry, repeat):
        x, state, counts = carry
        for at in range(cfg.full_attention_interval):
            x, state, c = layer(cfg, params["layers"], at, repeat, x,
                                operators, state, valid, kmesh)
            with tracing.part("moe_combine"):
                counts = counts + c
        return (x, state, counts), None

    counts = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    with tracing.part("stack"):
        (x, state, counts), _ = lax.scan(
            body, (x, state, counts), jnp.arange(cfg.periods))
    return x, state, counts


@tracing.part("head")
def lm_head(cfg: Qwen3NextConfig, params, x, kmesh=None):
    """x: [..., H] -> float32 logits [..., V] (untied head)."""
    x = rms_norm(x, unit_offset(params["final_norm"]), cfg.norm_eps, kmesh)
    return jnp.dot(x, params["lm_head"], preferred_element_type=jnp.float32)


def forward(cfg: Qwen3NextConfig, params: dict, tokens, *,
            kmesh: KernelMesh | None = None):
    """tokens [B, S] -> (float32 logits [B, S, V], router counts int32[6]).
    Whole sequences, no cache and no state: the convolution and the rule
    start from zeros, the attention is causal over the sequence."""
    b, s = tokens.shape
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    with tracing.part("attn"):
        positions = jnp.arange(s)
        inv_freq = rope_frequencies(cfg.rotary_dim, cfg.rope_theta)
    valid = jnp.ones(tokens.shape, bool)

    def linear(line, lp, xn, state):
        mixed, z, g, beta = linear_inputs(cfg, lp, xn)
        prior = jnp.zeros((b, cfg.linear_conv_kernel_dim - 1, cfg.conv_dim),
                          xn.dtype)
        q, k, v = linear_key_heads(cfg, lp, conv_window(prior, mixed), s)
        zero = jnp.zeros((cfg.linear_num_value_heads,
                          cfg.linear_key_head_dim,
                          cfg.linear_value_head_dim), jnp.float32)
        with tracing.part("linear_attn"), tracing.part("delta_rule"):
            o = jax.vmap(lambda *a: gated_delta_chunk(*a, zero)[0])(
                q, k, v, g, beta)
        return linear_output(cfg, lp, o, z, xn.dtype), state

    def attention(line, ap, xn, state):
        q, k, v, gate = attention_heads(cfg, ap, xn, positions, inv_freq)
        o = blockwise_attention(q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return attention_output(ap, o, gate, xn.dtype), state

    x, _, counts = run_layers(cfg, params, x,
                              {LINEAR: linear, ATTENTION: attention}, None,
                              valid, kmesh)
    return lm_head(cfg, params, x, kmesh), counts
