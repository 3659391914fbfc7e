"""LFM2-MoE family: gated short convolutions beside a few attentions, dense
SwiGLUs in the leading layers and routed experts in the rest.

The family of ``model_type: "lfm2_moe"`` (huggingface.co/LiquidAI/
LFM2-24B-A2B). A layer is ``h = h + op(N(h))`` then ``h = h + ffn(N(h))``
with RMSNorms N; ``op`` is, by ``layer_types[l]``,

- ``conv``, the gated short convolution: ``[B, C, x] = split3(u W_in)``,
  ``z = B * x``, a depthwise causal convolution of width ``conv_L_cache``
  over the positions of ``z`` (``v_t = sum_j w[j] z_{t - (L - 1) + j}``,
  zeros before position 0), ``y = (C * v) W_out``. What a token leaves
  behind for the tokens after it is the last ``conv_L_cache - 1`` rows of
  ``z``: a state of fixed size, whatever the length;
- ``full_attention``: causal GQA whose queries and keys are RMS-normed a
  head (weights of ``head_dim``) before the half-rotated RoPE; heads of 64;

and ``ffn`` is a dense SwiGLU in the first ``num_dense_layers`` layers and
the routed layer of models/routed.py after them: sigmoid scores, the choice
by score + ``expert_bias``, the chosen weights renormalised (``+ 1e-6``) and
scaled; no shared expert, no zero expert, nothing dropped. After the last
layer one RMSNorm, then the head, tied to the embedding.

Params are a flat pytree. Layers are of four kinds that do not line up (an
operator kind and a feed-forward kind each), so every leaf of ``layers`` is
stacked over the layers *that have it*: the two norms over all layers, the
convolution's leaves over the ``conv`` layers, the attention's over the
``full_attention`` layers, the dense SwiGLU's over the dense layers, the
router's and the experts' over the routed layers. A layer loop indexes each
stack where it is used (as models/longcat.py does) and scans over the
repeats of the layer pattern's period (:attr:`Lfm2Config.segments`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.routed import (
    MOE_COUNTERS,
    RouterRule,
    layer_of,
    moe_block,
)
from ray_tpu.ops.attention import blockwise_attention
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm, rms_norm_reference
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.util import tracing

CONV, ATTENTION = "conv", "full_attention"
# One period of the published pattern after the two leading layers.
_PERIOD = (ATTENTION, CONV, CONV, CONV)
PUBLISHED_LAYER_TYPES = (CONV, CONV) + _PERIOD * 9 + (ATTENTION, CONV)

CONV_LEAVES = ("conv_in", "conv_w", "conv_out")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")


@dataclass(frozen=True)
class Segment:
    """``repeats`` times the ``period`` layers from layer ``first`` on."""

    first: int
    period: int
    repeats: int


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776         # the leading dense SwiGLUs
    moe_intermediate_size: int = 1536      # one expert
    layer_types: tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    conv_L_cache: int = 3
    num_experts: int = 64                  # in the whole model, all shards
    num_experts_per_tok: int = 4
    router_score: str = "sigmoid"
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # What this program holds of the routed experts (models/routed.py).
    expert_shard: int = 0
    expert_shards: int = 1
    max_seq_len: int = 128000
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {CONV, ATTENTION}
        if bad:
            raise ValueError(f"layer_types {sorted(bad)}: {CONV!r} or "
                             f"{ATTENTION!r}")
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError(f"num_dense_layers {self.num_dense_layers} of "
                             f"{self.num_layers} layers")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache under 2 leaves no state")
        self.router_rule  # refuses a share the experts do not divide into

    @staticmethod
    def tiny(**kw) -> "Lfm2Config":
        """Test-size config with every mechanism: both operator kinds, one
        dense layer, 8 experts of 32 with 2 a token, a period that repeats."""
        base = dict(vocab_size=512, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32,
                    layer_types=(CONV, ATTENTION, CONV, ATTENTION, CONV),
                    num_dense_layers=1, num_heads=4, num_kv_heads=2,
                    head_dim=16, num_experts=8, num_experts_per_tok=2,
                    max_seq_len=256, dtype="float32")
        base.update(kw)
        return Lfm2Config(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def conv_lines(self) -> int:
        """Layers that leave a convolution state a slot."""
        return self.layer_types.count(CONV)

    @property
    def attention_lines(self) -> int:
        """Layers that leave keys and values a position."""
        return self.layer_types.count(ATTENTION)

    @property
    def num_routed_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def experts_held(self) -> int:
        return self.router_rule.held

    @property
    def router_rule(self) -> RouterRule:
        return RouterRule(
            experts=self.num_experts, topk=self.num_experts_per_tok,
            score=self.router_score, use_bias=self.use_expert_bias,
            renormalize=self.norm_topk_prob,
            scaling_factor=self.routed_scaling_factor,
            expert_shard=self.expert_shard,
            expert_shards=self.expert_shards)

    def kind(self, layer: int) -> tuple[str, bool]:
        """(operator kind, whether the feed-forward is routed)."""
        return self.layer_types[layer], layer >= self.num_dense_layers

    def rank(self, layer: int) -> tuple[int, int]:
        """Where ``layer`` lies in the stacks of its operator kind and of
        its feed-forward kind: the layers of that kind before it."""
        op, routed = self.kind(layer)
        return (self.layer_types[:layer].count(op),
                layer - self.num_dense_layers if routed else layer)

    @property
    def segments(self) -> tuple[Segment, ...]:
        """The layers as runs of a repeating period: at each layer the
        period (up to 8 layers) whose repeats cover most layers, a single
        layer where nothing repeats. The published 40 are two leading
        layers, nine periods of four and two single layers."""
        kinds = [self.kind(l) for l in range(self.num_layers)]
        out, at = [], 0
        while at < len(kinds):
            best = Segment(at, 1, 1)
            for p in range(1, 9):
                r = 1
                while kinds[at + r * p:at + (r + 1) * p] == kinds[at:at + p]:
                    r += 1
                if r > 1 and r * p > best.period * best.repeats:
                    best = Segment(at, p, r)
            out.append(best)
            at += best.period * best.repeats
        return tuple(out)

    def num_params(self) -> int:
        """Parameters held here (this shard's experts)."""
        h, d = self.hidden_size, self.head_dim
        conv = 3 * h * h + self.conv_L_cache * h + h * h
        attn = (2 * h * self.num_heads * d + 2 * h * self.num_kv_heads * d
                + 2 * d)
        dense = 3 * h * self.intermediate_size
        routed = (h * self.num_experts + self.num_experts
                  + self.experts_held * 3 * h * self.moe_intermediate_size)
        head = 0 if self.tie_embeddings else self.vocab_size * h
        return (self.conv_lines * conv + self.attention_lines * attn
                + self.num_dense_layers * dense
                + self.num_routed_layers * routed + 2 * self.num_layers * h
                + self.vocab_size * h + h + head)


def param_logical_axes(cfg: Lfm2Config) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules).
    ``layers`` is the stacked axis, over whichever layers have the leaf."""
    axes = {
        "embed_tokens": ("vocab", "embed"),
        "final_norm": ("embed",),
        "layers": {
            "operator_norm": ("layers", "embed"),
            "ffn_norm": ("layers", "embed"),
            "conv_in": ("layers", "embed", None),
            "conv_w": ("layers", None, "embed"),
            "conv_out": ("layers", None, "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "q_norm": ("layers", None),
            "k_norm": ("layers", None),
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
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# The selection bias's seeded scale. Sigmoid scores of unit-variance logits
# lie close together at the top (the fourth and fifth of 64 about 0.02
# apart), so 0.01 already changes the four chosen for about a quarter of
# tokens (0.24 to 0.31 over three seeds at the published router widths;
# tests/test_lfm2.py counts the share): a program that dropped the bias
# would not pass for right, and the scores still decide most choices (at
# 0.05 the bias decided four in five).
EXPERT_BIAS_SCALE = 0.01


def init_params(cfg: Lfm2Config, key: jax.Array) -> dict:
    """Scaled-normal init that keeps every projection's output at unit
    variance. What a trained checkpoint has and an all-ones init would hide
    is drawn too: the norms' weights (the heads' ``q_norm`` and ``k_norm``
    among them) near 1, the convolution's taps at 1/sqrt(taps), and
    ``router_bias`` (``expert_bias``, a buffer the published model tunes by
    a controller, not by gradients) at ``EXPERT_BIAS_SCALE``.

    The operators' and the dense SwiGLUs' output projections are not scaled
    down by depth, as in models/longcat.py and for its reason: every branch
    adds about unit variance and the residual stream grows along the layers
    as a trained one does. The experts' down-projections are, by 1 /
    sqrt(2 x routed layers) (models/llama.init_params scales its ``w_down``
    so): the router's fourth place is a discrete choice between two scores
    that are nearly equal, a near-tie falls differently in bfloat16 and in
    float32 for one token and routed layer in twenty, and each such swap
    exchanges a quarter of the layer's output. With that term at full size
    the first run read 0.69 to 1.30 of a logit against the float32 reference
    (models/lfm2.forward alone 0.90 over 1,032 positions; my chip run, PR 38),
    within a factor 3 of the same reference on fp8 weights; scaled, a swap
    is rounding's size and a sound run reads what rounding leaves (at a
    quarter of the widths on the CPU: 0.07 where it read 0.37 to 0.45, the
    fp8 control 0.92 to 0.97 where it read 1.22 to 1.32). What a swap costs
    a trained model is its own affair; here it must not pass for, or hide, a
    computation one precision lower."""
    h, d, L = cfg.hidden_size, cfg.head_dim, cfg.num_layers
    nc, na = cfg.conv_lines, cfg.attention_lines
    nd, nm = cfg.num_dense_layers, cfg.num_routed_layers
    f, fe, E = cfg.intermediate_size, cfg.moe_intermediate_size, \
        cfg.experts_held
    qd, kvd = cfg.num_heads * d, cfg.num_kv_heads * d
    dt = cfg.jnp_dtype
    keys = iter(jax.random.split(key, 24))

    def matrix(*shape, dtype=dt, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def norm(*shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dt)

    params = {
        "embed_tokens": matrix(cfg.vocab_size, h, scale=0.02),
        "final_norm": norm(h),
        "layers": {
            "operator_norm": norm(L, h),
            "ffn_norm": norm(L, h),
            "conv_in": matrix(nc, h, 3 * h),
            "conv_w": matrix(nc, cfg.conv_L_cache, h,
                             scale=1.0 / math.sqrt(cfg.conv_L_cache)),
            "conv_out": matrix(nc, h, h),
            "wq": matrix(na, h, qd),
            "wk": matrix(na, h, kvd),
            "wv": matrix(na, h, kvd),
            "wo": matrix(na, qd, h),
            "q_norm": norm(na, d),
            "k_norm": norm(na, d),
            "w_gate": matrix(nd, h, f),
            "w_up": matrix(nd, h, f),
            "w_down": matrix(nd, f, h),
            # The router stays float32: its top-k is a discrete choice.
            "router": matrix(nm, h, cfg.num_experts, dtype=jnp.float32),
            "router_bias": matrix(nm, cfg.num_experts, dtype=jnp.float32,
                                  scale=EXPERT_BIAS_SCALE),
            "we_gate": matrix(nm, E, h, fe),
            "we_up": matrix(nm, E, h, fe),
            "we_down": matrix(nm, E, fe, h,
                              scale=1.0 / math.sqrt(2 * max(nm, 1) * fe)),
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = matrix(h, cfg.vocab_size)
    return params


# ---------------------------------------------------------------- blocks

def conv_gates(cp: dict, xn):
    """The convolution's projection in, on xn [..., H] (normed) -> (z,
    gate_out): the gated input, whose last rows a sequence keeps as its
    state, and the gate on the output."""
    with tracing.part("conv"):
        gate_in, gate_out, x = jnp.split(xn @ cp["conv_in"], 3, axis=-1)
        return gate_in * x, gate_out


def conv_window(prior, z):
    """``prior`` [B, conv_L_cache - 1, H] and then the rows ``z`` [B, S, H]
    of this call: what the taps slide over, and what the state is cut
    from."""
    with tracing.part("conv_state"):
        return jnp.concatenate([prior.astype(z.dtype), z], axis=1)


def conv_taps(cfg: Lfm2Config, cp: dict, zz, s: int):
    """The depthwise causal convolution over ``zz`` [B, conv_L_cache - 1 +
    S, H] at its last ``s`` positions -> [B, S, H]."""
    with tracing.part("conv"):
        taps = cp["conv_w"].astype(jnp.float32)              # [taps, H]
        v = sum(taps[j] * zz[:, j:j + s].astype(jnp.float32)
                for j in range(cfg.conv_L_cache))
        return v.astype(zz.dtype)


def conv_out(cp: dict, gate_out, v, dtype):
    """The gate on the taps' output and the projection out."""
    with tracing.part("conv"):
        return ((gate_out * v) @ cp["conv_out"]).astype(dtype)


def short_conv(cfg: Lfm2Config, cp: dict, xn, prior):
    """The gated short convolution on xn [B, S, H] (normed) after
    ``prior`` [B, conv_L_cache - 1, H], the rows of ``z`` before the first
    position (zeros at a sequence's start). Returns (y [B, S, H], zz
    [B, conv_L_cache - 1 + S, H]): ``prior`` and then this call's rows of
    ``z``, of which the caller keeps its state. Its four pieces are
    functions of their own for a program whose rows are of several
    sequences (llm/lfm2_serving.py's mixed step): the projections take
    all rows at once, the window and the taps a sequence's."""
    z, gate_out = conv_gates(cp, xn)
    zz = conv_window(prior, z)
    v = conv_taps(cfg, cp, zz, xn.shape[1])
    return conv_out(cp, gate_out, v, xn.dtype), zz


def attention_heads(cfg: Lfm2Config, ap: dict, xn, positions, inv_freq):
    """xn [B, S, H] (normed) -> the normed, rotated queries [B, nh, S, D]
    and keys [B, nkv, S, D], and the values [B, nkv, S, D]."""
    b, s, _ = xn.shape
    # Arrays of their own before they are split into heads, as in
    # models/ouro.block: XLA otherwise folds the split into the product and
    # copies the stacked matrices transposed.
    q, k, v = lax.optimization_barrier(
        (xn @ ap["wq"], xn @ ap["wk"], xn @ ap["wv"]))
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    # A head's 64 values fill half a lane row: plain jnp, which XLA fuses
    # into the rotation that follows.
    q = rms_norm_reference(q, ap["q_norm"], cfg.norm_eps)
    k = rms_norm_reference(k, ap["k_norm"], cfg.norm_eps)
    return (apply_rope(q, positions, inv_freq),
            apply_rope(k, positions, inv_freq), v)


def swiglu(x, w_gate, w_up, w_down):
    dt = x.dtype
    gate = jax.nn.silu((x @ w_gate).astype(jnp.float32)).astype(dt)
    # Kept as an array of its own, as llm/llama_serving._mlp keeps it: fused into
    # the down projection XLA computes it again for every tile of the
    # output.
    act = lax.optimization_barrier(gate * (x @ w_up))
    return (act @ w_down).astype(dt)


def layer(cfg: Lfm2Config, layers: dict, at: int, repeat, seg: Segment, x,
          operators: dict, state, valid, kmesh=None):
    """Layer ``seg.first + repeat * seg.period + at`` on x [B, S, H]:
    ``at`` is the layer's place in the period (static), ``repeat`` the
    period's index (a run-time value). ``layers`` is the whole stacked
    ``params["layers"]``: every leaf is indexed where it is used.
    ``operators[kind](line, p, xn, state) -> (y, state)`` runs the layer's
    operator on normed input with its own params ``p``; ``line`` is the
    layer's rank among the layers of its operator kind (its cache line) and
    ``state`` whatever the operators thread. Returns (x, state, counts)."""
    b, s, hid = x.shape
    first = seg.first + at
    op, routed = cfg.kind(first)
    period = [cfg.kind(l) for l in range(seg.first, seg.first + seg.period)]
    line0, ffn0 = cfg.rank(first)
    index = seg.first + at + repeat * seg.period
    line = line0 + repeat * sum(1 for k in period if k[0] == op)
    ffn = ffn0 + repeat * sum(1 for k in period if k[1] == routed)
    with tracing.part("stack"):
        p = {k: layer_of(layers[k], line)
             for k in (CONV_LEAVES if op == CONV else ATTENTION_LEAVES)}
        op_norm = layer_of(layers["operator_norm"], index)
        ffn_norm = layer_of(layers["ffn_norm"], index)
        if not routed:
            p.update({k: layer_of(layers[k], ffn) for k in DENSE_LEAVES})
    # The operator's place is ``attn`` for either kind; the convolution
    # opens ``conv`` and ``conv_state`` inside it (tracing.SUBPARTS).
    with tracing.part("attn"):
        y, state = operators[op](
            line, p, rms_norm(x, op_norm, cfg.norm_eps, kmesh), state)
        x = x + y
    if routed:
        with tracing.part("moe_route"):
            u = rms_norm(x, ffn_norm, cfg.norm_eps, kmesh)
        m, counts = moe_block(cfg.router_rule, layers, ffn,
                              u.reshape(b * s, hid), valid.reshape(b * s))
        with tracing.part("moe_combine"):
            x = x + m.reshape(b, s, hid)
    else:
        with tracing.part("mlp"):
            u = rms_norm(x, ffn_norm, cfg.norm_eps, kmesh)
            x = x + swiglu(u, p["w_gate"], p["w_up"], p["w_down"])
        counts = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    return x, state, counts


def run_layers(cfg: Lfm2Config, params, x, operators: dict, state, valid,
               kmesh=None):
    """Every layer over x [B, S, H], ``state`` as carry of every loop: one
    scan a segment, over the repeats of its period. Returns (x, state,
    counts int32[6] summed over the routed layers)."""
    counts = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    for seg in cfg.segments:
        def body(carry, repeat, seg=seg):
            x, state, counts = carry
            for at in range(seg.period):
                x, state, c = layer(cfg, params["layers"], at, repeat, seg,
                                    x, operators, state, valid, kmesh)
                with tracing.part("moe_combine"):
                    counts = counts + c
            return (x, state, counts), None

        with tracing.part("stack"):
            (x, state, counts), _ = lax.scan(
                body, (x, state, counts), jnp.arange(seg.repeats))
    return x, state, counts


@tracing.part("head")
def lm_head(cfg: Lfm2Config, params, x, kmesh=None):
    """x: [..., H] -> float32 logits [..., V]; the head is the embedding
    unless the configuration unties it."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)
    if cfg.tie_embeddings:
        return jnp.einsum("...h,vh->...v", x, params["embed_tokens"],
                          preferred_element_type=jnp.float32)
    return jnp.dot(x, params["lm_head"], preferred_element_type=jnp.float32)


def forward(cfg: Lfm2Config, params: dict, tokens, *,
            kmesh: KernelMesh | None = None):
    """tokens [B, S] -> (float32 logits [B, S, V], router counts int32[6]).
    Whole sequences, no cache and no state: the convolution starts from
    zeros, the attention is causal over the sequence."""
    b, s = tokens.shape
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    with tracing.part("attn"):
        positions = jnp.arange(s)
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)
    valid = jnp.ones(tokens.shape, bool)

    def conv(line, cp, xn, state):
        prior = jnp.zeros((b, cfg.conv_L_cache - 1, cfg.hidden_size),
                          xn.dtype)
        return short_conv(cfg, cp, xn, prior)[0], state

    def attention(line, ap, xn, state):
        q, k, v = attention_heads(cfg, ap, xn, positions, inv_freq)
        o = blockwise_attention(q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return (o @ ap["wo"]).astype(xn.dtype), state

    x, _, counts = run_layers(cfg, params, x,
                              {CONV: conv, ATTENTION: attention}, None,
                              valid, kmesh)
    return lm_head(cfg, params, x, kmesh), counts
