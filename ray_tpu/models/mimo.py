"""MiMo-V2 family (``model_type: "mimo_v2"``): window and full attention
mixed, the two kinds with different KV head counts, keys wider than values,
a learned sink in the window layers' softmax, a rotary over a third of a
head at a base a kind, and a routed layer of many small experts chosen by a
sigmoid rule.

Layer ``l``, input ``h``, ``N`` an RMSNorm with float32 statistics::

    a   = h + Attn_l(N(h))            u = N(a)
    out = a + F_l(u)

``F_l`` is a dense SwiGLU where ``moe_layer_freq[l]`` is 0 (layer 0) and the
routed layer elsewhere: ``sum_e w_e E_e(u)`` over the ``num_experts_per_tok``
experts the rule chose, no shared expert. The rule (``noaux_tc`` without
groups): ``s = sigmoid(u W_g)`` in float32, the choice the largest of ``s +
b``, the weights ``s`` at the chosen over their sum. The routed layer is
models/routed.py's, told which experts it holds.

``Attn_l`` is a **full** layer where ``hybrid_layer_pattern[l]`` is 0
(``num_kv_heads`` KV heads, causal over the whole sequence, rotary base
``rope_theta``) and a **window** layer where it is 1 (``swa_num_kv_heads``
KV heads, query ``p`` sees keys ``p - sliding_window + 1 .. p``, rotary base
``swa_rope_theta``, and a learned ``sink[h]`` a query head that joins the
softmax's denominator and has no value). Both: ``num_heads`` query heads,
keys and queries of ``head_dim``, values of ``v_head_dim`` scaled by
``attention_value_scale`` where they are projected, one fused ``qkv``
product (the published storage layout), and a rotary over the first
``rotary_dim = int(head_dim * partial_rotary_factor)`` lanes of a head
(ops/rope.apply_rope_partial).

**A cached row is a key and a value of one KV head, side by side**
(ops/decode_attention.py's packed convention at ``D = head_dim``): the
value is padded with ``head_dim - v_head_dim`` zero lanes, so a row of 192
+ 128 is 384 lanes, three whole lane rows (:attr:`MimoConfig.kv_row`). The
kernels give the values' mix in the output's last ``head_dim`` lanes, of
which the first ``v_head_dim`` are kept.

Parameters are stacked over the layers that have them and indexed by a
run-time index where they are used (models/longcat.py's finding on scanned
slices): the norms, ``wo`` over all layers; ``wqkv_full`` and
``wqkv_window`` (and ``sink``) over the layers of their kind; the dense
SwiGLU over the dense layers; the router and the experts over the routed
layers. The stack runs as one ``lax.scan`` a *run* of consecutive layers of
one kind (:attr:`MimoConfig.runs`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.lfm2 import swiglu
from ray_tpu.models.phi4flash import window_visible
from ray_tpu.models.routed import (
    MOE_COUNTERS,
    RouterRule,
    layer_of,
    moe_block,
)
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope_partial, rope_frequencies
from ray_tpu.util import tracing

NEG_INF = -1e30
FULL, WINDOW = 0, 1
_PERIOD = (FULL,) + (WINDOW,) * 5


@dataclass(frozen=True)
class Run:
    """Consecutive layers of one kind: ``n`` layers from ``first``, whose
    first is the ``line``-th of its attention kind and, where ``routed``,
    the ``ffn``-th routed layer (else the ``ffn``-th dense one)."""

    kind: int
    routed: bool
    first: int
    n: int
    line: int
    ffn: int


@dataclass(frozen=True)
class MimoConfig:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384         # the dense SwiGLU
    moe_intermediate_size: int = 2048      # one routed expert
    num_layers: int = 48
    num_heads: int = 64
    num_kv_heads: int = 4                  # a full layer's
    swa_num_kv_heads: int = 8              # a window layer's
    head_dim: int = 192                    # queries and keys, both kinds
    v_head_dim: int = 128
    # hybrid_layer_pattern (0 full, 1 window) and moe_layer_freq (0 dense,
    # 1 routed), a value a layer; None: the published ones cut to
    # ``num_layers`` (full at 0, 5, 11, 17, ...; dense at 0 alone).
    layer_kinds: tuple[int, ...] | None = None
    layer_routed: tuple[int, ...] | None = None
    sliding_window: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7                # a full layer's
    swa_rope_theta: float = 1e4            # a window layer's
    attention_value_scale: float = 0.707
    window_sink: bool = True               # add_swa_attention_sink_bias
    n_routed_experts: int = 256            # in the whole model, all shards
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    max_seq_len: int = 1048576
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # What this program holds of the routed experts (models/longcat.py).
    expert_shard: int = 0
    expert_shards: int = 1

    def __post_init__(self):
        self.router_rule  # refuses shares that do not divide
        for name, per_layer in (("layer_kinds", self.kinds),
                                ("layer_routed", self.routed)):
            if len(per_layer) != self.num_layers or set(per_layer) - {0, 1}:
                raise ValueError(f"{name}: a 0 or a 1 for each of "
                                 f"{self.num_layers} layers")
        if not 0 < self.v_head_dim <= self.head_dim:
            raise ValueError("a cached row holds a value in a key's width: "
                             f"v_head_dim {self.v_head_dim} of head_dim "
                             f"{self.head_dim}")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"a rotary over {self.rotary_dim} lanes of "
                             f"{self.head_dim}")

    @staticmethod
    def tiny(**kw) -> "MimoConfig":
        """Test-size config with every mechanism: a dense full layer, three
        window layers, a routed full layer and a window layer after it;
        window 8, 2 KV heads in a full layer and 4 in a window layer, keys
        of 24 beside values of 16 with 8 lanes rotated, 16 experts of which
        4 a token."""
        base = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                    moe_intermediate_size=32, num_layers=6, num_heads=8,
                    num_kv_heads=2, swa_num_kv_heads=4, head_dim=24,
                    v_head_dim=16, layer_kinds=(0, 1, 1, 1, 0, 1),
                    sliding_window=8, n_routed_experts=16,
                    num_experts_per_tok=4, max_seq_len=256, dtype="float32")
        base.update(kw)
        return MimoConfig(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def kinds(self) -> tuple[int, ...]:
        if self.layer_kinds is not None:
            return tuple(self.layer_kinds)
        return tuple(FULL if l == 0 else _PERIOD[(l - 5) % 6]
                     for l in range(self.num_layers))

    @property
    def routed(self) -> tuple[int, ...]:
        if self.layer_routed is not None:
            return tuple(self.layer_routed)
        return tuple(int(l > 0) for l in range(self.num_layers))

    @property
    def full_lines(self) -> int:
        return self.kinds.count(FULL)

    @property
    def window_lines(self) -> int:
        return self.kinds.count(WINDOW)

    @property
    def num_routed_layers(self) -> int:
        return sum(self.routed)

    @property
    def num_dense_layers(self) -> int:
        return self.num_layers - self.num_routed_layers

    @property
    def runs(self) -> tuple[Run, ...]:
        runs, seen = [], {FULL: 0, WINDOW: 0, "routed": 0, "dense": 0}
        for l, (kind, routed) in enumerate(zip(self.kinds, self.routed)):
            last = runs[-1] if runs else None
            if last and (last.kind, last.routed) == (kind, bool(routed)):
                runs[-1] = replace(last, n=last.n + 1)
            else:
                runs.append(Run(kind, bool(routed), l, 1, seen[kind],
                                seen["routed" if routed else "dense"]))
            seen[kind] += 1
            seen["routed" if routed else "dense"] += 1
        return tuple(runs)

    def kv_heads(self, kind: int) -> int:
        return self.swa_num_kv_heads if kind == WINDOW else self.num_kv_heads

    def qkv_width(self, kind: int) -> int:
        """Columns of a kind's fused projection: the queries, the keys, the
        values."""
        return ((self.num_heads + self.kv_heads(kind)) * self.head_dim
                + self.kv_heads(kind) * self.v_head_dim)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def kv_row(self) -> int:
        """Lanes of a cached row: a key, then a value padded to a key's
        width."""
        return 2 * self.head_dim

    @property
    def sm_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.expert_shards

    @property
    def router_rule(self) -> RouterRule:
        """A sigmoid of each output, the choice by score + bias, the weights
        by score alone over their sum (+ 1e-20), then scaled."""
        return RouterRule(
            experts=self.n_routed_experts, topk=self.num_experts_per_tok,
            score="sigmoid", use_bias=True, renormalize=self.norm_topk_prob,
            renorm_eps=1e-20, scaling_factor=self.routed_scaling_factor,
            expert_shard=self.expert_shard, expert_shards=self.expert_shards)

    def num_params(self) -> int:
        """Parameters held here (this shard's experts)."""
        h = self.hidden_size
        attn = sum(h * self.qkv_width(k) for k in self.kinds) \
            + self.num_layers * (self.num_heads * self.v_head_dim * h + 2 * h)
        sinks = self.window_lines * self.num_heads * self.window_sink
        dense = self.num_dense_layers * 3 * h * self.intermediate_size
        routed = self.num_routed_layers * (
            (h + 1) * self.n_routed_experts
            + self.experts_held * 3 * h * self.moe_intermediate_size)
        return attn + sinks + dense + routed + 2 * self.vocab_size * h + h


DENSE_LEAVES = ("w_gate", "w_up", "w_down")
QKV = {FULL: "wqkv_full", WINDOW: "wqkv_window"}


def param_logical_axes(cfg: MimoConfig) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules).
    ``layers`` is the stacked axis, whichever layers a leaf is stacked
    over."""
    layers = {
        "attn_norm": ("layers", "embed"),
        "post_norm": ("layers", "embed"),
        "wqkv_full": ("layers", "embed", "heads"),
        "wqkv_window": ("layers", "embed", "heads"),
        "wo": ("layers", "heads", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
        "router": ("layers", "embed", None),
        "router_bias": ("layers", None),
        "we_gate": ("layers", "expert", "embed", "mlp"),
        "we_up": ("layers", "expert", "embed", "mlp"),
        "we_down": ("layers", "expert", "mlp", "embed"),
    }
    if cfg.window_sink:
        layers["sink"] = ("layers", "heads")
    return {"embed_tokens": ("vocab", "embed"), "lm_head": ("embed", "vocab"),
            "final_norm": ("embed",), "layers": layers}


def init_params(cfg: MimoConfig, key: jax.Array) -> dict:
    """models/deepseek.init_params' scheme: every projection's output at
    unit variance, the norms' weights near 1, the attention's and the dense
    SwiGLU's output projections not scaled down by depth, the routed
    experts' down-projections by ``1 / sqrt(8 x routed layers)`` so that a
    swapped pick at the rule's last place (a discrete choice between
    sigmoid scores that are nearly equal, which falls differently in
    bfloat16 and in float32 for some tokens) is of rounding's size. The
    value columns of ``wqkv`` are as the others: the values' scale is the
    model's own (``attention_value_scale``, applied where they are
    projected). The selection bias is small and non-zero (0.02 x normal: a
    hundredth of a sigmoid's spread, so that it decides near-ties and the
    weights show that it is left out of them); the sinks are unit normal,
    so that one weighs in a window's softmax like a key (scores of unit
    variance)."""
    h, L = cfg.hidden_size, cfg.num_layers
    nd, nm = cfg.num_dense_layers, cfg.num_routed_layers
    f, fe, E = (cfg.intermediate_size, cfg.moe_intermediate_size,
                cfg.experts_held)
    dt = cfg.jnp_dtype
    keys = iter(jax.random.split(key, 32))

    def matrix(*shape, dtype=dt, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def norm(*shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dt)

    layers = {
        "attn_norm": norm(L, h),
        "post_norm": norm(L, h),
        "wqkv_full": matrix(cfg.full_lines, h, cfg.qkv_width(FULL)),
        "wqkv_window": matrix(cfg.window_lines, h, cfg.qkv_width(WINDOW)),
        "wo": matrix(L, cfg.num_heads * cfg.v_head_dim, h),
        "w_gate": matrix(nd, h, f),
        "w_up": matrix(nd, h, f),
        "w_down": matrix(nd, f, h),
        # The router stays float32: its choices are discrete.
        "router": matrix(nm, h, cfg.n_routed_experts, dtype=jnp.float32),
        "router_bias": 0.02 * jax.random.normal(
            next(keys), (nm, cfg.n_routed_experts), jnp.float32),
        "we_gate": matrix(nm, E, h, fe),
        "we_up": matrix(nm, E, h, fe),
        "we_down": matrix(nm, E, fe, h,
                          scale=1.0 / math.sqrt(8 * max(nm, 1) * fe)),
    }
    if cfg.window_sink:
        layers["sink"] = jax.random.normal(
            next(keys), (cfg.window_lines, cfg.num_heads), jnp.float32)
    return {"embed_tokens": matrix(cfg.vocab_size, h, scale=0.02),
            "lm_head": matrix(h, cfg.vocab_size),
            "final_norm": norm(h), "layers": layers}


# ---------------------------------------------------------------- blocks

def inv_frequencies(cfg: MimoConfig, kind: int):
    """The rotary's inverse frequencies of a kind of layer, over the
    rotated lanes' pairs."""
    return rope_frequencies(
        cfg.rotary_dim, cfg.swa_rope_theta if kind == WINDOW
        else cfg.rope_theta)


def attention_heads(cfg: MimoConfig, kind: int, wqkv, xn, positions):
    """xn [B, S, H] (normed) through a kind's fused projection -> the
    rotated queries [B, nh, S, D] and keys [B, nkv, S, D], and the cached
    rows' value halves [B, nkv, S, D]: the scaled values and ``D - Dv``
    zero lanes (the module docstring's row). positions: [S] or [B, S]."""
    b, s, _ = xn.shape
    nh, nkv, d, dv = (cfg.num_heads, cfg.kv_heads(kind), cfg.head_dim,
                      cfg.v_head_dim)
    # An array of its own before it is split into heads, as in
    # models/lfm2.attention_heads: XLA otherwise folds the split into the
    # product and copies the stacked matrix transposed.
    qkv = lax.optimization_barrier(xn @ wqkv)
    q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
    q = q.reshape(b, s, nh, d).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, nkv, d).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, nkv, dv).transpose(0, 2, 1, 3)
    v = (v.astype(jnp.float32) * cfg.attention_value_scale).astype(xn.dtype)
    inv_freq = inv_frequencies(cfg, kind)
    return (apply_rope_partial(q, positions, inv_freq),
            apply_rope_partial(k, positions, inv_freq),
            jnp.pad(v, ((0, 0),) * 3 + ((0, d - dv),)))


def sunk_attention(q, k, v, visible, sink, sm_scale: float):
    """Masked softmax attention in jnp, grouped (no repeated K/V), with an
    optional sink: q [..., nh, S, D], k [..., nkv, K, D], v [..., nkv, K,
    Dv], visible [..., S, K] bool (over the leading dimensions, not the
    heads), sink [nh] float32 or None -> [..., nh, S, Dv]. Float32 scores and
    accumulation. ``out = sum_j e^(s_j - m) v_j / (sum_j e^(s_j - m) +
    e^(sink - m))``, ``m`` the largest of the scores and the sink; a row
    that sees nothing gives zeros."""
    *lead, nh, s, d = q.shape
    nkv = k.shape[-3]
    g = nh // nkv
    qg = q.reshape(*lead, nkv, g, s, d)
    scores = jnp.einsum("...hgsd,...hkd->...hgsk", qg, k.astype(q.dtype),
                        preferred_element_type=jnp.float32) * sm_scale
    vis = visible[..., None, None, :, :]
    scores = jnp.where(vis, scores, NEG_INF)
    top = scores.max(-1, keepdims=True)
    if sink is not None:
        at = sink.astype(jnp.float32).reshape(nkv, g)[:, :, None, None]
        top = jnp.maximum(top, at)
    p = jnp.where(vis, jnp.exp(scores - top), 0.0)
    denom = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    if sink is not None:
        denom = denom + jnp.exp(at - top)
    out = jnp.einsum("...hgsk,...hkd->...hgsd", p.astype(q.dtype),
                     v.astype(q.dtype),
                     preferred_element_type=jnp.float32) / denom
    return out.astype(q.dtype).reshape(*lead, nh, s, v.shape[-1])


def attention_output(cfg: MimoConfig, wo, o, dtype):
    """o [B, nh, S, D] as the kernels give it (the values' mix in a head's
    first ``v_head_dim`` lanes) -> [B, S, H] through ``wo``."""
    b, _, s, _ = o.shape
    o = o[..., :cfg.v_head_dim].transpose(0, 2, 1, 3).reshape(b, s, -1)
    return (o @ wo).astype(dtype)


def layer(cfg: MimoConfig, layers: dict, run: Run, at, h, attn, state, valid,
          kmesh=None):
    """The ``at``-th layer of ``run`` (a run-time index) on h [B, S, H].
    ``layers`` is the whole stacked ``params["layers"]``: every leaf is
    indexed where it is used. ``attn[kind](line, wqkv, sink, xn, state) ->
    (o [B, nh, S, D], state)`` is the layer's attention on normed input,
    ``line`` its rank among the layers of its kind, ``sink`` None in a
    layer without one; ``state`` is whatever it threads (a cache).
    ``valid`` [B, S] marks real tokens for the router's counters. Returns
    (h, state, counts)."""
    b, s, hid = h.shape
    index, line, ffn = run.first + at, run.line + at, run.ffn + at
    with tracing.part("stack"):
        attn_norm, post_norm, wo = (layer_of(layers[k], index) for k in
                                    ("attn_norm", "post_norm", "wo"))
        wqkv = layer_of(layers[QKV[run.kind]], line)
        sink = (layer_of(layers["sink"], line)
                if run.kind == WINDOW and cfg.window_sink else None)
    with tracing.part("attn"):
        o, state = attn[run.kind](
            line, wqkv, sink, rms_norm(h, attn_norm, cfg.norm_eps, kmesh),
            state)
        a = h + attention_output(cfg, wo, o, h.dtype)
    with tracing.part("mlp"):
        u = rms_norm(a, post_norm, cfg.norm_eps, kmesh)
    if not run.routed:
        with tracing.part("mlp"):
            out = a + swiglu(u, *(layer_of(layers[k], ffn)
                                  for k in DENSE_LEAVES))
        return out, state, jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    m, counts = moe_block(cfg.router_rule, layers, ffn,
                          u.reshape(b * s, hid), valid.reshape(b * s))
    with tracing.part("moe_combine"):
        out = a + m.reshape(b, s, hid)
    return out, state, counts


def run_layers(cfg: MimoConfig, params, x, attn, state, valid, kmesh=None):
    """Every layer over x [B, S, H], ``state`` as carry: one scan a run.
    Returns (x, state, counts int32[6] summed over the routed layers)."""
    counts = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    for run in cfg.runs:
        def body(carry, at, run=run):
            x, state, counts = carry
            x, state, c = layer(cfg, params["layers"], run, at, x, attn,
                                state, valid, kmesh)
            with tracing.part("moe_combine"):
                return (x, state, counts + c), None

        with tracing.part("stack"):
            (x, state, counts), _ = lax.scan(
                body, (x, state, counts), jnp.arange(run.n))
    return x, state, counts


@tracing.part("head")
def lm_head(cfg: MimoConfig, params, x, kmesh=None):
    """x: [..., H] -> float32 logits [..., V] (untied head)."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)
    return jnp.dot(x, params["lm_head"], preferred_element_type=jnp.float32)


def forward(cfg: MimoConfig, params: dict, tokens, *,
            kmesh: KernelMesh | None = None):
    """tokens [B, S] -> (float32 logits [B, S, V], router counts int32[6]).
    Whole sequences, no cache: the shape of a training forward pass and of
    the parity tests."""
    s = tokens.shape[1]
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    positions = jnp.arange(s)
    causal = positions[None, :] <= positions[:, None]
    valid = jnp.ones(tokens.shape, bool)

    def attend(kind, visible):
        def attn(line, wqkv, sink, xn, state):
            q, k, v = attention_heads(cfg, kind, wqkv, xn, positions)
            return sunk_attention(q, k, v, visible, sink,
                                  cfg.sm_scale), state
        return attn

    attn = {FULL: attend(FULL, causal),
            WINDOW: attend(WINDOW, window_visible(positions, positions,
                                                  cfg.sliding_window))}
    x, _, counts = run_layers(cfg, params, x, attn, None, valid, kmesh)
    return lm_head(cfg, params, x, kmesh), counts
