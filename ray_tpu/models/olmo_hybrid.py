"""Olmo-Hybrid family: Gated DeltaNet layers beside a full attention without
positions every few layers, a dense SwiGLU in every layer, norms after the
operator.

The family of ``model_type: "olmo_hybrid"`` (huggingface.co/allenai/
Olmo-Hybrid-7B): an Olmo 3 block whose operator is, by ``layer_types``, a
full attention or a Gated DeltaNet. Layer ``l``, input ``h``, ``N(x; w) = w
x rsqrt(mean(x^2) + eps)`` (statistics in float32), no norm before an
operator::

    a  = h + N(Mix_l(h); post_attention_layernorm)
    h' = a + N(F(a); post_feedforward_layernorm)

``F`` is a SwiGLU without bias. ``Mix_l`` is the full attention where ``(l +
1) % full_attention_interval == 0`` and Gated DeltaNet otherwise:

- **Gated DeltaNet.** ``x W_q``, ``x W_k``, ``x W_v`` each pass a depthwise
  causal convolution of ``linear_conv_kernel_dim`` taps (no bias, zeros
  before position 0), then ``silu``. ``beta = 2 sigmoid(x W_b)`` where
  ``linear_allow_neg_eigval`` (``sigmoid`` otherwise): a step in (0, 2), so
  that ``I - beta k k^T`` has the eigenvalue ``1 - beta`` in (-1, 1). ``g =
  -exp(A_log) softplus(x W_a + dt_bias)`` in float32, a number a head and
  token. ``q`` and ``k`` are L2-normalised a head (``x rsqrt(sum x^2 +
  1e-6)``) and ``q`` scaled by ``Dk^-1/2``. Then the gated delta rule
  (ops/gated_delta.py) a head, from a zero state, keys of ``Dk`` and values
  of ``Dv`` (96 and 192). The output a head is ``N(o; o_norm) silu(z)``
  with ``z = x W_g``, the heads side by side through ``W_o``.
- **Full attention.** ``q = N(x W_q; q_norm)`` and ``k = N(x W_k; k_norm)``,
  each norm over all heads' values at once, ``v = x W_v``; no rotary (the
  recurrent layers carry position); causal softmax attention at
  ``head_dim^-1/2``; ``W_o``.

After the last layer ``N(h; norm)``, then an untied head.

This module trains: whole sequences, no cache and no state handed on. Params
are a flat pytree stacked over the periods (``lax.scan`` runs one period's
body), and inside a period over the layers that have the leaf: the rule's
leaves ``[periods, interval - 1, ...]``, the attention's ``[periods, ...]``,
the feed-forward's and the two norms' ``[periods, interval, ...]``. Every
layer runs under ``jax.checkpoint`` by ``models/llama._remat_wrap``'s
policy.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.llama import _attention, _remat_wrap
from ray_tpu.models.qwen3_next import DECAY_RATES, short_conv_silu, unit_heads
from ray_tpu.ops.gated_delta import gated_delta_chunk
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm, rms_norm_reference
from ray_tpu.util import tracing

LINEAR, ATTENTION = "linear_attention", "full_attention"
# The seeded embedding's size: no norm stands between the embedding and the
# first operator, so a row at unit size is what that operator's projections
# are scaled for (a stream of 0.02 would leave every gate at its centre).
EMBED_SIZE = 1.0

LINEAR_LEAVES = ("lin_wq", "lin_wk", "lin_wv", "lin_wz", "lin_wa", "lin_wb",
                 "conv_q", "conv_k", "conv_v", "a_log", "dt_bias", "o_norm",
                 "lin_wo")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
LAYER_LEAVES = ("post_attn_norm", "post_ffn_norm", "w_gate", "w_up",
                "w_down")


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_layers: int = 32
    full_attention_interval: int = 4
    num_heads: int = 30
    num_kv_heads: int = 30
    head_dim: int = 128
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    max_seq_len: int = 65536
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
        if self.linear_conv_kernel_dim < 2:
            raise ValueError("linear_conv_kernel_dim under 2 leaves no "
                             "window")

    @staticmethod
    def tiny(**kw) -> "OlmoHybridConfig":
        """Test-size config with every mechanism: two periods of two linear
        layers and an attention, three heads (not a multiple of 8), keys of
        8 beside values of 16, steps in (0, 2)."""
        base = dict(vocab_size=256, hidden_size=48, intermediate_size=96,
                    num_layers=6, full_attention_interval=3, num_heads=3,
                    num_kv_heads=3, head_dim=16, linear_num_key_heads=3,
                    linear_num_value_heads=3, linear_key_head_dim=8,
                    linear_value_head_dim=16, max_seq_len=256,
                    dtype="float32")
        base.update(kw)
        return OlmoHybridConfig(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def periods(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def linear_lines(self) -> int:
        return self.num_layers - self.periods

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    def kind(self, layer: int) -> str:
        return (ATTENTION if (layer + 1) % self.full_attention_interval == 0
                else LINEAR)

    def num_params(self) -> int:
        h, nv = self.hidden_size, self.linear_num_value_heads
        linear = (2 * h * self.key_dim + 2 * h * self.value_dim + 2 * h * nv
                  + self.linear_conv_kernel_dim
                  * (2 * self.key_dim + self.value_dim) + 2 * nv
                  + self.linear_value_head_dim + self.value_dim * h)
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        attn = 2 * h * qd + 2 * h * kvd + qd + kvd
        ffn = 3 * h * self.intermediate_size
        return (self.linear_lines * linear + self.periods * attn
                + self.num_layers * (ffn + 2 * h)
                + 2 * self.vocab_size * h + h)


def param_logical_axes(cfg: OlmoHybridConfig) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules).
    ``layers`` is the stacked axis of the periods; the axis after it, where
    a leaf has one, the layers of its kind inside a period."""
    wide = ("layers", None, "embed", None)
    return {
        "embed_tokens": ("vocab", "embed"),
        "lm_head": ("embed", "vocab"),
        "final_norm": ("embed",),
        "layers": {
            "lin_wq": wide, "lin_wk": wide, "lin_wv": wide, "lin_wz": wide,
            "lin_wa": wide, "lin_wb": wide,
            "conv_q": ("layers", None, None, None),
            "conv_k": ("layers", None, None, None),
            "conv_v": ("layers", None, None, None),
            "a_log": ("layers", None, None),
            "dt_bias": ("layers", None, None),
            "o_norm": ("layers", None, None),
            "lin_wo": ("layers", None, None, "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "q_norm": ("layers", None),
            "k_norm": ("layers", None),
            "post_attn_norm": ("layers", None, "embed"),
            "post_ffn_norm": ("layers", None, "embed"),
            "w_gate": ("layers", None, "embed", "mlp"),
            "w_up": ("layers", None, "embed", "mlp"),
            "w_down": ("layers", None, "mlp", "embed"),
        },
    }


def init_params(cfg: OlmoHybridConfig, key: jax.Array) -> dict:
    """Scaled-normal init, every projection at ``1 / sqrt(inputs)``. What a
    trained checkpoint has and an init at the published defaults would hide
    is drawn too, as models/qwen3_next.py draws it: the norms (published 1)
    at ``1 + 0.1 x normal``, the taps at ``1 / sqrt(taps)``, ``A =
    exp(a_log)`` uniform over 1 to 16 and ``dt_bias`` such that ``A
    softplus(dt_bias)`` is a head's rate of ``DECAY_RATES`` (``exp(g)``
    from about 0.5 to 0.999 over the heads).

    No norm stands before an operator and every sub-layer adds a normed
    output to the stream, so layer ``l`` reads a stream of about ``sqrt(1 +
    2 l)`` from an embedding of ``EMBED_SIZE``. The two gates' projections
    are scaled down by that, so that ``x W_b`` and ``x W_a`` are of unit
    variance in every layer: ``beta = 2 sigmoid(.)`` spreads over (0, 2),
    half of the tokens above 1, and the input moves a head's decay about
    its rate by a factor of ``e`` and not of ``e^3``. The output
    projections' size is the norm's after them to undo."""
    h, d, P = cfg.hidden_size, cfg.head_dim, cfg.periods
    per = cfg.full_attention_interval
    nl = per - 1
    nv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    kd, vd, taps = cfg.key_dim, cfg.value_dim, cfg.linear_conv_kernel_dim
    qd, kvd = cfg.num_heads * d, cfg.num_kv_heads * d
    i = cfg.intermediate_size
    dt = cfg.jnp_dtype
    keys = iter(jax.random.split(key, 32))

    def matrix(*shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def norm(*shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dt)

    # The stream a linear layer reads, [periods, linear layers a period].
    layer = (jnp.arange(P)[:, None] * per + jnp.arange(nl)[None, :])
    stream = jnp.sqrt(1.0 + 2.0 * layer.astype(jnp.float32))

    def gate(*shape):
        w = jax.random.normal(next(keys), shape, jnp.float32) \
            / (math.sqrt(shape[-2]) * stream[:, :, None, None])
        return w.astype(dt)

    amount = jax.random.uniform(next(keys), (P, nl, nv), jnp.float32,
                                1.0, 16.0)
    rate = jnp.exp(jax.random.uniform(
        next(keys), (P, nl, nv), jnp.float32, math.log(DECAY_RATES[0]),
        math.log(DECAY_RATES[1])))
    step = rate / amount
    conv_scale = 1.0 / math.sqrt(taps)
    return {
        "embed_tokens": matrix(cfg.vocab_size, h, scale=EMBED_SIZE),
        "lm_head": matrix(h, cfg.vocab_size),
        "final_norm": norm(h),
        "layers": {
            "lin_wq": matrix(P, nl, h, kd),
            "lin_wk": matrix(P, nl, h, kd),
            "lin_wv": matrix(P, nl, h, vd),
            "lin_wz": matrix(P, nl, h, vd),
            "lin_wa": gate(P, nl, h, nv),
            "lin_wb": gate(P, nl, h, nv),
            "conv_q": matrix(P, nl, taps, kd, scale=conv_scale),
            "conv_k": matrix(P, nl, taps, kd, scale=conv_scale),
            "conv_v": matrix(P, nl, taps, vd, scale=conv_scale),
            "a_log": jnp.log(amount),
            # softplus^-1(step): step + log(1 - exp(-step)).
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "o_norm": norm(P, nl, dv),
            "lin_wo": matrix(P, nl, vd, h),
            "wq": matrix(P, h, qd),
            "wk": matrix(P, h, kvd),
            "wv": matrix(P, h, kvd),
            "wo": matrix(P, qd, h),
            "q_norm": norm(P, qd),
            "k_norm": norm(P, kvd),
            "post_attn_norm": norm(P, per, h),
            "post_ffn_norm": norm(P, per, h),
            "w_gate": matrix(P, per, h, i),
            "w_up": matrix(P, per, h, i),
            "w_down": matrix(P, per, i, h),
        },
    }


# ---------------------------------------------------------------- blocks

def _causal_conv_silu(conv_w, x):
    """x [B, S, channels] through ``conv_w`` [taps, channels], zeros before
    position 0, then ``silu``: float32."""
    s = x.shape[1]
    window = jnp.pad(x, ((0, 0), (conv_w.shape[0] - 1, 0), (0, 0)))
    return short_conv_silu(conv_w, window, s)


def gated_delta_net(cfg: OlmoHybridConfig, lp: dict, x):
    """x [B, S, H] -> the operator's output [B, S, H]."""
    b, s, _ = x.shape
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    with tracing.part("linear_attn"):
        q, k, v, z = (x @ lp[n] for n in
                      ("lin_wq", "lin_wk", "lin_wv", "lin_wz"))
        a = (x @ lp["lin_wa"]).astype(jnp.float32)
        beta = jax.nn.sigmoid((x @ lp["lin_wb"]).astype(jnp.float32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(a + lp["dt_bias"])
        with tracing.part("conv"):
            q, k, v = (_causal_conv_silu(lp[n], y) for n, y in
                       (("conv_q", q), ("conv_k", k), ("conv_v", v)))
        q = unit_heads(q, nk) * cfg.linear_key_head_dim ** -0.5
        k = unit_heads(k, nk)
        v = v.reshape(b, s, nv, cfg.linear_value_head_dim)
        zero = jnp.zeros((b, nv, cfg.linear_key_head_dim,
                          cfg.linear_value_head_dim), jnp.float32)
        with tracing.part("delta_rule"):
            o, _ = gated_delta_chunk(q, k, v, g, beta, zero)
        o = rms_norm_reference(o, lp["o_norm"], cfg.norm_eps)
        o = o * jax.nn.silu(z.astype(jnp.float32).reshape(o.shape))
        return (o.astype(x.dtype).reshape(b, s, -1) @ lp["lin_wo"]).astype(
            x.dtype)


def full_attention(cfg: OlmoHybridConfig, ap: dict, x, attn_impl: str,
                   kmesh: KernelMesh | None = None):
    """x [B, S, H] -> the operator's output [B, S, H]: queries and keys
    normed over all heads at once, no positions."""
    b, s, _ = x.shape
    q = rms_norm(x @ ap["wq"], ap["q_norm"], cfg.norm_eps, kmesh)
    k = rms_norm(x @ ap["wk"], ap["k_norm"], cfg.norm_eps, kmesh)
    v = x @ ap["wv"]

    def heads(a, n):
        return a.reshape(b, s, n, cfg.head_dim).transpose(0, 2, 1, 3)

    o = _attention(cfg, heads(q, cfg.num_heads), heads(k, cfg.num_kv_heads),
                   heads(v, cfg.num_kv_heads), attn_impl, None, kmesh)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return (o @ ap["wo"]).astype(x.dtype)


def _layer(cfg: OlmoHybridConfig, kind: str, attn_impl: str,
           kmesh: KernelMesh | None, x, op: dict, lp: dict):
    """One block on x [B, S, H]: ``op`` the operator's leaves, ``lp`` the
    feed-forward's and the two norms'."""
    dt = x.dtype
    with tracing.part("attn"):
        if kind == LINEAR:
            y = gated_delta_net(cfg, op, x)
        else:
            y = full_attention(cfg, op, x, attn_impl, kmesh)
        x = x + rms_norm(y, lp["post_attn_norm"], cfg.norm_eps, kmesh)
    with tracing.part("mlp"):          # SwiGLU
        gate = checkpoint_name(
            jax.nn.silu((x @ lp["w_gate"]).astype(jnp.float32)).astype(dt),
            "mlp_gate")
        y = ((gate * (x @ lp["w_up"])) @ lp["w_down"]).astype(dt)
        return x + rms_norm(y, lp["post_ffn_norm"], cfg.norm_eps, kmesh)


def forward_hidden(cfg: OlmoHybridConfig, params: dict, tokens: jax.Array,
                   attn_impl: str = "flash", remat: bool | str = True,
                   kmesh: KernelMesh | None = None) -> jax.Array:
    """tokens [B, S] -> final-norm hidden states [B, S, H]. ``remat`` is one
    policy of ``models/llama._remat_wrap`` for every layer."""
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    per = cfg.full_attention_interval
    blocks = [_remat_wrap(partial(_layer, cfg, cfg.kind(at), attn_impl,
                                  kmesh), remat) for at in range(per)]

    def period(x, leaves):
        for at, block in enumerate(blocks):
            with tracing.part("stack"):
                if cfg.kind(at) == LINEAR:
                    op = {n: leaves[n][at] for n in LINEAR_LEAVES}
                else:
                    op = {n: leaves[n] for n in ATTENTION_LEAVES}
                lp = {n: leaves[n][at] for n in LAYER_LEAVES}
            x = block(x, op, lp)
        return x, None

    with tracing.part("stack"):
        x, _ = lax.scan(period, x, params["layers"])
    with tracing.part("head"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)


def forward(cfg: OlmoHybridConfig, params: dict, tokens: jax.Array,
            **fwd_kwargs) -> jax.Array:
    """tokens [B, S] -> float32 logits [B, S, V]."""
    x = forward_hidden(cfg, params, tokens, **fwd_kwargs)
    with tracing.part("head"):
        return jnp.einsum("bsh,hv->bsv", x, params["lm_head"],
                          preferred_element_type=jnp.float32)


def loss_fn(cfg: OlmoHybridConfig, params: dict, tokens: jax.Array,
            targets: jax.Array, mask: jax.Array | None = None,
            **fwd_kwargs) -> jax.Array:
    """Mean next-token cross-entropy over unmasked positions; the head's
    matmul runs inside the loss's chunks (ops/loss.py)."""
    from ray_tpu.ops.loss import default_ce_chunk, fused_cross_entropy

    x = forward_hidden(cfg, params, tokens, **fwd_kwargs)
    with tracing.part("loss"):
        return fused_cross_entropy(x, params["lm_head"], targets, mask,
                                   default_ce_chunk())
