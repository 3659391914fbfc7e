"""A looped decoder: one stack of layers applied several times a token.

The family of ``model_type: "ouro"`` (huggingface.co/ByteDance/Ouro-2.6B):
``num_layers`` dense blocks, plain multi-head attention and SwiGLU, whose
weights every one of ``total_ut_steps`` passes reads again. What sets it
apart from models/llama.py:

- **the block is a sandwich**: a sublayer's input is normed and so is its
  output, before the residual add (four norms a layer);
- **the loop**: the state that leaves the last layer is normed by the
  model's one final norm and that normed state is what the next pass starts
  from; after the last pass the head reads it with no further norm;
- **the exit gate**: a ``Linear(H -> 1)`` and a sigmoid on each pass's
  normed state give g_t; pass t (1-based) is left with probability
  ``p_t = g_t * prod_{j<t} (1 - g_j)``, the last with what remains, and
  the pass whose state feeds the head is the first at which the summed
  probability reaches ``early_exit_threshold`` (the last where none does).
  At the published threshold of 1 that is the last pass, short of a gate
  that saturates;
- **what is not shared**: keys and values. A token at pass t attends to
  what earlier tokens wrote *in pass t*, so a cache has a line for every
  (pass, layer), ``cache_line(t, l) = t * num_layers + l``.

Every pass runs for every token whatever the gate says: a token that left
early would still owe its later passes' keys and values to the tokens
after it (llm/ouro_serving.py refuses a threshold under 1 for that reason;
here, with no cache, the head simply reads the state the rule picks).

Rope, ``rms_norm`` and the attention kernels are the shared ops/.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import blockwise_attention, flash_attention
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.util import tracing

# What the programs count on the device, over valid tokens only: tokens
# that went through the loop, and the sum over them of the pass (1-based)
# the exit rule picked.
LOOP_COUNTERS = ("loop_tokens", "loop_exit_steps")


@dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 48
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 128
    max_seq_len: int = 65536
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    dtype: str = "bfloat16"

    @staticmethod
    def tiny(**kw) -> "OuroConfig":
        """Test-size config: three layers, four passes."""
        base = dict(vocab_size=512, hidden_size=64, intermediate_size=176,
                    num_layers=3, num_heads=4, num_kv_heads=4, head_dim=16,
                    max_seq_len=256, dtype="float32")
        base.update(kw)
        return OuroConfig(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def cache_lines(self) -> int:
        """Lines of keys and values a token leaves: one a (pass, layer)."""
        return self.total_ut_steps * self.num_layers

    def cache_line(self, step, layer):
        """The line of pass ``step`` (0-based) and layer ``layer``."""
        return step * self.num_layers + layer


def param_logical_axes(cfg: OuroConfig) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules)."""
    return {
        "embed_tokens": ("vocab", "embed"),
        "final_norm": ("embed",),
        "exit_gate": {"w": ("embed",), "b": ()},
        "lm_head": ("embed", "vocab"),
        "layers": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
            "attn_norm": ("layers", "embed"),
            "attn_post_norm": ("layers", "embed"),
            "mlp_norm": ("layers", "embed"),
            "mlp_post_norm": ("layers", "embed"),
        },
    }


def init_params(cfg: OuroConfig, key: jax.Array) -> dict:
    """Scaled-normal init; layer params stacked on the leading axis. The
    norms' weights and the gate's bias are drawn too (near 1 and near 0),
    so that a program that dropped one of them would not pass for right."""
    h, L = cfg.hidden_size, cfg.num_layers
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    i = cfg.intermediate_size
    dt = cfg.jnp_dtype
    keys = jax.random.split(key, 16)

    def matrix(k, *shape):
        scale = 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    def norm(k, *shape, mean=1.0):
        return (mean * (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32))
                ).astype(dt)

    # A sublayer's output is normed before the residual add, so the usual
    # 1 / sqrt(2 L) on the output projections (models/llama.init_params)
    # would be undone; it goes on the output norms' weights instead. At 1
    # every sublayer adds a unit vector to a stream that starts each pass at
    # unit size, and bfloat16's rounding grows through 192 applications
    # until a sound run reads 0.33 to 1.0 under the reference's top logit
    # (my chip run, PR 34), where the dense cells read 0.05.
    branch = 1.0 / math.sqrt(2 * L)

    return {
        "embed_tokens": (jax.random.normal(keys[0], (cfg.vocab_size, h),
                                           jnp.float32) * 0.02).astype(dt),
        "final_norm": norm(keys[1], h),
        "exit_gate": {
            "w": (jax.random.normal(keys[2], (h,), jnp.float32)
                  / math.sqrt(h)).astype(dt),
            "b": (0.1 * jax.random.normal(keys[3], (), jnp.float32)
                  ).astype(dt)},
        "lm_head": matrix(keys[4], h, cfg.vocab_size),
        "layers": {
            "wq": matrix(keys[5], L, h, qd),
            "wk": matrix(keys[6], L, h, kvd),
            "wv": matrix(keys[7], L, h, kvd),
            "wo": matrix(keys[8], L, qd, h),
            "w_gate": matrix(keys[9], L, h, i),
            "w_up": matrix(keys[10], L, h, i),
            "w_down": matrix(keys[11], L, i, h),
            "attn_norm": norm(keys[12], L, h),
            "attn_post_norm": norm(keys[13], L, h, mean=branch),
            "mlp_norm": norm(keys[14], L, h),
            "mlp_post_norm": norm(keys[15], L, h, mean=branch),
        },
    }


def block(cfg: OuroConfig, lp, x, positions, inv_freq, attend, state,
          kmesh: KernelMesh | None = None):
    """One layer application. x: [B, S, H]; ``attend(q, k, v, state) ->
    (o, state)`` is given the rotated queries [B, H, S, D] and this
    application's keys and values [B, Hkv, S, D] and returns what the
    queries see, [B, H, S, D] (a cache, if any, rides ``state``).
    Returns (x, state)."""
    b, s, _ = x.shape
    dt = x.dtype
    eps = cfg.norm_eps
    with tracing.part("attn"):
        xn = rms_norm(x, lp["attn_norm"], eps, kmesh)
        # Arrays of their own before they are split into heads: XLA
        # otherwise folds the split into the product as a convolution over
        # the heads, wants each stacked matrix transposed for it, and copies
        # all three (3 x 0.375 GiB at the published widths) at the top of
        # every program (the first AOT compile, PR 34).
        q, k, v = lax.optimization_barrier(
            (xn @ lp["wq"], xn @ lp["wk"], xn @ lp["wv"]))
        q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        q = apply_rope(q.transpose(0, 2, 1, 3), positions, inv_freq)
        k = apply_rope(k.transpose(0, 2, 1, 3), positions, inv_freq)
        o, state = attend(q, k, v.transpose(0, 2, 1, 3), state)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        x = x + rms_norm((o @ lp["wo"]).astype(dt), lp["attn_post_norm"],
                         eps, kmesh)
    with tracing.part("mlp"):
        xn = rms_norm(x, lp["mlp_norm"], eps, kmesh)
        gate = jax.nn.silu((xn @ lp["w_gate"]).astype(jnp.float32)).astype(dt)
        # Kept as an array of its own, as llm/llama_serving._mlp keeps it: fused
        # into the down projection XLA computes it again for every tile of
        # the output.
        act = lax.optimization_barrier(gate * (xn @ lp["w_up"]))
        x = x + rms_norm((act @ lp["w_down"]).astype(dt),
                         lp["mlp_post_norm"], eps, kmesh)
    return x, state


def loop(cfg: OuroConfig, params, x, stack, state,
         kmesh: KernelMesh | None = None):
    """The passes around ``stack(x, step, state) -> (x, state)`` (the
    layers, once): the norm between passes, the gate, the exit rule.
    Returns (the normed state of the pass the rule picks [B, S, H], state,
    the exit distribution [T, B, S] in float32, the picked pass [B, S],
    1-based)."""
    steps = cfg.total_ut_steps
    gw = params["exit_gate"]["w"].astype(jnp.float32)
    gb = params["exit_gate"]["b"].astype(jnp.float32)

    def one_pass(carry, t):
        x, state, left, reached, picked, chosen = carry
        x, state = stack(x, t, state)
        with tracing.part("loop"):
            x = rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)
            g = jax.nn.sigmoid(x.astype(jnp.float32) @ gw + gb)
            last = t == steps - 1
            p = jnp.where(last, left, g * left)
            reached = reached + p
            take = (chosen == 0) & (
                last | (reached >= cfg.early_exit_threshold))
            picked = jnp.where(take[..., None], x, picked)
            chosen = jnp.where(take, t + 1, chosen)
            return (x, state, left * (1.0 - g), reached, picked, chosen), p

    shape = x.shape[:2]
    with tracing.part("stack"):
        (_, state, _, _, picked, chosen), pdf = lax.scan(
            one_pass,
            (x, state, jnp.ones(shape, jnp.float32),
             jnp.zeros(shape, jnp.float32), jnp.zeros_like(x),
             jnp.zeros(shape, jnp.int32)),
            jnp.arange(steps))
    return picked, state, pdf, chosen


@tracing.part("loop")
def loop_counts(chosen, valid):
    """``LOOP_COUNTERS`` of one program: int32[2] over the tokens ``valid``
    marks (idle slots and padding count nowhere)."""
    return jnp.stack([valid.sum(), jnp.where(valid, chosen, 0).sum()]
                     ).astype(jnp.int32)


@tracing.part("head")
def lm_head(params, x):
    """x: [..., H], a pass's normed state -> float32 logits [..., V]."""
    return jnp.dot(x, params["lm_head"], preferred_element_type=jnp.float32)


def forward(cfg: OuroConfig, params: dict, tokens, *,
            attn_impl: str = "blockwise",
            kmesh: KernelMesh | None = None):
    """tokens [B, S] -> (float32 logits [B, S, V], exit distribution
    [B, S, T]). Whole sequences, no cache: every pass is causal over its
    own keys and values."""
    with tracing.part("attn"):
        positions = jnp.arange(tokens.shape[1])
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)

    def attend(q, k, v, state):
        if attn_impl == "flash":
            return flash_attention(q, k, v, True, None, True, kmesh), state
        return blockwise_attention(q, k, v, causal=True), state

    def stack(x, step, state):
        def body(x, lp):
            return block(cfg, lp, x, positions, inv_freq, attend, None,
                         kmesh)[0], None

        with tracing.part("stack"):
            return lax.scan(body, x, params["layers"])[0], state

    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    x, _, pdf, _ = loop(cfg, params, x, stack, None, kmesh)
    with tracing.part("loop"):
        pdf = pdf.transpose(1, 2, 0)
    return lm_head(params, x), pdf
