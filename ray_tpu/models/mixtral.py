"""Mixtral-family sparse Mixture-of-Experts transformer.

Covers the reference's MoE serving/training capability (reference: BASELINE
config 5 runs Mixtral via vLLM engine kwargs + ray.util.collective all-to-all;
the reference has no first-class MoE implementation — SURVEY.md §2.4 EP row).
Here MoE is first-class and TPU-native:

- GShard/Switch-style capacity-based routing: top-k gates, per-expert token
  slots, dispatch/combine einsums. Everything is STATIC-shaped — no gather by
  dynamic token counts — so XLA tiles it onto the MXU and the ``expert``-
  sharded einsums lower to all-to-all over the mesh's ``ep`` axis
  automatically (the TPU-idiomatic equivalent of the reference's explicit
  collective all-to-all).
- Attention/rope/norms are shared with the Llama family; only the MLP is
  replaced by the expert layer; layers still scan-stacked.
- Load-balancing auxiliary loss (Switch Transformer form) returned alongside
  the LM loss.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import llama as _llama
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies


@dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    max_seq_len: int = 8192
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.02
    dtype: str = "bfloat16"

    @staticmethod
    def mixtral_8x7b() -> "MixtralConfig":
        return MixtralConfig()

    @staticmethod
    def tiny() -> "MixtralConfig":
        """Test-size: compiles in seconds, exercises routing + all code paths."""
        return MixtralConfig(vocab_size=256, hidden_size=64,
                             intermediate_size=128, num_layers=2, num_heads=4,
                             num_kv_heads=2, head_dim=16, max_seq_len=256,
                             num_experts=4, top_k=2, dtype="float32")

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    def capacity(self, num_tokens: int) -> int:
        """Per-expert token slots for a batch of ``num_tokens``."""
        return max(1, int(math.ceil(
            self.capacity_factor * self.top_k * num_tokens / self.num_experts)))


def param_logical_axes(cfg: MixtralConfig) -> dict:
    return {
        "embed_tokens": ("vocab", "embed"),
        "lm_head": ("embed", "vocab"),
        "final_norm": ("embed",),
        "layers": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "router": ("layers", "embed", None),
            # Expert weights carry the ``expert`` logical axis → mesh ``ep``.
            "we_gate": ("layers", "expert", "embed", "mlp"),
            "we_up": ("layers", "expert", "embed", "mlp"),
            "we_down": ("layers", "expert", "mlp", "embed"),
            "attn_norm": ("layers", "embed"),
            "mlp_norm": ("layers", "embed"),
        },
    }


def init_params(cfg: MixtralConfig, key: jax.Array) -> dict:
    h, L, E = cfg.hidden_size, cfg.num_layers, cfg.num_experts
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    i = cfg.intermediate_size
    dt = cfg.jnp_dtype
    keys = jax.random.split(key, 12)

    def norm_init(k, *shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    return {
        "embed_tokens": (jax.random.normal(keys[0], (cfg.vocab_size, h),
                                           jnp.float32) * 0.02).astype(dt),
        "lm_head": norm_init(keys[1], h, cfg.vocab_size,
                             scale=1.0 / math.sqrt(h)),
        "final_norm": jnp.ones((h,), dt),
        "layers": {
            "wq": norm_init(keys[2], L, h, qd),
            "wk": norm_init(keys[3], L, h, kvd),
            "wv": norm_init(keys[4], L, h, kvd),
            "wo": norm_init(keys[5], L, qd, h, scale=1.0 / math.sqrt(qd * 2 * L)),
            "router": norm_init(keys[6], L, h, E, scale=0.02),
            "we_gate": norm_init(keys[7], L, E, h, i),
            "we_up": norm_init(keys[8], L, E, h, i),
            "we_down": norm_init(keys[9], L, E, i, h,
                                 scale=1.0 / math.sqrt(i * 2 * L)),
            "attn_norm": jnp.ones((L, h), dt),
            "mlp_norm": jnp.ones((L, h), dt),
        },
    }


def compute_routing(cfg: MixtralConfig, logits: jax.Array, capacity: int):
    """Router logits [T, E] → (dispatch [T,E,C], combine [T,E,C], aux).

    Top-k gates renormalized to sum to 1 per token; slot positions assigned by
    running claim count per expert (token-major priority); claims beyond
    ``capacity`` are dropped. For a kept token, combine[t].sum() == 1.
    """
    T = logits.shape[0]
    E, K, C = cfg.num_experts, cfg.top_k, capacity
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, K)  # [T, K]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)  # renormalize top-k

    # Slot assignment: for the k-th choice of each token, its position within
    # the chosen expert is the running count of earlier claims on that expert.
    expert_onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # [T, K, E]
    flat_claims = expert_onehot.reshape(T * K, E)  # priority: token-major, k-minor
    position = jnp.cumsum(flat_claims, axis=0) - flat_claims  # claims before us
    position = (position * flat_claims).sum(-1).reshape(T, K)  # [T, K]
    kept = position < C

    # dispatch[t, e, c] = 1 where token t owns slot c of expert e
    slot_onehot = jax.nn.one_hot(position, C, dtype=jnp.float32)  # [T, K, C]
    dispatch = jnp.einsum("tke,tkc->tec", expert_onehot.astype(jnp.float32),
                          slot_onehot * kept[..., None])
    combine = jnp.einsum("tk,tke,tkc->tec",
                         gate_vals * kept, expert_onehot.astype(jnp.float32),
                         slot_onehot)

    # Switch load-balancing loss: E * Σ_e (token fraction)·(mean router prob).
    token_frac = dispatch.sum((0, 2)) / jnp.maximum(dispatch.sum(), 1.0)
    prob_frac = probs.mean(0)
    aux = E * jnp.sum(token_frac * prob_frac)
    return dispatch, combine, aux


def moe_block(cfg: MixtralConfig, x: jax.Array, lp: dict):
    """Capacity-routed expert MLP. x: [B, S, H] → ([B, S, H], aux_loss).

    Static-shape dispatch: tokens → [E, C, H] slots via one-hot einsum (the
    ``e``-sharded operands make XLA emit the ep all-to-all), per-expert SwiGLU
    as batched einsums on the MXU, combine back with the gate weights.
    Overflowing tokens beyond an expert's capacity are dropped (their residual
    stream passes through unchanged) — Switch/GShard semantics.
    """
    b, s, h = x.shape
    T = b * s
    C = cfg.capacity(T)
    dt = x.dtype
    xt = x.reshape(T, h)

    logits = (xt @ lp["router"]).astype(jnp.float32)  # [T, E]
    dispatch, combine, aux = compute_routing(cfg, logits, C)

    # [E, C, H] expert inputs — this einsum is the ep all-to-all boundary.
    expert_in = jnp.einsum("tec,th->ech", dispatch.astype(dt), xt)
    gate = jax.nn.silu(jnp.einsum(
        "ech,ehi->eci", expert_in, lp["we_gate"]).astype(jnp.float32)).astype(dt)
    up = jnp.einsum("ech,ehi->eci", expert_in, lp["we_up"])
    expert_out = jnp.einsum("eci,eih->ech", gate * up, lp["we_down"])
    y = jnp.einsum("tec,ech->th", combine.astype(dt), expert_out)
    return y.reshape(b, s, h), aux


def _layer(cfg: MixtralConfig, x, lp, inv_freq, positions, attn_impl,
           kmesh=None):
    b, s, h = x.shape
    dt = x.dtype
    xn = rms_norm(x, lp["attn_norm"], cfg.norm_eps, kmesh)
    q = (xn @ lp["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = (xn @ lp["wk"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = (xn @ lp["wv"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    o = _llama._attention(cfg, q, k, v, attn_impl, None, kmesh)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.num_heads * cfg.head_dim)
    x = x + (o @ lp["wo"]).astype(dt)

    xn = rms_norm(x, lp["mlp_norm"], cfg.norm_eps, kmesh)
    y, aux = moe_block(cfg, xn, lp)
    return x + y.astype(dt), aux


def forward(cfg: MixtralConfig, params: dict, tokens: jax.Array,
            positions: jax.Array | None = None, attn_impl: str = "flash",
            remat: bool = True, kmesh=None):
    """tokens [B, S] → (logits [B, S, V] fp32, mean aux loss). ``kmesh``:
    the caller's mesh for the Pallas kernels (ops/kernels.py)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.arange(s)
    x = params["embed_tokens"][tokens]
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, None)

    from ray_tpu.models.llama import _remat_wrap

    layer_fn = _remat_wrap(
        partial(_layer, cfg, inv_freq=inv_freq, positions=positions,
                attn_impl=attn_impl, kmesh=kmesh),
        remat)

    def scan_body(x, lp):
        x, aux = layer_fn(x, lp)
        return x, aux

    x, aux = lax.scan(scan_body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)
    # bf16 MXU matmul with f32 accumulation — casting both operands to f32
    # would fall off the MXU fast path (see llama.forward).
    logits = jnp.einsum("bsh,hv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, aux.mean()


def loss_fn(cfg: MixtralConfig, params: dict, tokens: jax.Array,
            targets: jax.Array, mask: jax.Array | None = None,
            **fwd_kwargs) -> jax.Array:
    """LM cross-entropy + router load-balancing loss."""
    logits, aux = forward(cfg, params, tokens, **fwd_kwargs)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        mask = jnp.ones_like(targets, jnp.float32)
    mask = mask.astype(jnp.float32)
    lm = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return lm + cfg.router_aux_coef * aux
