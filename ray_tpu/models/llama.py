"""Llama-3 family: pure-functional JAX transformer with declarative sharding.

The flagship model of the framework (the reference delegates models to
torch/vLLM; a TPU-native framework owns them — BASELINE config 2: Llama-3-8B
DDP fine-tune is the north-star workload).

Design points (TPU-first):
- Params are a flat pytree of arrays; every leaf has a logical-axis tuple in
  ``param_logical_axes`` consumed by ray_tpu.parallel.sharding rules, so the
  same model runs pure-DP, FSDP, TP, or any mix by changing the rule table.
- Layers are stacked on a leading ``layers`` axis and iterated with
  ``lax.scan`` → one compiled layer body regardless of depth (fast compiles,
  XLA-friendly).
- Attention goes through ray_tpu.ops (flash kernel on TPU, blockwise
  elsewhere, ring attention when the mesh has an ``sp`` axis).
- bfloat16 activations/params by default, fp32 RMSNorm statistics and logits.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import blockwise_attention, flash_attention
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.ring_attention import ring_attention_local
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.util import tracing


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rope_scaling: dict | None = None
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_1b() -> "LlamaConfig":
        # Llama-3.2-1B geometry
        return LlamaConfig(hidden_size=2048, intermediate_size=8192,
                           num_layers=16, num_heads=32, num_kv_heads=8,
                           head_dim=64, tie_embeddings=True)

    @staticmethod
    def tiny() -> "LlamaConfig":
        """Test-size config: compiles in seconds, exercises every code path."""
        return LlamaConfig(vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_layers=2, num_heads=4,
                           num_kv_heads=2, head_dim=16, max_seq_len=256,
                           dtype="float32")

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    def num_params(self) -> int:
        h, v, i, L = self.hidden_size, self.vocab_size, self.intermediate_size, self.num_layers
        qkv = h * self.num_heads * self.head_dim + 2 * h * self.num_kv_heads * self.head_dim
        o = self.num_heads * self.head_dim * h
        mlp = 3 * h * i
        embed = v * h * (1 if self.tie_embeddings else 2)
        return embed + L * (qkv + o + mlp + 2 * h) + h


def param_logical_axes(cfg: LlamaConfig) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules)."""
    axes = {
        "embed_tokens": ("vocab", "embed"),
        "final_norm": ("embed",),
        "layers": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
            "attn_norm": ("layers", "embed"),
            "mlp_norm": ("layers", "embed"),
        },
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_params(cfg: LlamaConfig, key: jax.Array) -> dict:
    """Scaled-normal init; layer params stacked on the leading axis."""
    h, L = cfg.hidden_size, cfg.num_layers
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    i = cfg.intermediate_size
    dt = cfg.jnp_dtype
    keys = jax.random.split(key, 10)

    def norm_init(k, *shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    params = {
        "embed_tokens": (jax.random.normal(keys[0], (cfg.vocab_size, h),
                                           jnp.float32) * 0.02).astype(dt),
        "final_norm": jnp.ones((h,), dt),
        "layers": {
            "wq": norm_init(keys[1], L, h, qd),
            "wk": norm_init(keys[2], L, h, kvd),
            "wv": norm_init(keys[3], L, h, kvd),
            "wo": norm_init(keys[4], L, qd, h, scale=1.0 / math.sqrt(qd * 2 * L)),
            "w_gate": norm_init(keys[5], L, h, i),
            "w_up": norm_init(keys[6], L, h, i),
            "w_down": norm_init(keys[7], L, i, h, scale=1.0 / math.sqrt(i * 2 * L)),
            "attn_norm": jnp.ones((L, h), dt),
            "mlp_norm": jnp.ones((L, h), dt),
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(keys[8], h, cfg.vocab_size,
                                      scale=1.0 / math.sqrt(h))
    return params


def _attention(cfg: LlamaConfig, q, k, v, attn_impl: str, sp_axis: str | None,
               kmesh: KernelMesh | None = None):
    """q: [B, H, S, D], k/v: [B, Hkv, S, D] (already rope'd)."""
    if sp_axis is not None:
        # Context parallel: sequence is sharded over sp_axis (we are inside
        # shard_map); the ring handles cross-shard causality.
        return ring_attention_local(q, k, v, axis_name=sp_axis, causal=True)
    if attn_impl == "flash":
        return flash_attention(q, k, v, True, None, True, kmesh)
    return blockwise_attention(q, k, v, causal=True)


def _layer(cfg: LlamaConfig, x, layer_params, inv_freq, positions,
           attn_impl: str, sp_axis: str | None,
           kmesh: KernelMesh | None = None):
    """One transformer block. x: [B, S, H]. ``kmesh``: the mesh the caller's
    arrays are sharded over, for the Pallas kernels (ops/kernels.py)."""
    b, s, h = x.shape
    lp = layer_params
    dt = x.dtype

    with tracing.part("attn"):
        xn = checkpoint_name(
            rms_norm(x, lp["attn_norm"], cfg.norm_eps, kmesh), "norm_out")
        q = (xn @ lp["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = (xn @ lp["wk"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        v = (xn @ lp["wv"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        q = q.transpose(0, 2, 1, 3)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
        q = checkpoint_name(apply_rope(q, positions, inv_freq), "rope_out")
        k = checkpoint_name(apply_rope(k, positions, inv_freq), "rope_out")
        v = checkpoint_name(v, "v_out")
        o = _attention(cfg, q, k, v, attn_impl, sp_axis, kmesh)
        o = o.transpose(0, 2, 1, 3).reshape(b, s,
                                            cfg.num_heads * cfg.head_dim)
        x = x + checkpoint_name((o @ lp["wo"]).astype(dt), "attn_proj")

    with tracing.part("mlp"):          # SwiGLU
        xn = checkpoint_name(
            rms_norm(x, lp["mlp_norm"], cfg.norm_eps, kmesh), "norm_out")
        gate = checkpoint_name(
            jax.nn.silu((xn @ lp["w_gate"]).astype(jnp.float32)).astype(dt),
            "mlp_gate")
        up = xn @ lp["w_up"]
        x = x + ((gate * up) @ lp["w_down"]).astype(dt)
    return x


def normalize_remat(remat, num_layers: int):
    """Canonicalize a remat spec: a scalar policy stays scalar; a per-layer
    sequence (one policy string per layer — the autotuner's save-lists
    keyed by layer index) is length-checked and collapsed back to a scalar
    when uniform, so the single-scan fast path still applies. Strings with
    commas ("attn:8,dots:8" or "attn,attn,dots,...") expand to per-layer
    form; "policy:N" runs N consecutive layers under that policy."""
    if isinstance(remat, str) and ("," in remat or ":" in remat):
        out = []
        for part in remat.split(","):
            part = part.strip()
            if ":" in part:
                pol, n = part.rsplit(":", 1)
                out.extend([pol] * int(n))
            elif part:
                out.append(part)
        remat = tuple(out)
    if isinstance(remat, (list, tuple)):
        if len(remat) != num_layers:
            raise ValueError(
                f"per-layer remat has {len(remat)} entries for "
                f"{num_layers} layers")
        if len(set(remat)) == 1:
            return remat[0]
        return tuple(remat)
    return remat


def _remat_runs(remat: tuple) -> list[tuple]:
    """Consecutive equal-policy runs of a per-layer remat spec:
    ('attn','attn','dots') -> [('attn', 0, 2), ('dots', 2, 3)]. Each run
    scans with ONE compiled layer body (same compile-size economics as the
    uniform case; the number of distinct bodies = number of runs)."""
    runs = []
    start = 0
    for i in range(1, len(remat) + 1):
        if i == len(remat) or remat[i] != remat[start]:
            runs.append((remat[start], start, i))
            start = i
    return runs


def _remat_wrap(layer_fn, remat):
    """remat policy: True/'full' = recompute everything (min memory),
    'attn' = save ONLY the attention residuals (rope'd q/k, v, flash
    out+lse) and the attention output projection — the backward pass
    never re-runs the attention kernel, but the wide SwiGLU activations
    ([B,S,intermediate], the two biggest per-layer tensors) are
    recomputed from the saved attn_proj (one cheap residual-add + norm +
    two matmuls). ~3x less activation HBM than 'dots' for ~18% more
    step FLOPs — the fit-enabling mode for HBM-bound configs,
    'dots' = save matmul outputs (jax.checkpoint_policies.checkpoint_dots)
    plus the flash-attention residuals (out, lse) — so the backward pass
    neither recomputes the matmuls nor re-runs the attention kernel,
    'dots+' = 'dots' plus the rms_norm/rope outputs (no elementwise
    recompute at all — highest memory short of 'none'),
    False/'none' = save all."""
    if remat in (False, "none"):
        return layer_fn
    if remat == "attn":
        policy = jax.checkpoint_policies.save_only_these_names(
            "flash_resid", "rope_out", "v_out", "attn_proj")
        return jax.checkpoint(layer_fn, policy=policy)
    if remat == "attn+":
        # 'attn' plus the post-silu gate ([B,S,intermediate] bf16, ~134 MB
        # per layer at b4/s2048): the backward re-runs only the w_up matmul
        # (up, and gate·up from the saved gate) instead of the full SwiGLU
        # re-forward — trades ~2.1 GB of HBM for roughly half the 'attn'
        # MLP recompute. (Saving gate·up itself would be useless: d(gate)
        # and d(up) each need the OTHER factor, so both matmuls would still
        # re-run.)
        policy = jax.checkpoint_policies.save_only_these_names(
            "flash_resid", "rope_out", "v_out", "attn_proj", "mlp_gate")
        return jax.checkpoint(layer_fn, policy=policy)
    if remat in ("dots", "dots+"):
        names = ("flash_resid",) if remat == "dots" else (
            "flash_resid", "norm_out", "rope_out")
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots,
            jax.checkpoint_policies.save_only_these_names(*names),
        )
        return jax.checkpoint(layer_fn, policy=policy)
    return jax.checkpoint(layer_fn)


def forward_hidden(cfg: LlamaConfig, params: dict, tokens: jax.Array,
                   positions: jax.Array | None = None,
                   attn_impl: str = "flash", sp_axis: str | None = None,
                   remat: bool | str | tuple = True,
                   kmesh: KernelMesh | None = None) -> jax.Array:
    """tokens [B, S] → final-norm hidden states [B, S, H].

    ``remat`` is a single policy (see :func:`_remat_wrap`) or a per-layer
    spec (tuple of policies / "pol:N,pol:N" string — see
    :func:`normalize_remat`): e.g. the autotuner's mixed save-lists spend
    HBM on cheap-to-save early layers while the deep layers stay lean."""
    b, s = tokens.shape
    if positions is None:
        with tracing.part("attn"):
            positions = jnp.arange(s)
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    with tracing.part("attn"):
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta,
                                    cfg.rope_scaling)

    base_fn = partial(_layer, cfg, inv_freq=inv_freq, positions=positions,
                      attn_impl=attn_impl, sp_axis=sp_axis, kmesh=kmesh)
    remat = normalize_remat(remat, cfg.num_layers)

    if isinstance(remat, tuple):
        # Per-layer policies: scan each equal-policy run over its slice of
        # the stacked layer params (still one compiled body per run).
        for policy, start, end in _remat_runs(remat):
            layer_fn = _remat_wrap(base_fn, policy)

            def scan_body(x, lp, _fn=layer_fn):
                return _fn(x, lp), None

            with tracing.part("stack"):
                run_params = jax.tree.map(lambda a: a[start:end],
                                          params["layers"])
                x, _ = lax.scan(scan_body, x, run_params)
        with tracing.part("head"):
            return rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)

    layer_fn = _remat_wrap(base_fn, remat)

    def scan_body(x, lp):
        return layer_fn(x, lp), None

    with tracing.part("stack"):
        x, _ = lax.scan(scan_body, x, params["layers"])
    with tracing.part("head"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)


def unembed_weights(cfg: LlamaConfig, params: dict) -> jax.Array:
    """[H, V] head matrix (transpose of tied embeddings stays a lazy dot
    permutation under XLA — never materialized)."""
    return params["embed_tokens"].T if cfg.tie_embeddings else params["lm_head"]


def forward(cfg: LlamaConfig, params: dict, tokens: jax.Array,
            positions: jax.Array | None = None, attn_impl: str = "flash",
            sp_axis: str | None = None, remat: bool | str = True,
            kmesh: KernelMesh | None = None) -> jax.Array:
    """tokens [B, S] → logits [B, S, V] (fp32). bf16 MXU matmul with fp32
    accumulation — a fp32×fp32 dot would run off the MXU fast path."""
    x = forward_hidden(cfg, params, tokens, positions, attn_impl, sp_axis,
                       remat, kmesh)
    with tracing.part("head"):
        head = unembed_weights(cfg, params)
        return jnp.einsum("bsh,hv->bsv", x, head,
                          preferred_element_type=jnp.float32)


def loss_fn(cfg: LlamaConfig, params: dict, tokens: jax.Array,
            targets: jax.Array, mask: jax.Array | None = None,
            fused_ce: bool = True, **fwd_kwargs) -> jax.Array:
    """Mean next-token cross-entropy over unmasked positions."""
    if fused_ce:
        from ray_tpu.ops.loss import default_ce_chunk, fused_cross_entropy

        x = forward_hidden(cfg, params, tokens, **fwd_kwargs)
        with tracing.part("head"):
            head = unembed_weights(cfg, params)
        # The head matmul runs inside the loss's chunks: booked with it.
        with tracing.part("loss"):
            return fused_cross_entropy(x, head, targets, mask,
                                       default_ce_chunk())
    logits = forward(cfg, params, tokens, **fwd_kwargs)
    with tracing.part("loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None],
                                   axis=-1)[..., 0]
        if mask is None:
            mask = jnp.ones_like(targets, jnp.float32)
        mask = mask.astype(jnp.float32)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
