"""Analytic peak-HBM model for Llama train-step candidates.

This is the free tier of the autotuner's two-tier estimator: closed-form
accounting from the model config alone — params, gradients, optimizer
state (``optimizer_state_bytes`` over ``jax.eval_shape``, so ZeRO-1
sharding divides it without materializing anything), per-layer saved
activations per remat policy, and the fused-CE / update-phase transients.
Candidates whose prediction exceeds the device budget are pruned before
any compilation; the compile-time tier (``hlo_stats.hbm_stats`` /
``compiled_hbm_bytes`` on the AOT module) then records predicted-vs-actual
for the few candidates that actually get measured.

Accounting notes (why these terms, from the jax.checkpoint semantics in
models/llama.py and the scan structure in train/spmd.py):

- The layer input is ALWAYS saved (it is the checkpointed function's
  argument), on top of whatever the policy's save-list names.
- The backward has three distinct peaks that must be MAXed, not summed
  (their transients never overlap): (1) the fused-CE backward, when every
  saved activation is still live but the layer-grad accumulators are not
  yet allocated; (2) the layer-scan backward's start, when the full
  stacked gradient accumulators coexist with the full saved-activation
  set plus one layer's recompute workspace; (3) the optimizer update,
  when activations are dead and grads + the updates tree coexist (the
  f32 moment arithmetic fuses elementwise into the bf16 state writes and
  materializes nothing leaf-sized).

Accuracy: heuristic, not buffer assignment. The bench prunes with a
configurable safety margin above budget so a few-percent overestimate
cannot drop a config that actually fits (pruning errs toward keeping; a
kept-but-OOM candidate costs one failed AOT attempt, the pre-autotuner
status quo for every over-budget row). devbench/autotune_bench.py records
the model's error against AOT-compiled modules.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field

from ray_tpu.autotune.space import Candidate

def device_hbm_budget_bytes(device=None) -> int | None:
    """Usable HBM of the accelerator the bench will run on: the backend's
    own ``memory_stats()["bytes_limit"]``. None on a host without a TPU
    (callers then skip pruning); a TPU that does not report its limit is an
    error, not a guess by device name. RTPU_HBM_BUDGET_GB always wins
    (float GB), which is also how to price for a chip from a CPU host."""
    env = os.environ.get("RTPU_HBM_BUDGET_GB")
    if env:
        return int(float(env) * (1 << 30))
    import jax

    d = device if device is not None else jax.devices()[0]
    if d.platform != "tpu":
        return None
    limit = (d.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(
            f"{d.device_kind!r} reports no memory_stats()['bytes_limit']; "
            "set RTPU_HBM_BUDGET_GB to price candidates for it")
    return int(limit)


@dataclass
class HbmPrediction:
    total_bytes: int
    components: dict = field(default_factory=dict)

    @property
    def total_gb(self) -> float:
        return round(self.total_bytes / (1 << 30), 3)


def _policy_layer_bytes(policy: str, mb: int, seq: int, cfg,
                        flash: bool) -> int:
    """Saved-activation bytes for ONE layer under one remat policy, at
    microbatch mb (see models/llama._remat_wrap for what each policy's
    save-list names)."""
    ab = cfg.jnp_dtype.itemsize          # activation dtype (bf16 = 2)
    h = cfg.hidden_size
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    inter = cfg.intermediate_size
    tok = mb * seq

    x_in = tok * h * ab                  # checkpointed layer input
    q = tok * qd * ab                    # rope_out q
    k = tok * kvd * ab                   # rope_out k
    v = tok * kvd * ab                   # v_out
    attn_o = tok * qd * ab               # flash out (blockwise: same shape)
    lse = mb * cfg.num_heads * seq * 4 if flash else 0
    proj = tok * h * ab                  # attn_proj
    gate = tok * inter * ab              # mlp_gate (post-silu)
    up = tok * inter * ab
    down = tok * h * ab
    norm2 = 2 * tok * h * ab

    if policy in (False, "none"):
        # save-all: dots+ plus every elementwise intermediate; ~25% on top
        # of the named tensors in practice
        return int((x_in + 2 * q + 2 * k + v + attn_o + lse + proj + gate
                    + up + down + norm2) * 1.25)
    if policy in (True, "full"):
        return x_in
    if policy == "attn":
        return x_in + q + k + v + attn_o + lse + proj
    if policy == "attn+":
        return x_in + q + k + v + attn_o + lse + proj + gate
    if policy == "dots":
        # checkpoint_dots: every matmul output + the flash residuals
        return (x_in + q + k + v + attn_o + lse + proj + gate + up + down)
    if policy == "dots+":
        # dots + norm/rope outputs (rope_out ~ q+k again)
        return (x_in + 2 * q + 2 * k + v + attn_o + lse + proj + gate + up
                + down + norm2)
    raise ValueError(f"unknown remat policy {policy!r}")


def _expand_remat(spec, num_layers: int) -> list:
    from ray_tpu.models.llama import normalize_remat

    norm = normalize_remat(spec, num_layers)
    if isinstance(norm, tuple):
        return list(norm)
    return [norm] * num_layers


# Recompute-FLOPs multiplier per policy (vs no remat), used by the search
# ranking: 'attn' re-runs norms + SwiGLU (~18% extra step FLOPs, measured —
# see models/llama.py), 'attn+' halves the MLP recompute, 'dots' only
# re-runs elementwise, 'full' re-runs the whole forward (~1/3 extra).
POLICY_FLOPS_FACTOR = {
    "none": 1.0, False: 1.0, "dots+": 1.02, "dots": 1.05,
    "attn+": 1.11, "attn": 1.18, "full": 1.33, True: 1.33,
}


def remat_flops_factor(spec, num_layers: int) -> float:
    layers = _expand_remat(spec, num_layers)
    return sum(POLICY_FLOPS_FACTOR[p] for p in layers) / len(layers)


@functools.lru_cache(maxsize=16)
def _optimizer_state_bytes(cfg, opt_name: str) -> int:
    """Replicated optimizer-state bytes via eval_shape (nothing allocated).
    Cached per (cfg, opt_name) — LlamaConfig is frozen/hashable, and a
    70-candidate search would otherwise re-trace the same two values
    ~0.7 s worth per round."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import init_params
    from ray_tpu.train.optim import adamw_lowmem, optimizer_state_bytes

    if opt_name == "lowmem":
        opt = adamw_lowmem(3e-4, weight_decay=0.1)
    else:
        opt = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    return optimizer_state_bytes(opt, shapes)


def predict_hbm(cfg, seq: int, cand: Candidate,
                data_shards: int = 1) -> HbmPrediction:
    """Peak-HBM prediction for one candidate on one device.

    ``data_shards``: devices the batch (and, under zero1, the optimizer
    state and weight update) shard over — 1 for the single-chip bench."""
    pb = cfg.jnp_dtype.itemsize
    n_params = cfg.num_params()
    mb = max(1, cand.batch // max(1, cand.grad_accum)) // max(1, data_shards)
    mb = max(1, mb)

    params = n_params * pb
    grads = n_params * pb                       # stacked scan accumulators
    opt_state = _optimizer_state_bytes(cfg, cand.opt)
    if cand.zero1 and data_shards > 1:
        opt_state //= data_shards

    flash = cand.attn == "flash"
    layers = _expand_remat(cand.remat, cfg.num_layers)
    acts = sum(_policy_layer_bytes(p, mb, seq, cfg, flash) for p in layers)
    # embedding output + final norm hidden (full batch lives outside the
    # per-layer checkpoint; under grad_accum only the microbatch slice is
    # in flight)
    embed = 2 * mb * seq * cfg.hidden_size * pb

    from ray_tpu.ops.loss import default_ce_chunk

    # The same resolution order the compiled step uses: explicit candidate
    # knob, else the process-level RTPU_CE_CHUNK override, else 512 — a
    # process override must be priced, not silently modeled at the default.
    chunk = cand.ce_chunk or default_ce_chunk()
    chunk = min(chunk, seq)
    if seq % chunk:
        chunk = seq                              # ops/loss.py fallback
    v = cfg.vocab_size
    # CE backward chunk workspace: recomputed logits + softmax p + dlogits
    # at f32 (~2.5 chunks at f32 after fusion), plus the f32 dhead
    # accumulator and the stacked dx output.
    ce = int(2.5 * mb * chunk * v * 4) + cfg.hidden_size * v * 4 \
        + mb * seq * cfg.hidden_size * 4
    # One layer's remat recompute workspace during the scan backward:
    # re-running the SwiGLU block keeps ~two f32 [mb, seq, inter] buffers
    # in flight for the recompute-heavy policies; the save-everything
    # policies recompute (almost) nothing.
    inter_f32 = mb * seq * cfg.intermediate_size * 4
    layer_tr = {
        "full": 2 * inter_f32, True: 2 * inter_f32, "attn": 2 * inter_f32,
        "attn+": inter_f32, "dots": inter_f32 // 4,
        "dots+": inter_f32 // 4, "none": 0, False: 0,
    }
    layer_transient = max(layer_tr.get(p, inter_f32) for p in layers)

    if cand.grad_accum > 1:
        # scan-carry accumulation: old + new grad trees live across the add
        grads += n_params * pb
    # optimizer update: grads + the updates tree (the f32 moment math fuses
    # into the bf16 state writes and materializes nothing leaf-sized)
    upd = n_params * pb

    # The three backward phases (module docstring) — max, not sum:
    backward_peak = max(
        acts + ce,                       # CE backward, grads not yet alloc'd
        acts + grads + layer_transient,  # layer-scan backward start
        grads + upd,                     # optimizer update, acts dead
    )
    total = params + opt_state + embed + backward_peak
    return HbmPrediction(
        total_bytes=int(total),
        components={
            "params": params, "grads": grads, "opt_state": opt_state,
            "activations": acts, "embed": embed, "ce_transient": ce,
            "layer_transient": layer_transient, "update_transient": upd,
            "backward_peak": backward_peak,
        },
    )
