"""Pipeline parallelism: GPipe-style microbatching over the ``pp`` mesh axis.

TPU-native design (SURVEY.md §2.4 PP row — the reference delegates PP to
vLLM's ``pipeline_parallel_size``, vllm_models.py:230, with stages as
separate worker processes over NCCL p2p): here the WHOLE pipeline is one
compiled SPMD program. Layer parameters are sharded over ``pp`` on their
stacked-layer axis, so each mesh slice holds its stage's layers; a
``lax.scan`` steps the GPipe schedule and hands activations to the next
stage with ``lax.ppermute`` over ICI. Autodiff through the scan + ppermute
yields the reverse pipeline schedule for the backward pass — no hand-written
stage actors, no p2p runtime.

Schedule: M microbatches, P stages, M + P - 1 ticks. At tick t, stage k
processes microbatch t - k (garbage flows through the bubble ticks and is
masked out of the loss). Loss is computed on the last stage and psum'd.

The complementary MPMD form — each stage its own actor with its own jitted
programs, activations over compiled-graph channels, for models too big for
one slice/program — is ``ray_tpu/dag/mpmd.py``; tests/test_mpmd.py pins the
two to loss parity on identical batches.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.llama import (
    LlamaConfig,
    _layer,
    init_params,
    rms_norm,
    rope_frequencies,
)
from ray_tpu.train.spmd import TrainState, _opt_shardings


def pp_param_shardings(cfg: LlamaConfig, mesh: Mesh) -> dict:
    """Layers shard over pp on the stacked-L axis; embeddings/norms
    replicate (stage 0 / last stage use them; grads psum over pp)."""
    layer_spec = NamedSharding(mesh, P("pp"))
    repl = NamedSharding(mesh, P())
    sh = {
        "embed_tokens": repl,
        "final_norm": repl,
        "layers": {k: layer_spec for k in
                   ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                    "attn_norm", "mlp_norm")},
    }
    if not cfg.tie_embeddings:
        sh["lm_head"] = repl
    return sh


def make_pp_train_step(
    cfg: LlamaConfig,
    mesh: Mesh,
    num_microbatches: int,
    optimizer: optax.GradientTransformation | None = None,
    attn_impl: str = "blockwise",
    seed: int = 0,
) -> tuple[Callable, Callable, Callable]:
    """Pipeline-parallel train-step factory. The mesh must have a ``pp``
    axis (>1) and may combine ``dp`` (batch shards run identical pipelines,
    grads allreduce over dp). Returns (step_fn, init_state, data_sharder)
    matching make_train_step's contract."""
    pp = mesh.shape["pp"]
    dp = mesh.shape.get("dp", 1)
    M = num_microbatches
    assert cfg.num_layers % pp == 0, "num_layers must divide pp"
    optimizer = optimizer or optax.adamw(3e-4, weight_decay=0.1)

    param_sh = pp_param_shardings(cfg, mesh)
    batch_sh = NamedSharding(mesh, P("dp"))
    layer_spec = P("pp")
    repl = P()

    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)

    def stage_loss(embed, final_norm, lm_head, local_layers, tokens, targets):
        """Runs inside shard_map over (pp, dp). tokens/targets: [B_local, S]
        (dp shard, replicated over pp). local_layers: this stage's [L/pp,…]
        slice. Returns (nll_sum, count) — psum'd by the caller."""
        b, s = tokens.shape
        assert b % M == 0, "local batch must divide num_microbatches"
        mb = b // M
        rank = lax.axis_index("pp")
        tok_m = tokens.reshape(M, mb, s)
        tgt_m = targets.reshape(M, mb, s)
        positions = jnp.arange(s)

        head = embed.T if cfg.tie_embeddings else lm_head

        def run_stage(x):
            def body(x, lp):
                return _layer(cfg, x, lp, inv_freq, positions,
                              attn_impl, None), None
            out, _ = lax.scan(body, x, local_layers)
            return out

        fwd_perm = [(i, i + 1) for i in range(pp - 1)]

        def tick(carry, t):
            x_in, nll_sum, cnt = carry
            # Stage 0 injects microbatch t (clamped during drain ticks).
            inject = embed[tok_m[jnp.minimum(t, M - 1)]]
            x = jnp.where(rank == 0, inject, x_in)
            x = run_stage(x)
            # Last stage: microbatch t - (pp-1) finished — take its loss.
            mb_idx = t - (pp - 1)
            valid = (rank == pp - 1) & (mb_idx >= 0) & (mb_idx < M)
            tgt = tgt_m[jnp.clip(mb_idx, 0, M - 1)]
            xn = rms_norm(x, final_norm, cfg.norm_eps)
            logits = jnp.einsum("bsh,hv->bsv", xn, head,
                                preferred_element_type=jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
            w = jnp.where(valid, 1.0, 0.0)
            nll_sum = nll_sum + nll.sum() * w
            cnt = cnt + nll.size * w
            # Hand activations to the next stage for the next tick.
            x_next = lax.ppermute(x, "pp", fwd_perm)
            return (x_next, nll_sum, cnt), None

        x0 = jnp.zeros((mb, s, cfg.hidden_size), embed.dtype)
        (_, nll_sum, cnt), _ = lax.scan(
            tick, (x0, jnp.float32(0.0), jnp.float32(0.0)),
            jnp.arange(M + pp - 1))
        return nll_sum, cnt

    def local_loss_and_grads(params, tokens, targets):
        """shard_map body: returns (loss, grads) with explicit reductions —
        layer grads are stage-local (pp-sharded), shared-param grads psum
        over pp; everything psums over dp."""
        lm_head = params.get("lm_head")
        # Static global token count: normalize LOCALLY inside the grad. A
        # psum inside the differentiated function would double-count —
        # psum's transpose is psum, so each device's cotangent would be
        # scaled by the axis size (grads came out exactly pp× too large).
        total_tokens = tokens.size * dp

        def scalar_loss(p):
            nll, _cnt = stage_loss(
                p["embed_tokens"], p["final_norm"], p.get("lm_head"),
                p["layers"], tokens, targets)
            return nll / total_tokens  # this device's share of the mean

        loss_local, grads = jax.value_and_grad(scalar_loss)(params)
        loss = lax.psum(loss_local, ("pp", "dp"))  # reporting only
        # Reductions the scalar psum does not imply for param cotangents
        # under check_vma=False: shared (replicated) params are used
        # divergently per stage, so their grads must sum across pp; every
        # grad sums across dp (data parallel).
        def reduce_grad(path_is_layer, g):
            axes = ("dp",) if path_is_layer else ("dp", "pp")
            return lax.psum(g, axes)

        grads = {
            "embed_tokens": reduce_grad(False, grads["embed_tokens"]),
            "final_norm": reduce_grad(False, grads["final_norm"]),
            "layers": {k: reduce_grad(True, v)
                       for k, v in grads["layers"].items()},
            **({"lm_head": reduce_grad(False, grads["lm_head"])}
               if lm_head is not None else {}),
        }
        return loss, grads

    param_specs = {
        "embed_tokens": repl,
        "final_norm": repl,
        "layers": {k: layer_spec for k in
                   ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                    "attn_norm", "mlp_norm")},
    }
    if not cfg.tie_embeddings:
        param_specs["lm_head"] = repl
    grad_specs = param_specs  # same placement as params

    sharded_lg = shard_map(
        local_loss_and_grads, mesh=mesh,
        in_specs=(param_specs, P("dp"), P("dp")),
        out_specs=(repl, grad_specs),
        check_vma=False,
    )

    def _step(state: TrainState, tokens, targets):
        loss, grads = sharded_lg(state.params, tokens, targets)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        return (
            TrainState(params=params, opt_state=opt_state,
                       step=state.step + 1),
            {"loss": loss, "grad_norm": optax.global_norm(grads)},
        )

    step_fn = jax.jit(_step, in_shardings=(None, batch_sh, batch_sh),
                      donate_argnums=(0,))

    def init_state() -> TrainState:
        params = jax.jit(partial(init_params, cfg),
                         out_shardings=param_sh)(jax.random.PRNGKey(seed))
        opt_state = jax.jit(
            optimizer.init,
            out_shardings=_opt_shardings(optimizer, params, param_sh),
        )(params)
        return TrainState(params=params, opt_state=opt_state,
                          step=jnp.zeros((), jnp.int32))

    def data_sharder(arr):
        return jax.device_put(arr, batch_sh)

    return step_fn, init_state, data_sharder
