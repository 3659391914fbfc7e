"""SPMD train-step factory: mesh + sharding rules + optax → one jitted step.

This is the compute heart of the Train layer (the reference's equivalent
surface is torch DDP/FSDP wrapping in train_loop_utils.py:153
prepare_model — here the whole step is a single compiled program and XLA
inserts the gradient/parameter collectives implied by the shardings).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.llama import (
    LlamaConfig,
    init_params,
    loss_fn,
    param_logical_axes,
)
from ray_tpu.parallel.sharding import (
    ShardingRules,
    batch_axes,
    kernel_mesh,
    tree_shardings,
    zero1_shardings,
)
from ray_tpu.util import tracing
from ray_tpu.utils.compile_cache import ensure_compile_cache


@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt_state", "step"], meta_fields=[]
)


def make_train_step(
    mesh: Mesh,
    *,
    loss: Callable,          # loss(params, tokens, targets, kmesh) -> scalar
    init_fn: Callable,       # init_fn(rng_key) -> params pytree
    logical_axes: Any,       # pytree of logical-axis tuples (see sharding.py)
    rules: ShardingRules | None = None,
    optimizer: optax.GradientTransformation | None = None,
    seed: int = 0,
    zero1: bool = False,
    grad_accum: int = 1,
    grad_norm_every: int | None = None,
    dcn_axes: tuple[str, ...] = (),
    dcn_quant: str | None = None,
    dcn_quant_bucket: int | None = None,
) -> tuple[Callable, Callable, Callable]:
    """Model-agnostic SPMD step factory: any pure loss + init + axis table
    becomes one jitted, donated, mesh-sharded train step. ``loss`` receives
    the step's ``KernelMesh`` (None on one device) as its fourth argument and
    hands it to the ops that run Pallas kernels (ops/kernels.py).

    Multi-slice / ZeRO-1 options:

    - ``dcn_axes`` names the mesh axes that cross slice boundaries (DCN).
      When set, the weight update is sharded across the slice's data-parallel
      replicas (arxiv 2004.13336): per-slice gradients are combined
      explicitly — flattened, sliced over the intra-slice (ICI) data axes
      locally, reduced across slices on shard-sized payloads only — the
      optimizer then runs on padded 1-D shards (moments 1/ici_degree HBM
      each) and parameters all-gather back over ICI. Numerically identical
      to the flat path (a pure reordering of the same sums).
    - ``zero1=True`` extends the update sharding over ALL data axes
      (including DCN ones): 1/world_dp optimizer HBM; the cross-slice
      reduction becomes a manual reduce-scatter (destination-chunked
      all-to-all + local sum) and params re-gather through a chained
      DCN→ICI all-gather, both shard-sized. Usable without ``dcn_axes`` too
      (single-slice ZeRO-1 via sharding constraints, leaf-shaped moments).
    - ``dcn_quant`` ("bf16" | "int8") quantizes the cross-slice stage
      (EQuARX-style, arxiv 2506.17615): only int8 values with one f32 scale
      per ``dcn_quant_bucket`` elements (or bf16 casts) cross the slice
      boundary; accumulation happens dequantized in f32. Requires
      ``dcn_axes``; adds a documented ~4e-3 relative gradient error per
      step (loss trajectories drift ~1e-2 on the dryrun proof).
    - ``grad_accum=N`` scans N microbatches of fwd/bwd, accumulating
      gradients in the scan carry and deferring the gradient sync + weight
      update to the boundary — the accumulate-then-use form XLA's all-reduce
      code-motion pass hoists out of the loop, letting DCN collectives
      overlap the next microbatch's compute under the latency-hiding
      scheduler (train/backend.py sets the flags).
    - ``grad_norm_every=N`` computes the grad-norm metric every N steps
      (skipped steps report -1); default from config
      ``train_grad_norm_every``.

    Returns (step_fn, init_state, data_sharder):
    - step_fn(state, tokens, targets) -> (state, metrics), with parameter/
      optimizer shardings from the rule table and batch over (dp, fsdp).
    - data_sharder(host_array) -> global sharded array.
    """
    ensure_compile_cache()
    rules = rules or ShardingRules()
    optimizer = optimizer or optax.adamw(3e-4, weight_decay=0.1,
                                         mu_dtype=jnp.bfloat16)
    from ray_tpu.utils.config import get_config

    if grad_norm_every is None:
        grad_norm_every = get_config().train_grad_norm_every
    grad_norm_every = max(1, int(grad_norm_every))
    grad_accum = max(1, int(grad_accum))

    param_sh = tree_shardings(mesh, logical_axes, rules)
    # Leading-axis-only spec: rank-agnostic (tokens [B,S], images
    # [B,H,W,C], labels [B] all shard their batch dim; trailing dims
    # replicate).
    batch_spec = rules.spec("batch")
    batch_sh = NamedSharding(mesh, batch_spec)

    # -- data-parallel domain split: intra-slice (ICI) vs cross-slice (DCN) -
    dcn_axes = tuple(dcn_axes)
    unknown = [a for a in dcn_axes if a not in mesh.axis_names]
    if unknown:
        raise ValueError(f"dcn_axes {unknown} not in mesh {mesh.axis_names}")
    data_axes = tuple(a for a in batch_axes(rules) if a in mesh.axis_names)
    dcn_data = tuple(a for a in data_axes if a in dcn_axes)
    ici_data = tuple(a for a in data_axes if a not in dcn_axes)
    if dcn_axes and not dcn_data:
        # Without this, a model-axis dcn_axes would silently activate the
        # single-slice ZeRO-1 update sharding instead of hierarchical sync.
        raise ValueError(
            f"dcn_axes {dcn_axes} must name batch (data-parallel) axes; "
            f"the batch shards over {data_axes}")
    if dcn_quant in ("", "none"):  # config-layer spelling of "disabled"
        dcn_quant = None
    if dcn_quant and not dcn_data:
        raise ValueError("dcn_quant requires dcn_axes naming a batch axis")
    if dcn_quant not in (None, "bf16", "int8"):
        raise ValueError(f"unknown dcn_quant {dcn_quant!r}")

    # Which axes the weight update (and optimizer moments) shard over:
    # hierarchical mode keeps the update within the slice; zero1 spreads it
    # over the whole data-parallel world.
    update_axes = (ici_data + dcn_data) if zero1 else \
        (ici_data if dcn_axes else ())
    wsc = jax.lax.with_sharding_constraint
    repl = NamedSharding(mesh, P())

    # Multi-slice meshes get the EXPLICIT hierarchical sync + a flat-space
    # sharded update: we own the gradient combine instead of leaving it to
    # sharding propagation (the partitioner, asked to produce dcn-sharded
    # grads straight out of the backward, is free to gather batch-sharded
    # activations across slices — measured catastrophically worse on the CE
    # head), and the update runs on padded 1-D views so shard layouts never
    # fight the leaf shapes.
    explicit_hier = bool(dcn_data) and bool(update_axes or dcn_quant)
    flat_update = explicit_hier and bool(update_axes)
    # The hierarchical path vmaps the loss over the slice dim, which carries
    # the DCN axes; inside it the batch splits over the ICI axes only.
    kmesh = kernel_mesh(mesh, rules,
                        batch=ici_data if explicit_hier else data_axes)
    loss = partial(loss, kmesh=kmesh)

    bucket = int(dcn_quant_bucket or
                 get_config().collective_dcn_quant_bucket)
    dcn_n = math.prod(mesh.shape[a] for a in dcn_data) if dcn_data else 1
    ici_n = math.prod(mesh.shape[a] for a in ici_data) if ici_data else 1
    dcn_lead = (dcn_data if len(dcn_data) > 1 else
                (dcn_data[0] if dcn_data else None))
    ici_lead = (ici_data if len(ici_data) > 1 else
                (ici_data[0] if ici_data else None))

    if explicit_hier:
        # Moments follow param sharding when the update isn't dp-sharded
        # (dcn_quant without zero1): init_state reads this when opt_sh
        # stays None below.
        ici_sh = param_sh
        opt_sh = None
        if flat_update:
            shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(seed))
            # Every flat view pads to `unit` so slice chunks and int8
            # buckets stay whole per shard.
            unit = dcn_n * ici_n * (bucket if dcn_quant == "int8" else 1)
            pad_to = lambda n: n + (-n) % unit  # noqa: E731
            if zero1:
                upd_flat_spec = P(tuple(dcn_data) + tuple(ici_data))
            else:
                upd_flat_spec = P(ici_lead)
            upd_flat_sh = NamedSharding(mesh, upd_flat_spec)
            flat_shapes = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct((pad_to(max(l.size, 1)),),
                                               l.dtype), shapes)
            opt_sh = _opt_shardings(
                optimizer, flat_shapes,
                jax.tree.map(lambda _: upd_flat_sh, flat_shapes))
            opt_sh = jax.tree.map(lambda s: s if s is not None else repl,
                                  opt_sh, is_leaf=lambda x: x is None)
    elif update_axes:
        # Single-slice ZeRO-1 (explicit_hier is False, so dcn_data is empty
        # and update_axes == ici_data): sharding-constraint lowering is safe
        # (every collective is ICI) and keeps leaf-shaped moments.
        shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(seed))
        ici_sh = zero1_shardings(mesh, shapes, param_sh, ici_data,
                                 logical_axes=logical_axes)
        opt_sh = _opt_shardings(optimizer, shapes, ici_sh)
        opt_sh = jax.tree.map(lambda s: s if s is not None else repl,
                              opt_sh, is_leaf=lambda x: x is None)
    else:
        ici_sh = param_sh
        opt_sh = None

    def _flatten_params(params):
        """Padded 1-D views, sharded like the update (a local slice of the
        replicated/model-sharded params)."""
        def one(p):
            f = p.reshape(-1)
            pad = pad_to(f.size) - f.size
            if pad:
                f = jnp.pad(f, (0, pad))
            return wsc(f, upd_flat_sh)
        return jax.tree.map(one, params)

    def init_state() -> TrainState:
        params = jax.jit(init_fn, out_shardings=param_sh)(
            jax.random.PRNGKey(seed))
        if flat_update:
            opt_state = jax.jit(
                lambda p: optimizer.init(_flatten_params(p)),
                out_shardings=opt_sh)(params)
        else:
            opt_state = jax.jit(
                optimizer.init,
                out_shardings=opt_sh if opt_sh is not None else
                _opt_shardings(optimizer, params, ici_sh),
            )(params)
        return TrainState(params=params, opt_state=opt_state,
                          step=jnp.zeros((), jnp.int32))

    def _microbatch_spec(ndim: int) -> NamedSharding:
        """[scan, micro_batch, ...]: scan dim replicated, the per-microbatch
        batch dim over the batch axes."""
        mb = (data_axes if len(data_axes) > 1 else
              (data_axes[0] if data_axes else None))
        return NamedSharding(mesh, P(None, mb, *([None] * (ndim - 2))))

    def _grads_flat(params, tokens, targets):
        loss_val, grads = jax.value_and_grad(loss)(params, tokens, targets)
        return loss_val, grads

    def _scan_microbatches(params, tok, tgt):
        """tok/tgt: [grad_accum, mb, ...] — scan fwd/bwd over microbatches,
        mean of losses and grads; the gradient sync is deferred to the
        boundary (the carry accumulates unconsumed grads). The single
        accumulation body both the dcn and non-dcn paths use, so their
        averaging cannot diverge."""
        if grad_accum == 1:
            return jax.value_and_grad(loss)(params, tok[0], tgt[0])

        def body(carry, mb):
            l_acc, g_acc = carry
            lv, g = jax.value_and_grad(loss)(params, *mb)
            with tracing.part("optim"):
                return (l_acc + lv, jax.tree.map(jnp.add, g_acc, g)), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (l_sum, g_sum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), (tok, tgt))
        inv = 1.0 / grad_accum
        with tracing.part("optim"):
            return l_sum * inv, jax.tree.map(lambda g: g * inv, g_sum)

    def _grads_accum(params, tokens, targets):
        b = tokens.shape[0]
        if b % grad_accum:
            raise ValueError(
                f"batch {b} not divisible by grad_accum={grad_accum}")

        def split(x):
            xm = x.reshape(grad_accum, b // grad_accum, *x.shape[1:])
            return wsc(xm, _microbatch_spec(xm.ndim))

        return _scan_microbatches(params, split(tokens), split(targets))

    def _grads_hier(params, tokens, targets):
        """Per-slice gradients (vmap over an explicit slice dim, so only
        intra-slice reductions happen inside; with grad_accum an inner scan
        defers everything to the boundary), then one explicit cross-slice
        combine per leaf: flatten (free), shard the flat payload over the
        intra-slice data axes (local), and reduce over the slice dim — the
        ONLY DCN traffic is that shard-sized reduction, optionally in the
        quantized wire format (int8 values + per-bucket f32 scales, or
        bf16)."""
        from ray_tpu.collective.xla_backend import (
            dequantize_int8_buckets,
            quantize_int8_bucketed,
        )

        n_slices = dcn_n
        b = tokens.shape[0]
        if b % (n_slices * grad_accum):
            raise ValueError(
                f"batch {b} not divisible by {n_slices} slices x "
                f"grad_accum={grad_accum}")

        def split(x):
            xs = x.reshape(n_slices, grad_accum,
                           b // (n_slices * grad_accum), *x.shape[1:])
            spec = P(dcn_lead, None,
                     (tuple(ici_data) if len(ici_data) > 1 else ici_lead),
                     *([None] * (xs.ndim - 3)))
            return wsc(xs, NamedSharding(mesh, spec))

        # per-slice: [grad_accum, mb, ...] through the shared scan body
        lv, g_slice = jax.vmap(_scan_microbatches, in_axes=(None, 0, 0),
                               spmd_axis_name=dcn_lead)(
            params, split(tokens), split(targets))

        slice_rows = NamedSharding(mesh, P(dcn_lead))
        stage_a = NamedSharding(mesh, P(dcn_lead, ici_lead))
        stage_a3 = NamedSharding(mesh, P(dcn_lead, ici_lead, None))
        gathered2 = NamedSharding(mesh, P(None, ici_lead))
        gathered3 = NamedSharding(mesh, P(None, ici_lead, None))
        # Destination-chunked views for the manual reduce-scatter: dim0 =
        # source slice OR destination chunk (both over the DCN axis), the
        # payload dims ici-sharded. Swapping dim0<->dim1 between two pins of
        # this layout IS the cross-slice all-to-all, on shard-sized pieces.
        chunk3 = NamedSharding(mesh, P(dcn_lead, None, ici_lead))
        chunk4 = NamedSharding(mesh, P(dcn_lead, None, ici_lead, None))
        ici_flat = NamedSharding(mesh, P(ici_lead))

        def combine(gs):  # gs: [n_slices, *leaf.shape]
            dt, orig, shape1 = gs.dtype, gs[0].size, gs.shape[1:]
            flat = wsc(gs.reshape(n_slices, -1), slice_rows)
            pad = pad_to(orig) - orig if flat_update else (-orig) % \
                (ici_n * (bucket if dcn_quant == "int8" else 1))
            if pad:
                flat = jnp.pad(flat, ((0, 0), (0, pad)))
            npad = orig + pad
            # Shard the payload over the intra-slice data axes: a pure local
            # slice (the reduce-scatter half of the hierarchy, for free).
            flat = wsc(flat, stage_a)
            if dcn_quant == "int8":
                fb = wsc(flat.reshape(n_slices, -1, bucket), stage_a3)
                q, sc = quantize_int8_bucketed(fb)
                # Pin sharded, move, then pin the destination layout: the
                # collective XLA inserts between the pins is forced onto the
                # int8 / f32-scale wire format.
                q, sc = wsc(q, stage_a3), wsc(sc, stage_a3)
                if zero1:
                    nb = npad // bucket
                    qc = wsc(q.reshape(n_slices, n_slices, nb // n_slices,
                                       bucket), chunk4)
                    scc = wsc(sc.reshape(n_slices, n_slices,
                                         nb // n_slices, 1), chunk4)
                    qt = wsc(jnp.swapaxes(qc, 0, 1), chunk4)
                    sct = wsc(jnp.swapaxes(scc, 0, 1), chunk4)
                    g = jnp.sum(dequantize_int8_buckets(qt, sct), axis=1)
                else:
                    q, sc = wsc(q, gathered3), wsc(sc, gathered3)
                    g = jnp.sum(dequantize_int8_buckets(q, sc), axis=0)
            elif dcn_quant == "bf16":
                x16 = wsc(flat.astype(jnp.bfloat16), stage_a)
                if zero1:
                    c = wsc(x16.reshape(n_slices, n_slices,
                                        npad // n_slices), chunk3)
                    t = wsc(jnp.swapaxes(c, 0, 1), chunk3)
                    g = jnp.sum(t.astype(jnp.float32), axis=1)
                else:
                    # Gather the bf16 rows (the DCN hop), THEN cast: summing
                    # in f32 after the move keeps the documented f32
                    # accumulation without widening the wire format.
                    x16 = wsc(x16, gathered2)
                    g = jnp.sum(x16.astype(jnp.float32), axis=0)
            else:
                if zero1:
                    # Manual reduce-scatter: destination-chunk, all-to-all
                    # over DCN (shard-sized), local sum over source slices.
                    c = wsc(flat.reshape(n_slices, n_slices,
                                         npad // n_slices), chunk3)
                    t = wsc(jnp.swapaxes(c, 0, 1), chunk3)
                    g = jnp.sum(t, axis=1)
                else:
                    # Sum over the slice-sharded dim: local row + psum over
                    # DCN on the ici-shard-sized payload.
                    g = jnp.sum(flat, axis=0)
            if flat_update:
                # [npad] flat, dcn-chunk-major then ici — the update's
                # 1-D shard layout.
                return (wsc(g.reshape(-1), upd_flat_sh) / n_slices).astype(dt)
            g = wsc(g.reshape(-1), ici_flat)
            g = g[:orig].reshape(shape1)
            return (g / n_slices).astype(dt)

        with tracing.part("optim"):
            grads = jax.tree.map(combine, g_slice)
            return jnp.mean(lv), grads

    def _unflatten_params(flats, params_like):
        """Inverse of :func:`_flatten_params` for the post-update params:
        gather the DCN chunks first (shard-sized), then let the final
        model-sharding pin all-gather over ICI."""
        def one(f, ref, psh):
            if zero1 and dcn_data:
                f2 = wsc(f.reshape(dcn_n, -1),
                         NamedSharding(mesh, P(dcn_lead, ici_lead)))
                f2 = wsc(f2, NamedSharding(mesh, P(None, ici_lead)))
                f = f2.reshape(-1)
            p = f[:ref.size].reshape(ref.shape)
            return wsc(p, psh)
        return jax.tree.map(one, flats, params_like, param_sh)

    def _step(state: TrainState, tokens, targets):
        params_in = state.params
        if update_axes:
            # Pin the model's view of the params: they are shared between
            # the forward/backward and apply_updates, and without the pin
            # the partitioner propagates the dcn-sharded UPDATE layout
            # backward through `params + updates` into every matmul of the
            # model — measured as cross-slice all-gathers of activations.
            params_in = jax.tree.map(lambda p, s: wsc(p, s), params_in,
                                     param_sh)
        if explicit_hier:
            loss_val, grads = _grads_hier(params_in, tokens, targets)
        elif grad_accum > 1:
            loss_val, grads = _grads_accum(params_in, tokens, targets)
        else:
            loss_val, grads = _grads_flat(params_in, tokens, targets)

        with tracing.part("optim"):
            if grad_norm_every > 1:
                gnorm = jax.lax.cond(
                    state.step % grad_norm_every == 0,
                    lambda g: optax.global_norm(g).astype(jnp.float32),
                    lambda g: jnp.float32(-1.0), grads)
            else:
                gnorm = optax.global_norm(grads)

            if flat_update:
                # Sharded flat-space update: grads arrived as padded 1-D
                # shards; moments and the adamw math stay 1/N per device,
                # then the params gather back through the DCN→ICI chain.
                p_flat = _flatten_params(params_in)
                updates, opt_state = optimizer.update(grads, state.opt_state,
                                                      p_flat)
                new_flat = optax.apply_updates(p_flat, updates)
                params = _unflatten_params(new_flat, params_in)
            else:
                if update_axes and not explicit_hier:
                    # The update-sharding constraint lowers the gradient
                    # sync to reduce-scatter over ICI (single-slice here —
                    # dcn_data is empty whenever this branch runs).
                    grads = jax.tree.map(lambda g, s: wsc(g, s), grads,
                                         ici_sh)
                updates, opt_state = optimizer.update(grads, state.opt_state,
                                                      params_in)
                params = optax.apply_updates(params_in, updates)
                if update_axes and not explicit_hier:
                    params = jax.tree.map(lambda p, s: wsc(p, s), params,
                                          param_sh)
            step = state.step + 1
        return (
            TrainState(params=params, opt_state=opt_state, step=step),
            {"loss": loss_val, "grad_norm": gnorm},
        )

    out_shardings = None
    if opt_sh is not None:
        out_shardings = (
            TrainState(params=param_sh, opt_state=opt_sh, step=repl),
            {"loss": repl, "grad_norm": repl},
        )

    step_fn = jax.jit(
        _step,
        in_shardings=(None, batch_sh, batch_sh),
        out_shardings=out_shardings,
        donate_argnums=(0,),
    )

    def data_sharder(arr):
        return jax.device_put(arr, batch_sh)

    return step_fn, init_state, data_sharder


def replica_payload(state: TrainState) -> dict:
    """What :func:`ray_tpu.train.replicate` should push for a ZeRO-1 /
    sharded-update train state: a host snapshot of the shards THIS process
    holds. Under ``zero1=True`` the optimizer moments are already 1/world_dp
    per device and the params re-gather every step anyway, so the replica
    of a slice's state is exactly the shard-sized payload the DCN
    all-gather already moves — replication costs one extra buddy-slice hop
    of the same bytes, not a second full-state transfer. Single-process
    (test) meshes degrade to a plain host copy of the full state.

    The payload restores via ``ctx.get_replica_state()``: step counter,
    params, and opt_state as numpy trees (or ``(index, shard)`` lists for
    partially addressable leaves), ready for ``jax.device_put`` against the
    run's shardings."""
    from ray_tpu.train.replica import host_snapshot

    return {
        "step": int(jax.device_get(state.step)),
        "params": host_snapshot(state.params),
        "opt_state": host_snapshot(state.opt_state),
    }


def make_llama_train_step(
    cfg: LlamaConfig,
    mesh: Mesh,
    rules: ShardingRules | None = None,
    optimizer: optax.GradientTransformation | None = None,
    attn_impl: str = "flash",
    remat: bool | str | tuple = True,
    seed: int = 0,
    **step_options,
) -> tuple[Callable, Callable, Callable]:
    """Llama-family specialization of :func:`make_train_step`.
    ``step_options`` forwards the multi-slice/ZeRO-1 knobs (``zero1``,
    ``grad_accum``, ``grad_norm_every``, ``dcn_axes``, ``dcn_quant``).
    ``remat`` accepts a single policy or a per-layer save-list spec
    (tuple / "pol:N,pol:N" string — models/llama.normalize_remat)."""
    return make_train_step(
        mesh,
        loss=lambda p, tokens, targets, kmesh: loss_fn(
            cfg, p, tokens, targets, attn_impl=attn_impl, remat=remat,
            kmesh=kmesh),
        init_fn=partial(init_params, cfg),
        logical_axes=param_logical_axes(cfg),
        rules=rules, optimizer=optimizer, seed=seed, **step_options,
    )


def make_mixtral_train_step(
    cfg,
    mesh: Mesh,
    rules: ShardingRules | None = None,
    optimizer: optax.GradientTransformation | None = None,
    attn_impl: str = "flash",
    remat: bool = True,
    seed: int = 0,
    **step_options,
) -> tuple[Callable, Callable, Callable]:
    """MoE specialization: expert weights shard over the mesh ``ep`` axis.
    Through the layers every ``ep`` chip holds every token of its dp/fsdp
    shard and a layer's output is one all-reduce of [T, H] over ``ep``; the
    head and the loss split those tokens over ``ep`` (models/mixtral.py)."""
    from ray_tpu.models import mixtral

    return make_train_step(
        mesh,
        loss=lambda p, tokens, targets, kmesh: mixtral.loss_fn(
            cfg, p, tokens, targets, attn_impl=attn_impl, remat=remat,
            kmesh=kmesh),
        init_fn=partial(mixtral.init_params, cfg),
        logical_axes=mixtral.param_logical_axes(cfg),
        rules=rules, optimizer=optimizer, seed=seed, **step_options,
    )


def make_olmo_hybrid_train_step(
    cfg,
    mesh: Mesh,
    rules: ShardingRules | None = None,
    optimizer: optax.GradientTransformation | None = None,
    attn_impl: str = "flash",
    remat: bool | str = True,
    seed: int = 0,
    **step_options,
) -> tuple[Callable, Callable, Callable]:
    """Olmo-Hybrid specialization: a model with a state made by a scan over
    the sequence (Gated DeltaNet layers beside a full attention,
    models/olmo_hybrid.py); the rule's backward is ops/gated_delta.py's."""
    from ray_tpu.models import olmo_hybrid

    return make_train_step(
        mesh,
        loss=lambda p, tokens, targets, kmesh: olmo_hybrid.loss_fn(
            cfg, p, tokens, targets, attn_impl=attn_impl, remat=remat,
            kmesh=kmesh),
        init_fn=partial(olmo_hybrid.init_params, cfg),
        logical_axes=olmo_hybrid.param_logical_axes(cfg),
        rules=rules, optimizer=optimizer, seed=seed, **step_options,
    )


def make_vit_train_step(
    cfg,
    mesh: Mesh,
    rules: ShardingRules | None = None,
    optimizer: optax.GradientTransformation | None = None,
    attn_impl: str = "flash",
    remat: bool | str = False,
    seed: int = 0,
    **step_options,
) -> tuple[Callable, Callable, Callable]:
    """ViT specialization: batch shards over (dp, fsdp) on the leading
    image axis, attention heads / MLP over tp — identical machinery to
    the llama step (models/vit.py holds the model)."""
    from ray_tpu.models import vit

    return make_train_step(
        mesh,
        loss=lambda p, images, labels, kmesh: vit.loss_fn(
            cfg, p, images, labels, attn_impl=attn_impl, remat=remat,
            kmesh=kmesh),
        init_fn=partial(vit.init_params, cfg),
        logical_axes=vit.param_logical_axes(cfg),
        rules=rules, optimizer=optimizer, seed=seed, **step_options,
    )


def _opt_shardings(optimizer, params, param_sh):
    """Optimizer-state shardings mirror their matching param leaves (ZeRO-
    style: Adam moments shard exactly like the params they track).

    Matching is by key path, not shape: moment pytrees (mu/nu) embed the
    param tree verbatim, so a state leaf's path ends with its param's path.
    (Shape matching would silently give two same-shaped params with
    different logical axes the first param's sharding.) Scalars and
    unmatched leaves replicate."""
    shapes = jax.eval_shape(optimizer.init, params)
    p_leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    s_leaves = jax.tree_util.tree_flatten_with_path(param_sh)[0]
    by_path = {
        tuple(map(str, path)): (leaf.shape, sh)
        for (path, leaf), (_, sh) in zip(p_leaves, s_leaves)
    }

    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        spath = tuple(map(str, path))
        sharding = None
        for n in range(len(spath), 0, -1):  # longest param-path suffix wins
            hit = by_path.get(spath[-n:])
            if hit is not None:
                pshape, sh = hit
                if pshape == leaf.shape:
                    sharding = sh
                break
        out.append(sharding)
    return jax.tree_util.tree_unflatten(jax.tree.structure(shapes), out)
