"""Mixtral's routed layer alone, and its head and loss alone, forward and
backward under ``ep=4`` at the shapes of ``mixtral8x7b-train-4chip`` (16,384
tokens, 8 experts of 14,336, capacity 5,120, a vocabulary of 32,000), and
the whole train step compiled for the same host.

    chiprun --chips 4 -- python3 devbench/mixtral_moe_bench.py layer head stats
    python3 devbench/mixtral_moe_bench.py aot-layer aot-step     # no chip

``layer``: wall milliseconds of one ``value_and_grad`` of ``moe_block`` (the
clock stops on ``block_until_ready``), then the ten largest device operations
and the collectives' seconds of a traced span of TRACED calls; every device
operation of that span goes to ``chiprun_out/moe_layer_ops.json``. ``head``:
the same of final norm -> ``lm_head`` -> token losses, once with every chip
on all 16,384 tokens and once with each on its quarter of every sequence
(``mixtral._head_spec``'s layout: the head's gradient summed over ``ep``, the
cotangent gathered back); the difference is what a step can save. ``stats``:
``mixtral.routing_stats`` on the cell's probe batch. ``aot-layer``
and ``aot-step`` compile the same program, and the cell's whole step, for a
described ``v5e:2x2`` (nothing runs: no time comes out of them) and print the
collectives by payload, the largest arrays named in the text, XLA's cost
analysis and ``memory_analysis``.

The script reads ``moe_block`` and ``make_mixtral_train_step`` only, so the
same file runs against an older tree laid beside it (``git archive`` into
``.parent/``, copy this file in, run that copy). One JSON object a mode.
"""

from __future__ import annotations

import collections
import inspect
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

BATCH, SEQ = 4, 4096
WIDTHS = dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
              num_heads=32, num_kv_heads=8, head_dim=128, max_seq_len=SEQ,
              rope_theta=1e6, num_experts=8, top_k=2, capacity_factor=1.25,
              dtype="bfloat16")
LAYERS = 2          # the cell's cut
TIMED, TRACED = 10, 3
STATS_SEEDS = (1, 3300000101)
LAYER_KEYS = ("router", "we_gate", "we_up", "we_down")


def _layer_program(cfg, mesh):
    """(jitted value_and_grad of one routed layer, shapes of x and of the
    layer's weights with their shardings on ``mesh``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import mixtral
    from ray_tpu.parallel.sharding import kernel_mesh, tree_shardings

    # The parent's moe_block takes no mesh.
    kwargs = {}
    if "kmesh" in inspect.signature(mixtral.moe_block).parameters:
        kwargs["kmesh"] = kernel_mesh(mesh)
    axes = mixtral.param_logical_axes(cfg)["layers"]
    shapes = jax.eval_shape(lambda: mixtral.init_params(
        cfg, jax.random.PRNGKey(0)))["layers"]
    sh = tree_shardings(mesh, {k: axes[k][1:] for k in LAYER_KEYS})
    lp = {k: jax.ShapeDtypeStruct(shapes[k].shape[1:], shapes[k].dtype,
                                  sharding=sh[k]) for k in LAYER_KEYS}
    x = jax.ShapeDtypeStruct((BATCH, SEQ, cfg.hidden_size), cfg.jnp_dtype,
                             sharding=NamedSharding(mesh, P(("dp", "fsdp"))))

    def loss(x, lp):
        y, aux = mixtral.moe_block(cfg, x, lp, **kwargs)
        # A cotangent that differs from row to row, as a real one does.
        ct = jnp.cos(jnp.arange(y.shape[1], dtype=jnp.float32))[None, :, None]
        return (y.astype(jnp.float32) * ct).mean() + aux

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))), x, lp


def _cfg(layers=LAYERS):
    from ray_tpu.models.mixtral import MixtralConfig

    return MixtralConfig(num_layers=layers, **WIDTHS)


def layer() -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = _cfg(1)
    mesh = build_mesh(MeshSpec(ep=4), jax.devices()[:4])
    step, x_s, lp_s = _layer_program(cfg, mesh)
    keys = jax.random.split(jax.random.PRNGKey(0), 1 + len(LAYER_KEYS))
    x = jax.jit(lambda k: jax.random.normal(k, x_s.shape, x_s.dtype),
                out_shardings=x_s.sharding)(keys[0])
    lp = {name: jax.jit(
        lambda k, s=lp_s[name]: (jax.random.normal(k, s.shape, jnp.float32)
                                 * 0.02).astype(s.dtype),
        out_shardings=lp_s[name].sharding)(k)
        for name, k in zip(LAYER_KEYS, keys[1:])}
    return {"mode": "layer", "device": jax.devices()[0].device_kind,
            "tokens": BATCH * SEQ, "capacity": cfg.capacity(BATCH * SEQ),
            **_time_and_trace(step, (x, lp), "moe_layer")}


def _time_and_trace(step, args, name: str) -> dict:
    """Wall ms a call of ``step(*args)`` over TIMED calls, then a traced span
    of TRACED calls: busy and collective seconds, the ten largest device
    operations, and all of them in ``chiprun_out/<name>_ops.json``."""
    import jax

    from rtbench import trace_reduce

    t0 = time.monotonic()
    jax.block_until_ready(step(*args))
    compile_s = time.monotonic() - t0
    jax.block_until_ready(step(*args))
    t0 = time.monotonic()
    for _ in range(TIMED):
        out = step(*args)
    jax.block_until_ready(out)
    wall_ms = (time.monotonic() - t0) / TIMED * 1e3

    trace_dir = os.path.join(ROOT, ".bench_trace", name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for _ in range(TRACED):
        out = step(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    coll_s, exposed_s = trace.collective_seconds()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"{name}_ops.json"),
              "w") as f:
        json.dump(trace.top_device_ops(10 ** 6), f)
    return {"first_call_s": round(compile_s, 2),
            "fwd_bwd_wall_ms": round(wall_ms, 3), "loss": float(out[0]),
            "traced_calls": TRACED, "busy_s": round(trace.busy_s(), 4),
            "collective_s": round(coll_s, 4),
            "collective_exposed_s": round(exposed_s, 4),
            "device_ops": trace.top_device_ops(10)}


def _head_program(cfg, mesh, split: bool):
    """Jitted value_and_grad of the last part of ``mixtral.loss_fn`` (final
    norm, ``lm_head``, mean token loss) over x [B, S, H] as the layers leave
    it, every token on every ``ep`` chip. ``split``: each chip takes its
    quarter of every sequence after the norm, as ``mixtral._head_spec`` lays
    it; the gradients come back whole either way."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import mixtral
    from ray_tpu.ops.norms import rms_norm
    from ray_tpu.parallel.sharding import kernel_mesh

    kmesh = kernel_mesh(mesh)
    rows = NamedSharding(mesh, kmesh.rows_spec(1))
    over_ep = NamedSharding(mesh, mixtral._head_spec(kmesh, BATCH, SEQ))
    repl = NamedSharding(mesh, P())

    def loss(x, norm_w, head_w, targets):
        x = rms_norm(x, norm_w, cfg.norm_eps, kmesh)
        if split:
            x = jax.lax.with_sharding_constraint(x, over_ep)
            targets = jax.lax.with_sharding_constraint(targets, over_ep)
        logits = jnp.einsum("bsh,hv->bsv", x, head_w,
                            preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                   out_shardings=(repl, (rows, repl, repl))), rows, repl


def head() -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = _cfg(1)
    mesh = build_mesh(MeshSpec(ep=4), jax.devices()[:4])
    out = {"mode": "head", "device": jax.devices()[0].device_kind,
           "tokens": BATCH * SEQ}
    programs = {name: _head_program(cfg, mesh, split) for name, split in
                (("replicated", False), ("split_over_ep", True))}
    _, rows, repl = programs["replicated"]
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    dt = cfg.jnp_dtype
    args = (
        jax.jit(lambda k: jax.random.normal(
            k, (BATCH, SEQ, cfg.hidden_size), dt), out_shardings=rows)(kx),
        jax.device_put(jnp.ones((cfg.hidden_size,), dt), repl),
        jax.jit(lambda k: (jax.random.normal(
            k, (cfg.hidden_size, cfg.vocab_size), jnp.float32)
            * cfg.hidden_size ** -0.5).astype(dt), out_shardings=repl)(kw),
        jax.jit(lambda k: jax.random.randint(
            k, (BATCH, SEQ), 0, cfg.vocab_size), out_shardings=rows)(kt))
    for name, (step, _, _) in programs.items():
        out[name] = _time_and_trace(step, args, f"moe_head_{name}")
    out["saved_ms"] = round(out["replicated"]["fwd_bwd_wall_ms"]
                            - out["split_over_ep"]["fwd_bwd_wall_ms"], 3)
    return out


# ------------------------------------------------------- compiled, not run

_ARRAY = re.compile(r"\b(pred|[suf]\d+|bf16)\[([0-9,]+)\]")


def _describe(compiled, n_partitions: int) -> dict:
    """What a compiled program's text says: collectives by (op, payload
    bytes), the largest arrays it names, cost and memory analysis."""
    from ray_tpu.parallel.hlo_stats import collective_stats

    text = compiled.as_text()
    stats = collective_stats(text, lambda p: 0, n_partitions=n_partitions)
    coll = collections.Counter((o.op, o.payload_bytes) for o in stats.ops)
    arrays = collections.Counter()
    for dtype, dims in _ARRAY.findall(text):
        n = 1
        for d in dims.split(","):
            n *= int(d)
        arrays[f"{dtype}[{dims}]"] = n
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    return {
        "collectives": sorted(([op, b, n] for (op, b), n in coll.items()),
                              key=lambda r: -r[1])[:12],
        "largest_arrays": [k for k, _ in arrays.most_common(8)],
        "tflop": round(cost.get("flops", 0) / 1e12, 3),
        "gb_accessed": round(cost.get("bytes accessed", 0) / 1e9, 2),
        "temp_bytes": mem.temp_size_in_bytes,
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes}


def _v5e_mesh():
    from jax.experimental import topologies

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    return build_mesh(MeshSpec(ep=4), devices), devices[0].device_kind


def aot_layer() -> dict:
    from ray_tpu.ops.kernels import force_kernel_backend

    mesh, kind = _v5e_mesh()
    with force_kernel_backend("mosaic", kind):
        step, x, lp = _layer_program(_cfg(1), mesh)
        t0 = time.monotonic()
        compiled = step.lower(x, lp).compile()
    return {"mode": "aot-layer", "compile_s": round(time.monotonic() - t0, 1),
            **_describe(compiled, 4)}


def aot_step() -> dict:
    """The cell's step (2 layers, flash, full remat, ``adamw_lowmem``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import mixtral
    from ray_tpu.ops.kernels import force_kernel_backend
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.train import optim
    from ray_tpu.train.spmd import (TrainState, _opt_shardings,
                                    make_mixtral_train_step)

    cfg = _cfg()
    mesh, kind = _v5e_mesh()
    optimizer = optim.adamw_lowmem(3e-4, weight_decay=0.1)
    with force_kernel_backend("mosaic", kind):
        step, init_state, _ = make_mixtral_train_step(
            cfg, mesh, optimizer=optimizer, attn_impl="flash", remat=True)
        shapes = jax.eval_shape(init_state)
        repl = NamedSharding(mesh, P())
        param_sh = tree_shardings(mesh, mixtral.param_logical_axes(cfg))
        opt_sh = jax.tree.map(
            lambda s: s if s is not None else repl,
            _opt_shardings(optimizer, shapes.params, param_sh),
            is_leaf=lambda x: x is None)

        def sds(tree, shardings):
            return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=s), tree, shardings)

        state = TrainState(
            params=sds(shapes.params, param_sh),
            opt_state=sds(shapes.opt_state, opt_sh),
            step=jax.ShapeDtypeStruct((), jnp.int32, sharding=repl))
        batch = jax.ShapeDtypeStruct(
            (BATCH, SEQ), jnp.int32,
            sharding=NamedSharding(mesh, P(("dp", "fsdp"))))
        t0 = time.monotonic()
        compiled = step.lower(state, batch, batch).compile()
    return {"mode": "aot-step", "compile_s": round(time.monotonic() - t0, 1),
            **_describe(compiled, 4)}


def stats() -> dict:
    """``mixtral.routing_stats`` on the cell's probe batch at its seeded
    weights (``--seed`` STATS_SEEDS, as ``rtbench/kinds/train_steps.py``
    draws them), on the chips: the routers' load and what capacity drops."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import mixtral
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import kernel_mesh, tree_shardings
    from rtbench import common, gen

    cfg = _cfg()
    mesh = build_mesh(MeshSpec(ep=4), jax.devices()[:4])
    init = jax.jit(lambda key: mixtral.init_params(cfg, key),
                   out_shardings=tree_shardings(
                       mesh, mixtral.param_logical_axes(cfg)))
    rows = []
    for seed in STATS_SEEDS:
        rng = np.random.default_rng(gen.train_batch_seed(seed, -1))
        tokens = jax.device_put(
            rng.integers(0, cfg.vocab_size, (BATCH, SEQ), dtype=np.int32),
            NamedSharding(mesh, P(("dp", "fsdp"))))
        out = mixtral.routing_stats(
            cfg, init(jax.random.PRNGKey(common.jax_seed(seed))), tokens,
            kmesh=kernel_mesh(mesh))
        rows.append({"seed": seed, **{k: np.asarray(v, np.float64).round(5).tolist()
                                      for k, v in out.items()}})
    return {"mode": "stats", "capacity": cfg.capacity(BATCH * SEQ),
            "device": jax.devices()[0].device_kind, "seeds": rows}


MODES = {"layer": layer, "head": head, "stats": stats, "aot-layer": aot_layer,
         "aot-step": aot_step}

if __name__ == "__main__":
    for name in sys.argv[1:] or ["layer"]:
        print(json.dumps(MODES[name]()), flush=True)
