"""Olmo-Hybrid's train step at the shapes of ``olmo-hybrid-train-8k`` (one
period at the published widths: three Gated DeltaNet of 30 heads, keys of 96,
values of 192, and one full attention; an eighth of the vocabulary; 2
sequences of 8,192): compiled for a described v5e with no chip, and measured
on one.

    python3 devbench/olmo_hybrid_bench.py aot          # no chip, two minutes
    OLMO_REMAT=full python3 devbench/olmo_hybrid_bench.py aot   # another policy
    chiprun -- python3 devbench/olmo_hybrid_bench.py rule grads
    chiprun -- python3 devbench/olmo_hybrid_bench.py margins

``aot``: ``train/spmd.make_olmo_hybrid_train_step``'s step under the traffic
file's optimizer and remat policy, compiled for ``v5e:2x2``'s first device
(nothing runs: no time comes out of it): XLA's ``memory_analysis``
(arguments, temporaries, their sum against the chip's 15.75 GiB: run it on
the parent's tree too, the figures are what the two are compared by), the
Mosaic calls, the rule's kernels by pass (``gated_delta_chunk`` on ``fwd``
and ``remat``, ``gated_delta_chunk_bwd`` on ``bwd``, one a linear layer
each), and the operations of the compiled step by ``tracing.part`` scope and
pass (``delta_rule`` on ``fwd``, ``bwd`` and ``remat``; none ``unnamed``).
``rule``: the gated delta rule alone (``ops/gated_delta.gated_delta_chunk``
on a batch) at the cell's shapes, forward (the kernel at padded widths, and
the jnp body), the backward alone (its kernel, and the jnp chunks in
reverse) and forward + backward, at the stated precision and with the
products in fewer bfloat16 passes: seconds a call, the share of the
yardstick
(``adapters/olmo_hybrid.delta_rule_train_token_work`` over the chip's peaks)
and the largest difference from the recurrence (forward) and from
``jax.grad`` through it (one sequence of 1,024: on the chip the backward
is the kernel's, and the jnp backward's is printed beside it). ``grads``: at the cell's
widths and one sequence of 8,192 (``OLMO_SEQ`` overrides), the program's
gradient of the loss against the float32 reference's, computed a layer at a
time (``jax.vjp`` of ``benchmark/reference/olmo_hybrid.layer``) so that it
fits: the relative error of every leaf (largest absolute difference over the
reference's largest absolute value), and of the rule's five operand
gradients in one linear layer against ``jax.grad`` through the recurrence;
beside it the control: the same with the state rounded to bfloat16 at every
chunk boundary, which the stated tolerances have to fail. ``margins``: the
step-0 loss of the program against the reference's on a seeded batch of the
cell's size, sound and under the control. One JSON object a mode.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

from devbench.lfm2_bench import GIB  # noqa: E402
from devbench.longcat_bench import timed  # noqa: E402

MOSAIC = 'custom_call_target="tpu_custom_call"'
# What ``grads`` holds a leaf's and an operand's gradient to (relative to the
# reference's largest value), and ``margins`` the loss: see PERF.md section
# 4, "olmo-hybrid-7b", for the readings they stand between.
LEAF_TOLERANCE = 0.1
RULE_TOLERANCE = 1e-4


def cell_files() -> tuple[dict, dict]:
    """(the configuration file, the traffic file) of the cell."""
    base = os.path.join(ROOT, "benchmark")
    with open(os.path.join(base, "configs", "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    with open(os.path.join(base, "traffic", "train-8k.json")) as f:
        traffic = json.load(f)
    return config, traffic


def model(config: dict, traffic: dict, **changes):
    from rtbench.adapters import olmo_hybrid as adapter

    cfg = adapter.model_config(config, traffic["use"], traffic["seq_len"])
    return dataclasses.replace(cfg, **changes)


def _optimizer(traffic: dict):
    from ray_tpu.train import optim

    spec = traffic["optimizer"]
    return getattr(optim, spec["name"])(spec["lr"],
                                        weight_decay=spec["weight_decay"])


def compile_step(cfg, traffic: dict, batch: int, seq: int):
    """The train step compiled for a described v5e's first chip:
    (memory analysis, HLO text, seconds). tests/test_tpu_aot.py reads the
    same at a reduced size."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import olmo_hybrid
    from ray_tpu.ops.kernels import force_kernel_backend
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.train.spmd import (TrainState, _opt_shardings,
                                    make_olmo_hybrid_train_step)

    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    with force_kernel_backend("mosaic", devices[0].device_kind):
        mesh = build_mesh(MeshSpec(**traffic["mesh"]), devices[:1])
        optimizer = _optimizer(traffic)
        step_fn, init_state, _ = make_olmo_hybrid_train_step(
            cfg, mesh, optimizer=optimizer, attn_impl=traffic["attn_impl"],
            remat=traffic["remat"])
        shapes = jax.eval_shape(init_state)
        repl = NamedSharding(mesh, P())
        param_sh = tree_shardings(mesh,
                                  olmo_hybrid.param_logical_axes(cfg))
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
        tokens = jax.ShapeDtypeStruct(
            (batch, seq), jnp.int32,
            sharding=NamedSharding(mesh, P(("dp", "fsdp"))))
        t0 = time.monotonic()
        compiled = step_fn.lower(state, tokens, tokens).compile()
        return (compiled.memory_analysis(), compiled.as_text(),
                time.monotonic() - t0)


def scopes_by_pass(text: str) -> dict:
    """{part or finer scope: {pass: operations}} of a compiled step's HLO,
    by the ``op_name`` of every instruction that has a path (a parameter's
    and a reducer's are bare names and run as no operation): the names the
    device trace will carry."""
    import re

    from rtbench import xplane_meta
    from rtbench.readers.scope_share import innermost

    names = set(xplane_meta.PARTS) | {"delta_rule", "linear_attn", "conv"}
    out: dict = {}
    for m in re.finditer(r'op_name="(jit\([^"]*)"', text):
        path = m.group(1)
        scope = innermost(path, names) or xplane_meta.part_of(path)
        which = xplane_meta.pass_of(path)
        out.setdefault(scope, {}).setdefault(which, 0)
        out[scope][which] += 1
    return out


def rule_kernels_by_pass(text: str) -> dict:
    """{kernel: {pass: calls}} of the Mosaic calls of a compiled step's HLO
    under the scope ``delta_rule``, a kernel by the name of its
    ``pallas_call``."""
    import re

    from rtbench import xplane_meta

    out: dict = {}
    for line in text.splitlines():
        m = MOSAIC in line and re.search(
            r'op_name="(jit\([^"]*/delta_rule/(\w+)/pallas_call)"', line)
        if m:
            calls = out.setdefault(m.group(2), {})
            which = xplane_meta.pass_of(m.group(1))
            calls[which] = calls.get(which, 0) + 1
    return out


def aot() -> dict:
    config, traffic = cell_files()
    if os.environ.get("OLMO_REMAT"):
        traffic["remat"] = os.environ["OLMO_REMAT"]
    cfg = model(config, traffic)
    mem, text, seconds = compile_step(cfg, traffic, traffic["global_batch"],
                                      traffic["seq_len"])
    return {"mode": "aot", "layers": cfg.num_layers,
            "params": cfg.num_params(), "remat": traffic["remat"],
            "batch": traffic["global_batch"], "seq_len": traffic["seq_len"],
            "compile_s": round(seconds, 1),
            "arguments_gib": round(mem.argument_size_in_bytes / GIB, 3),
            "outputs_gib": round(mem.output_size_in_bytes / GIB, 3),
            "aliased_gib": round(mem.alias_size_in_bytes / GIB, 3),
            "temporaries_gib": round(mem.temp_size_in_bytes / GIB, 3),
            "sum_gib": round((mem.argument_size_in_bytes
                              + mem.output_size_in_bytes
                              - mem.alias_size_in_bytes
                              + mem.temp_size_in_bytes) / GIB, 3),
            "mosaic_calls": text.count(MOSAIC),
            "rule_kernels": rule_kernels_by_pass(text),
            "scopes": scopes_by_pass(text)}


def _peaks():
    import jax

    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        return json.load(f)[jax.devices()[0].device_kind]


def rule_inputs(key, batch: int, seq: int, heads: int, dk: int, dv: int):
    """Unit keys, scaled unit queries, ``exp(g)`` over 0.5 to 0.999, steps
    over (0, 2), a cotangent for the outputs."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    ks = jax.random.split(key, 6)
    unit = lambda x: x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))  # noqa: E731
    q = unit(jax.random.normal(ks[0], (batch, seq, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, seq, heads, dk)))
    v = jax.random.normal(ks[2], (batch, seq, heads, dv))
    g = jnp.log(jax.random.uniform(ks[3], (batch, seq, heads), minval=0.5,
                                   maxval=0.999))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads)))
    weight = jax.random.normal(ks[5], (batch, seq, heads, dv))
    return (q, k, v, g, beta), weight


@contextlib.contextmanager
def bf16_states(gd):
    """``ops/gated_delta`` with the state rounded to bfloat16 at every chunk
    boundary: the states the backward walks back from (the jnp chunks' and
    those the forward's kernel keeps for the backward's kernel) and the
    forward's jnp chunks (the kernel's forward keeps its float32 states
    inside): the control. Nothing traced on either side of it is found on
    the other."""
    import jax
    import jax.numpy as jnp

    sound, sound_kernel = gd._a_chunk, gd._batch_forward_kernel

    def low(state):
        return state.astype(jnp.bfloat16).astype(jnp.float32)

    def rounded(q, k, v, g, beta, state):
        return sound(q, k, v, g, beta, low(state))

    def rounded_kernel(*a):
        o, state, starts = sound_kernel(*a)
        return o, state, low(starts)

    gd._a_chunk, gd._batch_forward_kernel = rounded, rounded_kernel
    jax.clear_caches()
    try:
        yield
    finally:
        gd._a_chunk, gd._batch_forward_kernel = sound, sound_kernel
        jax.clear_caches()


def rule() -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from rtbench.adapters import olmo_hybrid as adapter

    from ray_tpu.ops import gated_delta as gd
    from ray_tpu.ops.kernels import force_kernel_backend

    config, traffic = cell_files()
    cfg, peaks = model(config, traffic), _peaks()
    heads, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
    batch, seq = traffic["global_batch"], traffic["seq_len"]
    work = adapter.delta_rule_train_token_work(config)
    per_token = max(work["flops"] / peaks["bf16_flops_per_s"],
                    work["bytes"] / peaks["hbm_bytes_per_s"])
    out = {"mode": "rule", "device": jax.devices()[0].device_kind,
           "batch": batch, "seq": seq, "heads": heads, "dk": dk, "dv": dv,
           "least_us_a_token_and_layer": round(per_token * 1e6, 4),
           "bound": "bytes" if work["bytes"] / peaks["hbm_bytes_per_s"]
           > work["flops"] / peaks["bf16_flops_per_s"] else "flops"}
    a, weight = rule_inputs(jax.random.PRNGKey(0), batch, seq, heads, dk, dv)
    zero = jnp.zeros((batch, heads, dk, dv), jnp.float32)

    def loss(*a):
        o, s = gd.gated_delta_chunk(*a, zero)
        return jnp.sum(o * weight) + jnp.sum(s)

    fwd = jax.jit(lambda *a: gd.gated_delta_chunk(*a, zero))
    both = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    sec_fwd = timed(lambda: fwd(*a), 5)
    sec_both = timed(lambda: both(*a), 5)
    tokens = batch * seq
    out["forward_ms"] = round(sec_fwd * 1e3, 3)
    # the forward's other body: plain jnp, what the backward's chunks run
    with force_kernel_backend("reference"):
        fwd_jnp = jax.jit(lambda *a: gd.gated_delta_chunk(*a, zero))
        out["forward_jnp_ms"] = round(timed(lambda: fwd_jnp(*a), 5) * 1e3, 3)
    out["forward_and_backward_ms"] = round(sec_both * 1e3, 3)

    def backward_ms():
        """The backward alone, from what the forward keeps for it
        (functions of their own: the backend is read where they are
        traced)."""
        saved = jax.jit(lambda *a: gd._batch_rule_fwd(*a, zero)[1])(*a)
        bwd = jax.jit(lambda saved, w: gd._batch_rule_bwd(saved, (w, zero)))
        return round(timed(lambda: bwd(saved, weight), 5) * 1e3, 3)

    out["backward_ms"] = backward_ms()
    with force_kernel_backend("reference"):
        out["backward_jnp_ms"] = backward_ms()
    # A train step runs the forward twice under full remat (the second is
    # time spent and not work needed) and the backward once.
    out["a_step_and_layer_ms"] = round((sec_fwd + sec_both) * 1e3, 3)
    out["roofline_pct_of_a_step"] = round(
        100 * tokens * per_token / (sec_fwd + sec_both), 2)
    out["forward_tflops"] = round(
        tokens * 7 * adapter.delta_rule_cell(config) / sec_fwd / 1e12, 2)
    # against the recurrence: one sequence of 1,024
    short, w1 = rule_inputs(jax.random.PRNGKey(1), 1, 1024, heads, dk, dv)
    z1 = zero[:1]

    def loss_chunk(*a):
        o, s = gd.gated_delta_chunk(*a, z1)
        return jnp.sum(o * w1) + jnp.sum(s)

    def loss_rec(*a):
        o, s = gd.gated_delta_recurrence(*(x[0] for x in a), z1[0])
        return jnp.sum(o * w1[0]) + jnp.sum(s)

    want = jax.jit(jax.grad(loss_rec, argnums=(0, 1, 2, 3, 4)))(*short)

    def errors():
        got = jax.jit(jax.grad(loss_chunk, argnums=(0, 1, 2, 3, 4)))(*short)
        return {n: float(jnp.abs(x - y).max() / jnp.abs(y).max())
                for n, x, y in zip(("dq", "dk", "dv", "dg", "dbeta"), got,
                                   want)}

    out["gradient_rel_err"] = errors()
    with force_kernel_backend("reference"):
        out["gradient_rel_err_jnp"] = errors()
    with bf16_states(gd):
        out["gradient_rel_err_bf16_states"] = errors()
    # The other choice of precision the issue leaves to measurement: the
    # rule's products in fewer bfloat16 passes (``DEFAULT`` is one pass:
    # bfloat16 operands, float32 accumulation, the published kernels'), the
    # state float32 as before. Times at the cell's shapes, errors as above.
    def products_row():
        # (functions of their own: nothing traced at another precision is
        # found again)
        fwd_p = jax.jit(lambda *a: gd.gated_delta_chunk(*a, zero))
        both_p = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
        return {"forward_ms": round(timed(lambda: fwd_p(*a), 3) * 1e3, 3),
                "forward_and_backward_ms": round(
                    timed(lambda: both_p(*a), 3) * 1e3, 3),
                "gradient_rel_err": errors()}

    sound = gd.PRECISION
    out["products"] = {}
    for name in ("HIGHEST", "HIGH", "DEFAULT"):
        # (both passes in jnp for all three rows, so that the rows differ in
        # the products' precision alone)
        gd.PRECISION = getattr(lax.Precision, name)
        jax.clear_caches()
        with force_kernel_backend("reference"):
            out["products"][name] = products_row()
    gd.PRECISION = sound
    jax.clear_caches()
    out["rule_tolerance"] = RULE_TOLERANCE
    return out


def _seeded(cfg, seed: int, batch: int, seq: int):
    import jax
    import numpy as np

    from ray_tpu.models import olmo_hybrid

    params = jax.jit(partial(olmo_hybrid.init_params, cfg))(
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    return params, tokens, np.roll(tokens, -1, axis=1)


def reference_grads(config: dict, weights: dict, tokens, targets):
    """(loss, gradients under the reference's names, on the host) of the
    float32 reference on one sequence, a layer at a time: the layers' inputs
    are kept, then ``jax.vjp`` of each layer from the last to the first."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import olmo_hybrid as ref

    st = ref._static(config)
    n = weights["layers"]["post_attn_norm"].shape[0]
    xs = [weights["embed"][tokens].astype(jnp.float32)]
    for l in range(n):
        kind, w = ref.layer_weights(config, weights, l)
        xs.append(ref.layer(st, kind, xs[-1], w))

    def head_loss(x, final_norm, head):
        lg = ref.head_logits(x, final_norm, head, config["rms_norm_eps"])
        return ref.nll_sum(lg, targets) / targets.shape[0]

    loss, (dx, d_norm, d_head) = jax.value_and_grad(
        head_loss, argnums=(0, 1, 2))(xs[-1], weights["final_norm"],
                                      weights["head"])
    host = lambda a: np.asarray(a, np.float32)  # noqa: E731
    grads = {"final_norm": host(d_norm), "head": host(d_head)}
    per_layer: dict = {}
    for l in reversed(range(n)):
        kind, w = ref.layer_weights(config, weights, l)
        _, pull = jax.vjp(partial(ref.layer, st, kind), xs[l], w)
        dx, dw = pull(dx)
        for k, v in dw.items():
            per_layer.setdefault(k, []).insert(0, host(v))
        del xs[l + 1], pull, dw
    grads["layers"] = {k: np.stack(v) for k, v in per_layer.items()}
    grads["embed"] = host(jnp.zeros(weights["embed"].shape, jnp.float32).at[
        tokens].add(dx))
    return float(loss), grads


def grads() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from rtbench.adapters import olmo_hybrid as adapter

    from ray_tpu.models import olmo_hybrid
    from ray_tpu.ops import gated_delta as gd

    config, traffic = cell_files()
    seq = int(os.environ.get("OLMO_SEQ", traffic["seq_len"]))
    cfg = model(config, traffic)
    params, tokens, targets = _seeded(cfg, 7, 1, seq)
    out = {"mode": "grads", "device": jax.devices()[0].device_kind,
           "seq": seq, "layers": cfg.num_layers,
           "leaf_tolerance": LEAF_TOLERANCE}

    def program():
        return jax.jit(jax.value_and_grad(
            lambda p: olmo_hybrid.loss_fn(
                cfg, p, tokens, targets, attn_impl=traffic["attn_impl"],
                remat=traffic["remat"])))(params)

    t0 = time.monotonic()
    ref_loss, want = reference_grads(
        config, adapter.reference_weights(params), jnp.asarray(tokens[0]),
        jnp.asarray(targets[0]))
    out["reference_s"] = round(time.monotonic() - t0, 1)
    out["reference_loss"] = ref_loss

    def leaves(got):
        """On the host, a leaf at a time: the chip keeps the weights."""
        flat = jax.tree_util.tree_flatten_with_path(
            adapter.reference_weights(jax.device_get(got)))[0]
        return {jax.tree_util.keystr(path): float(
            np.abs(np.asarray(a, np.float32) - b).max() / np.abs(b).max())
            for (path, a), b in zip(flat, jax.tree.leaves(want))}

    loss, got = program()
    out["loss"], out["leaf_rel_err"] = float(loss), leaves(got)
    del got
    with bf16_states(gd):
        loss, got = program()
    out["loss_bf16_states"] = float(loss)
    out["leaf_rel_err_bf16_states"] = leaves(got)
    del got
    for name in ("leaf_rel_err", "leaf_rel_err_bf16_states"):
        out["worst_" + name] = max(out[name].items(), key=lambda kv: kv[1])
    return out


def margins() -> dict:
    import jax
    import jax.numpy as jnp
    from reference import olmo_hybrid as ref
    from rtbench.adapters import olmo_hybrid as adapter

    from ray_tpu.models import olmo_hybrid
    from ray_tpu.ops import gated_delta as gd

    config, traffic = cell_files()
    cfg = model(config, traffic)
    batch, seq = traffic["global_batch"], traffic["seq_len"]
    out = {"mode": "margins", "device": jax.devices()[0].device_kind,
           "loss_tolerance": traffic["loss_tolerance"], "seeds": {}}
    for seed in (11, 12, 13):
        params, tokens, targets = _seeded(cfg, seed, batch, seq)

        def program():
            return float(jax.jit(lambda p: olmo_hybrid.loss_fn(
                cfg, p, tokens, targets, attn_impl=traffic["attn_impl"],
                remat=traffic["remat"]))(params))

        sound = program()
        with bf16_states(gd):
            control = program()
        want = ref.loss(config, adapter.reference_weights(params),
                        jnp.asarray(tokens), jnp.asarray(targets))
        out["seeds"][seed] = {
            "reference_loss": want, "loss": sound,
            "rel": abs(sound - want) / abs(want),
            "loss_bf16_states": control,
            "rel_bf16_states": abs(control - want) / abs(want)}
    return out


MODES = {"aot": aot, "rule": rule, "grads": grads, "margins": margins}

if __name__ == "__main__":
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for mode in sys.argv[1:] or ["aot"]:
        result = MODES[mode]()
        print(json.dumps(result), flush=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               f"olmo_hybrid_{mode}.json"), "w") as f:
            json.dump(result, f, indent=1)
