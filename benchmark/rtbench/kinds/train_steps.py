"""Traffic kind ``train_steps``: a training job through ``JaxTrainer.fit``
with one worker that holds the cell's chips.

The worker builds the program's own train step over the mesh the traffic
file names, takes ``untimed_steps`` steps (the first on a probe batch),
then steps on a fresh seeded batch each until ``--seconds`` are up,
reporting through ``session.report`` after every step. The clock of the
window stops on the blocking read of the last step's loss.

``correct``: every loss finite; the probe batch's loss lower after the
window than before it; the program's step-0 loss on the probe batch within
``loss_tolerance`` (relative) of the plain reference's at the published
widths; no compilation inside the window.
"""

from __future__ import annotations

import importlib
import math
import time

from rtbench import common, gen, readers

ADAPTER_NEEDS = ("REFERENCE", "model_config", "reference_weights",
                 "train_step")


def _batch(np, seed: int, step: int, vocab: int, batch: int, seq: int):
    rng = np.random.default_rng(gen.train_batch_seed(seed, step))
    tokens = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
    return tokens, np.roll(tokens, -1, axis=1)


def _train_loop(config: dict) -> None:
    """Runs on the trainer's worker. Everything it learns goes out through
    ``session.report``; a failed check raises and reaches the driver as
    ``Result.error``."""
    import jax
    import numpy as np

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train import session
    from ray_tpu.train import optim

    cell, seed, seconds = config["cell"], config["seed"], config["seconds"]
    traffic, model_json = cell["traffic"], cell["config"]
    chips = cell["workload"]["chips"]
    clock = common.SetupClock(config["t_start"])
    clock._last = config["t_handoff"]
    counter = common.CompileCounter()
    adapter = importlib.import_module(
        "rtbench.adapters." + model_json["adapter"])

    devices = jax.local_devices()[:chips]
    mesh = build_mesh(MeshSpec(**traffic["mesh"]), devices)
    seq, batch = traffic["seq_len"], traffic["global_batch"]
    model_cfg = adapter.model_config(model_json, traffic["use"], seq)
    opt_spec = traffic["optimizer"]
    optimizer = getattr(optim, opt_spec["name"])(
        opt_spec["lr"], weight_decay=opt_spec["weight_decay"])
    step_fn, init_state, shard, init_fn = adapter.train_step(
        model_cfg, mesh, optimizer, traffic, common.jax_seed(seed))
    clock.mark("trainer_to_worker_and_step_factory")

    state = init_state()
    jax.block_until_ready(state.params)
    clock.mark("init_state")

    vocab = model_json["vocab_size"]
    probe_host = _batch(np, seed, -1, vocab, batch, seq)
    probe = tuple(shard(a) for a in probe_host)
    losses: list[float] = []

    def one_step(tokens, targets) -> float:
        nonlocal state
        state, metrics = step_fn(state, tokens, targets)
        loss = float(metrics["loss"])       # waits for the step
        losses.append(loss)
        return loss

    # The jitted step, not an AOT-compiled copy of it: where XLA lays a
    # step's output state out otherwise than init_state did (the router
    # under ep), the second call specialises once more, and that belongs
    # to set-up. The window's compile count shows that it ended there.
    snap = counter.snapshot()
    probe_first = one_step(*probe)
    hit = counter.counts["hits"] - snap["hits"]
    clock.mark(f"first_step_with_compile(cache_hits={hit})")
    for k in range(1, traffic["untimed_steps"]):
        one_step(*(shard(a) for a in
                   _batch(np, seed, -1 - k, vocab, batch, seq)))
    clock.mark("untimed_steps")

    # ---- the window ------------------------------------------------------
    t_open = time.monotonic()
    clock.summary(t_open)
    snap = counter.snapshot()
    tracing, traced_steps, steps = False, 0, 0
    t_prev = t_open
    while True:
        if config["trace"] and steps == 2 and not tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(common.trace_dir(fresh=True),
                                     profiler_options=opts)
            tracing = True
        t_a = time.monotonic()
        tokens, targets = (shard(a) for a in
                           _batch(np, seed, steps, vocab, batch, seq))
        t_b = time.monotonic()
        loss = one_step(tokens, targets)
        t_c = time.monotonic()
        steps += 1
        session.report({"step": steps, "loss": loss, "step_s": t_c - t_prev,
                        "input_wait_s": t_b - t_a, "tokens": batch * seq})
        t_prev = t_c
        if tracing:
            traced_steps += 1
            if traced_steps >= traffic["trace_steps"]:
                jax.profiler.stop_trace()
                tracing = False
        if t_c - t_open >= seconds:
            break
    t_close = t_prev
    if tracing:
        jax.profiler.stop_trace()
    compiles = counter.compiled_since(snap)
    common.log(f"compilations inside the window: {compiles}")

    probe_second = one_step(*probe)
    device = common.device_record(devices)
    del state, probe

    # ---- the plain reference, after the window, on the initial weights ---
    t_ref = time.monotonic()
    reference = importlib.import_module(adapter.REFERENCE)
    params = jax.jit(init_fn)(jax.random.PRNGKey(common.jax_seed(seed)))
    ref_loss = reference.loss(model_json, adapter.reference_weights(params),
                              jax.numpy.asarray(probe_host[0]),
                              jax.numpy.asarray(probe_host[1]))
    del params
    rel = abs(probe_first - ref_loss) / abs(ref_loss)
    common.log(f"reference loss {ref_loss:.6f} program step-0 loss "
               f"{probe_first:.6f} relative difference {rel:.2e} "
               f"(tolerance {traffic['loss_tolerance']:.0e}) in "
               f"{time.monotonic() - t_ref:.1f}s")

    session.report({"final": {
        "t_open": t_open, "t_close": t_close, "steps": steps,
        "compiles_in_window": compiles,
        "losses_finite": all(math.isfinite(x) for x in losses),
        "probe_first": probe_first, "probe_second": probe_second,
        "reference_loss": ref_loss, "reference_rel": rel,
        "device": device,
        "cache_counts": counter.counts}})


def run(ctx: dict) -> None:
    cell, clock = ctx["cell"], ctx["clock"]
    chips = cell["workload"]["chips"]
    jax, devices, _counter = common.start_jax(chips)
    import ray_tpu
    from ray_tpu.train.config import ScalingConfig
    from ray_tpu.train.trainer import JaxTrainer

    clock.mark("imports_and_backend")
    ray_tpu.init(resources={"TPU": float(chips)})
    clock.mark("runtime_init")
    try:
        result = JaxTrainer(
            _train_loop,
            train_loop_config={
                "cell": {k: cell[k] for k in ("workload", "config",
                                              "traffic")},
                "seed": ctx["seed"], "seconds": ctx["seconds"],
                "trace": ctx["trace"], "t_start": clock.t_start,
                "t_handoff": time.monotonic()},
            scaling_config=ScalingConfig(
                num_workers=1, resources_per_worker={"TPU": float(chips)}),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error:
        raise RuntimeError(f"training failed:\n{result.error}")
    final = [m["final"] for m in result.metrics_history if "final" in m][0]
    step_reports = [m for m in result.metrics_history if "final" not in m]
    if len(step_reports) != final["steps"]:
        raise RuntimeError(f"{len(step_reports)} reports for "
                           f"{final['steps']} steps")

    traffic = cell["traffic"]
    window_s = final["t_close"] - final["t_open"]
    tokens = final["steps"] * traffic["global_batch"] * traffic["seq_len"]
    tok_s_chip = tokens / window_s / chips
    common.log(f"window {window_s:.3f}s steps {final['steps']} "
               f"tokens/s/chip {tok_s_chip:.1f} cache {final['cache_counts']}")

    correct = (final["losses_finite"]
               and final["probe_second"] < final["probe_first"]
               and final["reference_rel"] <= traffic["loss_tolerance"]
               and final["compiles_in_window"] == 0)
    if not correct:
        common.log(f"NOT correct: {final}")

    values = {"train_tok_s_chip": tok_s_chip,
              "setup_s": final["t_open"] - clock.t_start}
    device = final["device"]
    device["count"] = len(devices)
    breakdown = None
    if ctx["trace"]:
        from rtbench import trace_reduce

        path = trace_reduce.find_xplane(common.trace_dir())
        trace = trace_reduce.load(path)
        device["busy_s"], device["window_s"] = trace.busy_s(), trace.window_s()
        breakdown = trace.breakdown()
        obs = {"kind": "train", "cell": cell, "trace": trace,
               "tok_s_chip": tok_s_chip, "window_s": window_s,
               "input_wait_s": sum(m["input_wait_s"] for m in step_reports),
               "peaks": common.peaks_for(devices[0].device_kind),
               "chips": chips}
        values = readers.read_all(cell["per_layer"], obs)
    ctx["emit"](correct, final["steps"], 0, values, device, breakdown)
