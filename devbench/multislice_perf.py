"""Multi-slice training fast-path scale proof (PERF_MULTISLICE.json).

Measures, on the 2-simulated-slice 8-device dryrun topology (dp=2 crossing
slices over DCN, fsdp=4 inside each slice over ICI, pure-DDP rules so params
replicate), the four gradient-sync modes of train/spmd.make_train_step:

- flat      — stock step: XLA all-reduces the full gradient over all 8
              devices; the DCN hop carries full-size payloads.
- hier      — hierarchical (arxiv 2004.13336): weight update sharded within
              the slice; reduce-scatter(ICI) → shard-sized cross-slice
              reduce(DCN) → all-gather(ICI).
- zero1     — update + optimizer moments sharded over the WHOLE dp world
              (1/8 optimizer HBM per device), shard-sized DCN RS/AG.
- zero1_q8  — zero1 + EQuARX-style int8 cross-slice stage (arxiv
              2506.17615): only int8 values + per-bucket f32 scales cross
              the slice boundary.

Cross-slice bytes per step are measured from the compiled partitioned HLO
(ray_tpu/parallel/hlo_stats.py — ring cost model, stated in the output), so
the number is real even on CPU hosts where no DCN exists. tokens/sec/chip on
a CPU host compares modes against each other, not against TPU numbers.

Run: JAX_PLATFORMS=cpu python devbench/multislice_perf.py [--quick]
(also wired into the dryrun entrypoint, __graft_entry__.dryrun_multichip).
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _force_cpu_devices(n: int = 8) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}")
    os.environ["JAX_PLATFORMS"] = "cpu"


def run_bench(quick: bool = False, out_path: str | None = None) -> dict:
    import jax
    import numpy as np
    import optax

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.hlo_stats import (
        COST_MODEL,
        collective_stats,
        mesh_slice_map,
    )
    from ray_tpu.parallel.mesh import MeshSpec, hybrid_mesh
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.train.optim import optimizer_state_bytes
    from ray_tpu.train.spmd import make_llama_train_step

    num_slices, per_slice = 2, 4
    devices = jax.devices()[: num_slices * per_slice]
    assert len(devices) == num_slices * per_slice, (
        f"need {num_slices * per_slice} devices, have {len(devices)}")
    spec = MeshSpec(dp=num_slices, fsdp=per_slice, dcn_axes=("dp",))
    mesh = hybrid_mesh(spec, num_slices=num_slices,
                       devices_per_slice=per_slice, devices=devices)
    # Pure data-parallel: params replicated everywhere, batch over (dp,fsdp)
    # — the Llama-DDP-fine-tune geometry the north star names.
    ddp_rules = ShardingRules().override(
        vocab=None, embed=None, mlp=None, heads=None, kv_heads=None)

    cfg = LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=8, num_kv_heads=4, head_dim=16,
        max_seq_len=128, dtype="float32",
    )
    batch, seq = 16, 64
    steps = 4 if quick else 12
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    targets = np.roll(tokens, -1, axis=1)
    slice_of = mesh_slice_map(len(devices), num_slices)

    modes = {
        "flat": {},
        "hier": dict(dcn_axes=("dp",)),
        "zero1": dict(zero1=True, dcn_axes=("dp",)),
        "zero1_q8": dict(zero1=True, dcn_axes=("dp",), dcn_quant="int8"),
    }
    if not quick:
        modes["zero1_accum4"] = dict(zero1=True, dcn_axes=("dp",),
                                     grad_accum=4)

    opt = optax.adamw(1e-2)
    report: dict = {
        "what": ("Multi-slice fast path: flat vs hierarchical vs zero1 vs "
                 "int8-quantized-DCN gradient sync on a 2-simulated-slice "
                 "8-device CPU mesh (dp=2 over DCN x fsdp=4 over ICI, "
                 "pure-DDP Llama)."),
        "geometry": {
            "num_slices": num_slices, "devices_per_slice": per_slice,
            "batch": batch, "seq": seq, "steps_timed": steps,
            "params": int(sum(np.prod(l.shape) for l in jax.tree.leaves(
                jax.eval_shape(lambda k: init_params(cfg, k),
                               jax.random.PRNGKey(0))))),
        },
        "modes": {},
    }

    flat_losses = None
    flat_dcn = None
    for name, kw in modes.items():
        step, init, shard = make_llama_train_step(
            cfg, mesh, rules=ddp_rules, optimizer=opt,
            attn_impl="blockwise", remat=False, **kw)
        state = init()
        ts, tg = shard(tokens), shard(targets)
        stats = collective_stats(
            step.lower(state, ts, tg).compile().as_text(), slice_of,
            n_partitions=len(devices))
        opt_bytes = optimizer_state_bytes(
            opt, state.params,
            shardings=jax.tree.map(lambda l: l.sharding, state.opt_state))
        state, m = step(state, ts, tg)  # warmup (donates + re-inits below)
        jax.block_until_ready(m["loss"])
        state = init()
        losses = []
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, ts, tg)
            losses.append(float(m["loss"]))  # also syncs
        dt = time.perf_counter() - t0
        row = {
            "dcn_bytes_per_step": stats.dcn_bytes,
            "dcn_collective_ops": stats.dcn_ops,
            # non-zero = the HLO had collectives the parser could not price,
            # so dcn_bytes_per_step UNDERCOUNTS for this row
            **({"dcn_unpriced_ops": stats.skipped_ops}
               if stats.skipped_ops else {}),
            "tokens_per_sec_per_chip": round(
                batch * seq * steps / dt / len(devices), 1),
            "step_ms": round(dt / steps * 1e3, 2),
            "opt_state_bytes_per_device": opt_bytes,
            "losses": [round(l, 6) for l in losses],
        }
        if name == "flat":
            flat_losses, flat_dcn = losses, stats.dcn_bytes
        else:
            row["dcn_reduction_vs_flat"] = round(
                flat_dcn / max(stats.dcn_bytes, 1), 2)
            n = min(len(losses), len(flat_losses))
            row["max_loss_delta_vs_flat"] = round(float(np.max(np.abs(
                np.asarray(losses[:n]) - np.asarray(flat_losses[:n])))), 6)
        report["modes"][name] = row

    report["dcn_cost_model"] = (
        "bytes from the compiled partitioned HLO; " + COST_MODEL)
    report["parity"] = {
        # fp32 hierarchy is a pure reorder of the same sums; allow float
        # reassociation noise across XLA versions/backends (the step-level
        # test asserts the same claim at rtol 1e-6)
        "hier_fp32_delta_lt_1e-6": report["modes"]["hier"][
            "max_loss_delta_vs_flat"] < 1e-6,
        "zero1_tolerance_1e-4": report["modes"]["zero1"][
            "max_loss_delta_vs_flat"] < 1e-4,
        "zero1_q8_tolerance_2e-2": report["modes"]["zero1_q8"][
            "max_loss_delta_vs_flat"] < 2e-2,
        "zero1_q8_dcn_reduction_ge_2x": report["modes"]["zero1_q8"][
            "dcn_reduction_vs_flat"] >= 2.0,
        "zero1_dcn_reduction_ge_2x": report["modes"]["zero1"][
            "dcn_reduction_vs_flat"] >= 2.0,
    }

    # Satellite: grad-norm amortization — the same flat step with the norm
    # computed every 8 steps instead of every step, timed back-to-back
    # (best-of-2 interleaved rounds so box-load drift can't flip the sign).
    # Skipped in quick (dryrun-embedded) runs: two extra compiles for a
    # number the committed full-run PERF_MULTISLICE.json already carries.
    if quick:
        out_path = out_path or os.path.join(REPO_ROOT,
                                            "PERF_MULTISLICE.json")
        # A committed full-run file keeps ALL its sections (geometry,
        # parity, rows) untouched — a quick (dryrun-embedded, fewer-steps)
        # refresh lands under its own key with its own geometry so rows are
        # never attributed to a configuration they weren't measured with.
        try:
            with open(out_path) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
        if merged.get("modes"):
            merged["quick_dryrun_refresh"] = {
                "geometry": report["geometry"],
                "modes": report["modes"],
                "parity": report["parity"],
            }
        else:
            merged = report
        with open(out_path, "w") as f:
            json.dump(merged, f, indent=1)
        return report
    steps_fns = {}
    for every in (1, 8):
        step, init, shard = make_llama_train_step(
            cfg, mesh, rules=ddp_rules, optimizer=opt, attn_impl="blockwise",
            remat=False, grad_norm_every=every)
        state = init()
        ts, tg = shard(tokens), shard(targets)
        state, m = step(state, ts, tg)
        jax.block_until_ready(m["loss"])
        steps_fns[every] = (step, state, ts, tg)
    # The differential is a few ms/step — smaller than this box's slow
    # thermal/load drift. Pair the two variants back-to-back within each
    # round (drift cancels in the difference), sync once per window
    # (per-step float(loss) sync injects more jitter than the signal), and
    # report the median of the per-round paired differences.
    round_ms = {1: [], 8: []}
    for _round in range(5):
        for every, (step, state, ts, tg) in steps_fns.items():
            t0 = time.perf_counter()
            for _ in range(steps):
                state, m = step(state, ts, tg)
            jax.block_until_ready(m["loss"])
            round_ms[every].append((time.perf_counter() - t0) / steps * 1e3)
            steps_fns[every] = (step, state, ts, tg)
    diffs = sorted(a - b for a, b in zip(round_ms[1], round_ms[8]))
    median = diffs[len(diffs) // 2]
    report["grad_norm_amortization"] = {
        "grad_norm_every": 8,
        "step_ms_every1": round(min(round_ms[1]), 2),
        "step_ms_every8": round(min(round_ms[8]), 2),
        "reclaimed_ms_per_step": round(median, 2),
        "per_round_diffs_ms": [round(d, 2) for d in diffs],
        "note": ("CPU-host numbers: median of 5 paired (back-to-back, "
                 "end-of-window-sync) round differences; per-round spread "
                 "shows the box noise floor. Not measured on a chip."),
    }

    out_path = out_path or os.path.join(REPO_ROOT, "PERF_MULTISLICE.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    return report


def main(argv: list[str] | None = None) -> dict:
    argv = sys.argv[1:] if argv is None else argv
    _force_cpu_devices()
    report = run_bench(quick="--quick" in argv)
    summary = {name: (row["dcn_bytes_per_step"],
                      row["tokens_per_sec_per_chip"])
               for name, row in report["modes"].items()}
    print("multislice_perf:", json.dumps(summary))
    return report


if __name__ == "__main__":
    sys.path.insert(0, REPO_ROOT)
    main()
