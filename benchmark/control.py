"""The control of the serving cells' ``correct``: the plain reference put in
the program's place, computed one precision below the configuration's.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \
        [--tokens 1024] [--record rows.jsonl]

Not part of a benchmark run (``run.py`` never calls it); a builder runs it on
the chip when a limit is set or a cell added. ``--record`` appends each
reading as a JSON line; ``benchmark/records/control.jsonl`` keeps those the
limits were set from, and PERF.md cites them.

A serving run is ``correct`` when, over prompt + generated tokens of a few
finished requests, the token the engine chose has at every generated position
a float32-reference logit within ``check.margin`` of the reference's own
maximum (``kinds/serve_common._reference_check``). The configuration serves in
bfloat16; the step below it that would tempt a later PR is weights kept in
fp8 (e4m3, half the bytes a decode step reads). So the control is
the same reference on weights rounded through fp8: at every position of a
seeded sequence it chooses its own top token, and the number compared is the
one a run compares, the float32 reference's maximum minus its logit of that
token, worst over the positions. The sound runs' readings of the same number
are the ``reference: worst margin`` lines that every run prints.

The weights are the program's own ``init_params`` from the seed at the cell's
depth and widths, as a run makes them. ``tests/bench_harness/
test_bh_reference.py`` keeps the control at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def to_fp8(weights: dict, donate: bool = False) -> dict:
    """Every matrix rounded to fp8's precision (e4m3: 4 exponent bits, 3
    of mantissa), scaled per matrix (per layer of a stacked one) so that
    its largest entry is 224, inside that format's range: what a
    weight-only fp8 path would hold. By ``lax.reduce_precision``, which
    the compiler keeps; a cast to ``float8_e4m3fn`` and back it removes on
    the TPU (excess precision allowed), and the control then read 0.0000
    (my chip run, PR 26). Norm vectors stay. One layer at a time in
    float32; ``donate`` gives each leaf's buffer up as it goes, where two
    sets of weights do not fit."""
    import jax
    import jax.numpy as jnp

    def one(a):                                   # [in, out]
        a32 = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(a32)) / 224.0
        q = jax.lax.reduce_precision(a32 / scale, exponent_bits=4,
                                     mantissa_bits=3)
        return (q * scale).astype(a.dtype)

    def leaf(a):
        if a.ndim < 2:
            return a
        return one(a) if a.ndim == 2 else jax.lax.map(one, a)

    rounded = jax.jit(leaf, donate_argnums=(0,) if donate else ())
    return {**{k: (rounded(v) if k in ("embed", "head") else v)
               for k, v in weights.items() if k != "layers"},
            "layers": {k: rounded(v) for k, v in weights["layers"].items()}}


def margin(want, got, first: int) -> float:
    """Worst, over positions ``first``.., of ``want``'s top logit minus its
    logit of the token that ``got`` puts on top (both [S, V])."""
    import numpy as np

    want, got = np.asarray(want)[first:], np.asarray(got)[first:]
    pick = got.argmax(axis=1)
    return float((want.max(axis=1) - want[np.arange(len(pick)), pick]).max())


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("--record")
    args = ap.parse_args(argv)

    from rtbench import common, gen, manifest

    cell = manifest.load_cell(args.workload)
    traffic, model_json = cell["traffic"], cell["config"]
    jax, devices, _counter = common.start_jax(cell["workload"]["chips"])
    import jax.numpy as jnp

    from ray_tpu.llm import engine as engine_mod

    adapter = importlib.import_module(
        "rtbench.adapters." + model_json["adapter"])
    reference = importlib.import_module(adapter.REFERENCE)
    model_cfg = adapter.model_config(
        model_json, traffic["use"], traffic["engine"]["max_seq_len"])
    init = jax.jit(engine_mod.init_params, static_argnums=0)
    limit = traffic["check"]["margin"]
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        params = init(model_cfg, jax.random.PRNGKey(common.jax_seed(seed)))
        weights = adapter.reference_weights(params)
        ids = gen.prompt_ids(seed, 1, args.tokens, model_json["vocab_size"])
        tokens = jnp.asarray(ids, jnp.int32)
        first = args.tokens // 4      # a prompt's worth of context first
        want = reference.logits(model_json, weights, tokens)
        weights = to_fp8(weights, donate=True)   # the originals go
        del params
        control = margin(want, reference.logits(model_json, weights, tokens),
                         first)
        row = {"workload": args.workload,
               "layers": adapter.depth(model_json, traffic["use"]),
               "device": devices[0].device_kind, "seed": seed,
               "context": first, "positions": args.tokens - first,
               "control_fp8_margin": control, "limit": limit,
               "control_correct": control <= limit}
        common.log(json.dumps(row))
        rows.append(row)
        if args.record:
            with open(args.record, "a") as f:
                f.write(json.dumps(row) + "\n")
        del weights
    worst = min(r["control_fp8_margin"] for r in rows)
    common.log(f"control: smallest margin {worst:.4f} over {len(rows)} seeds "
               f"against the limit {limit}: "
               f"{'NOT separated' if worst <= limit else 'separated'}")
    return 0 if worst > limit else 1


if __name__ == "__main__":
    sys.exit(main())
