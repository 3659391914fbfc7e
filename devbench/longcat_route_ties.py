"""How often bfloat16 and the float32 reference choose different experts, and
what that moves.

    chiprun -- python3 devbench/longcat_route_ties.py [tokens] [seed]

The router's top 12 of 768 is a discrete choice. The program computes the
scores in float32 from a bfloat16 input, the reference from its own float32
one, so a near-tie between the 12th and the 13th score can fall differently
and swap one weighted expert term. On this chip's share a swap changes the
result only where it involves a held expert (1 of 16) or a zero expert (the
other 496 routed experts add nothing here either way). This script runs one
seeded sequence through ``models/longcat.forward`` in bfloat16 and through
``benchmark/reference/longcat.py`` at the benchmark's configuration, records
both sides' choices at every routed layer, and prints how many (token,
layer) pairs differ, on which kind of expert, and the margin (the
reference's top logit minus its logit of the program's top token) of the
positions with and without a swap that counts.

A measurement for PERF.md, not part of a benchmark run; the reference is
never given the program's choices.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main(argv: list[str]) -> int:
    tokens = int(argv[0]) if argv else 2048
    seed = int(argv[1]) if len(argv) > 1 else 2600027101
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("longcat_route_ties: needs a TPU", file=sys.stderr)
        return 1
    from reference import longcat as reference
    from rtbench import common, gen
    from rtbench.adapters import longcat as adapter

    from ray_tpu.llm import served
    from ray_tpu.models import longcat, routed

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "longcat-flash-chat.json")) as f:
        config = json.load(f)
    cfg = adapter.model_config(config, "serve_agent", 8192)
    params = jax.jit(served.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(common.jax_seed(seed)))
    ids = jnp.asarray(gen.prompt_ids(seed, 1, tokens, config["vocab_size"]),
                      jnp.int32)

    program_choice: list = []
    inner_route = routed.route

    def route(rule, router, bias, u):
        idx, w = inner_route(rule, router, bias, u)
        jax.debug.callback(lambda i: program_choice.append(np.asarray(i)),
                           idx, ordered=True)
        return idx, w

    routed.route = route
    got, _ = jax.jit(longcat.forward, static_argnums=0)(cfg, params,
                                                        ids[None])
    got = np.asarray(jax.block_until_ready(got)[0])
    routed.route = inner_route

    reference_choice: list = []
    inner = reference._route

    def recorded(c, u, router, bias):
        weights = inner(c, u, router, bias)
        reference_choice.append(np.asarray(weights > 0))
        return weights

    reference._route = recorded
    want = np.asarray(reference.logits(
        config, adapter.reference_weights(params), ids))
    reference._route = inner

    held, total = config["n_routed_experts"], 512
    swaps = {"held": 0, "zero": 0, "absent": 0}
    counted = np.zeros(tokens, bool)   # a swap on a held or a zero expert
    pairs = differ = 0
    for layer, (idx, chosen) in enumerate(zip(program_choice,
                                              reference_choice)):
        mine = np.zeros_like(chosen)
        np.put_along_axis(mine, idx, True, axis=1)
        only = mine ^ chosen                        # on one side alone
        rows = only.any(axis=1)
        pairs += tokens
        differ += int(rows.sum())
        kinds = {"held": only[:, :held], "absent": only[:, held:total],
                 "zero": only[:, total:]}
        for kind, cols in kinds.items():
            swaps[kind] += int(cols.any(axis=1).sum())
        counted |= kinds["held"].any(axis=1) | kinds["zero"].any(axis=1)
        print(json.dumps({"layer": layer, "tokens_differing": int(rows.sum()),
                          **{k: int(v.any(axis=1).sum())
                             for k, v in kinds.items()}}), flush=True)

    pick = got.argmax(axis=1)
    margin = want.max(axis=1) - want[np.arange(tokens), pick]
    err = np.abs(got - want).max(axis=1)
    first = tokens // 4

    def worst(mask):
        mask = mask.copy()
        mask[:first] = False
        return (float(margin[mask].max()) if mask.any() else None,
                float(err[mask].max()) if mask.any() else None,
                int(mask.sum()))

    # A swap moves every later position too (attention reads its row), so
    # the split is by position: from the first counted swap on, or before.
    after = np.maximum.accumulate(counted)
    print(json.dumps({
        "tokens": tokens, "seed": seed, "token_layer_pairs": pairs,
        "pairs_differing": differ, "pairs_differing_share": differ / pairs,
        "swaps_by_kind": swaps,
        "positions_with_a_counted_swap": int(counted.sum()),
        "margin_err_n_at_positions_with_a_counted_swap": worst(counted),
        "margin_err_n_at_positions_without": worst(~counted),
        "margin_err_n_before_the_first_counted_swap": worst(~after),
        "logit_scale": float(np.abs(want).max())}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
