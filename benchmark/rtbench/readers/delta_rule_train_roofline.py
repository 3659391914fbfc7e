"""Roofline share of the gated delta rule in training
(``ops/gated_delta.py`` ``gated_delta_chunk`` on a batch, forward and
backward): the least time the rule's work needs over the device time of
every operation whose innermost scope is ``delta_rule``, all three passes
(forward, backward, and the forward that ``jax.checkpoint`` runs again), in
the traced steps. The yardstick is defined on the work
(``adapters/olmo_hybrid.delta_rule_train_token_work``: FLOPs and bytes a
token and linear layer, forward and backward, at the published ``Dk`` and
``Dv``), not on the implementation, so a later kernel is read against the
same numbers; the recomputed forward is time spent and not work needed, as
``train_mfu`` counts it.

Steps are counted from the trace, not assumed: the step programs
(``params["programs"]``, ``jit__step``) that lie whole inside the device's
window, each ``global_batch x seq_len`` tokens over the mesh's data-parallel
ranks on the first chip; the scopes' self seconds are summed inside those
programs alone, so a step cut by either edge of the trace counts on neither
side.

None where the trace has none of the scopes or no whole step: a program
without them (the parent commit) leaves the metric out.
"""

import bisect

from rtbench import trace_reduce, xplane_meta
from rtbench.readers import adapter_of, program_per_count, scope_share

ADAPTER_NEEDS = ("delta_rule_train_token_work", "linear_layers", "depth")


def read(obs, params):
    trace = obs.get("trace")
    if trace is None or obs.get("kind") != "train":
        return None
    dev = xplane_meta.of(obs)
    if dev is None:
        return None
    ops = sorted(scope_share.scoped_ops(dev, set(params["scopes"])),
                 key=lambda op: op.start)
    if not ops:
        return None
    w0, w1 = trace.window()
    edge = program_per_count.EDGE_S
    steps = [e for e in trace.devices[0].modules
             if any(trace_reduce.module_base(e.name).startswith(x)
                    for x in params["programs"])
             and e.start > w0 + edge and e.end < w1 - edge]
    starts = [op.start for op in ops]
    spent = sum(op.self_s for step in steps
                for op in ops[bisect.bisect_left(starts, step.start):
                              bisect.bisect_right(starts, step.end)])
    if not spent:
        return None
    cell, adapter, peaks = obs["cell"], adapter_of(obs), obs["peaks"]
    config, traffic = cell["config"], cell["traffic"]
    mesh = traffic["mesh"]
    tokens = (len(steps) * traffic["global_batch"] * traffic["seq_len"]
              // (mesh.get("dp", 1) * mesh.get("fsdp", 1)))
    work = adapter.delta_rule_train_token_work(config)
    layers = adapter.linear_layers(config,
                                   adapter.depth(config, traffic["use"]))
    least = tokens * layers * max(
        work["flops"] / peaks["bf16_flops_per_s"],
        work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
