"""Device milliseconds of some parts of the model inside some programs,
over a count the program itself took as it dispatched them: what the
routed layer costs beyond its kernels (router, top-k, plan, gathers: the
parts ``moe_route``, ``moe_dispatch``, ``moe_combine``) inside the decode
programs, per decode step. ``kernel_ms_per_count`` reads a kernel by its
name; this reads every operation by the part on its name stack
(``rtbench/xplane_meta.py``), self times, so a loop does not count its
body twice.

Programs and dispatch phases are paired as ``program_per_count`` pairs
them (in order, whole programs only); the operations are those that begin
inside a paired program. None where the trace has no part (a commit
without the scopes) or no pair.
"""

import bisect

from rtbench import trace_reduce, xplane_meta
from rtbench.readers import phases, program_per_count


def read(obs, params):
    trace = obs.get("trace")
    if trace is None:
        return None
    dev = xplane_meta.of(obs)
    if dev is None or not dev.has_parts():
        return None
    dispatches = [p for p in phases.of(obs) if p.name == params["phase"]
                  and params["count"] in p.stats]
    programs = [e for e in trace.devices[0].modules
                if any(trace_reduce.module_base(e.name).startswith(x)
                       for x in params["programs"])]
    w0, w1 = trace.window()
    edge = program_per_count.EDGE_S
    pairs = [(d, e) for d, e in phases.pair_in_order(dispatches, programs)
             if e.start > w0 + edge and e.end < w1 - edge]
    count = sum(d.stats[params["count"]] for d, _ in pairs)
    if not count:
        return None
    ops = sorted((op for op in dev.ops if op.part in params["parts"]),
                 key=lambda op: op.start)
    starts = [op.start for op in ops]
    seconds = 0.0
    for _, prog in pairs:
        lo = bisect.bisect_left(starts, prog.start)
        hi = bisect.bisect_right(starts, prog.end)
        seconds += sum(op.self_s for op in ops[lo:hi])
    return seconds * 1e3 / count
