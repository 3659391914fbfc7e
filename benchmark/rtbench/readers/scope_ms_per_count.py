"""Device milliseconds of the operations under some named scopes inside
some programs, over a count the program itself took as it dispatched them:
what a short convolution (``conv``, ``conv_state``) costs inside the decode
programs, per decode step.

``part_ms_per_count`` with ``scope_share``'s test in place of the part's:
programs and dispatch phases are paired as ``program_per_count`` pairs them
(in order, whole programs only); the operations are those under the scopes
that begin inside a paired program, self times. None where the trace has
none of the scopes (a commit without them) or no pair.
"""

import bisect

from rtbench import trace_reduce, xplane_meta
from rtbench.readers import phases, program_per_count, scope_share


def read(obs, params):
    trace = obs.get("trace")
    if trace is None:
        return None
    dev = xplane_meta.of(obs)
    if dev is None:
        return None
    ops = sorted(scope_share.scoped_ops(dev, set(params["scopes"])),
                 key=lambda op: op.start)
    if not ops:
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
    starts = [op.start for op in ops]
    seconds = 0.0
    for _, prog in pairs:
        lo = bisect.bisect_left(starts, prog.start)
        hi = bisect.bisect_right(starts, prog.end)
        seconds += sum(op.self_s for op in ops[lo:hi])
    return seconds * 1e3 / count
