"""Device milliseconds of one kernel inside some programs, over a count the
program itself took as it dispatched them: the routed experts' grouped
matmuls inside the decode programs over decode steps. The trace names
kernels and programs and nothing between (an XLA fusion carries no scope),
so what a layer costs beyond its kernels is not in this number.

Programs and dispatch phases are paired as ``program_per_count`` pairs
them (in order, whole programs only); the kernel's events are those that
begin inside a paired program."""

import bisect

from rtbench import trace_reduce
from rtbench.readers import phases, program_per_count


def read(obs, params):
    trace = obs.get("trace")
    if trace is None:
        return None
    events = sorted(trace.kernel_events(params["kernel"]),
                    key=lambda e: e.start)
    if not events:
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
    starts = [e.start for e in events]
    seconds = 0.0
    for _, prog in pairs:
        lo = bisect.bisect_left(starts, prog.start)
        hi = bisect.bisect_right(starts, prog.end)
        seconds += sum(e.end - e.start for e in events[lo:hi])
    return seconds * 1e3 / count
