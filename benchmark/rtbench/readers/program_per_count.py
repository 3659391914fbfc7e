"""Device milliseconds of some programs over a count that the program
itself took as it dispatched them: decode steps (``engine.decode_dispatch``
carries ``steps``), prompt tokens (``engine.prefill_dispatch`` carries
``tokens``; ``per`` 1000 makes it per thousand). Programs and dispatch
phases are paired in order inside the trace (``phases.pair_in_order``),
and only pairs count, seconds and counts alike: nothing is inferred from
the clients' side. A program that was running when the trace began or
ended is recorded cut to the trace (looked at by hand, my chip run,
PR 24: a burst of 705 ms shows as 71 ms at the start and 160 ms at the
end), so a pair whose program touches either edge of the device's window
is left out as well."""

EDGE_S = 1e-6

from rtbench import trace_reduce
from rtbench.readers import phases


def read(obs, params):
    trace = obs.get("trace")
    if trace is None:
        return None
    dispatches = [p for p in phases.of(obs) if p.name == params["phase"]
                  and params["count"] in p.stats]
    programs = [e for e in trace.devices[0].modules
                if any(trace_reduce.module_base(e.name).startswith(x)
                       for x in params["programs"])]
    w0, w1 = trace.window()
    pairs = [(d, e) for d, e in phases.pair_in_order(dispatches, programs)
             if e.start > w0 + EDGE_S and e.end < w1 - EDGE_S]
    count = sum(d.stats[params["count"]] for d, _ in pairs)
    if not count:
        return None
    seconds = sum(e.end - e.start for _, e in pairs)
    return seconds * 1e3 / (count / params.get("per", 1))
