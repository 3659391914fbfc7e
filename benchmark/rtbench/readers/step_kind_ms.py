"""Device milliseconds of a decode step by the kind of step it was, where a
program's steps are not all alike: a burst whose first steps each carry a
prefill chunk is one program and one event of the trace
(``llm/served.mixed_burst_program``), so the program's time over its steps
is a mean over two kinds of step and moves with how many chunks rode.

The program says which is which, twice. On the device the riding steps'
operations carry a named scope on their paths (``params["scope"]``:
``mixed_step`` of ``ray_tpu/util/tracing.py`` ``STEP_KINDS``, opened around
a step's parts, so it is looked for anywhere on a path and not innermost).
On the host every ``engine.decode_dispatch`` phase carries how many of its
steps took a chunk along (``params["riders"]``) beside how many it has
(``params["count"]``).

Programs and dispatch phases are paired as ``program_per_count`` pairs them
(in order, whole programs only, the same ``params["programs"]``). Over the
pairs: ``R`` riders, ``S`` steps, ``T`` the programs' device seconds, ``M``
the self seconds of the operations under the scope that begin inside a
paired program. ``params["kind"]`` chooses:

- ``mixed``: ``M / R``, a step that carries a chunk;
- ``plain``: ``(T - M) / (S - R)``, a step that carries none: the plain
  steps of mixed bursts, the bursts and single steps without riders, and
  what a program does before and after its steps;
- ``share``: ``100 R / S``, the steps that carried a chunk, in percent.

So ``share x mixed + (1 - share) x plain`` is ``T / S``, what
``program_per_count`` reads on the same trace.

None where no pair is found. Where chunks rode and no operation carries the
scope (a commit without it) the two times are None, never the mean over
both kinds under a new name; the share needs the phases alone.
"""

import bisect

from rtbench import trace_reduce, xplane_meta
from rtbench.readers import phases, program_per_count, scope_share


def read(obs, params):
    trace = obs.get("trace")
    if trace is None:
        return None
    count, riding = params["count"], params["riders"]
    dispatches = [p for p in phases.of(obs) if p.name == params["phase"]
                  and count in p.stats and riding in p.stats]
    programs = [e for e in trace.devices[0].modules
                if any(trace_reduce.module_base(e.name).startswith(x)
                       for x in params["programs"])]
    w0, w1 = trace.window()
    edge = program_per_count.EDGE_S
    pairs = [(d, e) for d, e in phases.pair_in_order(dispatches, programs)
             if e.start > w0 + edge and e.end < w1 - edge]
    steps = sum(d.stats[count] for d, _ in pairs)
    if not steps:
        return None
    riders = sum(d.stats[riding] for d, _ in pairs)
    if params["kind"] == "share":
        return 100.0 * riders / steps
    dev = xplane_meta.of(obs)
    if dev is None:
        return None
    scope = {params["scope"]}
    ops = sorted((op for op in dev.ops
                  if scope_share.innermost(op.tf_op, scope)),
                 key=lambda op: op.start)
    starts = [op.start for op in ops]
    mixed_s = 0.0
    for _, prog in pairs:
        lo = bisect.bisect_left(starts, prog.start)
        hi = bisect.bisect_right(starts, prog.end)
        mixed_s += sum(op.self_s for op in ops[lo:hi])
    if riders and not mixed_s:
        return None
    if params["kind"] == "mixed":
        return mixed_s * 1e3 / riders if riders else None
    plain = steps - riders
    if not plain:
        return None
    return (sum(e.end - e.start for _, e in pairs) - mixed_s) * 1e3 / plain
