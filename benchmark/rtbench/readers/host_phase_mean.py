"""Mean over the host events of given names in the profiler's trace: of one
of their stats, or of their durations in seconds where ``stat`` is not
given, times ``scale``. For phases that a thread other than the scheduler's
opens (``tracing.phase`` in ``ray_tpu/serve/http_proxy.py``: a
``serve.chunk_out`` a streamed chunk, its ``lag_us`` the chunk's way from
the replica to the proxy and its duration the write; a ``serve.close`` a
stream's end), which ``readers/phases.py`` does not keep.

The ``/host:CPU`` plane is read once a run: every event whose name has the
shape of a phase's (dotted lower-case words, as ``engine.tick``; an XLA
operation's ``copy.22`` is none) is kept in
``obs`` under ``KEY`` by name, as (seconds, stats) pairs; a test hands them
in there. A trace without such events (a parent commit), a run without a
trace, or events that lack the stat give None.
"""

from __future__ import annotations

import re

from rtbench import common, trace_reduce

KEY = "host_phase_events"
_PHASE_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")


def load(path: str) -> dict[str, list[tuple[float, dict]]]:
    import jax

    out: dict[str, list[tuple[float, dict]]] = {}
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if _PHASE_NAME.match(e.name):
                    out.setdefault(e.name, []).append(
                        (e.duration_ns * 1e-9, dict(e.stats)))
    return out


def of(obs: dict) -> dict[str, list[tuple[float, dict]]]:
    if KEY not in obs:
        path = (trace_reduce.find_xplane(common.trace_dir())
                if obs.get("trace") is not None else None)
        obs[KEY] = load(path) if path else {}
    return obs[KEY]


def read(obs, params):
    events = of(obs)
    mine = [e for name in params["events"] for e in events.get(name, ())]
    stat = params.get("stat")
    values = ([seconds for seconds, _ in mine] if stat is None
              else [stats[stat] for _, stats in mine if stat in stats])
    if not values:
        return None
    return params.get("scale", 1.0) * sum(values) / len(values)
