"""The program's own phases in the profiler's trace (``tracing.phase`` in
``ray_tpu/util/tracing.py``: host events named ``engine.*`` or
``train.*`` on the device trace's clock, their counts as event stats),
and their joins with the device's programs. ``trace_reduce.load`` keeps
names and times alone, so the host plane is read again here, once a run.

A program that has no such phases (a parent commit) leaves the list
empty, and every reader built on it returns None.
"""

from __future__ import annotations

import dataclasses

from rtbench import common, trace_reduce


@dataclasses.dataclass
class Phase:
    name: str
    start: float   # seconds, the trace's clock
    end: float
    stats: dict


PREFIXES = ("engine.", "train.")


def load(path: str) -> list[Phase]:
    import jax

    out = []
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append(Phase(
                        e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats)))
    out.sort(key=lambda p: p.start)
    return out


def of(obs: dict, prefix: str = "engine.") -> list[Phase]:
    """The run's phases under ``prefix``, read once and kept in ``obs``
    (a test hands them in under ``obs["phases"]``)."""
    if "phases" not in obs:
        path = trace_reduce.find_xplane(common.trace_dir())
        obs["phases"] = load(path) if path else []
    return [p for p in obs["phases"] if p.name.startswith(prefix)]


def pair_in_order(dispatches: list[Phase], programs: list) -> list[tuple]:
    """(dispatch phase, program event) pairs. Each dispatch phase starts
    one program and the device runs programs in the order they were
    dispatched, but the trace cuts both sequences where it starts and
    ends: a program whose dispatch came before the trace, a dispatch
    whose program ran after it. Walking back from the last program, each
    takes the latest dispatch not yet taken that began no later than the
    program did; what finds no partner is left out, on both sides."""
    dispatches = sorted(dispatches, key=lambda p: p.start)
    pairs = []
    i = len(dispatches) - 1
    for prog in sorted(programs, key=lambda e: e.start, reverse=True):
        while i >= 0 and dispatches[i].start > prog.start:
            i -= 1
        if i < 0:
            break
        pairs.append((dispatches[i], prog))
        i -= 1
    pairs.reverse()
    return pairs


class Cover:
    """The shortest phase that covers each of a rising sequence of
    instants (phases nest: a tick holds its dispatches, fetches and
    emits)."""

    def __init__(self, phases: list[Phase]):
        self._todo = sorted(phases, key=lambda p: p.start, reverse=True)
        self._open: list[Phase] = []

    def at(self, t: float) -> Phase | None:
        while self._todo and self._todo[-1].start <= t:
            self._open.append(self._todo.pop())
        self._open = [p for p in self._open if p.end >= t]
        return min(self._open, key=lambda p: p.end - p.start, default=None)
