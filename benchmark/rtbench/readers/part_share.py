"""Self seconds of the device operations that belong to some parts of the
model, over the busy seconds of the first chip, in percent.

The program opens ``tracing.part(name)`` scopes inside its jitted functions
and the device trace carries each operation's name stack (``rtbench/
xplane_meta.py``): an operation belongs to the innermost part on its path,
to ``unnamed`` when its path has none (a hole in the scoping), to
``lowered`` when it has no path at all (the compiler's own copies and
slices between memory spaces). ``params``: ``parts``, a list of those
names; ``pass``, optional, one of ``fwd``, ``bwd``, ``remat`` (the
recomputed forward of ``jax.checkpoint``), which cuts across the parts.
Self times partition the busy time, so the shares of a cell's metrics that
list every name once sum to 100.

A fusion carries the metadata of its root operation: a norm fused into the
next matmul is booked with the matmul.

None when no operation of the trace has any part: a commit without the
scopes, whose line then lacks the metric.
"""

from rtbench import xplane_meta


def read(obs, params):
    if obs.get("trace") is None:
        return None
    dev = xplane_meta.of(obs)
    if dev is None or not dev.has_parts():
        return None
    busy = dev.busy_s()
    if not busy:
        return None
    return 100.0 * dev.seconds(params.get("parts"), params.get("pass")) / busy
