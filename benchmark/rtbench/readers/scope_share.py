"""Self seconds of the device operations under some named scopes of the
program, over the busy seconds of the first chip, in percent.

``part_share`` partitions a trace by the vocabulary ``rtbench/
xplane_meta.py`` knows (``PARTS``), and an operation belongs to the
innermost of those names on its path. A model may open finer scopes inside
a part (``ray_tpu/util/tracing.py`` ``SUBPARTS``: a short convolution's
``conv`` and ``conv_state`` inside ``attn``, the operator's place); those
readers book such an operation to the part around it. This reader is given
the finer names (``params["scopes"]``) and finds them on the same path: an
operation counts when the innermost of ``PARTS`` and the given names on
its path is one of the given. So the share it reads lies *inside* the
share of the part around it and outside a cell's sum to 100, until the
vocabulary there learns the names.

None when no operation of the trace carries any of the scopes: a commit
without them, whose line then lacks the metric.
"""

from rtbench import xplane_meta

_WRAPPERS = ("transpose(", "jvp(", "vmap(")


def innermost(tf_op, names) -> str | None:
    """The innermost of ``names`` on a name-stack path, as
    ``xplane_meta.part_of`` walks it; None where there is none."""
    found = None
    for seg in (tf_op or "").rstrip(":").split("/"):
        while seg.startswith(_WRAPPERS):
            seg = seg[seg.index("(") + 1:]
        if "(" in seg:
            continue
        seg = seg.rstrip(")")
        if seg in names:
            found = seg
    return found


def scoped_ops(dev, scopes) -> list:
    """The operations whose innermost known name is one of ``scopes``."""
    names = set(xplane_meta.PARTS) | set(scopes)
    return [op for op in dev.ops if innermost(op.tf_op, names) in scopes]


def read(obs, params):
    if obs.get("trace") is None:
        return None
    dev = xplane_meta.of(obs)
    if dev is None:
        return None
    ops = scoped_ops(dev, set(params["scopes"]))
    busy = dev.busy_s()
    if not ops or not busy:
        return None
    return 100.0 * sum(op.self_s for op in ops) / busy
