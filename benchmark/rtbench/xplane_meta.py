"""What the device trace says of each operation beyond its name and time:
the part of the model it belongs to, the pass, the source line.

``jax.profiler.ProfileData`` (JAX 0.9) gives an event's name, start and
duration and no more. The ``.xplane.pb`` itself carries, on the device
plane's ``event_metadata``, for every HLO operation the stats ``tf_op``
(JAX's name stack when the operation was traced: ``jit(_step)/transpose(
jvp(stack))/while/body/closed_call/checkpoint/rematted_computation/attn/
dot_general:``), ``source`` (Python file:line) and ``program_id``. The
program opens ``tracing.part(name)`` scopes (``ray_tpu/util/tracing.py``,
``PARTS``) inside its jitted functions, and a scope's name lands on that
path: so every operation's self time can be booked to the innermost part
on its path. A fusion carries the metadata of one of its operations (its
root): a norm fused into the next matmul is booked with the matmul.

This module reads the file with a small decoder of the protobuf wire
format for the messages involved (XSpace.planes -> XPlane.lines /
event_metadata / stat_metadata -> XLine.events -> XEvent, and XStat with
``str_value`` or ``ref_value``): no dependency beyond Python. Field
numbers are those of tsl/profiler/protobuf/xplane.proto.

Never imports JAX or the program: ``PARTS`` is the benchmark's own copy
of the program's vocabulary (a test holds the two equal), so the same
files read a trace of a commit that has no scopes, and find no part.
"""

from __future__ import annotations

import dataclasses
import re

from rtbench import trace_reduce

# The program's vocabulary (ray_tpu/util/tracing.py PARTS), and the two
# names a path can resolve to besides: UNNAMED, a path with no part on it
# (a hole in the scoping), and LOWERED, no path at all (the compiler's own
# operations: copies and slices between memory spaces, broadcasts).
PARTS = ("embed", "attn", "cache", "mlp", "moe_route", "moe_dispatch",
         "moe_experts", "moe_combine", "head", "loss", "sample", "loop",
         "optim", "stack")
UNNAMED = "unnamed"
LOWERED = "lowered"
PASSES = ("fwd", "bwd", "remat")

# A scope opened outside a transformed function is wrapped by the
# transformation's name, one opened inside is bare.
_WRAPPERS = ("transpose(", "jvp(", "vmap(")


def part_of(tf_op: str | None) -> str:
    """The innermost part on a name-stack path; UNNAMED for a path without
    one, LOWERED for no path. ``jit(name)`` is a function's name and never
    a part."""
    if not tf_op:
        return LOWERED
    found = UNNAMED
    for seg in tf_op.rstrip(":").split("/"):
        while seg.startswith(_WRAPPERS):
            seg = seg[seg.index("(") + 1:]
        if "(" in seg:
            continue
        seg = seg.rstrip(")")
        if seg in PARTS:
            found = seg
    return found


def pass_of(tf_op: str | None) -> str:
    """``remat`` for the recomputed forward of ``jax.checkpoint``, ``bwd``
    for the transposed (backward) pass, else ``fwd``."""
    if tf_op:
        if "rematted_computation" in tf_op:
            return "remat"
        if "transpose(" in tf_op:
            return "bwd"
    return "fwd"


@dataclasses.dataclass
class Op(trace_reduce.Event):
    """One ``XLA Ops`` event: ``trace_reduce.Event`` (name, start, end in
    seconds, self_s, leaf) and its metadata."""
    tf_op: str | None = None
    source: str | None = None
    program_id: int | None = None
    part: str = LOWERED
    pass_: str = "fwd"


@dataclasses.dataclass
class DeviceOps:
    ordinal: int
    ops: list[Op]                        # with self times, by start
    modules: list[trace_reduce.Event]    # "XLA Modules"

    def busy_s(self) -> float:
        """The self times partition the chip's busy time (a ``%while``
        contains its body): their sum is the union of the intervals."""
        return sum(op.self_s for op in self.ops)

    def has_parts(self) -> bool:
        return any(op.part in PARTS for op in self.ops)

    def program_names(self) -> dict[int, str]:
        """program_id -> ``jit_<fn>``, from the module events' names
        (``jit_<fn>(<program_id>)``)."""
        out = {}
        for e in self.modules:
            m = re.search(r"\((\d+)\)$", e.name)
            if m:
                out[int(m.group(1))] = trace_reduce.module_base(e.name)
        return out

    def seconds(self, parts=None, pass_=None) -> float:
        """Self seconds of the operations in ``parts`` (any, when None)
        and of the pass ``pass_`` (any, when None)."""
        return sum(op.self_s for op in self.ops
                   if (parts is None or op.part in parts)
                   and (pass_ is None or op.pass_ == pass_))


# ---- the wire format ------------------------------------------------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    x = buf[i]
    i += 1
    if x < 0x80:
        return x, i
    x &= 0x7F
    shift = 7
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, wire type, value) of one message: a varint's value,
    or the (start, end) of a length-delimited or fixed field's bytes."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = (i, i + 8)
            i += 8
        elif wire == 5:
            value = (i, i + 4)
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, value


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span: tuple[int, int]):
    """A map<int64, Message> entry: (key, span of the value)."""
    key, value = 0, (span[1], span[1])
    for num, _w, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


_WANTED_STATS = ("tf_op", "source", "program_id")


def _event_metadata(buf, span, stat_names) -> dict:
    """XEventMetadata -> {"name", and whichever of tf_op, source,
    program_id it carries}."""
    out = {"name": ""}
    for num, _w, v in _fields(buf, *span):
        if num == 2:                      # name: the whole HLO line
            out["name"] = _text(buf, v)
        elif num == 5:                    # stats: XStat
            stat_id, value = 0, None
            for snum, _sw, sv in _fields(buf, *v):
                if snum == 1:
                    stat_id = sv
                elif snum in (3, 4):      # uint64_value, int64_value
                    value = sv
                elif snum == 5:           # str_value
                    value = _text(buf, sv)
                elif snum == 7:           # ref_value: a stat_metadata name
                    value = stat_names.get(sv, "")
            key = stat_names.get(stat_id)
            if key in _WANTED_STATS and value is not None:
                out[key] = value
    return out


def _plane(buf, span):
    """XPlane -> (name, line spans, event-metadata spans by id, stat
    names by id)."""
    name, lines, metadata, stat_names = "", [], {}, {}
    for num, _w, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            key, value = _map_entry(buf, v)
            metadata[key] = value
        elif num == 5:
            key, value = _map_entry(buf, v)
            for snum, _sw, sv in _fields(buf, *value):
                if snum == 2:
                    stat_names[key] = _text(buf, sv)
    return name, lines, metadata, stat_names


def _line(buf, span):
    """XLine -> (name, timestamp_ns, event spans)."""
    name, timestamp_ns, events = "", 0, []
    for num, _w, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            timestamp_ns = v
        elif num == 4:
            events.append(v)
    return name, timestamp_ns, events


def _event(buf, span) -> tuple[int, int, int]:
    """XEvent -> (metadata_id, offset_ps, duration_ps)."""
    metadata_id = offset_ps = duration_ps = 0
    for num, _w, v in _fields(buf, *span):
        if num == 1:
            metadata_id = v
        elif num == 2:
            offset_ps = v
        elif num == 3:
            duration_ps = v
    return metadata_id, offset_ps, duration_ps


def load(path: str) -> DeviceOps:
    """The first device plane (lowest ordinal with operations) of an
    ``.xplane.pb``: every ``XLA Ops`` event with its metadata, self times
    as ``trace_reduce`` computes them, and the ``XLA Modules`` events."""
    with open(path, "rb") as f:
        buf = f.read()
    best = None
    for num, _w, v in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        # A plane's name comes before its lines in the file, but the
        # format does not promise that: read the plane's top level.
        name, lines, metadata, stat_names = _plane(buf, v)
        m = trace_reduce._DEVICE_PLANE.match(name)
        if not m:
            continue
        ordinal = int(m.group(1))
        if best is not None and best.ordinal < ordinal:
            continue
        decoded: dict[int, dict] = {}
        ops, modules = [], []
        for span in lines:
            line_name, timestamp_ns, events = _line(buf, span)
            if line_name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in events:
                metadata_id, offset_ps, duration_ps = _event(buf, ev)
                md = decoded.get(metadata_id)
                if md is None:
                    md = decoded[metadata_id] = _event_metadata(
                        buf, metadata.get(metadata_id, (0, 0)), stat_names)
                # Whole nanoseconds, as ProfileData gives them: the same
                # events at the same times as trace_reduce.load's.
                start_ns = timestamp_ns + offset_ps // 1000
                start = start_ns * 1e-9
                end = (start_ns + duration_ps // 1000) * 1e-9
                if line_name == "XLA Modules":
                    modules.append(trace_reduce.Event(md["name"], start, end))
                    continue
                tf_op = md.get("tf_op") or None
                ops.append(Op(md["name"], start, end, tf_op=tf_op,
                              source=md.get("source") or None,
                              program_id=md.get("program_id"),
                              part=part_of(tf_op), pass_=pass_of(tf_op)))
        if ops:
            trace_reduce._self_times(ops)
            best = DeviceOps(ordinal, ops, modules)
    if best is None:
        raise ValueError(f"no operation ran on a device in the trace {path}")
    return best


def of(obs: dict) -> DeviceOps | None:
    """The run's operations, read once and kept in ``obs`` (a test hands
    them in under ``obs["device_ops"]``). None where the run left no
    trace."""
    if "device_ops" not in obs:
        from rtbench import common

        path = trace_reduce.find_xplane(common.trace_dir())
        obs["device_ops"] = load(path) if path else None
    return obs["device_ops"]
