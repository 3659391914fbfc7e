"""Reduction from a profiler trace (``.xplane.pb``) to numbers.

One module, checked on a small recorded trace (testdata/small.xplane.pb,
tests/bench_harness/test_trace_reduce.py). What a TPU trace looks like
(looked at by hand, my chip run, PR 23): one plane per chip named
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per program
execution, ``jit_<fn>(<hash>)``), ``XLA Ops`` (one event per HLO op, named
by its whole HLO line ``%flash_fwd.6 = (...) custom-call(...)``; a
``%while`` event *contains* its body's ops) and ``Async XLA Ops`` (copies
and collectives in flight); one plane ``/host:CPU`` with a line per host
thread. All start times are nanoseconds on one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)")
# "%name.12 = f32[8,128]{...} opcode(" -> name, dtype, dims
_OP_HEAD = re.compile(r"^%?([^\s=]+)\s*=\s*\(?\s*([a-z0-9]+)\[([0-9,]*)\]")


@dataclasses.dataclass
class Event:
    name: str
    start: float   # seconds
    end: float
    self_s: float = 0.0
    leaf: bool = True


def short_op_name(hlo_line: str) -> str:
    """``%fusion.4 = bf16[16,128]{...} fusion(...)`` -> ``fusion.4_bf16_16_128_``
    (the op and the shape of its first result: enough to recognise it in the
    program text, short enough for a ledger line)."""
    m = _OP_HEAD.match(hlo_line)
    if m is None:
        return hlo_line.lstrip("%").split(" ", 1)[0][:80]
    name, dtype, dims = m.groups()
    return f"{name}_{dtype}_{dims.replace(',', '_')}_"[:120]


def op_base(hlo_line: str) -> str:
    """The op's own name without the ``%`` and the trailing ``.N``."""
    name = hlo_line.lstrip("%").split(" ", 1)[0].split("=", 1)[0]
    return re.sub(r"\.\d+$", "", name)


def module_base(name: str) -> str:
    """``jit_prefill_chunk(123456)`` -> ``jit_prefill_chunk``."""
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list[tuple[float, float]],
             b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The part of union ``a`` not covered by union ``b`` (both sorted,
    disjoint)."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _self_times(events: list[Event]) -> None:
    """Fill ``self_s`` and ``leaf`` for events of one line, where an event
    may contain later ones (a while loop and its body)."""
    events.sort(key=lambda ev: (ev.start, -ev.end))
    stack: list[Event] = []
    for ev in events:
        ev.self_s = ev.end - ev.start
        while stack and stack[-1].end <= ev.start:
            stack.pop()
        if stack:
            parent = stack[-1]
            parent.leaf = False
            parent.self_s -= min(ev.end, parent.end) - ev.start
        stack.append(ev)


@dataclasses.dataclass
class DeviceTrace:
    ordinal: int
    ops: list[Event]          # "XLA Ops", with self times
    async_ops: list[Event]    # "Async XLA Ops"
    modules: list[Event]      # "XLA Modules"

    def busy(self) -> list[tuple[float, float]]:
        return union([(e.start, e.end) for e in self.ops])


@dataclasses.dataclass
class Trace:
    devices: list[DeviceTrace]
    host: dict[str, list[Event]]   # host thread line -> events

    # ---- window and busy -------------------------------------------------
    def window(self) -> tuple[float, float]:
        starts = [e.start for d in self.devices for e in d.ops + d.modules]
        ends = [e.end for d in self.devices for e in d.ops + d.modules]
        if not starts:
            raise ValueError("no operation ran on a device in this trace")
        return min(starts), max(ends)

    def window_s(self) -> float:
        w0, w1 = self.window()
        return w1 - w0

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(total(d.busy()) for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # ---- per program, per op, per kernel ---------------------------------
    def module_seconds(self) -> dict[str, float]:
        """Device seconds per program (``jit_<fn>``), averaged over chips."""
        out: dict[str, float] = {}
        for d in self.devices:
            for e in d.modules:
                key = module_base(e.name)
                out[key] = out.get(key, 0.0) + (e.end - e.start)
        return {k: v / len(self.devices) for k, v in out.items()}

    def module_counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for e in self.devices[0].modules:
            key = module_base(e.name)
            out[key] = out.get(key, 0) + 1
        return out

    def op_self_seconds(self) -> dict[str, float]:
        """Self seconds per op on the first chip, by short name."""
        out: dict[str, float] = {}
        for e in self.devices[0].ops:
            key = short_op_name(e.name)
            out[key] = out.get(key, 0.0) + e.self_s
        return out

    def kernel_events(self, prefix: str) -> list[Event]:
        """Leaf op events on the first chip whose op name starts with
        ``prefix`` (a Pallas kernel carries its ``name`` as the op name)."""
        return [e for e in self.devices[0].ops
                if e.leaf and op_base(e.name).startswith(prefix)]

    # ---- collectives -----------------------------------------------------
    def collective_seconds(self) -> tuple[float, float]:
        """(collective seconds, of which no compute op ran on that chip),
        averaged over the chips. A collective is an op, synchronous or in
        flight, whose name says so; compute is every other leaf op."""
        coll_total = exposed_total = 0.0
        for d in self.devices:
            coll = union(
                [(e.start, e.end) for e in d.ops
                 if e.leaf and _COLLECTIVE.match(op_base(e.name))]
                + [(e.start, e.end) for e in d.async_ops
                   if _COLLECTIVE.match(op_base(e.name))])
            compute = union([(e.start, e.end) for e in d.ops
                             if e.leaf
                             and not _COLLECTIVE.match(op_base(e.name))])
            coll_total += total(coll)
            exposed_total += total(subtract(coll, compute))
        n = len(self.devices)
        return coll_total / n, exposed_total / n

    # ---- breakdown -------------------------------------------------------
    def top_device_ops(self, n: int = 10) -> list[list]:
        ops = sorted(self.op_self_seconds().items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ops[:n]]

    def idle_gaps(self, n: int = 10, min_gap_s: float = 2e-6) -> list[list]:
        """The idle time of the first chip by what the host was doing: each
        gap between device ops goes to the shortest host event that covers
        its midpoint; summed by name."""
        busy = self.devices[0].busy()
        host = sorted((e for evs in self.host.values() for e in evs),
                      key=lambda e: e.start)
        starts = [e.start for e in host]
        import bisect

        # Longest host event bounds how far back a cover can start.
        longest = max((e.end - e.start for e in host), default=0.0)
        out: dict[str, float] = {}
        for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
            gap = s1 - e0
            if gap < min_gap_s:
                continue
            mid = e0 + gap / 2
            hi = bisect.bisect_right(starts, mid)
            lo = bisect.bisect_left(starts, mid - longest)
            best = None
            for ev in host[lo:hi]:
                if ev.end >= mid and (best is None or
                                      ev.end - ev.start < best.end - best.start):
                    best = ev
            name = _host_name(best.name) if best else "_no_host_event_"
            out[name] = out.get(name, 0.0) + gap
        gaps = sorted(out.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in gaps[:n]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_device_ops(),
                "idle_gaps": self.idle_gaps()}


def _host_name(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:$-]", "_", name)[:80]


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` with nothing but JAX."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {"XLA Ops": [], "Async XLA Ops": [], "XLA Modules": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] = [
                        Event(e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
            _self_times(lines["XLA Ops"])
            devices.append(DeviceTrace(int(m.group(1)), lines["XLA Ops"],
                                       lines["Async XLA Ops"],
                                       lines["XLA Modules"]))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host[line.name] = [
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]
    devices = [d for d in devices if d.ops or d.modules]
    if not devices:
        raise ValueError(f"no operation ran on a device in the trace {path}")
    devices.sort(key=lambda d: d.ordinal)
    return Trace(devices, host)
