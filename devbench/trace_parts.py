"""A device trace by part of the model: who owns each microsecond.

    python3 devbench/trace_parts.py <trace dir or .xplane.pb> [--top N]
                                    [--json FILE]

Reads the first chip's ``XLA Ops`` with their metadata
(``benchmark/rtbench/xplane_meta.py``: the ``tracing.part`` scopes on each
operation's name stack, the pass, the source line) and prints

- the share of the busy time of every part (``unnamed``: a path with no
  part on it; ``lowered``: no path at all, the compiler's own copies and
  slices), split by pass (``fwd``, ``bwd``, ``remat``) and by program;
- the N largest operations by self time (default 20) with their part,
  pass, program and ``source`` file:line, so that ``fusion.282`` has a
  name;
- for ``unnamed`` and ``lowered``, their own largest operations.

A trace directory is what ``benchmark/run.py --trace 1`` leaves in
``.bench_trace/``; the newest ``.xplane.pb`` under it is read. Needs no
chip and no JAX. ``--json`` also writes the tables to a file.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from rtbench import trace_reduce, xplane_meta  # noqa: E402


def _source(op) -> str:
    if not op.source:
        return "-"
    return op.source.replace(ROOT + "/", "").replace("/root/repo/", "")


def tables(dev: xplane_meta.DeviceOps, top: int = 20) -> dict:
    programs = dev.program_names()
    busy = dev.busy_s()
    by_part = collections.defaultdict(
        lambda: dict.fromkeys(xplane_meta.PASSES, 0.0))
    by_program = collections.defaultdict(
        lambda: collections.defaultdict(float))
    by_op: dict = {}
    for op in dev.ops:
        program = programs.get(op.program_id, "?")
        by_part[op.part][op.pass_] += op.self_s
        by_program[program][op.part] += op.self_s
        key = (trace_reduce.short_op_name(op.name), program)
        row = by_op.get(key)
        if row is None:
            row = by_op[key] = {"op": key[0], "program": program,
                                "part": op.part, "pass": op.pass_,
                                "source": _source(op), "tf_op": op.tf_op,
                                "self_s": 0.0, "count": 0}
        row["self_s"] += op.self_s
        row["count"] += 1
    ops = sorted(by_op.values(), key=lambda r: -r["self_s"])
    holes = {name: [r for r in ops if r["part"] == name][:top // 2]
             for name in (xplane_meta.UNNAMED, xplane_meta.LOWERED)}
    return {"busy_s": busy, "parts": {k: dict(v) for k, v in by_part.items()},
            "programs": {k: dict(v) for k, v in by_program.items()},
            "top_ops": ops[:top], "holes": holes}


def render(t: dict) -> str:
    busy = t["busy_s"]
    out = [f"busy {busy:.6f} s on chip 0", "",
           "| part | % of busy | ms | fwd | bwd | remat |",
           "| --- | --- | --- | --- | --- | --- |"]
    order = [*xplane_meta.PARTS, xplane_meta.LOWERED, xplane_meta.UNNAMED]
    for part in order:
        row = t["parts"].get(part)
        if row is None:
            continue
        s = sum(row.values())
        out.append(f"| {part} | {100 * s / busy:.2f} | {s * 1e3:.3f} | "
                   + " | ".join(f"{row[p] * 1e3:.3f}"
                                for p in xplane_meta.PASSES) + " |")
    out += ["", "| program | ms | parts (% of the program) |",
            "| --- | --- | --- |"]
    for program, parts in sorted(t["programs"].items(),
                                 key=lambda kv: -sum(kv[1].values())):
        s = sum(parts.values())
        shares = ", ".join(
            f"{k} {100 * v / s:.1f}" for k, v in
            sorted(parts.items(), key=lambda kv: -kv[1]) if v / s >= 0.001)
        out.append(f"| {program} | {s * 1e3:.3f} | {shares} |")

    def op_rows(rows):
        lines = ["| operation | ms | % | calls | part | pass | program | "
                 "source |", "| --- | --- | --- | --- | --- | --- | --- | "
                 "--- |"]
        for r in rows:
            lines.append(
                f"| {r['op']} | {r['self_s'] * 1e3:.3f} | "
                f"{100 * r['self_s'] / busy:.2f} | {r['count']} | "
                f"{r['part']} | {r['pass']} | {r['program']} | "
                f"{r['source']} |")
        return lines

    out += ["", f"largest {len(t['top_ops'])} operations by self time:", ""]
    out += op_rows(t["top_ops"])
    for name, rows in t["holes"].items():
        if rows:
            out += ["", f"largest `{name}` operations:", ""] + op_rows(rows)
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--json")
    args = ap.parse_args(argv)
    path = args.trace
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
        if path is None:
            print(f"no .xplane.pb under {args.trace}", file=sys.stderr)
            return 1
    t = tables(xplane_meta.load(path), args.top)
    print(render(t))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(t, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
