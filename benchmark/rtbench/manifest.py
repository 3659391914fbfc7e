"""BENCHMARK.json: loading, and the checks of the contract that need no run.

Never imports JAX.
"""

from __future__ import annotations

import ast
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")


def repo_root() -> str:
    """The checkout: the parent of ``benchmark/``."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(here))


def bench_dir(root: str | None = None) -> str:
    return os.path.join(root or repo_root(), "benchmark")


def load(root: str | None = None) -> dict:
    with open(os.path.join(root or repo_root(), "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(root: str | None, *parts: str) -> dict:
    with open(os.path.join(bench_dir(root), *parts)) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def metrics_of(manifest: dict, section: str, workload: str) -> list[dict]:
    """The metrics of ``section`` that ``workload`` reports: those with no
    ``workloads`` key, and those that list it."""
    return [m for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]]


def traffic_path(root: str | None, traffic: str) -> str:
    base = os.path.join(bench_dir(root), "traffic", traffic)
    for ext in TRAFFIC_EXT:
        if os.path.exists(base + ext):
            return base + ext
    raise FileNotFoundError(f"no traffic file {base}.*")


def load_cell(name: str, root: str | None = None) -> dict:
    """Everything a run of one cell needs, found by the names in
    BENCHMARK.json: the cell, its configuration file, its traffic file and
    its metrics with, for per-layer metrics, their reader files."""
    m = load(root)
    w = cell(m, name)
    c = config_entry(m, w["config"])
    with open(os.path.join(root or repo_root(), c["file"])) as f:
        config = json.load(f)
    with open(traffic_path(root, w["traffic"])) as f:
        traffic = json.load(f)
    layer = []
    for pm in metrics_of(m, "per_layer", name):
        spec = load_json(root, "layer_metrics", pm["name"] + ".json")
        layer.append({**pm, **{k: spec[k] for k in ("reader", "params")
                               if k in spec}})
    return {"manifest": m, "workload": w, "config_entry": c,
            "config": config, "traffic": traffic,
            "end_to_end": metrics_of(m, "end_to_end", name),
            "per_layer": layer, "run_seconds": m["run_seconds"]}


def module_names(path: str) -> dict | None:
    """The names a module defines at its top level, each with its value
    where that is a literal (None otherwise), read from the source and
    never by importing it (an adapter or a kind imports JAX and the
    program). None where there is no such file."""
    if not os.path.exists(path):
        return None
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    out: dict = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            out[node.name] = None
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                out[(a.asname or a.name).split(".")[0]] = None
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            try:
                value = ast.literal_eval(node.value)
            except (ValueError, TypeError, SyntaxError):
                value = None
            for t in targets:
                if isinstance(t, ast.Name):
                    out[t.id] = value
    return out


def check_modules(m: dict, root: str | None = None) -> list[str]:
    """What a cell's files name has to be there before a run: the traffic
    file's ``kind`` is a module ``rtbench/kinds/<kind>.py`` with ``run``;
    the configuration's ``adapter`` is a module ``rtbench/adapters/
    <adapter>.py`` that has ``depth`` and every name the kind and the
    cell's readers say they call on an adapter (their ``ADAPTER_NEEDS``, a
    literal tuple), and whose ``REFERENCE`` names a module under
    ``benchmark/reference/``; each per-layer metric's ``reader`` is a module
    with ``read``. A model kind or a traffic kind the harness has not seen
    passes by bringing its module. Nothing is imported."""
    errs: list[str] = []
    root = root or repo_root()
    rt = os.path.join(bench_dir(root), "rtbench")
    for w in m["workloads"]:
        try:
            cell = load_cell(w["name"], root)
        except (OSError, KeyError, ValueError) as e:
            errs.append(f"cell {w.get('name')}: {e!r}")
            continue
        kind = cell["traffic"].get("kind")
        names = module_names(os.path.join(rt, "kinds", f"{kind}.py"))
        if names is None or "run" not in names:
            errs.append(f"cell {w['name']}: traffic kind {kind!r} needs "
                        f"rtbench/kinds/{kind}.py with run()")
            continue
        needs = {"depth", *(names.get("ADAPTER_NEEDS") or ())}
        for x in cell["per_layer"]:
            reader = module_names(os.path.join(
                rt, "readers", f"{x.get('reader')}.py"))
            if reader is None or "read" not in reader:
                errs.append(f"metric {x['name']}: reader "
                            f"{x.get('reader')!r} needs rtbench/readers/"
                            f"{x.get('reader')}.py with read()")
                continue
            needs |= set(reader.get("ADAPTER_NEEDS") or ())
        adapter = cell["config"].get("adapter")
        have = module_names(os.path.join(rt, "adapters", f"{adapter}.py"))
        if have is None:
            errs.append(f"cell {w['name']}: adapter {adapter!r} needs "
                        f"rtbench/adapters/{adapter}.py")
            continue
        if needs - set(have):
            errs.append(f"cell {w['name']}: adapter {adapter!r} lacks "
                        f"{sorted(needs - set(have))}")
        ref = have.get("REFERENCE")
        if "REFERENCE" in needs and not (
                isinstance(ref, str) and ref.startswith("reference.")
                and os.path.exists(os.path.join(
                    bench_dir(root), *ref.split(".")) + ".py")):
            errs.append(f"cell {w['name']}: adapter {adapter!r} names the "
                        f"reference {ref!r}, which benchmark/reference/ "
                        "lacks")
    return errs


def check(m: dict, root: str | None = None) -> list[str]:
    """Every breach of the contract that can be seen without a run."""
    errs: list[str] = []
    root = root or repo_root()
    if set(m) != TOP_KEYS:
        errs.append(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
        return errs
    if not (1 <= len(m["paths"]) <= 16):
        errs.append("paths: 1 to 16 directories")
    for p in m["paths"]:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            errs.append(f"path {p!r}")
    if not isinstance(m["run_seconds"], int) or not 1 <= m["run_seconds"] <= 51:
        errs.append("run_seconds: a whole number from 1 to 51")
    if len(m["command"]) > 32:
        errs.append("command: at most 32 strings")

    def name_ok(kind: str, n) -> None:
        if not isinstance(n, str) or not NAME.match(n):
            errs.append(f"{kind} name {n!r} outside the allowed characters")

    def line_ok(kind: str, s) -> None:
        if not isinstance(s, str) or not 1 <= len(s) <= 200 or \
                "\n" in s or "\t" in s:
            errs.append(f"{kind} {s!r}: 1 to 200 characters on one line")

    seen: set[str] = set()

    def unique(kind: str, n: str) -> None:
        if (kind, n) in seen:
            errs.append(f"two {kind}s named {n!r}")
        seen.add((kind, n))

    # configs
    if not 1 <= len(m["configs"]) <= 24:
        errs.append("configs: 1 to 24")
    files = set()
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errs.append(f"config keys {sorted(c)}")
            continue
        name_ok("config", c["name"])
        unique("config", c["name"])
        line_ok("source", c["source"])
        line_ok("why", c["why"])
        if len(c["reduced"]) > 16:
            errs.append(f"config {c['name']}: more than 16 reduced keys")
        for k in c["reduced"]:
            name_ok("reduced key", k)
            if re.search(r"(_dim|_rank|hidden_size|intermediate_size|"
                         r"num_experts_per_tok|head_dim)$", k):
                errs.append(f"config {c['name']}: reduced names a width {k!r}")
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in m["paths"]):
            errs.append(f"config file {c['file']!r} outside paths")
        if c["file"] in files:
            errs.append(f"config file {c['file']!r} used twice")
        files.add(c["file"])
        if not os.path.exists(os.path.join(root, c["file"])):
            errs.append(f"config file {c['file']!r} missing")
    # workloads
    if not 1 <= len(m["workloads"]) <= 24:
        errs.append("workloads: 1 to 24")
    pairs = set()
    config_names = {c.get("name") for c in m["configs"]}
    used = set()
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errs.append(f"workload keys {sorted(w)}")
            continue
        name_ok("workload", w["name"])
        unique("workload", w["name"])
        name_ok("traffic", w["traffic"])
        line_ok("why", w["why"])
        if w["chips"] not in (1, 4):
            errs.append(f"workload {w['name']}: chips must be 1 or 4")
        if w["config"] not in config_names:
            errs.append(f"workload {w['name']}: unknown config {w['config']!r}")
        used.add(w["config"])
        if (w["config"], w["traffic"]) in pairs:
            errs.append(f"pair {(w['config'], w['traffic'])} appears twice")
        pairs.add((w["config"], w["traffic"]))
        try:
            traffic_path(root, w["traffic"])
        except FileNotFoundError as e:
            errs.append(str(e))
    for c in config_names - used:
        errs.append(f"config {c!r} is used by no cell")
    four = sum(1 for w in m["workloads"] if w.get("chips") == 4)
    if four > max(1, len(m["workloads"]) // 4):
        errs.append(f"{four} four-chip cells of {len(m['workloads'])}: at "
                    "most 25%, rounded down, and one always may")
    # metrics
    cells = [w.get("name") for w in m["workloads"]]
    e2e_cells: dict[str, set] = {}
    for sec, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                      "source"}),
                      ("per_layer", {"name", "unit", "better", "source",
                                     "layer", "moves"})):
        lim = 16 if sec == "end_to_end" else 128
        if not 1 <= len(m[sec]) <= lim:
            errs.append(f"{sec}: 1 to {lim} metrics")
        for x in m[sec]:
            if set(x) - {"workloads"} != keys:
                errs.append(f"{sec} metric keys {sorted(x)}")
                continue
            name_ok("metric", x["name"])
            unique("metric", x["name"])
            if not UNIT.match(x["unit"]):
                errs.append(f"metric {x['name']}: unit {x['unit']!r}")
            if x["better"] not in ("lower", "higher"):
                errs.append(f"metric {x['name']}: better {x['better']!r}")
            if x["source"] not in SOURCES:
                errs.append(f"metric {x['name']}: source {x['source']!r}")
            mine = set(x.get("workloads", cells))
            if not mine <= set(cells):
                errs.append(f"metric {x['name']}: unknown workloads "
                            f"{sorted(mine - set(cells))}")
            if sec == "end_to_end":
                e2e_cells[x["name"]] = mine
                if x["source"] not in ("host_clock", "device_trace"):
                    errs.append(f"end-to-end metric {x['name']}: source "
                                f"{x['source']!r}")
                if not 0 < x["bound"] <= 0.1:
                    errs.append(f"metric {x['name']}: bound {x['bound']}")
            else:
                line_ok("layer", x["layer"])
                if x["moves"] not in e2e_cells:
                    errs.append(f"metric {x['name']}: moves unknown "
                                f"{x['moves']!r}")
                elif not mine <= e2e_cells[x["moves"]]:
                    errs.append(
                        f"metric {x['name']}: moves {x['moves']!r}, which "
                        f"{sorted(mine - e2e_cells[x['moves']])} do not report")
                if not os.path.exists(os.path.join(
                        bench_dir(root), "layer_metrics",
                        x["name"] + ".json")):
                    errs.append(f"metric {x['name']}: no reader file")
    if "setup_s" not in e2e_cells or e2e_cells.get("setup_s") != set(cells):
        errs.append("setup_s must be an end-to-end metric of every cell")
    for c in cells:
        if sum(1 for n, s in e2e_cells.items() if c in s) < 2:
            errs.append(f"cell {c}: needs setup_s and one more end-to-end "
                        "metric")
        if not metrics_of(m, "per_layer", c):
            errs.append(f"cell {c}: needs a per-layer metric")
    return errs or check_modules(m, root)
