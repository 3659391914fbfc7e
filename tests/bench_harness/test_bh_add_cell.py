"""A configuration, a traffic mix and a per-layer metric are each added as
files plus an entry; no file that is there is edited."""

import hashlib
import json
import os
import shutil

from conftest import REPO
from rtbench import manifest


def _digests(root):
    out = {}
    for base, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in base:
            continue
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_cell_is_files_and_entries(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root)
    m = manifest.load(REPO)

    # files: a configuration, a traffic mix, a per-layer metric and its
    # reader
    cfg = manifest.load_json(root, "configs", "mistral-7b-v0.3.json")
    cfg["source"] = "https://example.org/new-model/config.json"
    cfg["num_hidden_layers"] = {"published": 40, "serve_burst": 10}
    new = {"configs/new-model.json": cfg,
           "traffic/serve-chat-burst.json": dict(
               manifest.load_json(root, "traffic", "serve-chat.json"),
               use="serve_burst", rate_per_s=1.0),
           "layer_metrics/engine_active_mean.json": {
               "name": "engine_active_mean", "reader": "stats_mean",
               "params": {"key": "active"}}}
    for rel, body in new.items():
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(body, f)
    with open(os.path.join(root, "benchmark", "rtbench", "readers",
                           "always_one.py"), "w") as f:
        f.write("def read(obs, params):\n    return 1.0\n")
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "one.json"), "w") as f:
        json.dump({"name": "one", "reader": "always_one"}, f)

    # entries
    m["configs"].append({"name": "new-model", "source": cfg["source"],
                         "file": "benchmark/configs/new-model.json",
                         "reduced": ["num_hidden_layers"], "why": "new"})
    m["workloads"] += [
        {"name": f"filler-{i}", "config": "new-model",
         "traffic": t, "chips": 1, "why": "keeps one four-chip cell in four"}
        for i, t in enumerate(["serve-chat", "serve-docqa", "train-4k"])]
    m["workloads"].append({"name": "newmodel-serve-chat-burst",
                           "config": "new-model",
                           "traffic": "serve-chat-burst", "chips": 1,
                           "why": "bursty arrivals"})
    cells = ["newmodel-serve-chat-burst"]
    for e in m["end_to_end"]:
        if e["name"] == "tpot_p90_ms":
            e["workloads"] = e["workloads"] + cells
    layer = "Engine scheduler (llm/engine.py _tick, _admit)"
    for name in ("engine_active_mean", "one"):
        m["per_layer"].append({"name": name, "unit": "requests",
                               "better": "higher",
                               "source": "program_counter", "layer": layer,
                               "moves": "tpot_p90_ms", "workloads": cells})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    errs = [e for e in manifest.check(manifest.load(root), root)
            if "filler" not in e]
    assert errs == []
    cell = manifest.load_cell("newmodel-serve-chat-burst", root)
    assert cell["config"]["num_hidden_layers"]["serve_burst"] == 10
    assert cell["traffic"]["rate_per_s"] == 1.0
    assert {x["name"]: x["reader"] for x in cell["per_layer"]} == {
        "engine_active_mean": "stats_mean", "one": "always_one"}

    # the harness reads the new metrics with the readers the files name
    import importlib
    import sys

    sys.path.insert(0, os.path.join(root, "benchmark", "rtbench", "readers"))
    try:
        from rtbench import readers

        importlib.import_module("always_one")
        readers_dir = os.path.join(root, "benchmark", "rtbench", "readers")
        readers.__path__.append(readers_dir)
        obs = {"t_open": 0.0, "t_close": 10.0,
               "polls": [(1.0, {"active": 3}), (2.0, {"active": 5})]}
        assert readers.read_all(cell["per_layer"], obs) == {
            "engine_active_mean": 4.0, "one": 1.0}
    finally:
        readers.__path__.remove(readers_dir)
        sys.path.pop(0)

    # nothing that was there changed
    after = _digests(root)
    assert {k: after[k] for k in before} == before
