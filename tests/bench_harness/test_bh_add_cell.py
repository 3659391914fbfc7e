"""A configuration, a traffic mix and a per-layer metric are each added as
files plus an entry; no file that is there is edited."""

import hashlib
import json
import os
import shutil

import pytest

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
        if e["name"] == "tpot_mean_ms":
            e["workloads"] = e["workloads"] + cells
    layer = "Engine scheduler (llm/engine.py _tick, _admit)"
    for name in ("engine_active_mean", "one"):
        m["per_layer"].append({"name": name, "unit": "requests",
                               "better": "higher",
                               "source": "program_counter", "layer": layer,
                               "moves": "tpot_mean_ms", "workloads": cells})
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


# ---------------------------------------------------------------------------
# A model kind and a traffic kind the harness has never seen: an adapter
# module, a reference, a kind module, a configuration, a traffic file and
# entries, all written here; then every check of test_bh_manifest.py on
# the copy. (Until PR 26 that file listed the adapters and kinds it knew,
# so the first model_config PR with an adapter of its own would have
# turned it red without being allowed to touch it.)

ADAPTER = '''"""A model kind of its own (written by test_bh_add_cell.py)."""
from rtbench.adapters import llama as _dense

REFERENCE = "reference.routed"
depth = _dense.depth
kv_bytes_per_token = _dense.kv_bytes_per_token
decode_attention_bytes = _dense.decode_attention_bytes


def decode_step_bytes(c, layers, live_kv_tokens, dtype_bytes=2):
    return 1.0


def model_config(config, use, max_seq_len):
    raise NotImplementedError


def reference_weights(params):
    return params
'''

KIND = '''"""A traffic kind of its own (written by test_bh_add_cell.py): a closed
loop whose plan it makes itself."""
from rtbench import common, gen

ADAPTER_NEEDS = ("REFERENCE", "model_config", "reference_weights")


def plan(traffic, seed, seconds):
    return gen.closed_loop_plan(traffic, seed, seconds)


def run(ctx):
    common.start_jax(ctx["cell"]["workload"]["chips"])   # no TPU: exits
    from rtbench.kinds import serve_common

    serve_common.run(ctx, plan, "closed_loop")
'''


@pytest.fixture(scope="module")
def copy_with_new_kinds(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("new_kinds"))
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root)
    m = manifest.load(REPO)
    bench = os.path.join(root, "benchmark")

    cfg = manifest.load_json(root, "configs", "mistral-7b-v0.3.json")
    cfg.update(source="https://example.org/routed-model/config.json",
               adapter="routed", num_local_experts=64,
               num_hidden_layers={"published": 16, "serve_reason": 16})
    traffic = dict(manifest.load_json(root, "traffic", "serve-reason.json"),
                   kind="replay")
    for rel, body in {"configs/routed-model.json": cfg,
                      "traffic/serve-replay.json": traffic}.items():
        with open(os.path.join(bench, rel), "w") as f:
            json.dump(body, f)
    for rel, src in {"rtbench/adapters/routed.py": ADAPTER,
                     "rtbench/kinds/replay.py": KIND,
                     "reference/routed.py": "def logits(c, w, t):\n"
                                            "    raise NotImplementedError\n"
                     }.items():
        with open(os.path.join(bench, rel), "w") as f:
            f.write(src)

    cell = "routed-serve-replay"
    m["configs"].append({"name": "routed-model", "source": cfg["source"],
                         "file": "benchmark/configs/routed-model.json",
                         "reduced": [], "why": "a routed expert layer"})
    m["workloads"].append({"name": cell, "config": "routed-model",
                           "traffic": "serve-replay", "chips": 1,
                           "why": "a kind and an adapter of its own"})
    # It reports what the dense control cell on the same traffic reports.
    for section in ("end_to_end", "per_layer"):
        for x in m[section]:
            if "mistral7b-serve-reason" in x.get("workloads", []):
                x["workloads"] = x["workloads"] + [cell]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return root, before, cell


@pytest.mark.parametrize("check", [
    "the_manifest_meets_the_contract", "every_cell_loads_with_its_files",
    "without_a_tpu_the_run_fails_and_prints_no_metric"])
def test_a_new_model_kind_and_traffic_kind_pass_every_manifest_check(
        copy_with_new_kinds, check):
    import test_bh_manifest as checks

    root, before, cell = copy_with_new_kinds
    assert manifest.load(root)["workloads"][-1]["name"] == cell
    getattr(checks, "check_" + check)(root)
    after = _digests(root)
    assert {k: after[k] for k in before} == before   # nothing there changed


def test_the_checker_sees_every_breach_on_the_copy_too(copy_with_new_kinds):
    import test_bh_manifest as checks

    root, _before, _cell = copy_with_new_kinds
    for breach in sorted(checks.BREACHES):
        checks.check_the_checker_sees(root, breach)


def test_the_new_cell_loads_with_its_adapter_and_kind(copy_with_new_kinds):
    root, _before, cell = copy_with_new_kinds
    loaded = manifest.load_cell(cell, root)
    assert loaded["config"]["adapter"] == "routed"
    assert loaded["traffic"]["kind"] == "replay"
    assert {"serve_tok_s", "setup_s"} == {x["name"]
                                          for x in loaded["end_to_end"]}
    assert "decode_attention_roofline.tok_s" in {
        x["name"] for x in loaded["per_layer"]}
    # and the adapter's lack of a function its readers call is seen
    path = os.path.join(root, "benchmark", "rtbench", "adapters", "routed.py")
    with open(path) as f:
        src = f.read()
    try:
        with open(path, "w") as f:
            f.write(src.replace("decode_attention_bytes = ", "_unused = "))
        errs = manifest.check_modules(manifest.load(root), root)
        assert len(errs) == 1 and "decode_attention_bytes" in errs[0]
    finally:
        with open(path, "w") as f:
            f.write(src)
