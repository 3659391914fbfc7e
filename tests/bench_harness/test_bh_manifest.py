"""The manifest checker, on BENCHMARK.json as committed and on breaches."""

import copy
import json
import os
import subprocess
import sys

import pytest

from conftest import REPO
from rtbench import manifest


# Each check takes the root of a checkout, so that test_bh_add_cell.py can
# run all of them again on a copy that holds a model kind and a traffic
# kind this file has never heard of. No adapter and no kind is named here.


def check_the_manifest_meets_the_contract(root):
    assert manifest.check(manifest.load(root), root) == []
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 * 1024


def check_every_cell_loads_with_its_files(root):
    m = manifest.load(root)
    assert manifest.check_modules(m, root) == []
    for w in m["workloads"]:
        cell = manifest.load_cell(w["name"], root)
        names = {x["name"] for x in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"] and all("reader" in x
                                         for x in cell["per_layer"])
        for x in cell["per_layer"]:   # moves is reported by this cell
            assert x["moves"] in names


def check_the_checker_sees(root, breach):
    m = manifest.load(root)
    assert manifest.check(_break(m, BREACHES[breach]), root), breach


def check_without_a_tpu_the_run_fails_and_prints_no_metric(root):
    """The run command with JAX held to the CPU: another exit code than 0
    and no result line, in every cell (the last is the newest)."""
    m = manifest.load(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)   # the program, for a copy without it
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, *m["command"][1:], "--workload",
         m["workloads"][-1]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode not in (0, None)
    assert "needs a TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
    assert '"metrics"' not in out.stdout


def test_the_committed_manifest_meets_the_contract():
    check_the_manifest_meets_the_contract(REPO)


def test_every_cell_loads_with_its_files():
    check_every_cell_loads_with_its_files(REPO)


def _break(m, how):
    m = copy.deepcopy(m)
    how(m)
    return m


BREACHES = {
    "name with a space": lambda m: m["workloads"][0].update(name="a b"),
    "name with a slash": lambda m: m["end_to_end"][0].update(name="tok/s"),
    "unit with a space": lambda m: m["end_to_end"][0].update(
        unit="tokens per second"),
    "unit in Greek": lambda m: m["end_to_end"][0].update(unit="μs"),
    "bound over 0.1": lambda m: m["end_to_end"][0].update(bound=0.2),
    "config without a cell": lambda m: m["configs"].append(
        dict(m["configs"][0], name="orphan",
             file="benchmark/configs/orphan.json")),
    "moves not reported by the cell": lambda m: m["per_layer"][0].update(
        moves="serve_tok_s"),
    "moves unknown": lambda m: m["per_layer"][0].update(moves="nope"),
    "two four-chip cells in four": lambda m: m["workloads"][0].update(
        chips=4),
    "three chips": lambda m: m["workloads"][0].update(chips=3),
    "extra key on a metric": lambda m: m["per_layer"][0].update(why="x"),
    "reduced names a width": lambda m: m["configs"][0].update(
        reduced=["hidden_size"]),
    "no setup_s": lambda m: m["end_to_end"].pop(),
    "run_seconds over 51": lambda m: m.update(run_seconds=60),
    "path leaves the repo": lambda m: m.update(paths=["../x"]),
    "pair twice": lambda m: m["workloads"].append(
        dict(m["workloads"][0], name="again")),
    "end-to-end from a counter": lambda m: m["end_to_end"][0].update(
        source="program_counter"),
    "why over 200 characters": lambda m: m["workloads"][0].update(
        why="x" * 201),
}


@pytest.mark.parametrize("breach", sorted(BREACHES))
def test_the_checker_sees(breach):
    check_the_checker_sees(REPO, breach)


def test_without_a_tpu_the_run_fails_and_prints_no_metric():
    check_without_a_tpu_the_run_fails_and_prints_no_metric(REPO)


@pytest.mark.parametrize("what", ["kind", "adapter", "reference", "reader",
                                  "adapter-function"])
def test_check_modules_sees_a_missing(tmp_path, what):
    """A cell whose traffic names a kind, whose configuration names an
    adapter, or whose metric names a reader that is not there is refused
    before a run, and so is an adapter without a function that the cell's
    kind or readers call."""
    import shutil

    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    m = manifest.load(root)
    assert manifest.check_modules(m, root) == []
    w = m["workloads"][0]
    cfg_path = os.path.join(root, manifest.config_entry(m, w["config"])["file"])
    traffic_path = manifest.traffic_path(root, w["traffic"])
    cell = manifest.load_cell(w["name"], root)
    rt = os.path.join(root, "benchmark", "rtbench")

    def rewrite(path, **over):
        with open(path) as f:
            body = json.load(f)
        body.update(over)
        with open(path, "w") as f:
            json.dump(body, f)

    if what == "kind":
        rewrite(traffic_path, kind="never_written")
    elif what == "adapter":
        rewrite(cfg_path, adapter="never_written")
    elif what == "reference":
        path = os.path.join(rt, "adapters", cell["config"]["adapter"] + ".py")
        with open(path) as f:
            src = f.read()
        with open(path, "w") as f:
            f.write(src + '\nREFERENCE = "reference.never_written"\n')
    elif what == "reader":
        rewrite(os.path.join(root, "benchmark", "layer_metrics",
                             cell["per_layer"][0]["name"] + ".json"),
                reader="never_written")
    else:
        path = os.path.join(rt, "kinds", cell["traffic"]["kind"] + ".py")
        with open(path, "a") as f:
            f.write('\nADAPTER_NEEDS = ("a_function_no_adapter_has",)\n')
    errs = manifest.check_modules(m, root)
    assert any(w["name"] in e or "metric" in e for e in errs), errs
    assert manifest.check(m, root) == errs
