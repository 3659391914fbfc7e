"""The manifest checker, on BENCHMARK.json as committed and on breaches."""

import copy
import json
import os
import subprocess
import sys

import pytest

from conftest import REPO
from rtbench import manifest


@pytest.fixture(scope="module")
def m():
    return manifest.load(REPO)


def test_the_committed_manifest_meets_the_contract(m):
    assert manifest.check(m, REPO) == []
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_every_cell_loads_with_its_files(m):
    for w in m["workloads"]:
        cell = manifest.load_cell(w["name"], REPO)
        assert cell["traffic"]["kind"] in ("train_steps", "open_loop",
                                           "closed_loop")
        assert cell["config"]["adapter"] in ("llama", "mixtral")
        names = {x["name"] for x in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"] and all("reader" in x
                                         for x in cell["per_layer"])
        for x in cell["per_layer"]:   # moves is reported by this cell
            assert x["moves"] in names


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
def test_the_checker_sees(m, breach):
    assert manifest.check(_break(m, BREACHES[breach]), REPO), breach


def test_without_a_tpu_the_run_fails_and_prints_no_metric(m):
    """The run command with JAX held to the CPU: another exit code than 0
    and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, *m["command"][1:], "--workload",
         "mistral7b-train-4k", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode not in (0, None)
    assert "needs a TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
    assert '"metrics"' not in out.stdout
