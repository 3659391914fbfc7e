"""Bench harness contract tests (no TPU needed).

A benchmark measures on the chip or not at all: without a TPU, bench.py and
bench_serve.py exit non-zero and print no metric line. They never fall back
to the CPU and never print a number read from an earlier run's record."""

import os
import subprocess
import sys

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script", ["bench.py", "bench_serve.py"])
def test_bench_without_a_chip_fails_and_prints_no_metric(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=120, env=env, cwd=_REPO_ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "needs a TPU" in r.stderr
    with open(os.path.join(_REPO_ROOT, script)) as f:
        source = f.read()
    assert 'jax.config.update("jax_platforms"' not in source
