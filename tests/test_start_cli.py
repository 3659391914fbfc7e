"""Standalone cluster bring-up over real OS processes.

Capability parity with the reference's deployment path (reference:
python/ray/scripts/scripts.py:681 `ray start`, tested by
python/ray/tests/test_cli.py): a head and two worker-node daemons launched
as SEPARATE SUBPROCESSES over localhost TCP, driven through the public
`ray_tpu.init(address=...)` API — tasks, actors, placement groups — and
surviving a daemon SIGKILL.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

import pytest


def _env():
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    # Subprocesses stay on the CPU backend.
    env["JAX_PLATFORMS"] = "cpu"
    return env


from _test_util import load_factor as _load_factor  # noqa: E402


def _cli(*argv, timeout=60):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu", *argv],
        env=_env(), capture_output=True, text=True,
        timeout=timeout * _load_factor())


@pytest.fixture
def temp_dir(tmp_path):
    return str(tmp_path / "rtpu")


def _read(path):
    with open(path) as f:
        return f.read().strip()


def _start_cluster(temp_dir, n_nodes=2):
    """head (no local daemon) + n worker-node daemons, all detached
    subprocesses. Returns (address, [node_ids])."""
    r = _cli("start", "--head", "--head-only", "--port", "0",
             "--temp-dir", temp_dir)
    assert r.returncode == 0, r.stderr + r.stdout
    address = _read(os.path.join(temp_dir, "head.addr"))
    node_ids = []
    for i in range(n_nodes):
        nid = f"testnode{i}"
        r = _cli("start", "--address", address, "--num-cpus", "2",
                 "--resources", '{"slot": 1}', "--node-id", nid,
                 "--temp-dir", temp_dir)
        assert r.returncode == 0, r.stderr + r.stdout
        node_ids.append(nid)
    return address, node_ids


def _stop(temp_dir):
    _cli("stop", "--temp-dir", temp_dir)


def test_start_head_nodes_tasks_actors_pgs(temp_dir):
    import ray_tpu
    from ray_tpu.util.placement_group import (
        PlacementGroupSchedulingStrategy, placement_group)

    address, node_ids = _start_cluster(temp_dir)
    try:
        ray_tpu.init(address=address)

        # Tasks cross the process boundary to daemon-forked workers.
        @ray_tpu.remote
        def add(a, b):
            return a + b

        assert ray_tpu.get([add.remote(i, 10) for i in range(4)]) == \
            [10, 11, 12, 13]

        # Actors: create, call, named lookup, kill.
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def inc(self):
                self.n += 1
                return self.n

        c = Counter.options(name="cnt").remote()
        assert ray_tpu.get([c.inc.remote() for _ in range(3)]) == [1, 2, 3]
        assert ray_tpu.get(
            ray_tpu.get_actor("cnt").inc.remote()) == 4

        # Placement group across the two standalone nodes.
        pg = placement_group([{"CPU": 1}, {"CPU": 1}],
                             strategy="STRICT_SPREAD")
        assert pg.ready(timeout=30)

        @ray_tpu.remote
        def where():
            return os.environ.get("RTPU_NODE_ID", "")

        homes = ray_tpu.get([
            where.options(
                num_cpus=1,
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    pg, i)).remote()
            for i in range(2)])
        assert len(set(homes)) == 2, homes
    finally:
        try:
            ray_tpu.shutdown()
        finally:
            _stop(temp_dir)


def test_daemon_sigkill_survival_and_stop(temp_dir):
    import ray_tpu

    # Load-gated deadlines (not bare wall clock): under full-suite load on a
    # 1-core box the surviving daemon's re-lease + worker boot can take
    # several times the isolated-run latency.
    slack = _load_factor()
    address, node_ids = _start_cluster(temp_dir)
    try:
        ray_tpu.init(address=address)

        @ray_tpu.remote(num_cpus=1)
        def pid():
            return os.getpid()

        assert len({p for p in ray_tpu.get(
            [pid.remote() for _ in range(4)],
            timeout=60 * slack)}) >= 1

        # SIGKILL one daemon process outright (kill -9 semantics).
        victim = node_ids[0]
        victim_pid = int(_read(os.path.join(temp_dir,
                                            f"node-{victim}.pid")))
        os.kill(victim_pid, signal.SIGKILL)
        deadline = time.monotonic() + 30 * slack
        while time.monotonic() < deadline:
            try:
                os.kill(victim_pid, 0)
                time.sleep(0.1)
            except ProcessLookupError:
                break

        # The cluster keeps serving: every task lands on the survivor.
        results = ray_tpu.get([pid.remote() for _ in range(4)],
                              timeout=120 * slack)
        assert len(results) == 4
    finally:
        try:
            ray_tpu.shutdown()
        finally:
            _stop(temp_dir)

    # stop reaped everything: pids gone, processes dead.
    leftovers = [n for n in os.listdir(temp_dir) if n.endswith(".pid")]
    assert leftovers == []


def test_init_auto_reads_started_head(temp_dir, monkeypatch):
    import ray_tpu

    monkeypatch.setenv("RAY_TPU_TEMP_DIR", temp_dir)
    r = _cli("start", "--head", "--port", "0", "--num-cpus", "2",
             "--temp-dir", temp_dir)
    assert r.returncode == 0, r.stderr + r.stdout
    try:
        ray_tpu.init(address="auto")

        @ray_tpu.remote
        def f():
            return "ok"

        assert ray_tpu.get(f.remote()) == "ok"
    finally:
        try:
            ray_tpu.shutdown()
        finally:
            _stop(temp_dir)
