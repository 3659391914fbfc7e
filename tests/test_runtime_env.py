"""runtime_env: env_vars, working_dir/py_modules packaging, plugins.

Mirrors the reference's runtime-env test surface (reference:
python/ray/tests/test_runtime_env*.py — env-var injection, working_dir
packaging round-trip, plugin hooks), against the in-process and cluster
runtimes.
"""

import os
import sys

import pytest

from ray_tpu.runtime_env import RuntimeEnv
from ray_tpu.runtime_env.packaging import upload_package, upload_runtime_env


class TestRuntimeEnvType:
    def test_validation(self, tmp_path):
        env = RuntimeEnv(env_vars={"A": "1"}, working_dir=str(tmp_path))
        assert env["env_vars"] == {"A": "1"}
        with pytest.raises(TypeError):
            RuntimeEnv(env_vars={"A": 1})
        with pytest.raises(ValueError):
            RuntimeEnv(working_dir="/nonexistent/dir")
        with pytest.raises(ValueError):
            RuntimeEnv(bogus_field=1)
        with pytest.raises(ValueError):
            RuntimeEnv(py_modules=["/nonexistent/mod"])

    def test_from_to_dict(self):
        env = RuntimeEnv.from_dict({"env_vars": {"X": "y"}})
        assert env.to_dict() == {"env_vars": {"X": "y"}}
        assert not env.has_uris()


class TestPackaging:
    def test_upload_and_extract_roundtrip(self, rt_start, tmp_path):
        rt = rt_start
        pkg = tmp_path / "proj"
        pkg.mkdir()
        (pkg / "mymod.py").write_text("MAGIC = 'xyz123'\n")
        (pkg / "sub").mkdir()
        (pkg / "sub" / "data.txt").write_text("hello")
        from ray_tpu.core.worker import global_worker

        uri = upload_package(global_worker.runtime, str(pkg))
        assert uri.startswith("kv://")
        # content-addressed: same tree, same URI
        assert upload_package(global_worker.runtime, str(pkg)) == uri

        from ray_tpu.runtime_env.packaging import UriCache

        cache = UriCache(str(tmp_path / "cache"))
        path = cache.get_or_extract(global_worker.runtime, uri)
        assert open(os.path.join(path, "sub", "data.txt")).read() == "hello"
        # cached: same dir back
        assert cache.get_or_extract(global_worker.runtime, uri) == path

    def test_upload_runtime_env_rewrites_paths(self, rt_start, tmp_path):
        from ray_tpu.core.worker import global_worker

        pkg = tmp_path / "wd"
        pkg.mkdir()
        (pkg / "f.txt").write_text("x")
        env = upload_runtime_env(global_worker.runtime,
                                 {"working_dir": str(pkg), "env_vars": {"A": "1"}})
        assert env["working_dir"].startswith("kv://")
        assert env["env_vars"] == {"A": "1"}


class TestExecution:
    def test_env_vars_injected(self, rt_start):
        rt = rt_start

        @rt.remote(runtime_env={"env_vars": {"RTPU_TEST_VAR": "hello42"}})
        def read_env():
            return os.environ.get("RTPU_TEST_VAR")

        assert rt.get(read_env.remote()) == "hello42"

    def test_py_modules_importable(self, rt_start, tmp_path):
        rt = rt_start
        mod_dir = tmp_path / "pymods" / "coolmod"
        mod_dir.mkdir(parents=True)
        (mod_dir / "__init__.py").write_text("VALUE = 777\n")

        @rt.remote(runtime_env={"py_modules": [str(mod_dir)]})
        def use_mod():
            import coolmod

            return coolmod.VALUE

        try:
            assert rt.get(use_mod.remote()) == 777
        finally:
            sys.modules.pop("coolmod", None)

    def test_pip_rejected(self, rt_start):
        rt = rt_start

        @rt.remote(runtime_env={"pip": ["requests"]}, max_retries=0)
        def nope():
            return 1

        with pytest.raises(Exception, match="immutable"):
            rt.get(nope.remote())

    def test_actor_runtime_env(self, rt_start):
        rt = rt_start

        @rt.remote(runtime_env={"env_vars": {"RTPU_ACTOR_VAR": "actorval"}})
        class A:
            def read(self):
                return os.environ.get("RTPU_ACTOR_VAR")

        a = A.remote()
        assert rt.get(a.read.remote()) == "actorval"


class TestPlugins:
    def test_custom_plugin(self, rt_start):
        rt = rt_start
        from ray_tpu.runtime_env.plugin import RuntimeEnvPlugin, register_plugin

        seen = {}

        class MyPlugin(RuntimeEnvPlugin):
            name = "config"

            def setup(self, value, runtime):
                seen.update(value)

        register_plugin(MyPlugin())

        @rt.remote(runtime_env={"config": {"knob": "v"}})
        def f():
            return 1

        assert rt.get(f.remote()) == 1
        assert seen == {"knob": "v"}


class TestClusterRuntimeEnv:
    def test_working_dir_ships_to_worker(self, tmp_path):
        """The packaged working_dir must be importable in a separate worker
        process (real shipping, not same-process sys.path)."""
        import ray_tpu

        pkg = tmp_path / "shipme"
        pkg.mkdir()
        (pkg / "shipped_module.py").write_text("TOKEN = 'shipped-ok'\n")
        ray_tpu.shutdown()
        ray_tpu.init(address="local-cluster", num_cpus=2)
        try:
            @ray_tpu.remote(runtime_env={"working_dir": str(pkg)})
            def load():
                import shipped_module

                return shipped_module.TOKEN

            assert ray_tpu.get(load.remote()) == "shipped-ok"

            # env_vars ride the scheduling key (regression: nested dicts made
            # the key unhashable, breaking every cluster task with env_vars)
            @ray_tpu.remote(runtime_env={"env_vars": {"RTPU_CL_VAR": "clv"}})
            def read_var():
                return os.environ.get("RTPU_CL_VAR")

            assert ray_tpu.get(read_var.remote()) == "clv"

            # Worker isolation: a no-env task must not see the env'd worker's
            # variables (the pool brands workers by env hash).
            @ray_tpu.remote
            def clean_env():
                return os.environ.get("RTPU_CL_VAR")

            assert ray_tpu.get(clean_env.remote()) is None
        finally:
            ray_tpu.shutdown()

    def test_plugin_field_accepted_in_validation(self):
        from ray_tpu.runtime_env.plugin import RuntimeEnvPlugin, register_plugin

        class ImgPlugin(RuntimeEnvPlugin):
            name = "image_uri_test"

            def setup(self, value, runtime):
                pass

        register_plugin(ImgPlugin())
        env = RuntimeEnv.from_dict({"image_uri_test": "img://x"})
        assert env["image_uri_test"] == "img://x"


_STUB_RUNNER = r'''#!/usr/bin/env python3
"""Stub container runner mimicking the `podman run` CLI: parses the flags
wrap_worker_command emits, then execs the worker with ONLY the -e-propagated
environment (so missing propagation breaks the worker boot, like a real
container would)."""
import os
import sys

args = sys.argv[1:]
assert args and args[0] == "run", args
args = args[1:]
env = {}
i = 0
while i < len(args):
    a = args[i]
    if a in ("--rm", "--network=host", "--ipc=host", "--pid=host"):
        i += 1
    elif a == "-v":
        i += 2
    elif a == "-e":
        k, v = args[i + 1].split("=", 1)
        env[k] = v
        i += 2
    else:
        break
image = args[i]
cmd = args[i + 1:]
env["RTPU_CONTAINERIZED_IMAGE"] = image
os.execvpe(cmd[0], cmd, env)
'''


class TestContainerRuntimeEnv:
    def test_wrap_worker_command_shape(self):
        """Command construction contract (reference:
        _private/runtime_env/image_uri.py podman wrapping)."""
        from ray_tpu.runtime_env.container import wrap_worker_command

        cmd = wrap_worker_command(
            ["python", "-m", "worker"],
            {"RTPU_HEAD": "h:1", "MY": "x"},
            {"image_uri": "docker.io/img:tag", "run_options": ["--gpus=all"]},
        )
        assert cmd[0:2] == ["podman", "run"]
        img_at = cmd.index("docker.io/img:tag")
        # The host interpreter path is swapped for the image's python3.
        assert cmd[img_at + 1:] == ["python3", "-m", "worker"]
        head = cmd[:img_at]
        assert "--network=host" in head and "--rm" in head
        assert "--gpus=all" in head  # run_options precede the image
        # every env pair is forwarded
        pairs = [head[i + 1] for i, a in enumerate(head) if a == "-e"]
        assert "RTPU_HEAD=h:1" in pairs and "MY=x" in pairs

    def test_validation(self):
        from ray_tpu.runtime_env import RuntimeEnv

        env = RuntimeEnv(image_uri="img:1",
                         container_run_options=["--cpus=2"])
        assert env["image_uri"] == "img:1"
        with pytest.raises(TypeError):
            RuntimeEnv(image_uri=123)
        with pytest.raises(ValueError):
            RuntimeEnv(container_run_options=["--x"])  # without image_uri

    def test_container_worker_end_to_end(self, tmp_path, monkeypatch):
        """A task with image_uri runs in a worker launched THROUGH the
        container runner: the image marker is visible, runtime_env env_vars
        propagate across the -e boundary, and plain tasks still get plain
        (non-containerized) workers."""
        import stat

        import ray_tpu

        stub = tmp_path / "stub_podman.py"
        stub.write_text(_STUB_RUNNER)
        stub.chmod(stub.stat().st_mode | stat.S_IEXEC)
        monkeypatch.setenv("RTPU_CONTAINER_RUNNER", str(stub))

        ray_tpu.shutdown()
        ray_tpu.init(address="local-cluster", num_cpus=2)
        try:
            @ray_tpu.remote(runtime_env={"image_uri": "example.com/app:v7",
                                         "env_vars": {"APP_FLAG": "on"}})
            def inside():
                return (os.environ.get("RTPU_CONTAINERIZED_IMAGE"),
                        os.environ.get("APP_FLAG"))

            img, flag = ray_tpu.get(inside.remote(), timeout=120)
            assert img == "example.com/app:v7"
            assert flag == "on"

            @ray_tpu.remote
            def outside():
                return os.environ.get("RTPU_CONTAINERIZED_IMAGE")

            assert ray_tpu.get(outside.remote(), timeout=120) is None

            # Actors: the dedicated worker is containerized too.
            @ray_tpu.remote(runtime_env={"image_uri": "example.com/app:v7"})
            class Probe:
                def image(self):
                    return os.environ.get("RTPU_CONTAINERIZED_IMAGE")

            a = Probe.remote()
            assert ray_tpu.get(a.image.remote(),
                               timeout=120) == "example.com/app:v7"
        finally:
            ray_tpu.shutdown()

    def test_bad_image_fails_task_with_diagnostic(self, tmp_path, monkeypatch):
        """A container that cannot boot (runner exits nonzero) surfaces as a
        TaskError naming the image after a bounded number of boot attempts —
        never an infinite crash-fork loop with a hung client."""
        import ray_tpu
        from ray_tpu.core.exceptions import TaskError
        from ray_tpu.utils.config import get_config

        crasher = tmp_path / "crasher.py"
        crasher.write_text("#!/usr/bin/env python3\nraise SystemExit(125)\n")
        crasher.chmod(0o755)
        monkeypatch.setenv("RTPU_CONTAINER_RUNNER", str(crasher))
        # Fast corpse reaping so the failure budget is spent quickly. The
        # node daemon runs in this process and reads the cached config, so
        # drop the cache an earlier test may have filled (and again on the
        # way out, so no later test inherits the short TTL).
        monkeypatch.setenv("RTPU_WORKER_IDLE_TTL_S", "1")
        import ray_tpu.utils.config as config_mod

        monkeypatch.setattr(config_mod, "_global_config", None)

        ray_tpu.shutdown()
        ray_tpu.init(address="local-cluster", num_cpus=2)
        try:
            @ray_tpu.remote(runtime_env={"image_uri": "no.such/image:404"})
            def doomed():
                return 1

            with pytest.raises(TaskError) as ei:
                ray_tpu.get(doomed.remote(), timeout=120)
            assert "no.such/image:404" in str(ei.value)
        finally:
            ray_tpu.shutdown()
