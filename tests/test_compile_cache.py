"""The persistent compilation cache is placed from outside the code
(ray_tpu/utils/compile_cache.py): JAX_COMPILATION_CACHE_DIR where set, one
fixed directory inside the checkout otherwise."""

import os
import subprocess
import sys

from ray_tpu.utils.compile_cache import DEFAULT_DIR, ENV_VAR

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A process that has imported JAX (driver, trainer, engine) and one that has
# not yet (a cluster worker at start). In both the cache's key holds the
# programs' metadata, so a cached program carries the name stacks
# (``tracing.part``) of the source that asks for it.
_AFTER_IMPORT = ("import jax; "
                 "from ray_tpu.utils.compile_cache import ensure_compile_cache;"
                 " ensure_compile_cache(); "
                 "assert jax.config."
                 "jax_compilation_cache_include_metadata_in_key; "
                 "print(jax.config.jax_compilation_cache_dir)")
_BEFORE_IMPORT = ("from ray_tpu.utils.compile_cache import "
                  "ensure_compile_cache; ensure_compile_cache(); "
                  "import sys; assert 'jax' not in sys.modules; import jax; "
                  "assert jax.config."
                  "jax_compilation_cache_include_metadata_in_key; "
                  "print(jax.config.jax_compilation_cache_dir)")


def _spawn(code, cwd, cache_env):
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env["PYTHONPATH"] = _REPO_ROOT
    env["JAX_PLATFORMS"] = "cpu"
    if cache_env is not None:
        env[ENV_VAR] = cache_env
    return subprocess.Popen([sys.executable, "-c", code], cwd=cwd, env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


def test_cache_dir_follows_env_else_fixed_checkout_path(tmp_path):
    outside = str(tmp_path / "from_outside")
    procs = [_spawn(_AFTER_IMPORT, _REPO_ROOT, None),
             _spawn(_BEFORE_IMPORT, str(tmp_path), None),
             _spawn(_AFTER_IMPORT, _REPO_ROOT, outside)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, err[-2000:]
        outs.append(out.strip())
    # Unset: the same in-checkout directory whatever the process and its cwd.
    assert outs[0] == outs[1] == DEFAULT_DIR
    assert DEFAULT_DIR == os.path.join(_REPO_ROOT, ".jax_cache")
    # Set: JAX's own handling, untouched.
    assert outs[2] == outside


def test_cache_dir_is_git_ignored():
    with open(os.path.join(_REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
