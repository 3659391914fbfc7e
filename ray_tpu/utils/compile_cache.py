"""Where JAX's persistent compilation cache lives.

A fresh process compiles every program again, and on the TPU one train step
or one engine program takes from seconds to minutes. A cache whose directory
moves between runs is never found again, so the directory is placed from
outside the code: by ``JAX_COMPILATION_CACHE_DIR`` where that is set, and
otherwise at one fixed path inside the checkout.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# JAX leaves location metadata (name stacks, source lines) out of the
# cache's key by default, and an executable keeps the metadata it was
# compiled with: a program cached before a ``tracing.part`` scope existed
# was served to the tree that had it, and its device trace showed the old
# name stacks (my chip run, PR 36: ``devbench/trace_parts_probe.py stale``).
# With the metadata in the key the trace names what the source says.
METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"

# <checkout>/.jax_cache (git-ignored). Fixed: never a temp dir, a pid or a
# timestamp, so every process of every run of this checkout shares it.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Make sure the persistent compilation cache has a directory; returns
    it. Called wherever the program first compiles, and at worker start.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it by itself and
    nothing is set here. Without it the cache goes to :data:`DEFAULT_DIR`:
    the variable is set for this process and the workers it forks, which is
    all a process that has not imported JAX yet needs (a cluster worker must
    not pay for the import, and must not touch a backend, before a task
    asks for JAX); a process that has imported it gets the same value
    through ``jax.config``. The cache's own thresholds (a program that
    compiles in under a second is not stored) stay at JAX's defaults.
    Either way the key holds the programs' metadata
    (:data:`METADATA_IN_KEY`), set the same two ways."""
    os.environ[METADATA_IN_KEY.upper()] = "true"
    path = os.environ.get(ENV_VAR)
    if not path:
        path = os.environ[ENV_VAR] = DEFAULT_DIR
    if "jax" in sys.modules:
        import jax

        jax.config.update(METADATA_IN_KEY, True)
        if path == DEFAULT_DIR:
            jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return path
