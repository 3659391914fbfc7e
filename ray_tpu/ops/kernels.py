"""How the Pallas kernels of ``ops/`` run: the one place that decides.

Two decisions live here so that no kernel wrapper makes them on its own.

**Which implementation.** :func:`kernel_backend` names it:

- ``"mosaic"``: the compiled Mosaic kernel. Chosen when JAX's default
  backend is a TPU.
- ``"reference"``: the jnp implementation of the same op. Chosen on every
  other backend, so CPU tests and dry runs keep working.
- ``"interpret"``: the kernel body through the Pallas interpreter. Never
  chosen by detection; tests force it to run the exact kernel code on the CPU.

:func:`force_kernel_backend` overrides the detection for a block of code:
tests force ``"interpret"``, and ahead-of-time compilation for a TPU topology
from a host without one forces ``"mosaic"``.

**Where the operands live.** XLA cannot partition a Mosaic call ("Mosaic
kernels cannot be automatically partitioned"), so under a mesh of more than
one device a kernel runs per shard inside ``jax.shard_map``. A
:class:`KernelMesh` tells the wrapper which mesh axes shard the batch and the
head dimensions of its operands; callers that own a mesh build one with
``parallel.sharding.kernel_mesh`` and pass it down. ``None`` means one device,
or a caller already inside ``shard_map``: the kernel is called directly.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass

import jax
from jax.sharding import Mesh, PartitionSpec as P

BACKENDS = ("mosaic", "interpret", "reference")

# (backend, device_kind), set only through force_kernel_backend. A plain
# module global, not a ContextVar: the serving engine traces its programs on
# its own scheduler thread, which must see what the test's main thread forced.
_forced: tuple[str, str | None] | None = None


def kernel_backend() -> str:
    if _forced is not None:
        return _forced[0]
    return "mosaic" if jax.default_backend() == "tpu" else "reference"


def target_device_kind() -> str:
    """``device_kind`` of the device the kernels compile for: the default
    backend's first device unless a block forced another."""
    if _forced is not None and _forced[1] is not None:
        return _forced[1]
    return jax.devices()[0].device_kind


@contextlib.contextmanager
def force_kernel_backend(name: str, device_kind: str | None = None):
    """Run a block with the kernel implementation pinned to ``name``.
    ``device_kind`` names the compile target when it is not the default
    backend's device (compiling for a TPU topology from a CPU host)."""
    global _forced
    if name not in BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; one of {BACKENDS}")
    prev, _forced = _forced, (name, device_kind)
    try:
        yield
    finally:
        _forced = prev


@dataclass(frozen=True)
class KernelMesh:
    """The mesh a kernel's operands are sharded over: ``batch`` names the
    mesh axes of the leading (batch) dimension, ``heads`` the axis of the
    attention-head dimension (None: heads are replicated). Every other
    dimension reaches the kernel whole."""

    mesh: Mesh
    batch: tuple[str, ...] = ()
    heads: str | None = None

    def heads_spec(self, rank: int) -> P:
        """[batch, heads, ...] operands: q/k/v/out and their row statistics."""
        return P(self.batch or None, self.heads, *[None] * (rank - 2))

    def rows_spec(self, rank: int) -> P:
        """[batch, ...] operands with no head dimension."""
        return P(self.batch or None, *[None] * (rank - 1))

    def shard(self, fn, in_specs, out_specs):
        """``fn`` on each device's own shard. ``check_vma=False``: a
        pallas_call's outputs carry no varying-axes annotation."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
