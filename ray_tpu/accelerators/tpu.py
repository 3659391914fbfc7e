"""TPU resource model: chip counting, visibility, topology, slice metadata.

Capability parity with the reference's TPU accelerator plugin (reference:
python/ray/_private/accelerators/tpu.py — TPU resource + ``TPU-{pod}-head``
marker resource, TPU_VISIBLE_CHIPS :38, GKE/GCE metadata autodetection :119,
topology tables :90, v2–v7 generations :67, chips-per-host rules :149-234,
worker-id labels :675) re-derived from public TPU platform facts, plus the
AcceleratorManager ABC shape (reference: accelerator.py:18).

Metadata access is injected (``metadata_getter``) so tests run without GCE.
"""

from __future__ import annotations

import os
from typing import Callable

TPU_RESOURCE = "TPU"
VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"
WORKER_ID_ENV = "TPU_WORKER_ID"
SLICE_NAME_ENV = "TPU_NAME"
TOPOLOGY_ENV = "TPU_TOPOLOGY"

# Generation → (chips per host, cores per chip). Public platform facts.
GENERATIONS: dict[str, dict] = {
    "v2": {"chips_per_host": 4, "cores_per_chip": 2},
    "v3": {"chips_per_host": 4, "cores_per_chip": 2},
    "v4": {"chips_per_host": 4, "cores_per_chip": 2},
    "v5p": {"chips_per_host": 4, "cores_per_chip": 2},
    "v5e": {"chips_per_host": 8, "cores_per_chip": 1},
    "v5litepod": {"chips_per_host": 8, "cores_per_chip": 1},
    "v6e": {"chips_per_host": 8, "cores_per_chip": 1},
    "v7x": {"chips_per_host": 4, "cores_per_chip": 2},
}


def parse_pod_type(pod_type: str) -> tuple[str, int]:
    """'v5p-64' → ('v5p', chips). The numeric suffix counts TensorCores for
    multi-core generations (so v5p-64 = 32 chips) and chips for single-core
    generations (v5e-64 = 64 chips)."""
    gen, _, size = pod_type.partition("-")
    gen = gen.lower()
    if gen not in GENERATIONS or not size.isdigit():
        raise ValueError(f"unrecognized TPU pod type {pod_type!r}")
    n = int(size)
    chips = n // GENERATIONS[gen]["cores_per_chip"]
    return gen, max(chips, 1)


def num_hosts(pod_type: str) -> int:
    gen, chips = parse_pod_type(pod_type)
    cph = GENERATIONS[gen]["chips_per_host"]
    return max(1, chips // cph)


def chips_per_host(pod_type: str) -> int:
    gen, chips = parse_pod_type(pod_type)
    return min(chips, GENERATIONS[gen]["chips_per_host"])


def require_tpu(program: str):
    """JAX's default device if it is a TPU; otherwise the process exits
    non-zero with the platform it found. For programs that measure on the
    chip or not at all (the devbench probes): they never fall
    back to another backend."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"{program}: needs a TPU, JAX found platform="
                         f"{device.platform!r} ({device.device_kind})")
    return device


def slice_head_resource(pod_type: str) -> str:
    """Marker resource placed only on worker 0 of a slice, used to reserve
    whole slices atomically (reference: TPU-{pod_type}-head)."""
    return f"TPU-{pod_type}-head"


class TpuAcceleratorManager:
    """Implements the accelerator-plugin surface for TPU hosts."""

    def __init__(self, env: dict | None = None,
                 metadata_getter: Callable[[str], str | None] | None = None):
        self._env = env if env is not None else os.environ
        self._metadata = metadata_getter or (lambda key: None)

    # -- identity ----------------------------------------------------------
    @staticmethod
    def get_resource_name() -> str:
        return TPU_RESOURCE

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return VISIBLE_CHIPS_ENV

    # -- detection ---------------------------------------------------------
    def get_current_node_accelerator_type(self) -> str | None:
        acc = self._env.get(ACCELERATOR_TYPE_ENV) or self._metadata(
            "accelerator-type")
        if not acc:
            return None
        return acc.partition("-")[0].lower()

    def get_current_pod_type(self) -> str | None:
        return self._env.get(ACCELERATOR_TYPE_ENV) or self._metadata(
            "accelerator-type")

    def get_current_node_num_accelerators(self) -> int:
        visible = self._env.get(VISIBLE_CHIPS_ENV)
        if visible:
            return len([c for c in visible.split(",") if c != ""])
        pod = self.get_current_pod_type()
        if pod:
            try:
                return chips_per_host(pod)
            except ValueError:
                return 0
        return 0

    def get_current_node_tpu_topology(self) -> str | None:
        return self._env.get(TOPOLOGY_ENV) or self._metadata("topology")

    def get_current_node_labels(self) -> dict[str, str]:
        """Node labels used by slice scheduling: slice name + worker id
        (reference: get_current_node_accelerator_labels tpu.py:675)."""
        labels = {}
        name = self._env.get(SLICE_NAME_ENV) or self._metadata("instance-id")
        if name:
            labels["rtpu.io/tpu-slice-name"] = str(name)
        wid = self._env.get(WORKER_ID_ENV) or self._metadata("agent-worker-number")
        if wid is not None:
            labels["rtpu.io/tpu-worker-id"] = str(wid)
        pod = self.get_current_pod_type()
        if pod:
            labels["rtpu.io/tpu-pod-type"] = pod
        return labels

    def get_current_node_resources(self) -> dict[str, float]:
        n = self.get_current_node_num_accelerators()
        if n == 0:
            return {}
        res = {TPU_RESOURCE: float(n)}
        pod = self.get_current_pod_type()
        wid = self._env.get(WORKER_ID_ENV) or self._metadata("agent-worker-number")
        if pod and str(wid) == "0":
            res[slice_head_resource(pod)] = 1.0
        return res

    # -- assignment --------------------------------------------------------
    @staticmethod
    def validate_resource_request_quantity(quantity: float) -> tuple[bool, str | None]:
        if quantity not in (0.5, 1.0, 2.0, 4.0, 8.0) and quantity != int(quantity):
            return False, "TPU request must be a whole chip count (or 0.5)"
        return True, None

    def set_visible_accelerator_ids(self, ids: list[str]) -> dict[str, str]:
        """Env to inject into a worker claiming these chips (reference: worker
        start claims TPU_VISIBLE_CHIPS — SURVEY.md §8.2 TPU note)."""
        return {VISIBLE_CHIPS_ENV: ",".join(ids)}
