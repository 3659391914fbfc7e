"""What every kind of run shares: the set-up clock, the compile counter,
the device record and the result line."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

from rtbench import manifest


def log(msg: str) -> None:
    """An earlier line of standard output (the result is the last)."""
    print(f"bench: {msg}", flush=True)


class SetupClock:
    """Seconds of each set-up phase, from the start of the process."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self._last = t_start
        self.phases: list[tuple[str, float]] = []

    def mark(self, phase: str, now: float | None = None) -> float:
        now = time.monotonic() if now is None else now
        took = now - self._last
        self.phases.append((phase, took))
        self._last = now
        log(f"setup phase {phase}: {took:.2f}s "
            f"(at {now - self.t_start:.2f}s)")
        return took

    def note(self, phase: str, seconds: float) -> None:
        """A part of a phase already marked ("of which")."""
        self.phases.append((phase, seconds))
        log(f"setup phase   of which {phase}: {seconds:.2f}s")

    def summary(self, t_open: float) -> None:
        log("setup breakdown: " + json.dumps(
            {k: round(v, 2) for k, v in self.phases}) +
            f" setup_s={t_open - self.t_start:.2f}")


class CompileCounter:
    """Compilations and persistent-cache traffic, from jax.monitoring's
    events (the idea of chip_smoke.CacheCounter). ``backend_compiles``
    counts real compilations: a cache hit is not one."""

    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}
    DURATIONS = {"/jax/core/compile/backend_compile_duration":
                 "backend_compiles"}

    def __init__(self):
        import jax

        self.counts = dict.fromkeys(
            [*self.EVENTS.values(), *self.DURATIONS.values()], 0)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        key = self.EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def _on_duration(self, event: str, _duration: float, **_kw) -> None:
        key = self.DURATIONS.get(event)
        if key is not None:
            self.counts[key] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def compiled_since(self, snap: dict) -> int:
        """Programs new to this process since ``snap``: each asks the
        persistent cache (``requests``, hit or miss) or, with the cache
        off, goes straight to the compiler (``backend_compiles``)."""
        return max(self.counts["requests"] - snap["requests"],
                   self.counts["backend_compiles"] - snap["backend_compiles"])


def start_jax(chips: int):
    """Import JAX, place the compile cache, and fail without a TPU or with
    fewer chips than the cell asks for. Returns (jax, devices, counter)."""
    import jax

    from ray_tpu.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    # Store every program, also those that compile in under a second (the
    # program's own helper leaves JAX's thresholds alone).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    require_tpu(devices, chips)
    counter = CompileCounter()
    log(f"device {devices[0].device_kind!r} x{len(devices)} jax "
        f"{jax.__version__} compile_cache {cache_dir}")
    return jax, devices, counter


def require_tpu(devices, chips: int) -> None:
    """No accelerator, or fewer chips than the cell asks for: exit with
    another code than 0 and print no result."""
    if devices[0].platform != "tpu":
        print(f"benchmark: needs a TPU, JAX found platform="
              f"{devices[0].platform!r}", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"benchmark: the cell asks for {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(2)


def peaks_for(device_kind: str) -> dict:
    table = manifest.load_json(None, "peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]


def device_record(devices, trace=None) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace is not None:
        rec["busy_s"] = trace.busy_s()
        rec["window_s"] = trace.window_s()
    return rec


def jax_seed(seed: int) -> int:
    """``--seed`` may be a little over 2**31; a PRNGKey seed is kept inside
    31 bits, distinct seeds staying distinct up to 2**31 - 1 apart."""
    return int(seed) % (2 ** 31 - 1)


def trace_dir(fresh: bool = False) -> str:
    """Where a traced run writes: inside the checkout (git-ignored).
    ``fresh`` empties it first, so that one run's trace is the only one."""
    path = os.path.join(manifest.repo_root(), ".bench_trace")
    if fresh:
        shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    return path


def emit_result(correct: bool, attempted: int, failed: int, metrics: dict,
                units: dict, device: dict, breakdown: dict | None) -> None:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": v, "unit": units[k]}
                       for k, v in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
