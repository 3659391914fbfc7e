"""Per-layer metric readers. Each metric has a file
``benchmark/layer_metrics/<name>.json`` that names its ``reader``, a module
here with ``read(obs, params) -> float | None``. ``obs`` is what the run
observed: the reduced trace, the clients' records, polled counters, spans,
the cell. A reader that finds nothing to read returns None, and the metric
is left out of the line."""

from __future__ import annotations

import importlib


def read_all(specs: list[dict], obs: dict) -> dict:
    out = {}
    for spec in specs:
        mod = importlib.import_module("rtbench.readers." + spec["reader"])
        value = mod.read(obs, spec.get("params", {}))
        if value is not None:
            out[spec["name"]] = float(value)
    return out


def adapter_of(obs: dict):
    return importlib.import_module(
        "rtbench.adapters." + obs["cell"]["config"]["adapter"])
