"""Bytes one decode step must read (weights once, plus the cached keys and
values live in the batch; from shapes, adapters/llama.decode_step_bytes)
over the chip's HBM bandwidth, over the time a decode step took."""

from rtbench.readers import adapter_of, decode_ms_per_step, serve_trace

ADAPTER_NEEDS = ("decode_step_bytes", "depth")


def read(obs, params):
    got = decode_ms_per_step.steps_and_seconds(obs, params)
    if got is None:
        return None
    steps, seconds = got
    cell, adapter = obs["cell"], adapter_of(obs)
    t0, t1 = obs["trace_span"]
    live = serve_trace.mean_live_kv_tokens(obs["records"], t0, t1)
    need = adapter.decode_step_bytes(
        cell["config"], adapter.depth(cell["config"], cell["traffic"]["use"]),
        live)
    least = need / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
