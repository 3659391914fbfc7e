"""Roofline share of the decode-attention kernel (``ops/decode_attention.py``,
kernel name ``decode_attention``). The kernel is bound by bytes (4 query
rows a KV head against whole blocks of keys and values), so the least time
is bytes over the chip's HBM bandwidth.

Both sides are taken per decode step, which needs no common clock. Bytes a
step: the growth of the engine's ``kv_positions_read`` over the growth of
``decode_steps``, between the last poll of ``stats()`` before the traced
span and the first after it, times the bytes a position costs over all
layers (the adapter's ``decode_attention_bytes``). Time a step: the
device seconds of the kernel's events in the trace over their number,
times the layers (one call a layer a step). A share over 100% means the
bytes are counted too high or part of the kernel's time is missed."""

from rtbench.readers import adapter_of

ADAPTER_NEEDS = ("decode_attention_bytes", "depth")


def read(obs, params):
    trace = obs.get("trace")
    if trace is None or "trace_span" not in obs:
        return None
    events = trace.kernel_events(params["kernel"])
    t0, t1 = obs["trace_span"]
    polls = obs.get("polls", [])
    before = [s for t, s in polls if t <= t0]
    after = [s for t, s in polls if t >= t1]
    if not events or not before or not after:
        return None
    first, last = before[-1], after[0]
    if any(k not in s for k in ("kv_positions_read", "decode_steps")
           for s in (first, last)):
        return None
    steps = last["decode_steps"] - first["decode_steps"]
    positions = last["kv_positions_read"] - first["kv_positions_read"]
    if steps <= 0 or positions <= 0:
        return None
    cell, adapter = obs["cell"], adapter_of(obs)
    layers = adapter.depth(cell["config"], cell["traffic"]["use"])
    least = adapter.decode_attention_bytes(
        cell["config"], layers, positions / steps
    ) / obs["peaks"]["hbm_bytes_per_s"]
    spent = sum(e.end - e.start for e in events) / len(events) * layers
    return 100.0 * least / spent
