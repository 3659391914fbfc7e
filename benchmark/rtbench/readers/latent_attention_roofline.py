"""Roofline share of the latent decode-attention kernel
(``ops/latent_attention.py``, kernel name ``latent_decode_attention``).

Unlike grouped-query decode attention the kernel is not bound by bytes
alone: every head scores and mixes each cached row, 64 x (576 + 512) x 2
FLOPs against 1,152 bytes, about half the chip's ridge. So the least time
of a step is the larger of bytes over the HBM bandwidth and FLOPs over the
bf16 peak, both from the adapter (``decode_attention_bytes``,
``decode_attention_flops``) for the positions a step fetched.

Both sides are taken per decode step, as ``decode_attention_roofline``
takes them, which needs no common clock. Positions a step: the growth of
the engine's ``kv_positions_read`` over the growth of ``decode_steps``
between the last poll of ``stats()`` before the traced span and the first
after it. Time a step: the mean device time of the kernel's events in the
trace times the adapter's ``attention_calls_per_step``. A share over 100%
means the work is counted too high or part of the kernel's time is missed,
never a result."""

from rtbench.readers import adapter_of

ADAPTER_NEEDS = ("decode_attention_bytes", "decode_attention_flops",
                 "attention_calls_per_step", "depth")


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
    cell, adapter, peaks = obs["cell"], adapter_of(obs), obs["peaks"]
    config = cell["config"]
    layers = adapter.depth(config, cell["traffic"]["use"])
    least = max(
        adapter.decode_attention_bytes(config, layers, positions / steps)
        / peaks["hbm_bytes_per_s"],
        adapter.decode_attention_flops(config, layers, positions / steps)
        / peaks["bf16_flops_per_s"])
    spent = (sum(e.end - e.start for e in events) / len(events)
             * adapter.attention_calls_per_step(config, layers))
    return 100.0 * least / spent
