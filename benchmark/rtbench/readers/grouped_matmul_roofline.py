"""Roofline share of the routed experts' grouped matmul
(``ops/grouped_matmul.py``, kernel name ``moe_grouped_matmul``; two calls a
routed layer: gate and up fused, then down).

Both sides are taken per routed layer-step (one routed layer in one program
step, prefill chunks and decode steps alike), which needs no common clock.
Work a layer-step, from the router's own counters in ``stats()`` between
the last poll before the traced span and the first after it: the growth of
``moe_experts_touched`` and of ``moe_picks_local`` over that of
``moe_layer_steps`` give the experts read and the rows multiplied; the
adapter's ``grouped_matmul_work`` turns them into bytes (each touched
expert's three matrices once, the rows in and out) and FLOPs, and the least
time is the larger of bytes over the HBM bandwidth and FLOPs over the bf16
peak (the bytes, at these row counts). Time a layer-step: the mean device
time of the kernel's events in the trace times ``calls_per_layer_step``. A
program without the counters, or a trace without the kernel, gives None."""

from rtbench.readers import adapter_of

ADAPTER_NEEDS = ("grouped_matmul_work",)
COUNTERS = ("moe_experts_touched", "moe_picks_local", "moe_layer_steps")


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
    if any(k not in s for k in COUNTERS for s in (first, last)):
        return None
    touched, rows, layer_steps = (last[k] - first[k] for k in COUNTERS)
    if layer_steps <= 0 or touched <= 0:
        return None
    work = adapter_of(obs).grouped_matmul_work(
        obs["cell"]["config"], touched / layer_steps, rows / layer_steps)
    least = max(work["bytes"] / obs["peaks"]["hbm_bytes_per_s"],
                work["flops"] / obs["peaks"]["bf16_flops_per_s"])
    spent = (sum(e.end - e.start for e in events) / len(events)
             * params["calls_per_layer_step"])
    return 100.0 * least / spent
