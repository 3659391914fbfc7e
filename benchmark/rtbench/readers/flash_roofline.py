"""Roofline share of the flash attention kernels: the least time the chip
could take for their calls (the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s, from the shapes) over their device time in the trace,
found by kernel name."""

from rtbench.readers import adapter_of

ADAPTER_NEEDS = ("flash_kernel_work",)


def read(obs, params):
    trace = obs.get("trace")
    if trace is None or obs.get("kind") != "train":
        return None
    cell, adapter, peaks = obs["cell"], adapter_of(obs), obs["peaks"]
    mesh = cell["traffic"]["mesh"]
    data_parallel = mesh.get("dp", 1) * mesh.get("fsdp", 1)
    work = adapter.flash_kernel_work(
        cell["config"], cell["traffic"]["global_batch"] // data_parallel,
        cell["traffic"]["seq_len"])
    least = spent = 0.0
    for kernel, w in work.items():
        events = trace.kernel_events(kernel)
        if not events:
            return None
        per_call = max(w["flops"] / peaks["bf16_flops_per_s"],
                       w["bytes"] / peaks["hbm_bytes_per_s"])
        least += per_call * len(events)
        spent += sum(e.end - e.start for e in events)
    return 100.0 * least / spent
