"""Traffic kind ``open_loop``: requests sent on a schedule whatever the
server does (independent users). Poisson arrivals at the cell's fixed rate,
below the knee; the end-to-end metric is the mean time between output
tokens (``tpot_mean_ms``), the tails are per-layer metrics. See
serve_common."""

from rtbench import gen
from rtbench.kinds import serve_common

ADAPTER_NEEDS = ("REFERENCE", "model_config", "reference_weights")


def run(ctx: dict) -> None:
    serve_common.run(ctx, gen.open_loop_plan, "open_loop")
