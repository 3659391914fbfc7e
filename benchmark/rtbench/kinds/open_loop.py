"""Traffic kind ``open_loop``: requests sent on a schedule whatever the
server does (independent users). Poisson arrivals at the cell's fixed rate,
below the knee; the tails are the end-to-end metrics. See serve_common."""

from rtbench import gen
from rtbench.kinds import serve_common


def run(ctx: dict) -> None:
    serve_common.run(ctx, gen.open_loop_plan)
