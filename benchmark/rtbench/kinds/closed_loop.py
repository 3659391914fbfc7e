"""Traffic kind ``closed_loop``: a fixed number of clients, each sending
its next request when the last answer ends (callers that wait for a
reply). Saturated, so the end-to-end metric is the tokens per second
served, counted pro rata. See serve_common."""

from rtbench import gen
from rtbench.kinds import serve_common

ADAPTER_NEEDS = ("REFERENCE", "model_config", "reference_weights")


def run(ctx: dict) -> None:
    serve_common.run(ctx, gen.closed_loop_plan, "closed_loop")
