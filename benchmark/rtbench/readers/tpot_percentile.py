"""A percentile of the time per output token over requests that finished
inside the window (``serve_common.tpot_ms``): the tail a client sees, in a
closed loop, and in an open loop whose end-to-end metric is the mean."""

from rtbench import stats
from rtbench.kinds import serve_common


def read(obs, params):
    if obs.get("kind") != "serve":
        return None
    tpot = serve_common.tpot_ms(obs["records"], obs["t_open"], obs["t_close"])
    if not tpot:
        return None
    return stats.percentile(tpot, params["q"])
