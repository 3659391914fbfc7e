"""A percentile of the time to the first token over requests due in the
window (``serve_common.ttft_ms``)."""

from rtbench import stats
from rtbench.kinds import serve_common


def read(obs, params):
    if obs.get("kind") != "serve":
        return None
    due = [r for r in obs["records"] if r["phase"] == "window"]
    if not due:
        return None
    ttft = serve_common.ttft_ms(due, obs["t_close"],
                                obs["cell"]["traffic"]["timeout_s"])
    return stats.percentile(ttft, params["q"])
