"""Growth of one cumulative counter of ``stats()`` over the growth of
another, between the first and the last poll inside the window: a mean
wait (seconds waited over requests that waited) or a share (tokens given
over slot-steps computed). ``den_times`` names a constant of ``stats()``
that multiplies the denominator (``slots``). A program whose ``stats()``
lacks a counter gives None, and so does a window in which the denominator
did not grow."""


def read(obs, params):
    polls = [s for t, s in obs.get("polls", [])
             if obs["t_open"] <= t <= obs["t_close"]]
    if len(polls) < 2:
        return None
    first, last = polls[0], polls[-1]
    keys = [params["num"], params["den"]]
    if "den_times" in params:
        keys.append(params["den_times"])
    if any(k not in first or k not in last for k in keys):
        return None
    den = last[params["den"]] - first[params["den"]]
    if "den_times" in params:
        den *= last[params["den_times"]]
    if den <= 0:
        return None
    num = last[params["num"]] - first[params["num"]]
    return params.get("scale", 1.0) * num / den
