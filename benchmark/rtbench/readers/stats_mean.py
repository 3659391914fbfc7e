"""Mean of one key of the engine's ``stats()``, polled every 100 ms inside
the window (``waiting``: requests submitted and not yet admitted to a
slot)."""


def read(obs, params):
    polls = [s for t, s in obs.get("polls", [])
             if obs["t_open"] <= t <= obs["t_close"]]
    if not polls:
        return None
    return sum(s[params["key"]] for s in polls) / len(polls)
