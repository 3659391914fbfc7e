"""Mean of ``stats()["active"]`` over the engine's slots, polled every
100 ms inside the window."""


def read(obs, params):
    polls = [s for t, s in obs.get("polls", [])
             if obs["t_open"] <= t <= obs["t_close"]]
    if not polls:
        return None
    return 100.0 * sum(s["active"] / s["slots"] for s in polls) / len(polls)
