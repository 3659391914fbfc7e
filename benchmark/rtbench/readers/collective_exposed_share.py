"""Collective time during which no compute op runs on that chip, over the
traced span (which is whole steps, so this is the share of step time)."""


def read(obs, params):
    trace = obs.get("trace")
    if trace is None or len(trace.devices) < 2:
        return None
    _total, exposed = trace.collective_seconds()
    return 100.0 * exposed / trace.window_s()
