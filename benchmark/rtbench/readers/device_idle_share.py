"""1 - union of device-op intervals over the traced span, averaged over
the chips."""


def read(obs, params):
    trace = obs.get("trace")
    if trace is None:
        return None
    return 100.0 * trace.idle_share()
