"""Device time of the decode programs in the traced span over the decode
steps taken, the steps counted as output tokens the clients received in
that span over the mean number of decoding requests (so steps a burst ran
past a request's end count as time and not as work)."""

from rtbench.readers import serve_trace


def steps_and_seconds(obs, params):
    trace = obs.get("trace")
    if trace is None or obs.get("kind") != "serve":
        return None
    t0, t1 = obs["trace_span"]
    seconds = serve_trace.program_seconds(trace, params["programs"])
    tokens = serve_trace.output_tokens_in(obs["records"], t0, t1)
    decoding = serve_trace.mean_decoding(obs["records"], t0, t1)
    if not seconds or not tokens or not decoding:
        return None
    # Device seconds are of the trace's own window, tokens of the host
    # span around it: scale the tokens to the trace's length.
    steps = tokens / decoding * trace.window_s() / (t1 - t0)
    return steps, seconds


def read(obs, params):
    got = steps_and_seconds(obs, params)
    if got is None:
        return None
    steps, seconds = got
    return seconds / steps * 1e3
