"""Device time of the prefill programs over the prompt kilotokens
prefilled in the traced span."""

from rtbench.readers import serve_trace


def read(obs, params):
    trace = obs.get("trace")
    if trace is None or obs.get("kind") != "serve":
        return None
    t0, t1 = obs["trace_span"]
    seconds = serve_trace.program_seconds(trace, params["programs"])
    tokens = serve_trace.prompt_tokens_prefilled(obs["records"], t0, t1)
    if not seconds or not tokens:
        return None
    tokens *= trace.window_s() / (t1 - t0)
    return seconds / (tokens / 1e3) * 1e3
