"""Share of the traced window in which the first chip ran nothing while
the program's scheduler was at work on the host: each gap between device
operations goes to the shortest phase under ``prefix`` that covers its
midpoint, and counts unless that phase is one of ``except`` (the blocking
fetch, where the host waits for the chip and not the chip for the host,
and the wait for requests). Beside ``device_idle_share`` it says how
much of the idle time is the scheduler's."""

from rtbench.readers import phases


def read(obs, params):
    trace = obs.get("trace")
    if trace is None:
        return None
    mine = phases.of(obs, params["prefix"])
    if not mine:
        return None
    busy = trace.devices[0].busy()
    cover = phases.Cover(mine)
    idle = 0.0
    for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
        phase = cover.at(e0 + (s1 - e0) / 2)
        if phase is not None and phase.name not in params["except"]:
            idle += s1 - e0
    return 100.0 * idle / trace.window_s()
