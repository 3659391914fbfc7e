"""One run of a serving cell, and what the service path's stamps say of it
(PR 50): the budget of a first token and of a slot's turn-round, from the
run's result line, the engine's last ``stats()``, the host phases of the
profiler's trace and the clients' records.

    python3 devbench/serve_path_budget.py <cell> <seed> [--trace 0|1]
        [--spans [RATE]] [--watch] [--root DIR] [--tag NAME]

runs ``benchmark/run.py`` of the checkout at ``--root`` (this one; a
parent's laid in ``.parent/``) in a process of its own, ``--spans`` with
``tracing.enable_tracing()`` on and every request sampled, or the share
``RATE`` of them (what the request spans cost: compare ``tpot_mean_ms`` or
``serve_tok_s`` with a run without it, same seed), ``--watch`` with a thread that keeps the router's own
metrics (``serve_router_queue_wait_s``, ``serve_breaker_transitions_total``
in all and by ``reason``: the whole run's, warm-up and ramp among it) for the
line. It writes one JSON
file a run under ``chiprun_out/serve_path/`` (metrics, the counters of the
budgets, the budgets of a traced run) and prints it. Needs the chips the cell needs:

    chiprun -- python3 devbench/serve_path_budget.py mistral7b-serve-docqa 7 --trace 1
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# run.py inside a process this file prepares (the runtime is in-process, so
# proxy, router, replica and engine are all there): SPB_SPANS turns request
# tracing on, at the head-sampling rate it holds; SPB_WATCH names a file that a thread
# rewrites once a second with the router's own metrics (the time requests
# waited in it for a replica, the circuit breaker's openings), since run.py
# leaves by os._exit.
_BOOT = """
import json, os, runpy, sys, threading, time
if os.environ.get("SPB_SPANS"):
    os.environ["RTPU_TRACE_SAMPLE_RATE"] = os.environ["SPB_SPANS"]
    from ray_tpu.util import tracing
    tracing.enable_tracing()
def watch(path):
    from ray_tpu.util import metrics
    while True:
        time.sleep(1.0)
        got = {}
        for e in metrics.registry().snapshot()["metrics"]:
            if e["name"] == "serve_router_queue_wait_s":
                got["router_wait_s"] = sum(v for _, v in e["sums"])
                got["router_waits"] = sum(v for _, v in e["counts"])
                got["router_wait_buckets"] = [
                    sum(col) for col in zip(*(b for _, b in e["buckets"]))]
                got["router_wait_boundaries"] = e["boundaries"]
            elif e["name"] == "serve_breaker_transitions_total":
                got["breaker_opens"] = sum(v for _, v in e["points"])
                # by the rule that opened it (PR 51); a tree without the
                # tag gives them all under "untagged"
                at = (e["tag_keys"].index("reason")
                      if "reason" in e["tag_keys"] else None)
                by = got["breaker_opens_by_reason"] = {}
                for key, v in e["points"]:
                    r = "untagged" if at is None else key[at]
                    by[r] = by.get(r, 0) + v
        with open(path + ".tmp", "w") as f:
            json.dump(got, f)
        os.replace(path + ".tmp", path)
if os.environ.get("SPB_WATCH"):
    threading.Thread(target=watch, args=(os.environ["SPB_WATCH"],),
                     daemon=True).start()
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
runpy.run_path("benchmark/run.py", run_name="__main__")
"""


def run_cell(root: str, cell: str, seed: int, trace: int,
             spans: float | None, watch: bool):
    """(result line, the engine's last stats(), every line printed, the
    router's metrics where watched)."""
    args = ["--workload", cell, "--seed", str(seed), "--seconds", "51",
            "--trace", str(trace)]
    head = ([sys.executable, "-c", _BOOT] if spans is not None or watch
            else [sys.executable, "benchmark/run.py"])
    env = dict(os.environ, PYTHONPATH=root)
    watched = os.path.join(root, ".bench_tmp", "router_watch.json")
    if spans is not None:
        env["SPB_SPANS"] = str(spans)
    if watch:
        os.makedirs(os.path.dirname(watched), exist_ok=True)
        if os.path.exists(watched):
            os.remove(watched)
        env["SPB_WATCH"] = watched
    proc = subprocess.run(["timeout", "900", *head, *args], cwd=root,
                          env=env, stdout=subprocess.PIPE, text=True)
    lines = proc.stdout.splitlines()
    if proc.returncode != 0 or not lines:
        sys.stdout.write(proc.stdout[-4000:])
        raise SystemExit(f"{cell} seed {seed} in {root}: exit "
                         f"{proc.returncode}")
    stats = {}
    for ln in lines:
        if ln.startswith("bench: engine stats "):
            stats = ast.literal_eval(ln[len("bench: engine stats "):])
    router = {}
    if watch and os.path.exists(watched):
        with open(watched) as f:
            router = json.load(f)
    return json.loads(lines[-1]), stats, lines, router


def _mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def _ratio_ms(stats: dict, num: str, den: str):
    return 1e3 * stats[num] / stats[den] if stats.get(den) else None


def host_phases(root: str) -> dict:
    """The trace's ``serve.chunk_out`` and ``serve.close`` events as
    (seconds, stats) pairs, and under ``last`` each stream's last chunk:
    the ``serve.chunk_out`` before a ``serve.close`` on the same thread's
    line (a request thread writes its stream's chunks, then closes it);
    the scheduler's blocking reads, ``engine.fetch``, likewise (their stats
    say ``which`` program was read and, since PR 66, ``why``)."""
    sys.path.insert(0, os.path.join(root, "benchmark"))
    import jax
    from rtbench import trace_reduce

    out = {"serve.chunk_out": [], "serve.close": [], "last": [],
           "engine.fetch": []}
    path = trace_reduce.find_xplane(os.path.join(root, ".bench_trace"))
    if not path:
        return out
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            prev = None
            for e in sorted((e for e in line.events if e.name in out),
                            key=lambda e: e.start_ns):
                ev = (e.duration_ns * 1e-9, dict(e.stats))
                out[e.name].append(ev)
                if e.name == "serve.close" and prev is not None:
                    out["last"].append(prev)
                prev = ev if e.name == "serve.chunk_out" else None
    return out


def client_side(root: str) -> dict:
    """From the load generator's records, over the window's requests: an
    open loop's are those due in it (``phase`` window; first-token time
    from ``due_t``, as ``ttft_p50_ms.chat`` counts); a closed loop's those
    sent in the plan's ``seconds`` before the last one was sent (the loop
    stops sending at the window's close). For a closed loop also the gap
    between an answer's end at the client (``end_t``: [DONE] read) and the
    next request's ``send_t`` (the next prompt's ids are made and its body
    written in between); None for an open loop, whose slots wait for
    arrivals."""
    tmp = os.path.join(root, ".bench_tmp")
    with open(os.path.join(tmp, "records.json")) as f:
        recs = [r for r in json.load(f)
                if r["phase"] != "warm" and r["first_t"] is not None]
    due = [r for r in recs if r["phase"] == "window"]
    if due:
        ttft = [1e3 * (r["first_t"] - r["due_t"]) for r in due]
        gaps = []
    else:
        with open(os.path.join(tmp, "plan.json")) as f:
            seconds = json.load(f)["seconds"]
        t_close = max(r["send_t"] for r in recs)
        due = [r for r in recs if r["send_t"] >= t_close - seconds]
        ttft = [1e3 * (r["first_t"] - r["send_t"]) for r in due]
        sends = sorted(r["send_t"] for r in due)
        gaps, i = [], 0
        for end in sorted(r["end_t"] for r in due):
            while i < len(sends) and sends[i] <= end:
                i += 1
            if i < len(sends) and sends[i] - end < 1.0:
                gaps.append(1e3 * (sends[i] - end))
                i += 1
    return {"requests": len(due), "ttft_p50_ms": statistics.median(ttft),
            "ttft_mean_ms": _mean(ttft), "client_gap_mean_ms": _mean(gaps),
            "client_gap_p50_ms": statistics.median(gaps) if gaps else None,
            "client_gaps": len(gaps)}


def budgets(root: str, metrics: dict, stats: dict) -> dict:
    """Both budgets in ms. Counters: the window's growth where the result
    line has the metric, else the whole run's (warm-up and ramp among it)."""
    def metric(stem):
        return next((v["value"] for k, v in metrics.items()
                     if k == stem or k.startswith(stem + ".")), None)

    ph = host_phases(root)
    out_ev, close_ev = ph["serve.chunk_out"], ph["serve.close"]
    last_ev = ph["last"]
    client = client_side(root)
    parts = {
        "ingress": metric("ingress_mean_ms")
        or _ratio_ms(stats, "ingress_s", "ingress_requests"),
        "queue_wait": metric("queue_wait_mean_ms")
        or _ratio_ms(stats, "queue_wait_s", "admitted"),
        "admit_to_first_token": metric("admit_to_first_token_mean_ms"),
        "first_frame_lag": metric("first_frame_lag_mean_ms")
        or _ratio_ms(stats, "first_frame_lag_s", "first_frames"),
        "chunk_lag": _mean(e[1]["lag_us"] / 1e3 for e in out_ev),
        "chunk_write": _mean(e[0] * 1e3 for e in out_ev),
    }
    first = {**parts, "ttft_p50": client["ttft_p50_ms"],
             "ttft_mean": client["ttft_mean_ms"]}
    if all(v is not None for v in parts.values()):
        first["sum"] = sum(parts.values())
        first["remainder_of_mean"] = first["ttft_mean"] - first["sum"]
    turn = {
        "slot_vacant": metric("slot_vacant_mean_ms")
        or _ratio_ms(stats, "slot_vacant_s", "slot_refills"),
        "last_frame_lag": metric("last_frame_lag_mean_ms")
        or _ratio_ms(stats, "last_frame_lag_s", "last_frames"),
        "last_chunk_lag": _mean(e[1]["lag_us"] / 1e3 for e in last_ev),
        "last_chunk_write": _mean(e[0] * 1e3 for e in last_ev),
        "close_lag": _mean(e[1]["lag_us"] / 1e3 for e in close_ev),
        "client_gap": client["client_gap_mean_ms"],
        "ingress": parts["ingress"], "queue_wait": parts["queue_wait"],
    }
    # The close is given beside and is not a part: the load generator sends
    # its next request when it has read [DONE], not at the connection's end.
    named = [turn[k] for k in turn if k not in ("slot_vacant", "close_lag")]
    if all(v is not None for v in [turn["slot_vacant"], *named]):
        turn["sum"] = sum(named)
        turn["remainder"] = turn["slot_vacant"] - turn["sum"]
    # The scheduler's blocking reads by what was read and why: a span's
    # idle seconds under ``engine.fetch`` belong to one of these.
    fetches: dict = {}
    for seconds, st in ph["engine.fetch"]:
        row = fetches.setdefault(f"{st.get('which')}/{st.get('why')}",
                                 {"n": 0, "ms": 0.0})
        row["n"] += 1
        row["ms"] += seconds * 1e3
    return {"first_token": first, "turn_round": turn, "client": client,
            "fetches": fetches,
            "events": {"chunk_out": len(out_ev), "last": len(last_ev),
                       "close": len(close_ev)}}


COUNTERS = ("ingress_s", "ingress_requests", "last_frame_lag_s",
            "last_frames", "first_frame_lag_s", "first_frames",
            "slot_vacant_s", "slot_refills", "queue_wait_s", "admitted",
            "first_token_wait_s", "first_tokens", "finished")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("seed", type=int)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spans", type=float, nargs="?", const=1.0,
                    metavar="RATE")
    ap.add_argument("--watch", action="store_true")
    ap.add_argument("--root", default=REPO)
    ap.add_argument("--tag", default="")
    a = ap.parse_args(argv)
    root = os.path.abspath(a.root)
    result, stats, lines, router = run_cell(root, a.cell, a.seed, a.trace,
                                            a.spans, a.watch)
    row = {"tag": a.tag, "root": os.path.relpath(root, REPO), "cell": a.cell,
           "seed": a.seed, "trace": a.trace, "spans": a.spans,
           "correct": result["correct"], "failed": result["failed"],
           "metrics": {k: v["value"] for k, v in result["metrics"].items()},
           "stats": {k: stats[k] for k in COUNTERS if k in stats},
           "setup": [ln for ln in lines if "setup" in ln][-1:],
           "router": router}
    if a.trace:
        row["budgets"] = budgets(root, result["metrics"], stats)
    out = os.path.join(REPO, "chiprun_out", "serve_path")
    os.makedirs(out, exist_ok=True)
    # A file a run: a later call's files merge beside an earlier call's.
    name = f"{a.cell}.{a.seed}.{a.tag or 'run'}.{int(time.time())}.json"
    with open(os.path.join(out, name), "w") as f:
        json.dump(row, f)
    print(json.dumps(row, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
