"""The load generator: a process of its own that never imports JAX.

    python3 benchmark/rtbench/loadgen.py <plan.json>

The plan (written by the harness from the cell's traffic file and the
seed) holds the server's URL, the warm-up requests, and either an open
loop's ramp and window requests with their due times or a closed loop's
request list and client count (``loop`` says which). Prompts are token
ids, sent as a list through ``/v1/completions`` with ``stream: true``, so
lengths are exact and no tokenizer is in the way.

It prints one JSON event per line (``warm_done``, ``window_open``,
``window_close``, ``done``), stamps each request with the time it was due
and the time it was sent, and writes every request's record to the plan's
``out`` file. Times are ``time.monotonic()``, which on Linux is one clock
for every process of the machine.

The SSE client is bench_serve.py's (frames split on blank lines, a stream
that ends in ``finish_reason: "error"`` fails), reading token frames as
JSON so that the generated ids can be recovered from the text.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time
import urllib.parse

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rtbench import gen  # noqa: E402


def emit(event: str, **kw) -> None:
    print(json.dumps({"event": event, "t": time.monotonic(), **kw}),
          flush=True)


class Request:
    def __init__(self, plan: dict, spec: dict, phase: str,
                 due_t: float | None):
        self.plan, self.spec, self.phase, self.due_t = plan, spec, phase, due_t
        self.rec = {"index": spec["index"], "phase": phase, "due_t": due_t,
                    "prompt_tokens": spec["prompt_tokens"],
                    "max_tokens": spec["max_tokens"], "send_t": None,
                    "first_t": None, "last_t": None, "end_t": None,
                    "frames": 0, "finish": None, "error": None,
                    "abandoned": False, "texts": []}
        self._conn: http.client.HTTPConnection | None = None
        self._abandon = False

    def abandon(self) -> None:
        """Stop reading (after the window, for a request no metric needs)."""
        self._abandon = True
        conn = self._conn
        if conn is not None and conn.sock is not None:
            try:
                conn.sock.shutdown(2)
            except OSError:
                pass

    def run(self) -> dict:
        rec, plan = self.rec, self.plan
        ids = gen.prompt_ids(plan["seed"], rec["index"], rec["prompt_tokens"],
                             plan["vocab"])
        body = json.dumps({"prompt": ids, "max_tokens": rec["max_tokens"],
                           "temperature": 0.0, "stream": True}).encode()
        url = urllib.parse.urlparse(plan["url"])
        try:
            self._conn = conn = http.client.HTTPConnection(
                url.hostname, url.port, timeout=plan["timeout_s"])
            rec["send_t"] = time.monotonic()
            conn.request("POST", url.path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}")
            buf, done = b"", False
            while not done:
                chunk = resp.read1(65536)
                if not chunk:
                    break
                now = time.monotonic()
                buf += chunk
                frames = buf.split(b"\n\n")
                buf = frames.pop()           # a partial frame stays buffered
                for f in frames:
                    if not f.startswith(b"data:"):
                        continue
                    data = f[5:].strip()
                    if data == b"[DONE]":
                        done = True
                        break
                    choice = json.loads(data)["choices"][0]
                    if choice["finish_reason"] is None:
                        rec["frames"] += 1
                        rec["texts"].append(choice["text"])
                        if rec["first_t"] is None:
                            rec["first_t"] = now
                        rec["last_t"] = now
                    else:
                        rec["finish"] = choice["finish_reason"]
            if not done:
                raise RuntimeError("stream ended without [DONE]")
            if rec["finish"] == "error":
                raise RuntimeError("engine failed the request")
        except Exception as e:  # noqa: BLE001 - recorded, counted as failed
            if self._abandon:
                rec["abandoned"] = True
            else:
                rec["error"] = repr(e)
        finally:
            rec["end_t"] = time.monotonic()
            if self._conn is not None:
                self._conn.close()
        return rec


def warm_up(plan: dict, records: list) -> None:
    """One lone request per warmed shape, one after the other."""
    for spec in plan["warmup"]:
        records.append(Request(plan, spec, "warm", None).run())
    emit("warm_done", requests=len(plan["warmup"]),
         failed=sum(1 for r in records if r["error"]))


def open_loop(plan: dict, records: list) -> None:
    """Ramp requests, then window requests, each sent at its due time
    whatever the server does. The window opens ``ramp_s`` after the first
    due time's origin and lasts ``seconds``."""
    lock = threading.Lock()
    live: list[Request] = []
    threads: list[threading.Thread] = []
    t0 = time.monotonic() + 0.05
    t_open = t0 + plan["ramp_s"]
    t_close = t_open + plan["seconds"]
    sched = ([(t0 + s["due_s"], s, "ramp") for s in plan["ramp"]]
             + [(t_open + s["due_s"], s, "window") for s in plan["window"]])

    def one(req: Request) -> None:
        rec = req.run()
        with lock:
            records.append(rec)

    opened = False
    for due, spec, phase in sched:
        if not opened and due >= t_open:
            _sleep_until(t_open)
            emit("window_open", t_open=t_open)
            opened = True
        _sleep_until(due)
        req = Request(plan, spec, phase, due)
        with lock:
            live.append(req)
        th = threading.Thread(target=one, args=(req,), daemon=True)
        th.start()
        threads.append(th)
    if not opened:
        _sleep_until(t_open)
        emit("window_open", t_open=t_open)
    _sleep_until(t_close)
    emit("window_close", t_close=t_close)
    # Outside every metric: wait until each request due in the window has
    # its first token (or failed), then abandon what is still streaming.
    deadline = time.monotonic() + plan["drain_s"]
    while time.monotonic() < deadline:
        with lock:
            waiting = [r for r in live if r.phase == "window"
                       and r.rec["first_t"] is None
                       and r.rec["end_t"] is None]
        if not waiting:
            break
        time.sleep(0.05)
    for r in live:
        if r.rec["end_t"] is None:
            r.abandon()
    for th in threads:
        th.join(10.0)
    with lock:
        done = {id(r) for r in records}
        for r in live:  # a thread that did not come back in time
            if id(r.rec) not in done:
                r.rec["abandoned"] = True
                records.append(r.rec)


def closed_loop(plan: dict, records: list) -> None:
    """``clients`` clients, each sending its next request when the last
    answer ends. Their first requests start ``stagger_s`` apart in all, so
    that the loop does not begin as one convoy. The window opens once
    every client is past its first request and lasts ``seconds``; requests
    straddling the end are let finish."""
    lock = threading.Lock()
    queue = iter(plan["requests"])
    state = {"stop": False, "first_done": 0, "t_open": None}
    opened = threading.Event()

    t_start = time.monotonic()

    def client(k: int) -> None:
        first = True
        _sleep_until(t_start + plan["stagger_s"] * k / plan["clients"])
        while True:
            with lock:
                if state["stop"]:
                    return
                spec = next(queue, None)
            if spec is None:
                return
            rec = Request(plan, spec, "loop", None).run()
            with lock:
                records.append(rec)
                if first:
                    first = False
                    state["first_done"] += 1
                    if state["first_done"] == plan["clients"]:
                        state["t_open"] = time.monotonic()
                        opened.set()

    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(plan["clients"])]
    for th in threads:
        th.start()
    if not opened.wait(plan["timeout_s"]):
        raise RuntimeError("clients did not finish their first requests")
    t_open = state["t_open"]
    emit("window_open", t_open=t_open)
    _sleep_until(t_open + plan["seconds"])
    with lock:
        state["stop"] = True
    emit("window_close", t_close=t_open + plan["seconds"])
    for th in threads:
        th.join(plan["timeout_s"])


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.5) if left > 0.002 else 0)


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        plan = json.load(f)
    records: list = []
    warm_up(plan, records)
    {"open_loop": open_loop, "closed_loop": closed_loop}[plan["loop"]](
        plan, records)
    with open(plan["out"], "w") as f:
        json.dump(records, f)
    emit("done", requests=len(records))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
