"""HTTP ingress proxy.

Capability parity with the reference's proxy (reference:
python/ray/serve/_private/proxy.py:1605 ProxyActor — HTTP ingress routed by
prefix to the application's ingress deployment, request forwarded through a
handle, response streamed back). Implemented over http.server in the proxy
actor's thread (stdlib-only; the box has no ASGI server).
"""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from ray_tpu.util import tracing


@dataclass
class Request:
    """What an ingress deployment's __call__ receives for an HTTP request
    (reference: starlette Request equivalent, minimal surface)."""

    method: str
    path: str
    query_params: dict[str, str] = field(default_factory=dict)
    headers: dict[str, str] = field(default_factory=dict)
    body: bytes = b""
    # time.time() when the proxy took the request off its socket, before it
    # read the body: where the request's way in starts (LLMServer books it
    # against engine.submit as ``ingress_s``). 0.0: no proxy stamped it.
    received_ts: float = 0.0

    def json(self):
        return json.loads(self.body) if self.body else None

    @property
    def text(self) -> str:
        return self.body.decode()


class ProxyActor:
    """Binds an HTTP server; routes longest-prefix-match to the ingress
    deployment's handle. Runs as an actor (one per node in the reference;
    one per cluster here until multi-node proxying lands)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 8000):
        from ray_tpu.serve.handle import DeploymentHandle

        self._routes: dict[str, str] = {}
        self._handles: dict[str, DeploymentHandle] = {}
        self._lock = threading.Lock()

        proxy = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _dispatch(self):
                received = time.time()
                parsed = urlparse(self.path)
                route, dep = proxy._match(parsed.path)
                if dep is None:
                    self.send_response(404)
                    self.end_headers()
                    self.wfile.write(b"no application at this route")
                    return
                if not tracing.tracing_enabled():
                    self._respond(parsed, route, dep, received)
                    return
                # The request's root span, arrival to close. The trace
                # starts here, so the head-sampling verdict is drawn here,
                # once: the handle's span opens under the root (the thread's
                # context) and inherits it, as everything downstream does.
                try:
                    rate = proxy._get_handle(dep).trace_sample_rate()
                except Exception:  # noqa: BLE001 - _respond reports it
                    rate = 1.0
                sampled = tracing.sample_request(rate)
                with tracing.span("proxy.request", kind="server",
                                  attributes={"path": parsed.path},
                                  ctx={"sampled": sampled}) as root:
                    root.start_ts = received
                    status, chunks, size = self._respond(parsed, route, dep,
                                                         received)
                    root.attributes.update(status=status, chunks=chunks,
                                           bytes=size)
                    if status >= 500:
                        root.status = f"ERROR: HTTP {status}"
                if status >= 500 and not sampled:
                    tracing.mark_keep(root.trace_id, "error")

            def _respond(self, parsed, route, dep, received):
                """Serve one request; (status, chunks streamed, their
                bytes)."""
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                req = Request(
                    method=self.command,
                    path=parsed.path[len(route.rstrip("/")):] or "/",
                    query_params={k: v[0] for k, v in
                                  parse_qs(parsed.query).items()},
                    headers={k: v for k, v in self.headers.items()},
                    body=body,
                    received_ts=received,
                )
                try:
                    hint = (self.headers.get("x-route-hint")
                            or _prefix_route_hint(body))
                    # Per-request budget: the x-request-timeout-s header
                    # overrides the deployment's request_timeout_s; the
                    # deadline rides the call end to end (router queue,
                    # replica admission, batcher).
                    timeout_s = None
                    raw_t = self.headers.get("x-request-timeout-s")
                    if raw_t:
                        try:
                            timeout_s = max(float(raw_t), 0.001)
                        except ValueError:
                            timeout_s = None
                    gen = proxy._get_handle(dep).options(
                        stream=True, route_hint=hint,
                        timeout_s=timeout_s).remote(req)
                    gen.timeout = timeout_s or 60.0  # bound per chunk
                    if gen.streaming:
                        # SSE/chunk streaming: write each produced chunk as
                        # it arrives; length-delimited by connection close
                        # (reference: proxy_request streaming path,
                        # proxy.py:481).
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "text/event-stream; charset=utf-8")
                        self.send_header("Cache-Control", "no-cache")
                        self.send_header("Connection", "close")
                        self.end_headers()
                        return (200, *self._stream(gen))
                    result = next(gen)
                except Exception as e:  # noqa: BLE001 - mapped below
                    # Resilience-aware status mapping (reference: serve
                    # returns 503 on backpressure so clients/load balancers
                    # back off instead of piling on):
                    #   Overloaded        → 503 + Retry-After
                    #   DeadlineExceeded  → 504 (budget spent in-cluster)
                    #   anything else     → 500
                    from ray_tpu.serve import resilience

                    cause = resilience.unwrap(e)
                    if isinstance(cause, resilience.Overloaded):
                        self.send_response(503)
                        self.send_header(
                            "Retry-After",
                            str(max(1, int(cause.retry_after_s))))
                        self.end_headers()
                        self.wfile.write(
                            f"overloaded ({cause.where})".encode())
                        return 503, 0, 0
                    if isinstance(cause, (resilience.DeadlineExceeded,
                                          TimeoutError)):
                        self.send_response(504)
                        self.end_headers()
                        self.wfile.write(b"request deadline exceeded")
                        return 504, 0, 0
                    self.send_response(500)
                    self.end_headers()
                    self.wfile.write(repr(e).encode())
                    return 500, 0, 0
                status, ctype, payload = _encode(result)
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)
                return status, 0, 0

            def _stream(self, gen):
                """A chunk's way out, timed where it ends: each chunk's
                write is a ``serve.chunk_out`` phase whose ``lag_us`` is
                the replica's stamp (``gen.last_chunk_ts``) to ``next(gen)``
                returning here; the stream's end a ``serve.close`` whose
                ``lag_us`` is the last chunk's stamp to StopIteration
                reaching this thread: the end marker's way. Returns
                (chunks written, their bytes)."""
                chunks = size = 0
                try:
                    for chunk in gen:
                        lag_us = _us_since(gen.last_chunk_ts)
                        if isinstance(chunk, str):
                            chunk = chunk.encode()
                        elif not isinstance(chunk, (bytes, bytearray)):
                            chunk = json.dumps(chunk).encode()
                        with tracing.phase("serve.chunk_out", lag_us=lag_us,
                                           bytes=len(chunk)):
                            self.wfile.write(chunk)
                            self.wfile.flush()
                        chunks += 1
                        size += len(chunk)
                    # An instant: the end has reached this thread (the
                    # handler closes the connection when it returns).
                    with tracing.phase(
                            "serve.close",
                            lag_us=_us_since(gen.last_chunk_ts)):
                        pass
                except Exception:  # noqa: BLE001
                    # 200 + body already on the wire: terminate the
                    # stream (connection close) — a second status
                    # line would corrupt the client's event stream.
                    pass
                return chunks, size

            do_GET = do_POST = do_PUT = do_DELETE = _dispatch

        self._server = ThreadingHTTPServer((host, port), Handler)
        self._port = self._server.server_address[1]
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True)
        self._thread.start()

    def _match(self, path: str):
        with self._lock:
            best = None
            for route, dep in self._routes.items():
                r = route.rstrip("/") or "/"
                if path == r or path.startswith(r.rstrip("/") + "/") or r == "/":
                    if best is None or len(r) > len(best[0]):
                        best = (r, dep)
            return best if best else ("/", None)

    def _get_handle(self, deployment_name: str):
        from ray_tpu.serve.handle import DeploymentHandle

        with self._lock:
            if deployment_name not in self._handles:
                self._handles[deployment_name] = DeploymentHandle(deployment_name)
            return self._handles[deployment_name]

    # -- control plane --

    def update_routes(self, routes: dict[str, str]) -> None:
        with self._lock:
            self._routes = dict(routes)

    def port(self) -> int:
        return self._port

    def ready(self) -> bool:
        return True

    def shutdown(self) -> None:
        self._server.shutdown()


def _us_since(stamp: float) -> int:
    """Microseconds from a ``time.time()`` stamp to now; 0 for no stamp (a
    chunk that came unframed) or a clock that stepped back."""
    return max(int((time.time() - stamp) * 1e6), 0) if stamp else 0


def _prefix_route_hint(body: bytes) -> str | None:
    """Prefix-affinity hint for LLM-shaped requests (reference:
    routing_policies/prefix_aware): requests sharing a prompt prefix hash
    to the same hint, so the router sends them to the replica whose engine
    already holds that prefix's KV (engine-side reuse: LLMEngine prefix
    cache). Non-JSON / non-LLM bodies get no hint (pow-2 routing)."""
    if not body or len(body) > 1 << 20:
        return None
    try:
        payload = json.loads(body)
    except Exception:
        return None
    if not isinstance(payload, dict):
        return None
    text = None
    if isinstance(payload.get("prompt"), str):
        text = payload["prompt"]
    elif isinstance(payload.get("messages"), list) and payload["messages"]:
        first = payload["messages"][0]
        if isinstance(first, dict) and isinstance(first.get("content"), str):
            text = first["content"]
    if not text:
        return None
    import hashlib

    # Hash a FIXED-size head block so the divergent tail never enters the
    # hint: prompts sharing >= 128 chars (the system-prompt shape) map to
    # one replica. Prefixes shorter than the block scatter — acceptable,
    # their prefill is cheap anyway.
    return hashlib.sha1(text[:128].encode("utf-8", "ignore")).hexdigest()[:16]


def _encode(result) -> tuple[int, str, bytes]:
    if isinstance(result, Response):
        return result.status_code, result.content_type, result.body
    if isinstance(result, bytes):
        return 200, "application/octet-stream", result
    if isinstance(result, str):
        return 200, "text/plain; charset=utf-8", result.encode()
    return 200, "application/json", json.dumps(result).encode()


@dataclass
class Response:
    body: bytes
    status_code: int = 200
    content_type: str = "application/octet-stream"
