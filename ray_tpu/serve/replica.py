"""Replica actor: hosts one copy of the user's callable.

Capability parity with the reference's replica (reference:
python/ray/serve/_private/replica.py:1812 Replica — runs the user callable,
counts ongoing requests for routing/autoscaling, exposes health checks and
reconfigure; sync methods run on the actor's thread pool).
"""

from __future__ import annotations

import threading
import time
from typing import Any, NamedTuple

from ray_tpu.serve.resilience import (
    DEADLINE_KEY,
    DeadlineExceeded,
    Overloaded,
    _set_current_deadline,
)
from ray_tpu.devtools.annotations import guarded_by
from ray_tpu.util import tracing
from ray_tpu.utils import serialization

_replica_metrics = None
_replica_metrics_lock = threading.Lock()

# Sub-second-centric buckets: TTFT/TPOT targets live in the 1 ms – 10 s
# band (reference capability: the TTFT/TPOT numbers LLM-serving papers
# compare on, PAPERS.md — readable off /metrics instead of bench scripts).
_LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                    0.5, 1.0, 2.5, 5.0, 10.0, 30.0)


def _get_replica_metrics():
    global _replica_metrics
    with _replica_metrics_lock:
        if _replica_metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge, Histogram

            _replica_metrics = {
                "ttft": Histogram(
                    "serve_ttft_s",
                    "time to first result: request start to first output "
                    "(first chunk when streaming, full result otherwise)",
                    boundaries=_LATENCY_BUCKETS, tag_keys=("deployment",)),
                "tpot": Histogram(
                    "serve_tpot_s",
                    "time per output token/chunk: gap between successive "
                    "streamed chunks",
                    boundaries=_LATENCY_BUCKETS, tag_keys=("deployment",)),
                "latency": Histogram(
                    "serve_request_latency_s",
                    "full request latency at the replica",
                    boundaries=_LATENCY_BUCKETS, tag_keys=("deployment",)),
                "ongoing": Gauge(
                    "serve_ongoing_requests",
                    "requests currently executing on this replica",
                    tag_keys=("deployment", "replica")),
                "requests": Counter(
                    "serve_replica_requests_total",
                    "requests handled by this replica",
                    tag_keys=("deployment", "replica")),
                "slo_tokens": Counter(
                    "serve_slo_tokens_total",
                    "output tokens/chunks produced within the request "
                    "deadline (SLO-attained work — the request-goodput "
                    "numerator; shed/expired requests contribute none)",
                    tag_keys=("deployment",)),
            }
        return _replica_metrics


class StampedChunk(NamedTuple):
    """A user generator's chunk on its way from the replica to the handle,
    framed with ``time.time()`` taken where the replica held it: the start
    of the chunk's way out. DeploymentResponseGenerator takes the frame off
    (``last_chunk_ts``); neither a handle's caller nor the wire sees it."""

    ts: float
    chunk: Any


def _steps_in_context(gen, ctx: dict):
    """``gen``, each of its steps run inside the propagated trace context
    ``ctx`` (and the thread's own context put back between steps: pool
    threads are shared with other requests)."""
    try:
        while True:
            with tracing.propagate_only(ctx):
                try:
                    chunk = next(gen)
                except StopIteration:
                    return
            yield chunk
    finally:
        with tracing.propagate_only(ctx):
            gen.close()


@guarded_by("_lock", "_ongoing", "_total", "_shed", "_expired")
class ServeReplica:
    """Created by the controller with max_concurrency == max_ongoing_requests
    so concurrent handle_request calls map to pool threads."""

    def __init__(self, deployment_name: str, replica_id: str,
                 cls_blob: bytes, init_args_blob: bytes,
                 user_config: Any = None, max_ongoing_requests: int = 0,
                 replica_queue_slack: int = 8):
        from ray_tpu.serve.resilience import shed_metrics

        self.deployment_name = deployment_name
        self.replica_id = replica_id
        cls = serialization.deserialize(cls_blob)
        args, kwargs = serialization.deserialize(init_args_blob)
        if isinstance(cls, type):
            self._callable = cls(*args, **kwargs)
        else:
            self._callable = cls  # plain function deployment
        self._ongoing = 0
        self._total = 0
        self._shed = 0
        self._expired = 0
        # Replica-side admission cap: every router caps its OWN in-flight
        # at max_ongoing_requests, but N independent routers can each fill
        # that cap against one replica; beyond the slack the replica says
        # Overloaded instead of queuing unboundedly. 0 = no self-defense
        # (router caps only).
        self._admit_cap = (max_ongoing_requests + replica_queue_slack
                           if max_ongoing_requests > 0 else 0)
        self._lock = threading.Lock()
        self._started_at = time.time()
        self._m = _get_replica_metrics()
        self._sm = shed_metrics()
        self._dep_tag = {"deployment": deployment_name}
        self._rep_tag = {"deployment": deployment_name,
                         "replica": replica_id}
        # Pre-bound series (Metric.bound()): the tag merge/validate is
        # paid once here instead of on every request (rtlint R4).
        self._b = {
            "ttft": self._m["ttft"].bound(self._dep_tag),
            "tpot": self._m["tpot"].bound(self._dep_tag),
            "latency": self._m["latency"].bound(self._dep_tag),
            "ongoing": self._m["ongoing"].bound(self._rep_tag),
            "requests": self._m["requests"].bound(self._rep_tag),
            "shed": self._sm["shed"].bound(
                {**self._dep_tag, "where": "replica"}),
            "expired": self._sm["expired"].bound(
                {**self._dep_tag, "where": "replica"}),
            "slo_tokens": self._m["slo_tokens"].bound(self._dep_tag),
        }
        if user_config is not None:
            self.reconfigure(user_config)

    def _begin_request(self, deadline: float | None = None) -> None:
        """Admission: shed when over the replica-side cap; drop requests
        whose deadline already passed — BEFORE any user/TPU work runs (a
        request that waited out its budget in queues must not spend
        compute producing an answer nobody is waiting for)."""
        from ray_tpu.serve.resilience import expired as _expired

        # Gauge set under the same lock as the counter: interleaved sets
        # outside it could publish a stale ongoing value that sticks until
        # the next request.
        with self._lock:
            if self._admit_cap and self._ongoing >= self._admit_cap:
                self._shed += 1
                try:
                    self._b["shed"].inc()
                except Exception:
                    pass
                raise Overloaded(
                    f"replica {self.replica_id} at admission cap "
                    f"({self._admit_cap} ongoing)",
                    retry_after_s=0.5, where="replica")
            if _expired(deadline):
                self._expired += 1
                try:
                    self._b["expired"].inc()
                except Exception:
                    pass
                raise DeadlineExceeded(
                    f"request expired before execution on replica "
                    f"{self.replica_id}")
            self._ongoing += 1
            self._total += 1
            try:
                self._b["ongoing"].set(self._ongoing)
                self._b["requests"].inc()
            except Exception:
                pass

    def _end_request(self) -> None:
        with self._lock:
            self._ongoing -= 1
            try:
                self._b["ongoing"].set(self._ongoing)
            except Exception:
                pass

    # -- data plane --

    def _chaos_probe(self, method_name: str) -> None:
        """serve.replica chaos point: kill (mode="raise" for in-process
        runtimes), error, and delay rules exercise the resilience layer
        end to end — a delay makes this replica a latency outlier (breaker
        food), an error feeds consecutive-failure tracking, a kill is a
        replica death mid-request."""
        from ray_tpu.chaos import injector

        if not injector.ACTIVE:
            return
        rule = injector.decide("serve.replica",
                               deployment=self.deployment_name,
                               replica=self.replica_id, method=method_name)
        if rule is None:
            return
        injector.write_mark(rule, "serve.replica",
                            {"deployment": self.deployment_name,
                             "replica": self.replica_id,
                             "method": method_name})
        if rule.action == "delay":
            time.sleep(max(0.0, float(rule.delay_s)))
        elif rule.action == "error":
            raise RuntimeError(
                f"chaos: injected error at serve.replica "
                f"({self.deployment_name}/{self.replica_id})")
        elif rule.action == "kill":
            if rule.mode == "raise":
                raise injector.ChaosKilled(
                    f"chaos: injected kill at serve.replica "
                    f"({self.replica_id})")
            import os as _os

            _os._exit(rule.exit_code)

    def handle_request(self, method_name: str, args: tuple, kwargs: dict):
        from ray_tpu.serve.multiplex import _set_multiplexed_model_id

        mux_id = kwargs.pop("__rtpu_mux_id", "")
        deadline = kwargs.pop(DEADLINE_KEY, None)
        _set_multiplexed_model_id(mux_id)
        self._begin_request(deadline)
        _set_current_deadline(deadline, self.deployment_name)
        t0 = time.perf_counter()
        try:
            self._chaos_probe(method_name)
            if method_name == "__call__":
                target = self._callable
                if not callable(target):
                    raise AttributeError(
                        f"deployment {self.deployment_name} is not callable; "
                        f"specify a method name")
            else:
                target = getattr(self._callable, method_name)
            result = target(*args, **kwargs)
            elapsed = time.perf_counter() - t0
            try:
                # Non-streaming: the full result IS the first output. The
                # live trace id rides along as an SLO exemplar, linking
                # the histogram bucket back to the request's trace.
                tid = tracing.current_trace_id()
                self._b["ttft"].observe(elapsed, exemplar=tid)
                self._b["latency"].observe(elapsed, exemplar=tid)
                self._count_slo_tokens(1, deadline)
            except Exception:
                pass
            return result
        finally:
            _set_current_deadline(None)
            self._end_request()

    def handle_request_streaming(self, method_name: str, args: tuple,
                                 kwargs: dict):
        """Streaming data plane: an actor method that returns a generator
        (called with num_returns="streaming"). First yield is a meta dict
        {"streaming": bool}; then either the single complete result or the
        user generator's chunks as they are produced (reference:
        replica.py streaming call path + proxy_request streaming)."""
        # The request's trace context is captured NOW, while the worker
        # span of this call is the thread's context: the runtime drives
        # the returned generator after that span has left the thread, and
        # each step of it re-enters the context (_steps_in_context), so
        # that what the user's generator submits (an LLM engine request)
        # joins the request's trace.
        gen = self._stream_request(method_name, args, kwargs,
                                   tracing.current_trace_id())
        if tracing.current_context() is None:
            return gen
        return _steps_in_context(gen, tracing.inject())

    def _stream_request(self, method_name: str, args: tuple, kwargs: dict,
                        tid: str | None):
        import inspect

        from ray_tpu.serve.multiplex import _set_multiplexed_model_id

        _set_multiplexed_model_id(kwargs.pop("__rtpu_mux_id", ""))
        deadline = kwargs.pop(DEADLINE_KEY, None)
        self._begin_request(deadline)
        _set_current_deadline(deadline, self.deployment_name)
        t0 = time.perf_counter()
        try:
            self._chaos_probe(method_name)
            if method_name == "__call__":
                target = self._callable
            else:
                target = getattr(self._callable, method_name)
            if inspect.isgeneratorfunction(target) or \
                    inspect.isgeneratorfunction(
                        getattr(target, "__call__", None)):
                yield {"streaming": True}
                yield from self._instrumented_stream(
                    target(*args, **kwargs), t0, deadline, tid)
                return
            result = target(*args, **kwargs)
            if inspect.isgenerator(result):
                yield {"streaming": True}
                yield from self._instrumented_stream(result, t0, deadline,
                                                     tid)
                return
            yield {"streaming": False}
            elapsed = time.perf_counter() - t0
            try:
                self._b["ttft"].observe(elapsed, exemplar=tid)
                self._b["latency"].observe(elapsed, exemplar=tid)
                self._count_slo_tokens(1, deadline)
            except Exception:
                pass
            yield result
        finally:
            _set_current_deadline(None)
            self._end_request()

    def _count_slo_tokens(self, n: int, deadline: float | None) -> None:
        """Request-goodput numerator (PR-8 SLO counters): output produced
        while the request's deadline is still attainable. Shed/expired
        requests never reach here; chunks produced after the deadline
        blew mid-stream are work nobody is waiting for, so they don't
        count either."""
        from ray_tpu.serve.resilience import expired as _deadline_expired

        if _deadline_expired(deadline):
            return
        try:
            self._b["slo_tokens"].inc(n)
        except Exception:
            pass

    def _instrumented_stream(self, gen, t0: float,
                             deadline: float | None = None,
                             exemplar: str | None = None):
        """TTFT on the first user chunk, TPOT on each inter-chunk gap, full
        latency at exhaustion — the streaming triple every serving
        comparison quotes. Each chunk counts toward the deployment's
        SLO-attained tokens while the deadline holds, and leaves framed
        with the wall-clock time it left at (StampedChunk)."""
        last = None
        try:
            for chunk in gen:
                now = time.perf_counter()
                try:
                    if last is None:
                        self._b["ttft"].observe(now - t0, exemplar=exemplar)
                    else:
                        self._b["tpot"].observe(now - last,
                                                exemplar=exemplar)
                except Exception:
                    pass
                self._count_slo_tokens(1, deadline)
                last = now
                yield StampedChunk(time.time(), chunk)
        finally:
            try:
                self._b["latency"].observe(time.perf_counter() - t0,
                                           exemplar=exemplar)
            except Exception:
                pass

    # -- control plane --

    def profile(self, seconds: float = 2.0, sample_hz: float = 0.0) -> dict:
        """Per-replica capture: sample THIS replica's process while it
        serves (called through the actor handle, so it runs concurrently
        with the data plane under max_concurrency). Answers "why is this
        one replica's TTFT 3x the fleet" with a flamegraph of that replica
        alone — the cluster-wide `profile` verb covers it too, but this
        targets one deployment copy without touching the rest."""
        from ray_tpu.profiling import capture_profile

        return capture_profile(
            seconds, sample_hz=sample_hz or None,
            meta={"kind": "serve_replica",
                  "deployment": self.deployment_name,
                  "source": self.replica_id,
                  "replica_id": self.replica_id})

    def get_metrics(self) -> dict:
        with self._lock:
            return {"replica_id": self.replica_id, "ongoing": self._ongoing,
                    "total": self._total, "shed": self._shed,
                    "expired": self._expired}

    def router_meta(self) -> dict | None:
        """Routing metadata the controller piggybacks on the replica
        snapshot (KV-block-aware prefix routing): user callables that
        define ``router_prefix_blocks() -> {"blocks": [...], "block": n}``
        publish their prefix-cache chain hashes (serve/prefix.py); the
        controller polls this on a cadence and routers score candidates by
        matched prefix length. None = this deployment doesn't publish (the
        controller then stops polling this replica). A RAISING
        router_prefix_blocks propagates: the controller treats a failed
        RPC as transient and retries next period — swallowing it to None
        here would permanently mark a capable replica incapable over one
        bad poll (e.g. mid device-failure recovery)."""
        fn = getattr(self._callable, "router_prefix_blocks", None)
        if not callable(fn):
            return None
        return fn() or None

    def check_health(self) -> bool:
        user_check = getattr(self._callable, "check_health", None)
        if callable(user_check):
            user_check()
        return True

    def reconfigure(self, user_config: Any) -> None:
        user_reconf = getattr(self._callable, "reconfigure", None)
        if callable(user_reconf):
            user_reconf(user_config)

    def prepare_for_shutdown(self, timeout_s: float = 5.0) -> bool:
        """Drain: wait for ongoing requests to finish (reference: graceful
        shutdown loop in replica.py)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                if self._ongoing == 0:
                    return True
            time.sleep(0.02)
        return False
