"""Router: assigns requests to replicas (power-of-two-choices).

Capability parity with the reference's router (reference:
python/ray/serve/_private/router.py:510 Router.assign_request :1028 →
request_router/pow_2_router.py:27 PowerOfTwoChoicesRequestRouter
.choose_replicas :52 — sample two replicas, pick the one with the smaller
queue; requests queue router-side when all replicas are saturated), plus
the request-resilience layer (ray_tpu/serve/resilience.py):

- queue waits are bounded by the request's absolute deadline;
- admission control sheds with :class:`Overloaded` once
  ``max_queued_requests`` callers are parked (bounded queues, not
  unbounded latency);
- the choose loop never picks a draining replica, a replica the caller
  already tried (retry exclusion), or one whose circuit breaker is open;
- per-replica breakers track consecutive failures and latency outliers
  (a replica against its peers, method by method) from the completion
  watcher, blacklist sick replicas with half-open recovery probes, and
  nudge the controller's health check on open.

KV-block-aware prefix routing (reference: serve prefix-aware routing
policy + vLLM prefix caching): replicas publish the chain hashes of the
prompt prefixes their engines hold (serve/prefix.py, piggybacked on the
long-poll snapshot); a request carrying ``prefix_hashes`` is scored by
matched prefix length and lands on the best-matched replica while its
load stays within the balance delta — a shared-prefix burst hits the
replica already holding the KV blocks instead of scattering pow-2.
Entries age out (TTL) and dead/draining replicas are dropped from the map
on every snapshot, so the router never hint-routes into a drain.

Hot path: the router is sized for 10k+ routing decisions/sec on one
process — metrics are pre-bound series (no per-call tag merging), replica
actor handles are cached per replica id, completion watching is ONE
reaper thread over all in-flight refs (a thread per request was ~100 µs
of create/teardown plus a parked stack each), and tracing spans are
skipped entirely when tracing is disabled.
"""

from __future__ import annotations

import contextlib
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable

import ray_tpu
from ray_tpu.devtools.annotations import guarded_by
from ray_tpu.serve.config import ReplicaInfo
from ray_tpu.serve.prefix import match_len
from ray_tpu.serve.resilience import (
    DEADLINE_KEY,
    CircuitBreaker,
    DeadlineExceeded,
    Overloaded,
    ResilienceSettings,
    open_reason_kind,
    shed_metrics,
)
from ray_tpu.util import tracing

_router_metrics = None
_router_metrics_lock = threading.Lock()


def _get_router_metrics():
    """Process-wide router metrics: admission wait, parked-caller depth,
    request count, and the resilience counters (shed/expired/retry/hedge/
    breaker) per deployment (reference: serve's
    ray_serve_num_router_requests / queued gauges). Lock-guarded creation:
    two racing first-requests must not register two metric objects and
    strand increments on the one the exporter can't see."""
    global _router_metrics
    with _router_metrics_lock:
        if _router_metrics is not None:
            return _router_metrics
        from ray_tpu.util.metrics import Counter, Gauge, Histogram

        _router_metrics = {
            "queue_wait": Histogram(
                "serve_router_queue_wait_s",
                "time a request waited in the router for a replica slot",
                tag_keys=("deployment",)),
            "queue_depth": Gauge(
                "serve_router_queue_depth",
                "callers currently parked waiting for replica capacity",
                tag_keys=("deployment",)),
            "requests": Counter(
                "serve_router_requests_total",
                "requests assigned to replicas", tag_keys=("deployment",)),
            "prefix_hits": Counter(
                "serve_router_prefix_hits_total",
                "requests routed by prefix-cache match",
                tag_keys=("deployment",)),
            "retries": Counter(
                "serve_retries_total",
                "assignment retries after replica failure/rejection",
                tag_keys=("deployment",)),
            "hedges": Counter(
                "serve_hedges_total",
                "tail-hedge duplicate attempts launched",
                tag_keys=("deployment",)),
            "breaker_transitions": Counter(
                "serve_breaker_transitions_total",
                "circuit breaker open transitions",
                tag_keys=("deployment", "replica", "reason")),
            "breaker_open": Gauge(
                "serve_breaker_open_replicas",
                "replicas currently blacklisted by the circuit breaker",
                tag_keys=("deployment",)),
        }
    return _router_metrics


@guarded_by("_cv", "_pending", "_obs_backlog")
class _CompletionReaper:
    """One thread watching EVERY in-flight unary ref of a router: releases
    the replica slot the moment a reply lands and hands outcome
    observation (a possibly-blocking local fetch in cluster mode) to a
    small pool. Replaces a watcher thread per request — at router hot-path
    rates, thread create/teardown alone was most of the per-request
    cost."""

    # Outcome observations queued behind the pool beyond this are settled
    # NEUTRAL instead (probe slot returned, no breaker signal): in cluster
    # mode one observation can block seconds on a result fetch, and an
    # unbounded backlog would defer breaker feedback minutes behind
    # completions — bounded-late health signal beats unbounded-late.
    OBS_BACKLOG_MAX = 256

    def __init__(self, router: "Router"):
        self._router = router
        self._cv = threading.Condition()
        # ref -> (rid, method, t_submit, is_probe)
        self._pending: dict = {}
        self._stopped = False
        self._obs_backlog = 0  # guarded by _cv
        # Observation pool: outcome gets are usually instant (actor
        # replies land in the caller's store) but a cluster-mode fetch can
        # block — it must never stall slot release for other requests.
        self._observe = ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="serve-reap")
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"serve-reaper-{router._deployment}")
        self._thread.start()

    def add(self, ref, rid: str, method: str, t_submit: float,
            is_probe: bool) -> None:
        with self._cv:
            self._pending[ref] = (rid, method, t_submit, is_probe)
            self._cv.notify()

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify()
        self._observe.shutdown(wait=False)

    def _loop(self) -> None:
        from ray_tpu.core.worker import global_worker

        router = self._router
        born_runtime = global_worker.runtime
        while True:
            with self._cv:
                while not self._pending and not self._stopped:
                    self._cv.wait()
                if self._stopped:
                    return
                refs = list(self._pending)
            if global_worker.runtime is not born_runtime:
                return  # our runtime is gone (LongPollClient discipline)
            try:
                # First-completion wake (event-driven in both runtimes),
                # then a zero-timeout sweep to drain everything already
                # ready in one pass. The timeout bounds the blind spot for
                # refs ADDED mid-wait (they're absent from this snapshot):
                # their observed latency — a breaker outlier input — is
                # overstated by at most one cycle, so keep it short.
                ready, _ = ray_tpu.wait(refs, num_returns=1, timeout=0.05,
                                        fetch_local=False)
                if ready and len(refs) > 1:
                    ready, _ = ray_tpu.wait(refs, num_returns=len(refs),
                                            timeout=0, fetch_local=False)
            except Exception:
                if self._stopped or \
                        global_worker.runtime is not born_runtime:
                    return
                # One poisoned ref must not wedge the SHARED reaper (the
                # per-request watchers it replaced failed one request per
                # bad ref): evict the refs wait() rejects individually,
                # releasing their slots with a neutral settle.
                self._evict_poisoned(refs)
                time.sleep(0.05)
                continue
            if not ready:
                continue
            now = time.perf_counter()
            done = []
            with self._cv:
                for ref in ready:
                    rec = self._pending.pop(ref, None)
                    if rec is not None:
                        done.append((ref, rec))
            for ref, (rid, method, t_submit, is_probe) in done:
                # Release first: _settle may block on a result fetch, and
                # parked callers must not wait out that fetch for a slot
                # the replica already freed.
                router._release(rid)
                with self._cv:
                    saturated = self._obs_backlog >= self.OBS_BACKLOG_MAX
                    if not saturated:
                        self._obs_backlog += 1
                if saturated:
                    router._settle_neutral(rid, is_probe)
                    continue
                try:
                    self._observe.submit(self._settle_one, ref, rid, method,
                                         now - t_submit, is_probe)
                except RuntimeError:  # shutting down
                    return

    def _settle_one(self, ref, rid: str, method: str, latency: float,
                    is_probe: bool) -> None:
        try:
            self._router._settle(ref, rid, method, latency, is_probe)
        finally:
            with self._cv:
                self._obs_backlog -= 1

    def _evict_poisoned(self, refs) -> None:
        """Drop every pending ref that ray_tpu.wait rejects on its own:
        its slot is released and settled neutral (no outcome will ever
        arrive for it), so the rest of the pending set keeps draining."""
        for ref in refs:
            try:
                ray_tpu.wait([ref], num_returns=1, timeout=0,
                             fetch_local=False)
            except Exception:
                with self._cv:
                    rec = self._pending.pop(ref, None)
                if rec is not None:
                    rid, _, _, is_probe = rec
                    self._router._release(rid)
                    self._router._settle_neutral(rid, is_probe)


class Router:
    def __init__(self, deployment_name: str,
                 get_replicas: Callable[[], list[ReplicaInfo]],
                 report_unhealthy: Callable[[str, str], None] | None = None):
        from ray_tpu.utils.config import get_config

        self._deployment = deployment_name
        # Span names interned once — these are stamped per request.
        self._trace_req_name = f"serve.request.{deployment_name}"
        self._trace_att_name = f"serve.attempt.{deployment_name}"
        self._get_replicas = get_replicas
        self._inflight: dict[str, int] = {}  # replica_id -> local in-flight
        self._lock = threading.Lock()
        self._not_saturated = threading.Condition(self._lock)
        self._rng = random.Random()
        self._waiting = 0  # callers parked for capacity (queue-depth gauge)
        # Set by _choose_locked (under _lock) when the chosen replica's
        # admission consumed a half-open breaker probe slot; read by
        # assign_request immediately after, per request.
        self._choice_was_probe = False
        self._report_unhealthy = report_unhealthy
        self.settings = ResilienceSettings()
        self._settings_adopted = False
        self.breaker = CircuitBreaker(self.settings.breaker,
                                      on_open=self._on_breaker_open)
        # Prefix-cache map: replica_id -> (frozenset of chain hashes,
        # receipt stamp). Rebuilt from every snapshot (dead/draining
        # replicas drop out immediately); entries older than the TTL are
        # ignored so a wedged control plane can't pin stale locality.
        self._prefix_map: dict[str, tuple[frozenset, float]] = {}
        cfg = get_config()
        self._prefix_ttl = float(
            getattr(cfg, "serve_prefix_map_ttl_s", 30.0))
        # Cached replica actor handles (get_actor is a name-table lookup —
        # an RPC in cluster mode — and handles are thread-safe now).
        self._actors: dict[str, object] = {}
        # Pre-bound metric series: the per-call tag-dict merge was a
        # measurable slice of the 10k-RPS budget.
        mtr = _get_router_metrics()
        smtr = shed_metrics()
        dep = {"deployment": deployment_name}
        self._m_queue_wait = mtr["queue_wait"].bound(dep)
        self._m_queue_depth = mtr["queue_depth"].bound(dep)
        self._m_requests = mtr["requests"].bound(dep)
        self._m_prefix_hits = mtr["prefix_hits"].bound(dep)
        self._m_retries = mtr["retries"].bound(dep)
        self._m_hedges = mtr["hedges"].bound(dep)
        self._m_breaker_open = mtr["breaker_open"].bound(dep)
        self._m_shed_router = smtr["shed"].bound(
            {**dep, "where": "router"})
        self._m_expired_router = smtr["expired"].bound(
            {**dep, "where": "router"})
        self._mtr = mtr
        self._reaper: _CompletionReaper | None = None
        self._reaper_lock = threading.Lock()

    # ------------------------------------------------------------ settings

    def _adopt_settings(self, replicas: list[ReplicaInfo]) -> None:
        """Adopt the deployment-level resilience settings riding the newest
        replica snapshot (cheap: dict identity check short-circuits)."""
        for r in replicas:
            s = getattr(r, "settings", None)
            if s is not None:
                if s is not getattr(self, "_last_settings_dict", None):
                    self._last_settings_dict = s
                    self.settings = ResilienceSettings.from_dict(s)
                    self.breaker.config = self.settings.breaker
                self._settings_adopted = True
                return

    def _on_breaker_open(self, replica_id: str, reason: str) -> None:
        try:
            self._mtr["breaker_transitions"].inc(
                tags={"deployment": self._deployment, "replica": replica_id,
                      "reason": open_reason_kind(reason)})
            self._m_breaker_open.set(self.breaker.open_count())
        except Exception:
            pass
        # Feed the controller's health check: a breaker trip means THIS
        # router has stopped routing there, but only the controller can
        # probe-and-replace a genuinely sick replica for everyone.
        if self._report_unhealthy is not None:
            try:
                self._report_unhealthy(replica_id, reason)
            except Exception:
                pass

    def _get_reaper(self) -> _CompletionReaper:
        reaper = self._reaper
        if reaper is None:
            with self._reaper_lock:
                reaper = self._reaper
                if reaper is None:
                    reaper = self._reaper = _CompletionReaper(self)
        return reaper

    def close(self) -> None:
        """Stop background machinery (called by serve.shutdown via
        handle._reset_routers)."""
        with self._reaper_lock:
            if self._reaper is not None:
                self._reaper.stop()
                self._reaper = None

    # ---------------------------------------------------------- data plane

    def assign_request(self, method_name: str, args: tuple, kwargs: dict,
                       timeout: float | None = None, stream: bool = False,
                       route_hint: str | None = None,
                       deadline: float | None = None,
                       exclude: set[str] | frozenset[str] | None = None,
                       no_park: bool = False,
                       prefix_hashes: tuple | None = None,
                       trace_ctx: dict | None = None,
                       trace_attrs: dict | None = None):
        """Pick a replica, submit, and return ``(result, replica_id)``
        where result is the ObjectRef (or ``(gen, on_done)`` when
        streaming). One attempt — retry/hedge loops live in the handle,
        which excludes already-tried replicas here.

        Placement order: ``prefix_hashes`` (KV-block-aware — the replica
        with the longest matched cached prefix wins while its load stays
        within the balance delta), then ``route_hint`` (rendezvous-hash
        affinity with the same balance bound), then pow-2 on local
        in-flight counts. Both locality mechanisms yield to load
        balancing beyond HINT_BALANCE_DELTA — a deployment-wide shared
        prefix must not pin all traffic to one replica while siblings
        idle.

        The wait for a replica slot is bounded by ``deadline`` (absolute
        wall clock; defaults to now + the deployment's request_timeout_s,
        or the legacy ``timeout`` argument when given). While every
        eligible replica is saturated the caller parks on a Condition that
        is notified on request completion and on replica-set changes — no
        sleep-poll — but only ``settings.max_queued_requests`` callers may
        park: beyond that, :class:`Overloaded` sheds the request
        immediately (admission control, reference: serve's
        max_queued_requests handle option).

        ``trace_ctx`` (a tracing propagation dict) parents this attempt
        under the handle's request-root span; routing decisions that end
        the attempt (shed, expiry, replica vanished) are stamped onto the
        trace as zero-duration point spans, and ``trace_attrs`` (attempt
        number, hedge flag) land on the attempt span."""
        t_enter = time.time()
        if deadline is None:
            budget = timeout if timeout is not None \
                else self.settings.request_timeout_s
            deadline = t_enter + budget
        with self._lock:
            parked = False
            try:
                while True:
                    replicas = self._get_replicas()
                    if replicas and not self._settings_adopted:
                        self._adopt_settings(replicas)
                    if replicas and exclude and all(
                            r.replica_id in exclude or
                            getattr(r, "draining", False)
                            for r in replicas):
                        # Retry exclusion covers every published replica:
                        # nothing a wake can change for THIS call — fail
                        # fast so the handle surfaces the original error
                        # instead of a full-budget park that also occupies
                        # an admission slot (a 0.5s retry-after shed must
                        # not become a 30s stall on a 1-replica app).
                        self._trace_point(trace_ctx, "router.shed",
                                          reason="exhausted")
                        raise Overloaded(
                            f"{self._deployment!r}: every replica already "
                            f"tried by this request", retry_after_s=0.5,
                            where="router")
                    chosen = (self._choose_locked(replicas, route_hint,
                                                  exclude, prefix_hashes)
                              if replicas else None)
                    if chosen is not None:
                        is_probe = self._choice_was_probe
                        self._inflight[chosen.replica_id] = \
                            self._inflight.get(chosen.replica_id, 0) + 1
                        break
                    remaining = deadline - time.time()
                    if remaining <= 0:
                        self._m_expired_router.inc()
                        self._trace_point(trace_ctx, "router.expired",
                                          waited_s=round(
                                              time.time() - t_enter, 6))
                        raise DeadlineExceeded(
                            f"no available replica for {self._deployment!r} "
                            f"within the request budget "
                            f"({deadline - t_enter:.1f}s)")
                    if not parked:
                        if no_park:
                            # Internal opportunistic assignment (hedging):
                            # take a free slot now or give up — a hedge
                            # that parks would add load exactly at
                            # saturation and block the caller's drive
                            # loop. Not counted as a shed: never
                            # user-visible.
                            raise Overloaded(
                                f"{self._deployment!r} has no free replica "
                                f"for an opportunistic assignment",
                                retry_after_s=0.0, where="router")
                        cap = self.settings.max_queued_requests
                        if cap >= 0 and self._waiting >= cap:
                            # Bounded router queue: shed instead of joining
                            # an unbounded wait (the client owns backoff).
                            self._m_shed_router.inc()
                            self._trace_point(trace_ctx, "router.shed",
                                              reason="queue_full")
                            raise Overloaded(
                                f"{self._deployment!r} router queue full "
                                f"({cap} waiting)",
                                retry_after_s=1.0, where="router")
                        parked = True
                        self._waiting += 1
                        self._m_queue_depth.set(self._waiting)
                    # Bounded wait: replica-set changes arrive via
                    # notify_replicas_changed(), completions via _release();
                    # the 0.5 s cap only covers lost-notify edge cases.
                    self._not_saturated.wait(timeout=min(remaining, 0.5))
            finally:
                if parked:
                    self._waiting -= 1
                    self._m_queue_depth.set(self._waiting)
        wait_s = time.time() - t_enter
        self._m_queue_wait.observe(
            wait_s, exemplar=trace_ctx.get("trace_id") if trace_ctx
            else None)
        self._m_requests.inc()

        # Propagate the budget: the replica drops the request if it expires
        # before execution starts (and exposes it to user code / batcher).
        # handle.remote builds a fresh kwargs dict per call, so the key is
        # written in place; retries/hedges sharing the dict skip the copy
        # (the deadline is constant for the request's lifetime).
        if kwargs.get(DEADLINE_KEY) != deadline:
            kwargs[DEADLINE_KEY] = deadline

        rid = chosen.replica_id
        try:
            handle = self._actors.get(rid)
            if handle is None:
                handle = ray_tpu.get_actor(chosen.actor_name,
                                           namespace="serve")
                self._actors[rid] = handle
        except Exception as e:
            # Replica vanished between the long-poll snapshot and submission:
            # give the slot back (a leaked increment would read as permanent
            # saturation), return any half-open probe slot, and count the
            # miss against the breaker. Surfaced as a NEVER-SENT actor death
            # (the request provably didn't reach any replica) carrying the
            # replica id, so the handle's retry loop can exclude it and
            # re-resolve onto a live sibling.
            from ray_tpu.core.exceptions import ActorDiedError

            self._release(rid)
            if is_probe:
                self.breaker.cancel_probe(rid)
            self.breaker.record_failure(rid)
            self._trace_point(trace_ctx, "router.never_sent", replica=rid)
            raise ActorDiedError(
                rid, f"replica {rid} vanished before submit: {e!r}",
                never_sent=True) from e
        # Client span around submission: inject() rides the TaskSpec, so
        # the replica's execution shows up as a child of this span — one
        # trace across processes. When the handle propagated a request-root
        # context (trace_ctx), this becomes the per-ATTEMPT span (retries
        # and hedges each get their own, numbered via trace_attrs) nested
        # under serve.request.<dep>; standalone callers keep the old
        # request-named root. Skipped entirely (nullcontext) when tracing
        # is off: span setup was measurable at router hot-path rates.
        traced = tracing.tracing_enabled() or trace_ctx is not None
        # Unsampled FIRST attempts propagate the context without
        # materializing the attempt span: it would cover only the submit
        # call and duplicate the root's attributes, and at production RPS
        # the skipped Span + id mint + tail-ring insert is the single
        # biggest per-request tracing cost. Retries, hedges, breaker
        # probes, and head-sampled traces keep their numbered attempt
        # spans; the handle stamps the chosen replica onto the root.
        if (trace_ctx is not None and not is_probe
                and (not trace_attrs or trace_attrs.get("attempt", 1) == 1)
                and "sampled" in trace_ctx
                and tracing._coerce_sampled(trace_ctx["sampled"]) is False):
            span = tracing.propagate_only(trace_ctx)
        elif traced:
            name = (self._trace_att_name if trace_ctx is not None
                    else self._trace_req_name)
            attrs = {"method": method_name, "replica": rid}
            if trace_attrs:
                attrs.update(trace_attrs)
            if is_probe:
                attrs["breaker_probe"] = True
            if wait_s > 0.001:
                attrs["queue_wait_s"] = round(wait_s, 6)
            if stream:
                attrs["stream"] = "true"
            span = tracing.span(name, kind="client", attributes=attrs,
                                ctx=trace_ctx)
        else:
            span = contextlib.nullcontext()
        if stream:
            try:
                with span:
                    gen = handle.handle_request_streaming.options(
                        num_returns="streaming").remote(
                            method_name, args, kwargs)
            except Exception:
                self._submit_failed(rid, is_probe)
                raise

            done = threading.Event()

            def on_stream_done():
                # In-flight until the consumer exhausts/abandons the stream
                # (keeps max_ongoing_requests honest for long-lived SSE).
                if not done.is_set():
                    done.set()
                    self._release(rid)
                    if is_probe:
                        # Settle this request's half-open probe slot if no
                        # outcome was recorded (abandoned stream): no-op
                        # once record_success/failure already moved the
                        # breaker out of half-open.
                        self.breaker.cancel_probe(rid)

            return (gen, on_stream_done), rid
        try:
            with span:
                ref = handle.handle_request.remote(method_name, args, kwargs)
        except Exception:
            self._submit_failed(rid, is_probe)
            raise

        self._get_reaper().add(ref, rid, method_name, time.perf_counter(),
                               is_probe)
        return ref, rid

    def _trace_point(self, trace_ctx: dict | None, name: str,
                     **attrs) -> None:
        """Zero-duration span stamping a routing decision (shed, expiry,
        vanished replica) onto the request's trace. No-op without a
        propagated context — untraced hot-path requests pay nothing."""
        if trace_ctx is None:
            return
        now = time.time()
        tracing.record_span(name, now, now, attributes=attrs,
                            ctx=trace_ctx)

    def _submit_failed(self, rid: str, is_probe: bool) -> None:
        self._actors.pop(rid, None)  # handle may be bound to a corpse
        self._release(rid)
        if is_probe:
            self.breaker.cancel_probe(rid)
        self.breaker.record_failure(rid)

    def _settle(self, ref, rid: str, method: str, latency: float,
                is_probe: bool) -> None:
        """Breaker bookkeeping for one completed unary call of ``method``
        (runs on the reaper's observation pool; the slot was already
        released)."""
        outcome = None
        try:
            outcome = self._observe_outcome(ref)
        finally:
            if outcome is True:
                self.breaker.record_success(rid, latency, method)
            elif outcome is False:
                self.breaker.record_failure(rid)
            elif is_probe:
                # Neutral (shed/expired/unknown): no health signal
                # either way — but THIS request's half-open probe
                # slot must be returned so the breaker doesn't wedge
                # half-open (and a shed must NOT close the breaker
                # on a still-sick replica). Only the probe request
                # settles the slot: a non-probe neutral completion
                # canceling it would over-admit probes.
                self.breaker.cancel_probe(rid)
            self._refresh_breaker_gauge()

    def _settle_neutral(self, rid: str, is_probe: bool) -> None:
        """Observation-backlog overflow path: no outcome signal either
        way, but a probe's half-open slot must still be returned."""
        if is_probe:
            self.breaker.cancel_probe(rid)
            self._refresh_breaker_gauge()

    def _observe_outcome(self, ref) -> bool | None:
        """Ternary outcome of the completed call: True = healthy answer,
        False = failure (infra or application), None = neutral — sheds and
        deadline expiries say nothing about replica health in EITHER
        direction (counting a fast shed as success would close a half-open
        breaker on a still-overloaded replica and seed its cleared latency
        window with bogus samples). The result is already local (actor
        replies land in the caller's store), so this get is cheap."""
        from ray_tpu.serve import resilience

        try:
            # Bounded get: in cluster mode the reply may still be a local
            # fetch away after wait(fetch_local=False); a timeout here is
            # "unknown" (neutral).
            ray_tpu.get(ref, timeout=5.0)
            return True
        except (resilience.Overloaded, resilience.DeadlineExceeded):
            return None
        except Exception as e:  # noqa: BLE001 - classify
            kind = resilience.classify(e)
            if kind in ("overloaded_replica", "overloaded_router",
                        "expired"):
                return None
            return False

    def _refresh_breaker_gauge(self) -> None:
        try:
            self._m_breaker_open.set(self.breaker.open_count())
        except Exception:
            pass

    # ----------------------------------------------------------- feedback

    def record_stream_outcome(self, replica_id: str, ok: bool,
                              latency_s: float | None = None,
                              method: str = "") -> None:
        """Breaker feedback for streaming calls: the generator wrapper
        reports first-chunk success (with TTFT as the latency sample of
        ``method``, which the breaker holds against the peers' first
        chunks of the same method and nothing else) or a mid-stream
        failure (the completion watcher can't see stream errors — they
        surface in the consumer)."""
        if ok:
            self.breaker.record_success(replica_id, latency_s or 0.0, method)
        else:
            self.breaker.record_failure(replica_id)
        self._refresh_breaker_gauge()

    def count_retry(self) -> None:
        try:
            self._m_retries.inc()
        except Exception:
            pass

    def count_hedge(self) -> None:
        try:
            self._m_hedges.inc()
        except Exception:
            pass

    def _release(self, replica_id: str) -> None:
        with self._lock:
            self._inflight[replica_id] -= 1
            self._not_saturated.notify_all()

    def notify_replicas_changed(self,
                                replicas: list[ReplicaInfo] | None = None
                                ) -> None:
        """Wake parked assign loops after a replica-set update (called from
        the long-poll callback in DeploymentHandle). With the new snapshot
        in hand, also adopt its settings, garbage-collect breaker state and
        cached actor handles for replicas the controller no longer
        publishes, and rebuild the prefix-cache map (dead and draining
        replicas drop out of it HERE — the choose loop must never
        prefix-route into a drain)."""
        if replicas is not None:
            self._adopt_settings(replicas)
            live = [r.replica_id for r in replicas]
            self.breaker.forget(live)
            live_set = set(live)
            for rid in list(self._actors):
                if rid not in live_set:
                    self._actors.pop(rid, None)
            now = time.monotonic()
            pm: dict[str, tuple[frozenset, float]] = {}
            for r in replicas:
                blocks = getattr(r, "prefix_blocks", None)
                if blocks and not getattr(r, "draining", False):
                    pm[r.replica_id] = (frozenset(blocks), now)
            self._prefix_map = pm
        with self._lock:
            self._not_saturated.notify_all()

    def touch_prefix_map(self) -> None:
        """Re-stamp every prefix-map entry (called after each successful
        long-poll round, updates or not). The controller republishes only
        on CHANGE, so a healthy deployment with a stable warm cache sends
        no snapshots — without this the TTL would expire exactly the
        steady-state publication it exists to protect, silently shutting
        prefix routing off after serve_prefix_map_ttl_s. The TTL then
        only trips when polling itself stops: a wedged/dead controller."""
        pm = self._prefix_map
        if pm:
            now = time.monotonic()
            self._prefix_map = {rid: (held, now)
                                for rid, (held, _) in pm.items()}

    # How far above the least-loaded replica a hint-preferred replica may
    # be before load balancing overrides cache locality.
    HINT_BALANCE_DELTA = 2

    def _eligible_locked(self, r: ReplicaInfo,
                         exclude) -> bool:
        if getattr(r, "draining", False):
            return False
        if exclude and r.replica_id in exclude:
            return False
        return not self.breaker.is_open(r.replica_id)

    def _choose_prefix_locked(self, replicas: list[ReplicaInfo],
                              prefix_hashes) -> ReplicaInfo | None:
        """Longest-matched-prefix choice over the (already eligible)
        candidate set. Ties on match length break to the least-loaded
        replica; a best-matched replica more than HINT_BALANCE_DELTA above
        the least-loaded one is skipped (locality yields to balance).
        Returns None when nothing matches — the caller falls through to
        rendezvous-hint and pow-2 choice."""
        pm = self._prefix_map
        if not pm:
            return None
        now = time.monotonic()
        ttl = self._prefix_ttl
        inflight = self._inflight
        min_load = min(inflight.get(r.replica_id, 0) for r in replicas)
        best = None
        best_m = 0
        best_load = 0
        for r in replicas:
            ent = pm.get(r.replica_id)
            if ent is None:
                continue
            held, stamp = ent
            if ttl > 0 and now - stamp > ttl:
                continue  # aged out: stale publication, ignore
            m = match_len(prefix_hashes, held)
            if m <= 0:
                continue
            load = inflight.get(r.replica_id, 0)
            if load >= r.max_ongoing_requests:
                continue
            if load - min_load > self.HINT_BALANCE_DELTA:
                continue
            if m > best_m or (m == best_m and load < best_load):
                best, best_m, best_load = r, m, load
        if best is None:
            return None
        ok, probe = self.breaker.allow_ex(best.replica_id)
        if not ok:
            return None  # half-open, probe budget spent: balance instead
        self._choice_was_probe = probe
        try:
            self._m_prefix_hits.inc()
        except Exception:
            pass
        return best

    def _choose_locked(self, replicas: list[ReplicaInfo],
                       route_hint: str | None = None,
                       exclude: set[str] | frozenset[str] | None = None,
                       prefix_hashes: tuple | None = None
                       ) -> ReplicaInfo | None:
        """Choice over the ELIGIBLE set: never a draining replica, never
        one the caller already tried, never one whose breaker is open
        (half-open admission happens below, via breaker.allow_ex).
        Prefix-match first, then rendezvous hint, then pow-2."""
        self._choice_was_probe = False
        replicas = [r for r in replicas if self._eligible_locked(r, exclude)]
        if not replicas:
            return None
        if prefix_hashes:
            got = self._choose_prefix_locked(replicas, prefix_hashes)
            if got is not None:
                return got
        if route_hint is not None:
            # Rendezvous hashing: every router maps the same hint to the
            # same replica without coordination — but only while the hinted
            # replica's load stays within HINT_BALANCE_DELTA of the
            # least-loaded replica. Beyond that, locality yields to pow-2
            # balancing (a deployment-wide shared prefix must not pin all
            # traffic to one replica while siblings idle).
            import zlib

            min_load = min(self._inflight.get(r.replica_id, 0)
                           for r in replicas)
            ranked = sorted(
                replicas,
                key=lambda r: zlib.crc32(
                    f"{route_hint}:{r.replica_id}".encode()),
            )
            for r in ranked:
                load = self._inflight.get(r.replica_id, 0)
                if load >= r.max_ongoing_requests:
                    continue
                if load - min_load <= self.HINT_BALANCE_DELTA:
                    ok, probe = self.breaker.allow_ex(r.replica_id)
                    if ok:
                        self._choice_was_probe = probe
                        return r
                    continue  # half-open and out of probe slots
                break  # hinted replica overloaded — balance instead
        candidates = (self._rng.sample(replicas, 2)
                      if len(replicas) >= 2 else list(replicas))
        best, best_load = None, None
        for r in candidates:
            load = self._inflight.get(r.replica_id, 0)
            if load >= r.max_ongoing_requests:
                continue
            if best_load is None or load < best_load:
                best, best_load = r, load
        if best is None:
            return None
        ok, probe = self.breaker.allow_ex(best.replica_id)
        if not ok:
            # Half-open with its probe budget spent: try the other pow-2
            # candidate; otherwise report saturation (the caller parks and
            # the breaker re-admits on the next wake).
            for r in candidates:
                if r.replica_id == best.replica_id:
                    continue
                load = self._inflight.get(r.replica_id, 0)
                if load < r.max_ongoing_requests:
                    ok2, probe2 = self.breaker.allow_ex(r.replica_id)
                    if ok2:
                        self._choice_was_probe = probe2
                        return r
            return None
        self._choice_was_probe = probe
        return best

    def metrics(self) -> dict[str, int]:
        with self._lock:
            return dict(self._inflight)
