"""Typed, env-overridable runtime configuration flags.

Same capability as the reference's RAY_CONFIG X-macro table
(reference: src/ray/common/ray_config_def.h — 233 flags, overridable via
``RAY_<name>`` env vars or a system-config JSON): a single registry of typed
flags with defaults, overridable per-process via ``RTPU_<NAME>`` environment
variables or a dict passed to ``Config.load(overrides=...)``.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, fields
from typing import Any

_ENV_PREFIX = "RTPU_"


def _coerce(value: str, typ: type) -> Any:
    if typ is bool:
        return value.lower() in ("1", "true", "yes", "on")
    if typ is int:
        return int(value)
    if typ is float:
        return float(value)
    return value


@dataclass
class Config:
    """Runtime flags. Add new flags as dataclass fields; env var = RTPU_<UPPER_NAME>."""

    # --- scheduling (reference: raylet scheduling policy knobs) ---
    scheduler_spread_threshold: float = 0.5  # hybrid policy: local-first until this load
    worker_lease_timeout_s: float = 30.0
    # Actor placement: how long a fresh worker fork may take to register
    # before the placement fails. Worker boot imports the framework (and
    # often jax) — seconds of CPU each; concurrent forks on small hosts
    # serialize, so this must be generous (reference: worker startup is
    # bounded by worker_register_timeout_seconds).
    worker_start_timeout_s: float = 120.0
    max_workers_per_node: int = 64
    worker_idle_ttl_s: float = 60.0  # idle pooled workers are reaped after this
    worker_startup_concurrency: int = 8
    lease_keepalive_s: float = 2.0  # idle driver-cached leases returned after this
    lease_spill_check_s: float = 0.3  # queued lease looks for a freer node after this
    # Max worker leases granted by ONE lease_workers RPC (the submitter
    # sizes requests by queue depth; the daemon grants up to this many idle
    # workers per round trip instead of one per RPC).
    lease_batch_max: int = 16
    # Idle workers the daemon keeps prestarted AHEAD of demand once leases
    # are being requested (0 disables): fan-out bursts land on a warm pool
    # instead of serializing on fork+register (~1 s of CPU per worker).
    idle_worker_pool: int = 1

    # --- object store (reference: plasma + spilling thresholds, ray_config_def.h:680-697) ---
    object_store_memory_bytes: int = 2 * 1024**3
    object_spilling_threshold: float = 0.8
    min_spilling_size_bytes: int = 100 * 1024**2
    max_fused_object_count: int = 2000
    inline_object_max_bytes: int = 100 * 1024  # small results ride in RPC replies

    # --- object transfer plane (reference: object_manager chunked transfer
    # knobs, ray_config_def.h object_manager_default_chunk_size) ---
    # Range size for chunked/pipelined pulls: each pull is split into
    # fixed-size ranges fetched concurrently from multiple serving copies,
    # and the cut-through watermark advances in units of this chunk.
    transfer_chunk_bytes: int = 16 * 1024 * 1024
    # Requests pipelined per transfer connection (the server streams range
    # after range without a request/response latency gap).
    transfer_pipeline_depth: int = 4
    # Serving copies the owner hands one puller (pipelined multi-source
    # pulls split ranges across them).
    transfer_max_sources: int = 3
    # Same-host zero-copy reads: a puller whose host boot id matches the
    # holder node's maps that node's arena directly and serves get() from
    # a pinned view — no wire transfer (plasma-style same-host sharing).
    # Disable to force every cross-node pull onto the TCP range engine
    # (e.g. when benchmarking the transfer plane itself).
    transfer_same_host_arena: bool = True

    # --- compiled graphs (ray_tpu/dag) ---
    # Channel transport for compiled DAGs in cluster mode: "direct" moves
    # payloads peer-to-peer over the actor push-frame path (head KV touched
    # once at compile time for route exchange, never per step); "kv" is the
    # head-KV fallback channel (every hop costs kv_put/kv_get head RPCs).
    # Local mode always uses in-process queues regardless of this knob.
    dag_channel: str = "direct"
    # Bounded execute_async() window: executions admitted into the pipeline
    # before the oldest completes (pipeline fill depth; backpressure blocks
    # the submitter beyond it).
    dag_max_inflight: int = 8
    # Per-channel capacity in unacked in-flight values: a direct-channel
    # writer blocks once this many writes are unacknowledged by a reader
    # (per-hop backpressure); also the queue bound of local channels.
    dag_channel_capacity: int = 16
    # Direct-channel payloads at or under this many serialized bytes ride
    # inline in the push frame; larger ones (activations/grads) become
    # store-backed buffers — same-host readers map them as pinned arena
    # views, cross-host readers pull them over the transfer plane.
    dag_inline_max_bytes: int = 64 * 1024

    # --- control plane ---
    health_check_period_s: float = 1.0
    # Failure-detection fast path (sub-minute recovery): how often the node
    # daemon polls its worker processes for death. The reap loop's idle-TTL
    # cadence (worker_idle_ttl_s/4 = 15 s) is far too slow to notice a
    # SIGKILLed train worker; this dedicated waitpid(WNOHANG) sweep costs
    # microseconds and bounds worker-death detection at ~this interval.
    # <= 0 falls back to reap-loop-only detection.
    worker_death_poll_s: float = 0.25
    # When a node daemon's persistent head connection drops, the head waits
    # this long for a re-register/heartbeat and then declares the node dead
    # immediately — instead of waiting for heartbeat aging (up to
    # health_check_period_s * health_check_failure_threshold = 5 s). A dead
    # daemon process closes its sockets at once, so this catches real node
    # death fast while the grace absorbs reconnect blips. < 0 disables the
    # fast path (heartbeat aging only).
    node_disconnect_grace_s: float = 0.5
    # Superseded by telemetry_flush_interval_s (the batched telemetry push
    # carries the task events); kept so existing RTPU_TASK_EVENT_* env
    # settings don't error, but no longer read.
    task_event_flush_interval_s: float = 0.5
    health_check_timeout_s: float = 5.0
    health_check_failure_threshold: int = 5
    gcs_pubsub_poll_timeout_s: float = 30.0
    actor_max_restarts_default: int = 0

    # --- core worker ---
    task_retry_delay_s: float = 0.1
    max_lineage_bytes: int = 64 * 1024**2
    max_direct_call_object_size: int = 100 * 1024
    task_events_buffer_size: int = 10000
    # Worker-side cache of deserialized function/class definitions fetched
    # from the head registry (LRU by serialized size; see core/fn_registry).
    fn_cache_max_bytes: int = 64 * 1024**2

    # --- memory monitor (reference: _private/memory_monitor.py:97 +
    # raylet/worker_killing_policy_group_by_owner.cc) ---
    memory_monitor_interval_s: float = 0.5  # 0 disables the watcher
    memory_usage_threshold: float = 0.95
    # Optional worker-memory budget: when set, the watcher also kills when
    # the sum of worker RSS exceeds threshold*budget (node-level pressure
    # against the detected cgroup/MemTotal limit always applies).
    memory_limit_bytes: int = 0

    # Head WAL group commit: mutation records buffered this long before one
    # coalesced write+flush. 0 = same-event-loop-tick coalescing (burst
    # mutations share one write, nothing outlives the tick that logged it);
    # > 0 trades a bounded durability window for fewer writes under churn.
    wal_group_commit_ms: float = 0.0

    # --- head fault tolerance (crash-consistent control plane) ---
    # Total wall budget one retrying head RPC (RpcClient.call_retrying)
    # may spend riding out a head crash/restart/partition before the
    # failure surfaces. This is what keeps RpcConnectionLost from
    # propagating into drivers, the serve controller, and the train
    # controller during a head outage shorter than the budget; mutations
    # stay exactly-once across the retries via the req-id dedup table.
    head_retry_budget_s: float = 30.0
    # Retry backoff bounds: each attempt sleeps uniform in [0, cap) with
    # cap doubling from base to max (full jitter — a restarted head with
    # hundreds of clients must see staggered retries, not a stampede).
    head_retry_base_s: float = 0.05
    head_retry_max_s: float = 2.0
    # Completed mutation request ids the head remembers (WAL-logged and
    # snapshotted with the tables they guard) so a retry after
    # crash-before-ACK is answered from the record instead of re-applied.
    # Oldest evicted beyond the bound; a retry older than the eviction
    # horizon falls back to the per-RPC natural-idempotence checks.
    head_dedup_max: int = 4096
    # Daemon heartbeat RPC timeout: bounds how long a partition-dropped
    # heartbeat frame can stall the loop before the daemon treats the
    # head as unreachable and enters its reconnect path. <= 0 disables
    # the bound (pre-FT behavior: a dropped frame wedges the loop).
    daemon_heartbeat_timeout_s: float = 5.0

    # --- fleet scale (thousand-node head fast path) ---
    # Delta heartbeats (ray_syncer's design, extending the PR-9 sid-table
    # telemetry scheme to the resource plane): after a full sync at
    # registration, a daemon ships only CHANGED availability keys per
    # heartbeat — or an empty beat when nothing moved — instead of its full
    # available/resources/demands maps every period. The head replies
    # ``resync`` (and daemons fall back to full maps) whenever it lacks a
    # baseline; head restarts resync through the existing re-register
    # path. 0 restores full-map heartbeats (the scale bench's "before").
    delta_heartbeat_enabled: bool = True
    # Indexed scheduling state: _pick_node walks a lazily-maintained
    # max-heap over effective CPU (plus a label inverted index and O(1)
    # affinity lookup) and _assign_bundles reads cached free-sums with
    # lazy per-node copies, instead of linearly scanning + deep-copying
    # the whole node table per placement/lease. 0 restores the linear
    # scans (kept as the parity reference in tests/test_scale.py).
    indexed_scheduler_enabled: bool = True
    # Pubsub fan-out coalescing window: publishes buffered this long are
    # batched into ONE pub_batch frame per subscriber connection, sent
    # concurrently — instead of one awaited notify per subscriber per
    # event. <= 0 restores immediate per-event, per-subscriber sends.
    pubsub_batch_window_s: float = 0.005
    # Head self-metrics cadence: the event-loop lag gauge
    # (head_loop_lag_s) and the per-RPC-method rate/latency series riding
    # the rpc.counts table are sampled this often into the watchdog store
    # and surfaced by head_status / `ray_tpu status`. <= 0 disables.
    head_metrics_period_s: float = 0.5
    # Simulated fleet (core/cluster/sim_fleet.py): default node count the
    # harness stands up when none is given, and the fake TPU inventory
    # each simulated node registers ("<kind>-<chips>", e.g. "v5e-8" →
    # resources {CPU, TPU: 8} + accelerator/topology labels).
    sim_fleet_nodes: int = 100
    sim_fleet_geometry: str = "v5e-8"
    # Streaming-split ingest backpressure: per-consumer prefetch bound —
    # blocks a SplitCoordinator may queue ahead of each consumer before
    # its producer thread stalls. Stalls/drains are counted in the
    # federated ``data_split_stall`` / ``data_split_empty_poll`` metrics
    # so the scale bench's ingest phase measures throughput instead of
    # unbounded buffering.
    data_split_prefetch_blocks: int = 8

    # --- collectives / multi-slice training ---
    # Cross-slice (DCN) wire format for hierarchical allreduce in multi-slice
    # collective groups ("none" | "bf16" | "int8"). "none" keeps the input
    # dtype. "bf16" halves DCN bytes at ~1e-3 relative error. "int8" is the
    # EQuARX-style per-bucket-scaled format: ~4x fewer DCN bytes at ~4e-3
    # relative error on the summed gradient (see tests/test_collective.py
    # parity tolerances). Per-group override: init_collective_group(
    # dcn_quant=...).
    collective_dcn_quant: str = "none"
    # Elements sharing one f32 scale in the int8 DCN format. Smaller buckets
    # track outliers better (lower error, more scale overhead); 256 keeps
    # scale overhead at 1.6% of payload.
    collective_dcn_quant_bucket: int = 256

    # --- kernels / train-step autotuning (env-only knobs) ---
    # The Pallas/loss kernel tuning knobs are read DIRECTLY from the
    # environment at trace time rather than through this Config: the ops
    # modules must stay importable without runtime initialization, and the
    # autotuner (ray_tpu/autotune) flips them per candidate between
    # compiles (Candidate.applied_env). Documented here because this file
    # is the flag registry of record:
    #   RTPU_FLASH_BLOCK_Q / RTPU_FLASH_BLOCK_K (512): flash-attention
    #     kernel block sizes — fwd, fused + split backward, ring chunk
    #     kernels; must divide the sequence length.
    #   RTPU_CE_CHUNK (512): fused cross-entropy sequence-chunk size —
    #     fewer scan steps vs a bigger [B, chunk, V] logits workspace.
    #   RTPU_FLASH_FUSED_BWD (1): fused dq+dkv backward kernel; 0 = the
    #     split dq / dkv kernel pair. Read ONCE at ops/attention import
    #     (module-level FUSED_BWD) — set it before the process starts;
    #     not flippable per candidate, unlike the trace-time knobs above.
    #   RTPU_FLASH_VMEM_LIMIT_MB (by TPU generation): scoped-VMEM ceiling
    #     for the flash kernels; 0 forces the compiler default.
    #   RTPU_HBM_BUDGET_GB (detected from the backend): HBM budget the
    #     autotuner's pruning tier compares predictions against.
    #   RTPU_AUTOTUNE_CACHE (<repo>/AUTOTUNE_CACHE.json): measured-
    #     throughput cache path (keyed device kind + geometry + config).
    #   RTPU_BENCH_MAX_MEASURE (6): candidates measured per bench round.

    # --- train ---
    # Compute the grad-norm metric every N steps (1 = every step, the
    # old behavior). The global-norm reduction is a full pass over the
    # gradients (its share of a step is not measured since the benchmark
    # was redefined); skipped steps report grad_norm = -1. Default for
    # make_train_step(grad_norm_every=None).
    train_grad_norm_every: int = 1
    # Set latency-hiding-scheduler / async-collective LIBTPU flags on train
    # workers before backend init, so DCN collectives overlap the next
    # microbatch's compute (train/backend.py _XLA_PERF_FLAGS). Flags ride
    # LIBTPU_INIT_ARGS, so they are inert on CPU hosts. Extra flags can be
    # appended via RTPU_TRAIN_XLA_PERF_FLAGS_EXTRA (space-separated).
    train_xla_perf_flags: bool = True

    # --- serve request resilience (per-deployment, not env flags) ---
    # The serve data-plane resilience knobs are deployment-scoped and live
    # on DeploymentConfig (ray_tpu/serve/config.py), set per deployment via
    # @serve.deployment(...) — different models need different budgets, so
    # a process-wide flag would be wrong. Documented here because this file
    # is the flag registry of record:
    #   request_timeout_s (30): default per-request budget; the absolute
    #     deadline rides handle → router → replica → batcher, bounding
    #     queue waits and dropping expired requests before they spend TPU
    #     time. Per call: handle.options(timeout_s=...); per HTTP request:
    #     x-request-timeout-s header; gRPC uses the client's deadline.
    #   max_queued_requests (256): router admission control — callers
    #     parked beyond this are shed with Overloaded (HTTP 503 +
    #     Retry-After / gRPC RESOURCE_EXHAUSTED). -1 = unbounded.
    #   replica_queue_slack (8): replica-side admission — reject once
    #     ongoing > max_ongoing_requests + slack (N routers can each fill
    #     their own per-router cap against one replica).
    #   retry_policy (RetryPolicy): max_retries (1) assignment retries on
    #     replica death / replica-side sheds, excluding replicas already
    #     tried; retry_never_sent (True) single safe retry of calls that
    #     provably never reached a replica; hedge_after_s (None) tail
    #     hedging for idempotent calls; backoff_s (0) jittered backoff.
    #   circuit_breaker (CircuitBreakerConfig): failure_threshold (3)
    #     consecutive failures → open; open_s (2.0) cooldown;
    #     half_open_probes (1) trial requests; latency_factor (5.0) /
    #     latency_min_samples (16) latency-outlier trip, a replica's
    #     recent median on a method vs its peers' on the same method.

    # --- serve inference fast path (KV-block-aware prefix routing +
    #     disaggregated P/D KV hand-off; serve/prefix.py, serve/router.py,
    #     llm/pd.py) ---
    # How often the controller polls each replica's router_meta() for its
    # prefix-cache block hashes and piggybacks them on the long-poll
    # replica snapshot. Replicas that answer None (non-LLM deployments)
    # are probed once and never polled again. <= 0 disables publication.
    serve_prefix_publish_period_s: float = 0.5
    # Router-side prefix-map entry TTL: an entry not refreshed by a
    # snapshot within this window is ignored (ages out state from a dead
    # controller / wedged long-poll; dead and draining replicas are
    # dropped from the map immediately on every snapshot). Aged-out
    # entries degrade to pow-2 routing — locality lost, correctness kept.
    serve_prefix_map_ttl_s: float = 30.0
    # Deployment/engine-scoped knobs documented here for the registry of
    # record (set on LLMConfig, not env flags):
    #   prefix_block_tokens (32): token-block granularity of the chain
    #     hashes replicas publish and request hints are computed with.
    #   pd_transfer_mode ("store"): disaggregated prefill→decode KV
    #     hand-off transport — "store" ships ObjectRefs to store-backed
    #     ndarrays over the zero-copy object plane (no serialize on the
    #     TTFT path); "inline" pickles the KV through the handle call.

    # --- chaos (ray_tpu/chaos) ---
    # Master gate for the fault-injection layer. Rules come from the
    # RTPU_CHAOS env var (JSON list), RTPU_CHAOS_FILE, the `chaos` CLI verb,
    # or util.state.inject_chaos(); with this False every installed rule is
    # inert (a production cluster can carry a chaos schedule disarmed).
    # Rule schema of record: ray_tpu/chaos/injector.py. Head-outage drills
    # use two dedicated points: ``head.tick`` (action "kill" = abrupt
    # control-plane death, no final flush — restart must replay the WAL)
    # and ``partition`` (directional head⇄node frame drop/delay; rule keys
    # ``match={"node": <regex>}`` and ``direction`` in
    # "to_head" | "from_head" | "both"). CLI: `ray_tpu chaos kill-head` /
    # `ray_tpu chaos partition --node <regex> [--direction D] [--drop]`.
    chaos_enabled: bool = True

    # --- train recovery ---
    # In-cluster replica shards a ReplicaStore keeps per run (newest
    # complete sets win; older steps are pruned). 2 lets a restore proceed
    # even when a worker died mid-way through pushing step N.
    train_replica_keep: int = 2
    # Seconds session.replicate()'s background pusher waits for one shard
    # push before counting it failed; replication disables itself after 3
    # consecutive failures (it must never become the thing that stalls or
    # kills a healthy run).
    train_replica_push_timeout_s: float = 30.0

    # --- observability ---
    # Flight recorder: JSON debug bundles dumped on task failure / worker
    # death / actor death under <temp_dir>/flight_records.
    flight_recorder_enabled: bool = True
    flight_recorder_max_bundles: int = 40
    # Cluster telemetry: how often each process pushes its metric snapshot,
    # finished spans, and drained task events to the head (<= 0 disables
    # the push entirely).
    telemetry_flush_interval_s: float = 0.5

    # --- request tracing (ray_tpu/util/tracing.py) ---
    # Head-sampling rate for serve ingress requests: the DeploymentHandle
    # draws one verdict per request and every downstream span (router,
    # replica, batcher, engine, DAG/KV hops) inherits it. Per-deployment
    # override: @serve.deployment(trace_sample_rate=...) rides the same
    # ResilienceSettings snapshot the other data-plane knobs use. Only
    # meaningful once tracing.enable_tracing() turned the master gate on.
    trace_sample_rate: float = 0.01
    # Tail-sampling ring bounds: spans of UNsampled traces are ringed per
    # trace_id (promotable by a retroactive keep when the request ends
    # slow / shed / expired / errored / breaker-implicated) instead of
    # discarded. Distinct traces held, spans kept per trace, and the ring
    # TTL — all per process; past any bound the oldest die unkept.
    trace_tail_traces: int = 512
    trace_tail_spans_per_trace: int = 64
    trace_tail_ttl_s: float = 30.0
    # "Ended slow" keep verdict: rolling per-deployment latency window —
    # sample count and the minimum history before the p99 gate judges
    # (no verdicts off a cold window).
    trace_slow_window: int = 512
    trace_slow_min_samples: int = 64
    # Recent exemplar (trace_id, value) pairs each histogram SERIES keeps
    # so TTFT/TPOT/latency buckets link back to traces (/api/metrics,
    # /api/traces, watchdog incident bundles). 0 disables exemplars.
    metrics_exemplar_count: int = 4

    # --- health watchdog (ray_tpu/observability) ---
    # Master gate: with this on, every process's telemetry flusher derives
    # delta-encoded samples for the hot-path series (train step/tokens/MFU,
    # collective latency+bytes, serve TTFT/TPOT/queue/shed, transfer bytes,
    # per-process RSS/HBM) and the head runs streaming anomaly detectors
    # over them, auto-capturing evidence on a trip. Off = no sampling, no
    # detection, no auto-captures (the pull-based surfaces still work).
    watchdog_enabled: bool = True
    # Head loop cadence: heartbeat-gap sampling + incident assembly tick.
    # Detection itself is streaming (evaluated at sample arrival), so this
    # bounds evidence-capture latency, not detection latency.
    watchdog_eval_interval_s: float = 0.5
    # Rolling points kept per series (ring buffer) and distinct series the
    # store accepts before dropping (watchdog_dropped_samples counts).
    watchdog_series_samples: int = 360
    watchdog_series_max: int = 4096
    # Detector firing discipline (see observability/detectors.py): no
    # verdicts before `warmup` samples; `debounce` CONSECUTIVE breaching
    # samples to trip; a tripped series is muted for `cooldown_s`.
    watchdog_warmup_samples: int = 10
    watchdog_debounce: int = 3
    watchdog_cooldown_s: float = 30.0
    # Spike rules (step-time drift, collective latency, serve p99,
    # heartbeat jitter): robust z-score above this AND value above
    # ratio * baseline (both, so steady-but-noisy series can't trip).
    watchdog_z_threshold: float = 6.0
    watchdog_spike_ratio: float = 2.0
    # Absolute floors for the baseline-free rules: shed/expiry rate
    # (healthy = 0/s), router queue growth (levels are fine, sustained
    # growth is the death spiral), per-process RSS/HBM leak slope.
    watchdog_shed_rate_per_s: float = 0.5
    watchdog_queue_growth_per_s: float = 2.0
    watchdog_mem_slope_mb_s: float = 256.0
    # Incident retention (bounded deque on the head).
    watchdog_max_incidents: int = 64
    # Anomaly-triggered targeted profiler captures (PR-5 profile_node RPC,
    # scoped to the implicated node) — hard guardrails: concurrent-capture
    # cap, per-node cooldown, and a lifetime budget per head, so the
    # watchdog can never pile profiling onto an already-sick cluster.
    watchdog_auto_capture: bool = True
    watchdog_capture_seconds: float = 1.5
    watchdog_max_auto_captures: int = 1
    watchdog_capture_cooldown_s: float = 60.0
    watchdog_capture_budget: int = 20

    # --- goodput ledger (ray_tpu/observability/goodput.py) ---
    # Master gate: with this on, every live TrainContext carries a
    # RankLedger classifying its wall clock into the goodput phase
    # taxonomy (snapshots ride the existing train-stats telemetry rows),
    # controllers/heads stamp restart/outage events onto the same pushes,
    # and the head aggregates a per-run + fleet goodput rollup. Off = no
    # ledgers, no event legs, no head store.
    goodput_enabled: bool = True
    # Badput-over-threshold watchdog rule: a run burning more than this
    # percentage of its chip-seconds in ONE badput phase opens a
    # `badput_over_threshold` incident with the ledger window attached.
    goodput_badput_pct: float = 50.0
    # No incident before the run has attributed at least this much wall
    # time (init/compile dominate any run's first seconds by design).
    goodput_badput_min_wall_s: float = 10.0
    # Per-run cooldown between badput incidents.
    goodput_badput_cooldown_s: float = 60.0
    # Head-side rollup/gauge/incident-check cadence (piggybacked on
    # telemetry ingest, throttled to at most once per this interval).
    goodput_check_interval_s: float = 5.0

    # --- on-demand profiler (ray_tpu/profiling) ---
    # Python stack-sampler rate for `profile` captures. 100 Hz keeps the
    # overhead within the <=2% budget devbench/profile_overhead.py measures;
    # raise for finer flamegraphs on beefy hosts. The sampler clamps any
    # requested rate to 1 kHz — above that the per-sample GIL cost
    # approaches the interval and a single profile request would busy-loop
    # every process in the cluster.
    profiler_sample_hz: float = 100.0
    # Hard ceiling on one capture's duration: a fat-fingered
    # `profile --seconds 86400` must not leave samplers running for a day.
    # Requests are clamped, not rejected.
    profiler_max_capture_s: float = 60.0
    # Concurrent `profile_node` captures a node daemon will run at once;
    # excess requests are refused (and counted in
    # profiler_dropped_captures) so profiling can't pile onto a node that
    # is already being profiled.
    profiler_max_concurrent_captures: int = 2
    # Allow `jax.profiler` device-trace capture inside profile sessions.
    # Off, or on a process without an initialized non-CPU jax backend, the
    # capture carries a no-op marker instead of a trace.
    profiler_xla_trace: bool = True

    # --- env-only knobs and internal plumbing (registry of record) ---
    # These are read straight from the environment (no Config field): the
    # first group is user-settable, the second is wiring the node daemon
    # stamps into forked worker processes (set them yourself only in
    # tests). rtlint rule R5 enforces that every RTPU_* read in the tree
    # has an entry here or a Config field.
    #   RTPU_USAGE_STATS_ENABLED (1): usage-stats collection master
    #     switch (usage/__init__.py); "0" disables.
    #   RTPU_PEAK_FLOPS (backend-detected): per-device peak FLOP/s
    #     override for the MFU/goodput denominators; without it the
    #     generation table in accelerators/flops.py resolves from the
    #     initialized backend's device_kind.
    #   RTPU_CONTAINER_RUNNER ("podman"): container runtime binary for
    #     runtime_env containers; tests point it at a stub
    #     (runtime_env/container.py).
    #   RTPU_HEAD / RTPU_NODE_DAEMON (internal): head / daemon host:port
    #     a forked worker connects back to.
    #   RTPU_NODE_ID (internal): hex node id of the owning daemon,
    #     stamped into worker registration.
    #   RTPU_WORKER_NONCE (internal): fork nonce tying a worker
    #     registration to the lease that requested it.
    #   RTPU_PARENT_PID (internal): daemon pid a worker watches so
    #     orphaned workers exit when the daemon dies.
    #   RTPU_SHM_NAME (internal): shared-memory arena name workers map
    #     for the same-host zero-copy object plane.

    # --- RL vectorized Podracer paths (registry of record) ---
    # The vectorized-RL knobs live on rl/ppo.py's PPOConfig rather than
    # here (they are per-algorithm, not per-process), but this block is
    # their registry of record for rtlint R5 and discoverability:
    #   PPOConfig.vectorized (False): route JAX-implemented envs
    #     (rl/vec_env.py registry) to the fused Anakin program
    #     (num_env_runners == 0) or Sebulba streaming actors
    #     (num_env_runners > 0); Python-only envs keep the EnvRunner path.
    #   PPOConfig.num_envs (0): total vectorized envs; 0 derives
    #     num_envs_per_runner x max(1, num_env_runners).
    #   PPOConfig.unroll_len (0): scan unroll length per rollout block;
    #     0 falls back to rollout_len.
    #   PPOConfig.sebulba_staleness (2): learner drops trajectory blocks
    #     older than this many weight versions (consume-time check).
    #   RTPU_RL_NUM_ENVS / RTPU_RL_UNROLL_LEN / RTPU_RL_ANAKIN_DEVICES
    #     (bench-only): geometry overrides read by devbench/rl_bench.py,
    #     not by the library (Anakin itself takes the device count via
    #     PPOConfig.extra["anakin_devices"]).

    # --- tpu ---
    tpu_visible_chips_env: str = "TPU_VISIBLE_CHIPS"
    tpu_premapped_buffer_bytes: int = 0  # 0 = library default

    # --- misc ---
    temp_dir: str = field(default_factory=lambda: os.environ.get("RTPU_TEMP_DIR", "/tmp/ray_tpu"))
    log_level: str = "INFO"

    @classmethod
    def load(cls, overrides: dict[str, Any] | None = None) -> "Config":
        cfg = cls()
        for f in fields(cls):
            env_key = _ENV_PREFIX + f.name.upper()
            if env_key in os.environ:
                typ = type(getattr(cfg, f.name))
                setattr(cfg, f.name, _coerce(os.environ[env_key], typ))
        for k, v in (overrides or {}).items():
            if not hasattr(cfg, k):
                raise ValueError(f"unknown config flag: {k}")
            setattr(cfg, k, v)
        return cfg

    @classmethod
    def from_json(cls, payload: str) -> "Config":
        return cls.load(json.loads(payload))

    def to_dict(self) -> dict[str, Any]:
        return {f.name: getattr(self, f.name) for f in fields(self)}


_global_config: Config | None = None


def get_config() -> Config:
    global _global_config
    if _global_config is None:
        _global_config = Config.load()
    return _global_config


def set_config(cfg: Config) -> None:
    global _global_config
    _global_config = cfg
