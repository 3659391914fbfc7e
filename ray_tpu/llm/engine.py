"""JAX LLM inference engine: continuous batching over a slot KV cache.

Capability parity with the reference's serving engine (reference: ray.llm
wraps vLLM — _internal/serve/engines/vllm/vllm_models.py:148; continuous
batching + paged KV are vLLM internals). TPU-native design instead of a
wrapper. This file is the scheduler and nothing of a model: it knows a
model through ``self.model`` only, the contract of llm/served.py
(``ServedModel``), which llm/config.SERVING_MODULES finds for a
configuration; a model's programs live in its llm/<name>_serving.py.

- **Static shapes everywhere** (XLA compiles once per prefill bucket):
  the cache is a model's pytree of dense ``[lines, slots, ...]`` leaves (for
  most, per-head K/V ``[lines, slots, kv_heads, max_seq, head_dim]``:
  llm/served.init_kv_cache); a sequence owns one slot for its lifetime —
  slot admission is the scheduling unit, like vLLM's paged blocks but
  shaped for XLA/TPU (no dynamic page tables). It is the engine's one KV
  layout; a pool of blocks shared between lines comes with ROADMAP R3, as
  tables the attention kernels read. The programs write a chunk's or a step's rows of
  a slot in place and read only the live blocks of its line: the stacked
  cache is loop carry, and no operation of a chunk or decode program has a
  whole layer of it as operand.
- **Continuous batching**: every engine tick admits waiting requests into
  free slots (bucketed prefill) and then decodes ALL active slots in one
  batched jitted step — new requests join mid-flight without stalling
  running ones.
- **Roundtrip-lean scheduling**: decode runs up to ``decode_burst`` steps
  per dispatch (sampled tokens fed forward on device via lax.scan), and the
  scheduler dispatches the next program before it reads the last: what it
  has dispatched and not read waits in one first-in-first-out list
  (``_in_flight``), a burst is queued behind the one that runs, tokens pass
  from program to program on the device, and results are read in the order
  the device makes them. The host reads, emits, admits and prepares inputs
  beside a running burst, not between two.
- **Sampling on-device**: temperature/top-k/top-p in fp32 logits, one
  fused jit (llm/served.sample_tokens); greedy when temperature == 0.
- Cache buffers are donated through jit so XLA updates them in place.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.devtools.annotations import guarded_by
from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.util import tracing
from ray_tpu.llm.tokenizer import get_tokenizer
from ray_tpu.ops.decode_attention import kv_positions_read
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.parallel.sharding import kernel_mesh, shard_params
from ray_tpu.utils.compile_cache import ensure_compile_cache

# The contract (llm/served.py) is all the scheduler knows of a model. Some
# of these names are also held in THIS module by other people's files, so
# they stay importable from here until ROADMAP R0 (f), the ``benchmark`` PR
# that points those files at llm/served.py and llm/llama_serving.py:
# - ``init_params``: benchmark/rtbench/kinds/serve_common.py and
#   benchmark/control.py put a jitted copy in this module's name (the engine
#   calls it through that name, looked up when it is called), and
#   tests/bench_harness/test_bh_{longcat,ouro,lfm2,sdar}.py call it here;
# - ``served_model``, ``ServedModel``, ``sample_tokens``: the contract as
#   benchmark/rtbench/adapters/{__init__,longcat}.py describe it, by this
#   module's name;
# - ``init_kv_cache``, ``prefill_chunk``, ``decode_step``: Llama's programs,
#   with their two-value returns, for
#   tests/bench_harness/test_bh_reference.py alone. Nothing in this file
#   uses them: the scheduler reaches every program through ``self.model``.
from ray_tpu.llm.served import (  # noqa: F401
    ServedModel,
    init_params,
    require_kv_handoff,
    require_tensor_parallel,
    sample_tokens,
    served_model,
)
from ray_tpu.llm.llama_serving import (  # noqa: F401
    decode_step,
    init_kv_cache,
    prefill_chunk,
)

logger = logging.getLogger(__name__)


def _lcp(a, b, cap: int) -> int:
    n = min(len(a), len(b), cap)
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i


@jax.jit
@tracing.part("sample")
def _last_row(toks):
    """A burst's tokens [steps, B] -> its last step's [B] ([steps, B, K] ->
    [B, K] from a model whose step is K positions): what every continuing
    line hands to the next burst, left on the device."""
    return toks[-1]


@jax.jit
@tracing.part("sample")
def _join_token(tokens, first, slot):
    """tokens [B] with ``first`` [1], a prompt's sampled first token, at
    ``slot``: the line joins a burst without a host read of that token."""
    return lax.dynamic_update_slice(tokens, first.astype(tokens.dtype),
                                    (slot,))



@dataclass
class GenerationRequest:
    request_id: str
    prompt_ids: list[int]
    sampling: SamplingParams
    out_tokens: list[int] = field(default_factory=list)
    stream_queue: queue.Queue | None = None
    done: threading.Event = field(default_factory=threading.Event)
    error: str | None = None
    finish_reason: str | None = None
    # Position the line's next step starts at (prompt positions among them,
    # where a prompt's tail rides into the first step); <0 = prefilling.
    next_pos: int = 0
    ahead: int = 0  # decode steps dispatched for it and not yet read
    prefilled_len: int = 0  # prompt tokens already in the KV cache
    preloaded: tuple | None = None  # (kv_k, kv_v, first_token) P/D import
    last_slot: int = -1  # slot the request last occupied (KV export)
    hold_slot: bool = False  # keep the slot (and its KV) after finishing
    draft_len: int = 0  # draft-cache positions filled (speculative decoding)
    draft_fail_count: int = 0  # consecutive draft catch-up failures
    spec_disabled: bool = False  # excluded from speculation (see _spec_decode)
    # Request tracing: the submitter's propagated context (None = untraced)
    # plus the phase timestamps the scheduler thread stamps engine spans
    # from (engine.queue / engine.prefill / engine.decode — the TTFT
    # breakdown). kv_imported marks a P/D hand-off continuation.
    trace_ctx: dict | None = None
    submit_ts: float = 0.0
    admit_ts: float = 0.0
    first_token_ts: float = 0.0
    finish_ts: float = 0.0
    kv_imported: bool = False


@dataclass
class _InFlight:
    """A dispatched program whose tokens the host has not read: a prompt's
    first token (``steps`` 0, ``toks`` [1]) or a burst of ``steps`` steps
    (``toks`` [steps, slots], or [steps, slots, K] from a model whose step
    is K positions; ``last_row`` its final row, handed to the next burst:
    a line's input token, or its ServedModel.pending_step).
    ``reqs`` are the lines it computes for, by slot; ``counts`` the model's
    own (ServedModel.counters), fetched with the tokens: the program's and
    those of the prefill chunks dispatched since the entry before it
    (LLMEngine._take_counts), which the device has run by then."""
    reqs: dict[int, GenerationRequest]
    toks: Any
    counts: list = field(default_factory=list)
    steps: int = 0
    last_row: Any = None


@dataclass
class GenerationResult:
    request_id: str
    prompt_ids: list[int]
    token_ids: list[int]
    text: str
    finish_reason: str


@guarded_by("_submit_lock", "_requests")
class LLMEngine:
    """The continuous-batching engine. Thread-safe: ``generate``/``submit``
    may be called concurrently (e.g. from serve replica threads); one
    background scheduler thread owns the device state."""

    def __init__(self, config: LLMConfig, params: Any = None):
        ensure_compile_cache()
        self.config = config
        self.model_cfg = config.model_config()
        self.model = served_model(self.model_cfg)
        if config.kv_block_size:
            raise ValueError(
                "kv_block_size must be 0: the engine has one KV layout, "
                "slot lines read in place; the block pool comes back with "
                "ROADMAP R3, as tables the attention kernels read")
        if self.model.refuse is not None:
            self.model.refuse(config)
        require_tensor_parallel(self.model_cfg, config.tensor_parallel_size)
        # Speculative decoding: draft model + its own KV cache. The draft
        # must share the tokenizer's vocab space with the target; the
        # target supplies the verify program and the draft the proposals.
        self.draft_cfg = config.draft_model_config()
        self.draft_model = (served_model(self.draft_cfg)
                            if self.draft_cfg is not None else None)
        if self.draft_model is not None:
            for cfg, program in (
                    (self.model_cfg, self.model.spec_verify_step),
                    (self.draft_cfg, self.draft_model.draft_propose)):
                if program is None:
                    raise ValueError(f"{type(cfg).__name__} does not "
                                     "support a speculative draft")
        self.tokenizer = get_tokenizer(config.tokenizer)
        self.max_slots = config.max_num_seqs

        if params is None and config.checkpoint_path:
            import os as _os

            if _os.path.isfile(_os.path.join(config.checkpoint_path,
                                             "config.json")):
                # HuggingFace checkpoint directory: geometry comes from the
                # checkpoint itself (reference: ray.llm passes HF ids to
                # vLLM; here llm/hf.py converts weights directly).
                from ray_tpu.llm.hf import convert_hf_llama

                self.model_cfg, params = convert_hf_llama(
                    config.checkpoint_path, dtype=config.dtype)
            else:
                params = _load_checkpoint(config.checkpoint_path)
        # Validate against the FINAL geometry — an HF checkpoint replaces
        # config.model's placeholder, and its (usually larger) vocab is
        # what the tokenizer must fit in.
        self.max_seq = config.max_seq_len or self.model_cfg.max_seq_len
        if self.tokenizer.vocab_size > self.model_cfg.vocab_size:
            raise ValueError("tokenizer vocab exceeds model vocab")
        if params is None:
            params = init_params(self.model_cfg,
                                 jax.random.PRNGKey(config.seed))
        # Tensor parallel: one mesh over the first tp devices. Params and
        # the KV cache shard their head/mlp dims over its tp axis and jit
        # propagates that into every program; the Pallas kernels get the
        # mesh as ``kmesh``. tp == 1 leaves everything on the default device.
        self.mesh = self.kmesh = None
        if config.tensor_parallel_size > 1:
            self.mesh = _tp_mesh(config.tensor_parallel_size)
            self.kmesh = kernel_mesh(self.mesh)
        self.params = self._place_params(params, self.model_cfg)
        # Times _recover_device_failure ran, and requests failed for any
        # reason: a failed device step fails the slotted requests and
        # serving goes on, so only these counters (in stats()) tell a
        # caller that a kernel or a program did not run.
        self.device_failures = 0
        self.requests_failed = 0
        # Work done and time waited, counted where it happens: cumulative,
        # never reset (not by a device failure either), written by the
        # scheduler thread alone and read through stats(), so a reader
        # that polls takes window deltas. decode_steps are forwards of the
        # stack (a burst of 8 counts 8, and a burst of 2 steps of 5
        # forwards 10; each computes every slot), decode_tokens
        # the tokens they gave that a request still wanted (so not its
        # first, where prefill gives it); decode_dispatches_ahead those
        # dispatches made while an earlier program's result was still
        # unread, so the device had work; queue_wait_s sums admit - submit
        # over `admitted`, first_token_wait_s first token - admit over
        # `first_tokens`. kv_positions_read / kv_positions_reserved say how
        # far the decode kernel's skipping of dead blocks engages: per
        # decode step (one kernel call a layer; a verify step is one),
        # the positions of every decoding slot's line the kernel fetches
        # (its length rounded up to the kernel's block) over slots x max_seq.
        # prefill_kv_positions_read / _reserved say the same of a prefill
        # chunk, for any model: the positions of its slot's line a
        # length-aware prefill attention has to visit (the cached rows and
        # the chunk's bucket, before rounding to a block) over max_seq.
        self.ticks = 0
        self.admitted = 0
        self.finished = 0
        self.prompt_tokens_prefilled = 0
        self.prefill_chunks = 0
        # Of prefill_chunks and prompt_tokens_prefilled, those that rode a
        # decode step (ServedModel.mixed_burst; 0 for a model without one).
        self.prefill_chunks_riding = 0
        self.prefill_tokens_riding = 0
        self.decode_dispatches = 0
        self.decode_dispatches_ahead = 0
        self.decode_steps = 0
        self.decode_tokens = 0
        self.kv_positions_read = 0
        self.kv_positions_reserved = 0
        self.prefill_kv_positions_read = 0
        self.prefill_kv_positions_reserved = 0
        self._kv_block = self.model.kv_block(self.model_cfg, self.max_seq)
        # One decode step of one line: positions decided, forwards spent.
        self._step_positions, self._step_forwards = (
            self.model.step(self.model_cfg) if self.model.step else (1, 1))
        # What the model's programs count themselves (ServedModel.counters).
        self.model_counts = dict.fromkeys(self.model.counters, 0)
        if self.model.constants is not None:
            self.model_counts.update(self.model.constants(self.model_cfg))
        self.first_tokens = 0
        self.queue_wait_s = 0.0
        self.first_token_wait_s = 0.0
        # A slot's turn-round: from the time it was freed (a line's finish,
        # a held slot's release; _slot_freed, by slot) to the admission of
        # the request that takes it next, summed over `slot_refills`. A
        # slot's first use counts in neither. With as many closed-loop
        # clients as slots this is what the device steps over empty while
        # an answer's end goes out and the next request comes in.
        self.slot_vacant_s = 0.0
        self.slot_refills = 0
        self._slot_freed: dict[int, float] = {}
        self.cache = self._new_cache(self.model_cfg)

        self.spec_k = max(1, int(config.speculative_tokens))
        self.draft_params = None
        self.draft_cache = None
        self.spec_ticks = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        if self.draft_cfg is not None:
            if self.draft_cfg.vocab_size != self.model_cfg.vocab_size:
                raise ValueError(
                    "speculative draft must share the target's vocab "
                    f"({self.draft_cfg.vocab_size} != "
                    f"{self.model_cfg.vocab_size})")
            dp = None
            if config.speculative_checkpoint_path:
                dp = _load_checkpoint(config.speculative_checkpoint_path)
            if dp is None:
                dp = init_params(self.draft_cfg,
                                 jax.random.PRNGKey(config.seed + 7))
            self.draft_params = self._place_params(dp, self.draft_cfg)
            self.draft_cache = self._new_cache(self.draft_cfg)

        self._slots: dict[int, GenerationRequest | None] = {
            i: None for i in range(self.max_slots)}
        # Prefix KV reuse (reference: vLLM automatic prefix caching +
        # routing_policies/prefix_aware/ — the serve router already sends
        # shared-prefix requests to the same replica; here the engine makes
        # the shared prefill actually free). Donor registry:
        # - _prefix_live: slot -> prompt tokens, prefill COMPLETE, request
        #   still running (adoption copies the line to the new slot).
        # - _prefix_cached: retired slot -> (tokens, last_use); the slot is
        #   unoccupied but its KV is intact — an exact/prefix re-hit admits
        #   straight into it with zero copy; unrelated admits evict LRU.
        self._prefix_live: dict[int, tuple[int, ...]] = {}
        self._prefix_cached: dict[int, tuple[tuple[int, ...], float]] = {}
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        # KV-block-aware routing: chain hashes of the cached prefixes are
        # published to the serve router (serve/prefix.py) so shared-prefix
        # bursts land on the replica already holding the blocks. The hash
        # cache is keyed by the prompt tuple and pruned to the live donor
        # set on every publish.
        self.prefix_block = int(getattr(config, "prefix_block_tokens", 32)
                                or 0) if self.model.prefix_from_line else 0
        self._prefix_hash_cache: dict[tuple, tuple[int, ...]] = {}
        self._cache_gen = 0  # bumped when a device failure rebuilds the cache
        self._prefill_rr = -1  # last slot that ran a prefill chunk
        # A burst that carries chunks (ServedModel.mixed_burst) has one
        # length, the one _burst_len gives while a slot is mid-prefill and
        # a line has that many steps left, and its chunks one size, the
        # full bucket: one compiled shape, which the first riders build.
        # 0: chunks never ride (the model offers no such program, a draft
        # model's ticks read the host's tokens, or a burst is one step).
        self._ride_steps = 0
        self._ride_rows = self._chunk_bucket(0, config.prefill_chunk)[0]
        if self.model.mixed_burst is not None and self.draft_cfg is None:
            steps = min(int(config.decode_burst or 1),
                        self.PREFILL_PRIORITY_BURST)
            self._ride_steps = 1 << (steps.bit_length() - 1) if steps > 1 \
                else 0
        self._tick_chunks = 0  # chunks this tick has dispatched, riders too
        self._waiting: queue.Queue[GenerationRequest] = queue.Queue()
        # Held slots returned by release_slot (user threads); the
        # scheduler thread frees + retires them at tick start — slot and
        # prefix-cache registries have a single mutating thread.
        self._released: queue.Queue[GenerationRequest] = queue.Queue()
        self._requests: dict[str, GenerationRequest] = {}
        # Serve replicas submit from max_concurrency pool threads: the
        # request-table insert must not interleave with another (rtlint
        # R1). The scheduler thread takes the lock only for its table pop.
        self._submit_lock = threading.Lock()
        self._rng_key = jax.random.PRNGKey(config.seed + 1)
        # Programs dispatched and not yet read, oldest first: the device
        # runs them in this order and the host reads them in it. Pipelined,
        # a tick leaves one burst queued behind the one that runs;
        # otherwise (and with a draft model, whose ticks read the host's
        # tokens) a tick reads out all it dispatched.
        self._in_flight: deque[_InFlight] = deque()
        # Model counts (ServedModel.counters) of prefill chunks dispatched
        # since the last program whose result the host reads: they go with
        # the next such program (_take_counts).
        self._chunk_counts: list = []
        self._pipelined = bool(config.decode_pipeline) \
            and self.draft_cfg is None
        self._stop = threading.Event()
        self._work = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="llm-engine")
        self._thread.start()

    # ---- public API ----

    def submit(self, prompt: str | list[int],
               sampling: SamplingParams | None = None,
               stream: bool = False) -> GenerationRequest:
        sampling = sampling or SamplingParams()
        if sampling.top_k and self.model.step is not None:
            raise ValueError(
                f"{type(self.model_cfg).__name__} does not support top_k: "
                "its step samples on the device, where k is static")
        ids = (self.tokenizer.encode(prompt) if isinstance(prompt, str)
               else list(prompt))
        ids = ids[: self.max_seq - 1]
        req = GenerationRequest(
            request_id=uuid.uuid4().hex[:12], prompt_ids=ids,
            sampling=sampling,
            stream_queue=queue.Queue() if stream else None)
        # Capture the submitter's trace context while its thread-local is
        # live: the scheduler thread stamps the engine phase spans onto
        # the REQUEST's trace from a thread that never entered it.
        req.trace_ctx = tracing.inject() if tracing.current_context() \
            else None
        req.submit_ts = time.time()
        with self._submit_lock:
            self._requests[req.request_id] = req
        self._waiting.put(req)
        self._work.set()
        return req

    def generate(self, prompt: str | list[int],
                 sampling: SamplingParams | None = None,
                 timeout: float = 300.0) -> GenerationResult:
        return self.result(self.submit(prompt, sampling), timeout)

    def result(self, req: GenerationRequest,
               timeout: float = 300.0) -> GenerationResult:
        """Wait for a submitted request's end."""
        if not req.done.wait(timeout):
            raise TimeoutError(f"generation {req.request_id} timed out")
        if req.error:
            raise RuntimeError(req.error)
        return self._result(req)

    # -- prefill/decode disaggregation (reference:
    #    serving_patterns/prefill_decode/pd_server.py + kv_transfer/ — a
    #    prefill engine computes the prompt's KV once, ships it, and a
    #    decode engine continues token generation from it) --

    def prefill_only(self, prompt: str | list[int],
                     sampling: SamplingParams | None = None) -> dict:
        """Run ONLY the prompt prefill; return the KV slice + first sampled
        token for hand-off to a decode engine."""
        require_kv_handoff(self.model_cfg)
        sampling = sampling or SamplingParams()
        ids = (self.tokenizer.encode(prompt) if isinstance(prompt, str)
               else list(prompt))
        ids = ids[: self.max_seq - 1]
        req = GenerationRequest(
            request_id=uuid.uuid4().hex[:12], prompt_ids=ids,
            sampling=replace(sampling, max_tokens=1), hold_slot=True)
        req.trace_ctx = tracing.inject() if tracing.current_context() \
            else None
        req.submit_ts = time.time()
        with self._submit_lock:
            self._requests[req.request_id] = req
        self._waiting.put(req)
        self._work.set()
        try:
            if not req.done.wait(120):
                raise TimeoutError("prefill timed out")
            # Capture the cache reference + generation BEFORE the error
            # check: if a device failure rebuilds the cache mid-export, the
            # gen re-check below turns a silent all-zero export into an
            # error (reading the old donated cache raises on its own).
            cache, gen = self.cache, self._cache_gen
            if req.error:
                raise RuntimeError(req.error)
            p = len(ids)
            # hold_slot kept the slot reserved so no other admit overwrote
            # the KV lines between finish and this export.
            slot = req.last_slot
            kv_k = np.asarray(cache["k"][:, slot, :, :p, :])
            kv_v = np.asarray(cache["v"][:, slot, :, :p, :])
            if self._cache_gen != gen or req.error:
                raise RuntimeError(
                    req.error or "KV cache lost during prefill export")
        finally:
            # On timeout the request may still be running: dropping
            # hold_slot lets its eventual _finish free the slot — orphaned
            # holds would leak slots until the engine deadlocks.
            req.hold_slot = False
            self.release_slot(req)
        return {"prompt_ids": ids, "kv_k": kv_k, "kv_v": kv_v,
                "first_token": req.out_tokens[0],
                "finish_reason": req.finish_reason}

    def release_slot(self, req: GenerationRequest) -> None:
        """Return a ``hold_slot`` reservation (prefill_only's export is
        done). Handed to the scheduler thread: it frees the slot and — the
        hand-off's KV line being a fully-prefilled prompt — RETIRES it as
        a cached prefix instead of discarding it, so a dedicated prefill
        engine accumulates the prefix cache its replica publishes for
        KV-block-aware routing (a shared-prefix burst then prefills only
        the tail). Freeing from this (user) thread raced the scheduler's
        admit: retire-then-clear could in-place-adopt a slot mid-release,
        clear-then-retire could mark a freshly re-admitted slot cached."""
        self._released.put(req)
        self._work.set()

    def _process_releases(self) -> None:
        """Scheduler-thread half of release_slot."""
        while True:
            try:
                req = self._released.get_nowait()
            except queue.Empty:
                return
            if req.finish_reason is None and not req.error:
                # Export timed out while the prefill still runs: its
                # _finish (hold_slot was dropped) frees the slot — freeing
                # here would hand a mid-prefill slot to the next admit.
                continue
            for slot, r in self._slots.items():
                if r is req:
                    self._slots[slot] = None
                    self._slot_freed[slot] = time.time()
                    self._prefix_live.pop(slot, None)
                    if (req.finish_reason not in (None, "error")
                            and not req.error):
                        # Clean completed prefill: the slot's KV holds
                        # exactly req.prompt_ids' prefix — retire it.
                        self._prefix_cached[slot] = (
                            tuple(req.prompt_ids), time.monotonic())

    def submit_prefilled(self, payload: dict,
                         sampling: SamplingParams | None = None,
                         stream: bool = False) -> GenerationRequest:
        """Continue decoding from a shipped prefill (KV import)."""
        require_kv_handoff(self.model_cfg)
        sampling = sampling or SamplingParams()
        req = GenerationRequest(
            request_id=uuid.uuid4().hex[:12],
            prompt_ids=list(payload["prompt_ids"]), sampling=sampling,
            stream_queue=queue.Queue() if stream else None)
        req.preloaded = (np.asarray(payload["kv_k"]),
                         np.asarray(payload["kv_v"]),
                         int(payload["first_token"]))
        req.trace_ctx = tracing.inject() if tracing.current_context() \
            else None
        req.submit_ts = time.time()
        req.kv_imported = True
        with self._submit_lock:
            self._requests[req.request_id] = req
        self._waiting.put(req)
        self._work.set()
        return req

    def generate_stream(self, prompt: str | list[int],
                        sampling: SamplingParams | None = None):
        """Yields decoded text fragments as tokens arrive."""
        req = self.submit(prompt, sampling, stream=True)
        while True:
            item = req.stream_queue.get()
            if item is None:
                break
            yield self.tokenizer.decode([item])
        if req.error:
            raise RuntimeError(req.error)

    def shutdown(self) -> None:
        self._stop.set()
        self._work.set()
        self._thread.join(timeout=5)

    def prefix_block_hashes(self) -> tuple[int, ...]:
        """Chain hashes (serve/prefix.py) of every prompt prefix whose KV
        this engine currently holds — live donors plus retired cached
        slots. This is what the replica publishes to the serve router for
        KV-block-aware routing. Safe from any thread: the registries are
        snapshotted (the scheduler thread mutates them concurrently) and
        the per-prompt hash cache swap is idempotent."""
        if self.prefix_block <= 0:
            return ()
        from ray_tpu.serve.prefix import block_hashes

        prefixes = list(self._prefix_live.values())
        prefixes += [toks for toks, _ in list(self._prefix_cached.values())]
        cache = self._prefix_hash_cache
        fresh: dict[tuple, tuple[int, ...]] = {}
        out: set[int] = set()
        for toks in prefixes:
            h = cache.get(toks)
            if h is None:
                h = block_hashes(toks, self.prefix_block)
            fresh[toks] = h
            out.update(h)
        self._prefix_hash_cache = fresh  # prune evicted prefixes
        return tuple(sorted(out))

    def router_prefix_blocks(self) -> dict | None:
        """The publication payload serve replicas answer router_meta()
        with (one definition of the contract for every deployment type:
        LLMServer and PrefillServer both delegate here). None when
        publication is disabled — the controller then stops polling."""
        if self.prefix_block <= 0:
            return None
        return {"blocks": list(self.prefix_block_hashes()),
                "block": self.prefix_block}

    def stats(self) -> dict:
        active = sum(1 for r in self._slots.values() if r is not None)
        out = {"active": active, "waiting": self._waiting.qsize(),
               "slots": self.max_slots,
               "prefix_hits": self.prefix_hits,
               "prefix_tokens_saved": self.prefix_tokens_saved,
               "prefix_cached_slots": len(self._prefix_cached),
               "prefix_block": self.prefix_block,
               "device_failures": self.device_failures,
               "requests_failed": self.requests_failed,
               "ticks": self.ticks, "admitted": self.admitted,
               "finished": self.finished,
               "prompt_tokens_prefilled": self.prompt_tokens_prefilled,
               "prefill_chunks": self.prefill_chunks,
               "prefill_chunks_riding": self.prefill_chunks_riding,
               "prefill_tokens_riding": self.prefill_tokens_riding,
               "decode_dispatches": self.decode_dispatches,
               "decode_dispatches_ahead": self.decode_dispatches_ahead,
               "decode_steps": self.decode_steps,
               "decode_tokens": self.decode_tokens,
               "kv_positions_read": self.kv_positions_read,
               "kv_positions_reserved": self.kv_positions_reserved,
               "prefill_kv_positions_read": self.prefill_kv_positions_read,
               "prefill_kv_positions_reserved":
                   self.prefill_kv_positions_reserved,
               "first_tokens": self.first_tokens,
               "queue_wait_s": self.queue_wait_s,
               "first_token_wait_s": self.first_token_wait_s,
               "slot_vacant_s": self.slot_vacant_s,
               "slot_refills": self.slot_refills,
               **self.model_counts}
        if self.draft_cfg is not None:
            out["spec_ticks"] = self.spec_ticks
            out["spec_proposed"] = self.spec_proposed
            out["spec_accepted"] = self.spec_accepted
            out["spec_acceptance"] = (
                round(self.spec_accepted / self.spec_proposed, 3)
                if self.spec_proposed else 0.0)
        return out

    # ---- scheduler ----

    def _loop(self) -> None:
        tracing.name_thread()
        while not self._stop.is_set():
            try:
                worked = self._tick()
            except Exception:  # noqa: BLE001 - one bad request must not
                # kill the scheduler thread (every queued request would
                # hang to its timeout). The dispatch and read paths fail
                # the offending requests where attributable; anything that
                # still escapes is logged and backed off, never hot-spun.
                logger.exception("LLMEngine scheduler tick failed")
                worked = False
            if worked:
                self.ticks += 1
            else:
                self._wait_for_work()
        # Read out what is in flight so its requests get their tokens
        # instead of hanging to their timeouts.
        try:
            self._read_all("stop")
        except Exception:  # noqa: BLE001 - shutdown path
            pass

    def _wait_for_work(self) -> None:
        """Sleep until work is signalled: one ``engine.wait`` phase for
        the whole idle stretch. While anything is queued, slotted or in
        flight the sleep ends after 20 ms at the latest, so a tick that
        failed is tried again; an empty engine has nothing to try."""
        with tracing.phase("engine.wait"):
            while not self._work.wait(timeout=0.02):
                if (self._stop.is_set() or self._in_flight
                        or not self._waiting.empty()
                        or not self._released.empty()
                        or any(r is not None
                               for r in self._slots.values())):
                    break
        self._work.clear()

    def _tick(self) -> bool:
        """One scheduler step: dispatch a program group (a bounded budget
        of prefill chunks, then one decode batch over the decoding slots)
        behind what the device runs, having read the oldest result in
        flight. Chunking + the budget stop a long prompt from
        head-of-line-blocking every active decode (reference shape: vLLM
        chunked prefill scheduling).

        Pipelined, the blocking read in the middle returns when the
        running burst ends and the one queued behind it starts: emitting,
        admitting and the next group's dispatch run beside that burst, so
        the device queue is never empty while a line decodes, and never
        holds more than one group behind the running one (a deeper queue
        would make a new arrival wait longer). The host therefore plans
        from projected state (_steps_left): its own plus the steps in
        flight. A line that ends on a stop token is discovered one burst
        late; its extra rows lie beyond every cached prefix and are
        overwritten before they are read, and its slot may be admitted at
        once, because the new prompt's chunks are dispatched, and so run,
        after the burst that still writes there."""
        # engine.tick encloses the tick's other phases: what a profile
        # shows in it and in none of them is the scheduler's own glue.
        with tracing.phase("engine.tick"):
            self._tick_chunks = 0
            self._process_releases()
            # Per-PASS chunk budget: the tick has two admission passes
            # (before and after the blocking read) and each gets a full
            # prefill_chunks_per_tick. A shared budget was measured ~25%
            # worse p50 TTFT at c8: completions arrive in bursts, and an
            # arrival landing after the read must not wait a whole burst
            # because the pass before it spent the budget.
            worked = self._admit()
            worked = self._prefill_steps() or worked
            # The read may finish requests and free slots for the second
            # pass. It blocks: a thread that polls while the tokens compute
            # competes for the cores that run the HTTP/router/SSE threads.
            worked = self._read_oldest() or worked
            worked = self._admit() or worked
            worked = self._prefill_steps() or worked
            if self._dispatch_decode():
                return True
            # Nothing to queue behind what runs (every line in flight ends
            # there, or only first tokens are): read it out.
            if self._in_flight:
                self._read_all("tail")
                return True
            return worked

    def _prefill_steps(self) -> bool:
        budget = max(1, self.config.prefill_chunks_per_tick)
        riding = self._riding()
        spent = 0
        while spent < budget and self._prefill_step(riding):
            spent += 1
        self._tick_chunks += spent
        return spent > 0

    def _riding(self) -> bool:
        """Whether the tick's decode batch will be a burst that carries
        chunks: the model offers one and the lines that still decode make a
        burst of its length. A full, non-final chunk is then left for that
        burst (_prefill_step, _take_riders). Where a read in between ends
        the lines and no such burst goes out, the chunk waits for the next
        tick's passes, which ask again."""
        if not self._ride_steps or all(
                r is None or r.next_pos >= 0 for r in self._slots.values()):
            return False    # no such program, or no slot mid-prefill
        active = {s: r for s, r in self._decoding().items()
                  if self._steps_left(r) > 0}
        return bool(active) and self._burst_len(active) == self._ride_steps

    def _decoding(self) -> dict[int, GenerationRequest]:
        return {s: r for s, r in self._slots.items()
                if r is not None and r.next_pos >= 0
                and not r.done.is_set()}

    def _dispatch_decode(self) -> bool:
        """The tick's decode batch. A burst goes behind whatever is in
        flight; the serial paths (speculative ticks, a single step: top_k
        or a last token) first read out everything, because they take
        their tokens from the host."""
        if self.draft_params is not None:
            self._read_all("speculative")
            decoding = self._decoding()
            # Speculative path serves greedy requests with spec headroom;
            # the rest (stochastic sampling, near end-of-cache) ride the
            # normal decode in the same tick.
            spec = {s: r for s, r in decoding.items()
                    if r.sampling.temperature <= 0.0
                    and r.next_pos + self.spec_k + 1 < self.max_seq}
            rest = {s: r for s, r in decoding.items() if s not in spec}
            if spec:
                self._spec_decode(spec)
            if rest:
                self._decode(rest)
            return bool(decoding)
        # Lines that still decode once everything in flight is read.
        active = {s: r for s, r in self._decoding().items()
                  if self._steps_left(r) > 0}
        if not active:
            return False
        # A line whose newest step only the host has (a KV import) cannot
        # ride behind a burst that runs without it.
        host_only = ((self.model.prefill_token or self.model.pending_step)
                     and any(e.steps for e in self._in_flight)
                     and any(r.out_tokens and not r.ahead
                             for r in active.values()))
        # So does a single step; a model whose step samples on the device
        # has none (ServedModel.step).
        if self._in_flight and (host_only or (
                self.model.step is None
                and self._burst_len(active) <= 1)):
            self._read_all("host_only" if host_only else "single_step")
            active = self._decoding()
        if active:
            self._decode(active)
        return True

    def _position(self, req: GenerationRequest) -> int:
        """Where the line's next step starts once everything in flight is
        read."""
        return req.next_pos + req.ahead * self._step_positions

    def _steps_to_line_end(self, pos: int) -> int:
        """Whole steps from ``pos`` whose tokens all lie inside the cache
        line: a step from ``pos`` decides the positions from ``pos`` on, or
        from ``pos + 1`` on where it takes the token at ``pos`` in (the
        models whose prefill gives the first token)."""
        return ((self.max_seq - self.model.prefill_token - pos)
                // self._step_positions)

    def _steps_left(self, req: GenerationRequest) -> int:
        """Decode steps the line can still take once everything in flight
        is read: the fewer of what its token budget needs (the last step
        rounded up) and what its cache line's end allows, both counted from
        the position it will stand at (the tokens it has and those in
        flight are the positions past its prompt, and the first token too
        where prefill gives it). 0: it ends there, whatever it samples."""
        k, pos = self._step_positions, self._position(req)
        tokens = pos - len(req.prompt_ids) + self.model.prefill_token
        return min(-(-(req.sampling.max_tokens - tokens) // k),
                   self._steps_to_line_end(pos))

    def _read_oldest(self) -> bool:
        """Pipelined: read results, oldest first, until one burst is left
        in flight (the one the device runs or is about to), and a first
        token at the head once a burst is queued behind it (its line has
        joined that burst on the device; its chunk ran, or runs now)."""
        read = False
        while self._in_flight:
            bursts = sum(1 for e in self._in_flight if e.steps)
            # the last burst stays, and a first token no burst follows
            if bursts <= 1 and (self._in_flight[0].steps or not bursts):
                break
            read = True
            if not self._read(self._in_flight.popleft(), "oldest"):
                break
        return read

    def _read_all(self, why: str) -> bool:
        """Read out everything in flight, oldest first; ``why`` is the
        caller's reason, which every ``engine.fetch`` it blocks in carries:
        ``tail`` (nothing to queue behind what runs), ``single_step`` and
        ``host_only`` (a decode batch that takes its tokens from the host),
        ``speculative``, ``serial`` (the schedule without the look-ahead)
        and ``stop``; ``oldest`` is _read_oldest's, the look-ahead's own
        read. False iff a device failure wiped the engine state on the
        way."""
        while self._in_flight:
            if not self._read(self._in_flight.popleft(), why):
                return False
        return True

    def _take_counts(self) -> list:
        """The model counts of the prefill chunks dispatched since the last
        call, for the program the caller has just dispatched: its fetch
        brings them, and the device, which runs programs in dispatch
        order, has run those chunks when that program's tokens are there.
        Never for a program dispatched before them: a fetch of the burst
        before last would wait on chunks queued behind the last."""
        counts, self._chunk_counts = self._chunk_counts, []
        return counts

    def _read(self, entry: _InFlight, why: str) -> bool:
        """Block on one program's tokens and emit them. False iff they did
        not come: an asynchronous dispatch error surfaces at
        materialization, and the engine state is suspect."""
        toks = entry.toks
        if not entry.steps:
            (req,) = entry.reqs.values()
            if req.done.is_set():  # failed meanwhile: no token is wanted,
                # but the chunks that came with it ran and are counted
                if not entry.counts:
                    return True
                toks = None
        try:
            with tracing.phase("engine.fetch",
                               which="burst" if entry.steps else "prefill",
                               why=why):
                toks, counts = jax.device_get((toks, entry.counts))
            self._add_model_counts(*counts)
        except Exception as e:  # noqa: BLE001 - cache donated & lost
            what = "decode" if entry.steps else "prefill"
            logger.exception("%s in flight failed (%d lines)", what,
                             len(entry.reqs))
            self._recover_device_failure(f"{what} failed: {e!r}")
            return False
        if entry.steps:
            for req in entry.reqs.values():
                req.ahead -= entry.steps
            self._emit_burst(entry.reqs, entry.steps, toks)
        elif toks is not None:
            with tracing.phase("engine.emit", tokens=1):
                self._emit(req, int(toks[0]))
        return True

    # Minimum adopted-prefix length that justifies a cross-slot KV copy
    # (the copy moves whole cache lines; tiny prefixes aren't worth it).
    PREFIX_COPY_MIN = 16

    # Decode-burst cap while a slot is mid-prefill (see _burst_len):
    # bounds how long the next prefill chunk waits behind decode work
    # while keeping most of the burst's dispatch amortization.
    PREFILL_PRIORITY_BURST = 8

    def _admit(self) -> bool:
        """Move waiting requests into unoccupied slots, as one
        ``engine.admit`` phase when there is a request and a slot for
        it."""
        if self._waiting.empty():
            return False
        if all(o is not None for o in self._slots.values()):
            return False
        with tracing.phase("engine.admit") as ph:
            admitted = self._admit_waiting()
            ph.set(requests=admitted)
        return admitted > 0

    def _admit_waiting(self) -> int:
        """The requests moved into unoccupied slots (prefill starts on
        subsequent ticks), adopting cached prompt prefixes when a donor
        slot shares one (vLLM-APC semantics: the final prompt token is
        always recomputed so its logits seed decoding)."""
        admitted = 0
        while any(o is None for o in self._slots.values()):
            try:
                req = self._waiting.get_nowait()
            except queue.Empty:
                break
            req.admit_ts = time.time()
            self.admitted += 1
            self.queue_wait_s += req.admit_ts - req.submit_ts
            if req.preloaded is not None:
                slot = self._take_slot()
                try:
                    self._admit_prefilled(req, slot)
                except Exception as e:  # noqa: BLE001 - bad KV payload
                    self._slots[slot] = None
                    self._fail(req, f"KV import failed: {e!r}")
                admitted += 1
                continue
            donor, adopt, retired = (
                self._best_prefix(req.prompt_ids)
                if self.model.prefix_from_line else (None, 0, False))
            req.prefilled_len = 0
            if donor is not None and adopt < self.PREFIX_COPY_MIN:
                # Trivial LCP (e.g. a shared few-token template label):
                # not worth a copy, and NEVER worth destroying a donor.
                donor = None
            if retired and donor is not None and \
                    adopt * 2 >= len(self._prefix_cached[donor][0]):
                # Zero-copy: admit straight into the retired slot whose KV
                # already holds the prefix — only when the new prompt
                # consumes most of it. An in-place adopt OVERWRITES the
                # donor: taking a 1000-token cached line for a 20-token
                # LCP (hot prompts sharing a template label) was measured
                # pinning the whole cache at ONE entry under prefix-skewed
                # load — every admit stole the same slot while fresh
                # slots idled.
                slot = donor
                self._prefix_cached.pop(slot, None)
                req.prefilled_len = adopt
                self.prefix_hits += 1
                self.prefix_tokens_saved += adopt
            else:
                slot = self._take_slot()
                if donor is not None and slot == donor:
                    # LRU eviction handed us the donor itself (no fresh
                    # slot): its KV line is already in place — in-place
                    # adoption after all, minus the copy.
                    req.prefilled_len = adopt
                    self.prefix_hits += 1
                    self.prefix_tokens_saved += adopt
                elif donor is not None:
                    # Content copy from the donor line (live OR retired —
                    # both hold intact KV) into the fresh slot, preserving
                    # the donor for future siblings.
                    try:
                        self.cache = self.model.copy_prefix_kv(
                            self.model_cfg, self.cache, jnp.int32(donor),
                            jnp.int32(slot))
                        req.prefilled_len = adopt
                        self.prefix_hits += 1
                        self.prefix_tokens_saved += adopt
                        if donor in self._prefix_cached:
                            # Donor USED: now is when it earns its LRU
                            # refresh (stamping at _best_prefix time let
                            # guard-rejected donors dodge eviction).
                            self._prefix_cached[donor] = (
                                self._prefix_cached[donor][0],
                                time.monotonic())
                    except Exception as e:  # noqa: BLE001
                        # copy_prefix_kv DONATES the cache: a failed
                        # dispatch consumed its buffers, so this is a
                        # device-failure event, not a per-request fallback
                        # — rebuild, then admit this request cold.
                        logger.exception("prefix copy failed")
                        self._recover_device_failure(
                            f"prefix copy failed: {e!r}")
                        req.prefilled_len = 0
            # next_pos < 0 marks "still prefilling" (prefilled_len tracks
            # progress); _finish frees by identity. A prompt shorter than a
            # step of a model whose prefill gives no token has nothing to
            # prefill: the line decodes from position 0.
            req.next_pos = -1 if (self.model.prefill_token
                                  or self._prefill_len(req)) else 0
            req.last_slot = slot
            self._occupy(slot, req)
            admitted += 1
        return admitted

    def _occupy(self, slot: int, req: GenerationRequest) -> None:
        """Put an admitted request into its slot, and book how long the
        slot stood vacant if it was in use before."""
        freed_ts = self._slot_freed.pop(slot, None)
        if freed_ts is not None:
            self.slot_vacant_s += max(req.admit_ts - freed_ts, 0.0)
            self.slot_refills += 1
        self._slots[slot] = req

    def _take_slot(self) -> int:
        """An unoccupied slot: prefer one with no cached prefix; otherwise
        evict the least-recently-used prefix entry."""
        fresh = [s for s, o in self._slots.items()
                 if o is None and s not in self._prefix_cached]
        if fresh:
            return fresh[0]
        slot = min((s for s, o in self._slots.items() if o is None),
                   key=lambda s: self._prefix_cached.get(s, ((), 0.0))[1])
        self._prefix_cached.pop(slot, None)
        return slot

    def _best_prefix(self, prompt_ids: list[int]):
        """(donor_slot, usable_prefix_len, donor_is_retired) — longest
        common prefix across donors, capped at len(prompt)-1. Retired
        donors win ties (adoption is zero-copy)."""
        cap = len(prompt_ids) - 1
        best_slot, best_p, best_retired = None, 0, False
        if cap <= 0:
            return best_slot, best_p, best_retired
        # Both registries are mutated only on this (scheduler) thread —
        # release_slot hands frees over via the _released queue — but
        # user threads READ them (prefix_block_hashes), so keep the
        # snapshot-iterate discipline for the shared-read invariant.
        # LRU re-stamping of a retired donor happens in _admit, and ONLY
        # when the donor is actually used: stamping here shielded lines
        # the admission guards then rejected (e.g. a trivial template-
        # label LCP) from eviction, starving genuinely hot entries.
        for slot, toks in list(self._prefix_live.items()):
            p = _lcp(prompt_ids, toks, cap)
            if p > best_p:
                best_slot, best_p, best_retired = slot, p, False
        for slot, (toks, _) in list(self._prefix_cached.items()):
            p = _lcp(prompt_ids, toks, cap)
            if p > best_p or (p == best_p and p > 0 and not best_retired):
                best_slot, best_p, best_retired = slot, p, True
        return best_slot, best_p, best_retired

    def _admit_prefilled(self, req: GenerationRequest, slot: int) -> None:
        """KV import: write the shipped prefill into this slot and enter
        decode directly (reference: kv_transfer connectors on the decode
        engine side)."""
        import jax.numpy as jnp
        from jax import lax

        kv_k, kv_v, first_token = req.preloaded
        # The cache's own lines, which a model may have more of than layers.
        lines, _, heads, _, dim = self.cache["k"].shape
        want = (lines, heads, dim)
        got = (kv_k.shape[0], kv_k.shape[1], kv_k.shape[3])
        p = kv_k.shape[2]
        if got != want or p > self.max_seq or kv_v.shape != kv_k.shape:
            raise ValueError(
                f"payload KV shape {kv_k.shape} incompatible with this "
                f"engine (lines/kv_heads/head_dim {want}, max_seq "
                f"{self.max_seq})")
        self.cache["k"] = lax.dynamic_update_slice(
            self.cache["k"],
            jnp.asarray(kv_k, self.cache["k"].dtype)[:, None],
            (0, slot, 0, 0, 0))
        self.cache["v"] = lax.dynamic_update_slice(
            self.cache["v"],
            jnp.asarray(kv_v, self.cache["v"].dtype)[:, None],
            (0, slot, 0, 0, 0))
        req.preloaded = None
        req.next_pos = p
        req.last_slot = slot
        self._occupy(slot, req)
        self._prefix_live[slot] = tuple(req.prompt_ids)  # imported KV = donor
        self._emit(req, first_token)

    def _prefill_step(self, riding: bool = False) -> bool:
        """Run ONE chunk of ONE prefilling request, rotating across slots so
        concurrent long prompts interleave chunks (true round-robin — a
        lowest-slot rescan would monopolize prefill for one prompt).
        ``riding``: a slot whose next chunk can ride the tick's burst is
        passed over, chunk and all (its later chunks come after it)."""
        for slot, req, bucket, take in self._next_chunks():
            if riding and self._rides(req, bucket, take):
                continue
            self._prefill_rr = slot
            with tracing.phase("engine.prefill_dispatch", tokens=take,
                               bucket=bucket):
                self._dispatch_prefill_chunk(slot, req, bucket, take)
            return True
        return False

    def _next_chunks(self):
        """(slot, request, bucket, take) of every prefilling slot's next
        chunk, in round-robin order from the slot after the last that ran
        one."""
        slots = list(self._slots.keys())
        n = len(slots)
        for i in range(n):
            slot = slots[(self._prefill_rr + 1 + i) % n]
            req = self._slots.get(slot)
            if req is None or req.next_pos >= 0:
                continue
            yield (slot, req, *self._chunk_bucket(
                req.prefilled_len,
                self._prefill_len(req) - req.prefilled_len))

    def _rides(self, req: GenerationRequest, bucket: int, take: int) -> bool:
        """Whether a chunk can ride a decode step: a full one of the full
        bucket that is not its prompt's last (a rider gives no token)."""
        return (take == bucket == self._ride_rows
                and req.prefilled_len + take < self._prefill_len(req))

    def _take_riders(self, steps: int):
        """The chunks that ride a burst of ``steps`` steps, at most one a
        step and as many as the tick's budget of chunks has left
        (prefill_chunks_per_tick an admission pass, of which a tick has
        two), each the next chunk of the next prefilling slot in
        _prefill_step's round-robin order, so a prompt's consecutive chunks
        may ride consecutive steps. The host's side of each is
        _dispatch_prefill_chunk's for a chunk that is not the last; its
        counts come with the burst's. Returns ``mixed_burst``'s ``riders``,
        or None where none rides."""
        room = min(steps, 2 * max(1, self.config.prefill_chunks_per_tick)
                   - self._tick_chunks)
        rows = self._ride_rows
        picked = []  # (slot, cached rows, prefilled length, the chunk)
        while len(picked) < room:
            rider = next((c for c in self._next_chunks()
                          if self._rides(*c[1:])), None)
            if rider is None:
                break
            slot, req = rider[:2]
            self._prefill_rr = slot
            at = req.prefilled_len
            picked.append((slot, at, self._prefill_len(req),
                           req.prompt_ids[at:at + rows]))
            self.prefill_kv_positions_read += min(at + rows, self.max_seq)
            self.prefill_kv_positions_reserved += self.max_seq
            req.prefilled_len += rows
        n = len(picked)
        if not n:
            return None
        self._tick_chunks += n
        self.prefill_chunks += n
        self.prefill_chunks_riding += n
        self.prompt_tokens_prefilled += n * rows
        self.prefill_tokens_riding += n * rows
        # The steps past the riders carry zeros that nothing reads.
        chunks = np.zeros((steps, rows), np.int32)
        chunks[:n] = [p[3] for p in picked]
        scalars = np.zeros((3, steps), np.int32)
        scalars[:, :n] = np.array([p[:3] for p in picked]).T
        return (jnp.asarray(chunks), *(jnp.asarray(a) for a in scalars),
                jnp.int32(n))

    def _prefill_len(self, req: GenerationRequest) -> int:
        """The prompt's tokens that are prefilled: its whole steps (all of
        it, where a step is one position)."""
        p = len(req.prompt_ids)
        return p - p % self._step_positions

    def _dispatch_prefill_chunk(self, slot: int, req: GenerationRequest,
                                bucket: int, take: int) -> None:
        """The host side of one chunk: pad it to its bucket, dispatch it,
        and on the prompt's last chunk dispatch the first token's sample
        too. That token goes in flight unread: the line decodes from here
        on, and joins the next burst on the device (_input_tokens). Where
        prefill gives no token the line decodes from the prefilled length
        on, and the prompt's tail rides into its first step."""
        p = self._prefill_len(req)
        toks = np.zeros((bucket,), np.int32)
        toks[:take] = req.prompt_ids[req.prefilled_len:
                                     req.prefilled_len + take]
        try:
            self.cache, logits, *counts = self.model.prefill_chunk(
                self.model_cfg, self.params, self.cache,
                jnp.asarray(toks), jnp.int32(req.prefilled_len),
                jnp.int32(p), jnp.int32(slot), kmesh=self.kmesh)
            self._chunk_counts += counts
            self.prefill_kv_positions_read += min(
                req.prefilled_len + bucket, self.max_seq)
            self.prefill_kv_positions_reserved += self.max_seq
            req.prefilled_len += take
            self.prefill_chunks += 1
            self.prompt_tokens_prefilled += take
            if req.prefilled_len >= p:  # final chunk: sample 1st token
                # The slot now holds the full prompt's KV: it becomes a
                # prefix donor for later shared-prefix requests.
                self._prefix_live[slot] = tuple(req.prompt_ids)
                req.next_pos = p
                if self.model.prefill_token:
                    out = self._sample_dispatch(logits[None], [req])
                    self._in_flight.append(
                        _InFlight({slot: req}, out, self._take_counts()))
        except Exception as e:  # noqa: BLE001 - e.g. OOM on long prompt
            logger.exception("prefill failed for %s", req.request_id)
            self._recover_device_failure(f"prefill failed: {e!r}")

    def _recover_device_failure(self, err: str) -> None:
        """After a failed prefill/decode dispatch the KV cache is gone —
        prefill_chunk/decode_step donate it (donate_argnums=(2,)), so its
        buffers were consumed by the very call that raised. Every slotted
        request's context lived there: fail them all, then rebuild a fresh
        cache so the engine keeps serving NEW traffic."""
        self.device_failures += 1
        self._cache_gen += 1  # invalidates in-flight prefill_only exports
        self._in_flight.clear()  # dispatched into the lost cache
        self._chunk_counts.clear()
        for req in list(self._slots.values()):
            if req is None:
                continue
            if req.done.is_set():
                # Already finished (hold_slot prefill awaiting export): its
                # waiter has the result — don't rewrite finish_reason, just
                # mark the held KV unusable so the export raises.
                req.error = err
            else:
                self._fail(req, err)
        self._slots = {i: None for i in range(self.max_slots)}
        self._prefix_live.clear()
        self._prefix_cached.clear()
        self.cache = self._new_cache(self.model_cfg)
        if self.draft_cfg is not None:
            # The draft cache may have been donated by the failing
            # speculative dispatch — rebuild it alongside.
            self.draft_cache = self._new_cache(self.draft_cfg)

    def _burst_len(self, active: dict[int, GenerationRequest]) -> int:
        """Largest safe burst length for this decode batch, counted from
        where its lines stand once everything in flight is read. The decode
        batch is the STATIC slot array, so a request finishing mid-burst
        costs nothing extra — the host just stops emitting its tokens
        (max_tokens/EOS truncation happens in _emit) and the spare KV
        writes are overwritten on slot reuse. The only hard bound is the
        KV cache end (a burst must never write past max_seq); rounded down
        to a power of two so only {8,4,2} burst shapes ever compile.
        1 means take the classic single-step path."""
        burst = int(getattr(self.config, "decode_burst", 1) or 1)
        if burst <= 1:
            return 1
        # Prefill priority (reference shape: vLLM chunked-prefill
        # scheduling): while a slot is mid-prefill, long decode bursts
        # head-of-line-block its next chunk for burst×step_ms. Cap the
        # burst so the scheduler returns to the prefill quickly;
        # steady-state decode (no prefilling slot) keeps full bursts. The
        # cap does not apply to a non-empty admission queue: under a
        # closed-loop arrival pattern that would make it near-permanent.
        if any(r is not None and r.next_pos < 0 and not r.done.is_set()
               for r in self._slots.values()):
            burst = min(burst, self.PREFILL_PRIORITY_BURST)
        budget = 0  # largest remaining token budget across the batch:
        # bounding by the MAX (not min) wastes no tail steps when every
        # request is nearly done, yet a single long request still gets
        # full-length bursts (short ones just stop emitting early).
        for req in active.values():
            if req.sampling.top_k:  # static-k sampling: single-step only
                return 1
            burst = min(burst,
                        self._steps_to_line_end(self._position(req)))
            budget = max(budget, self._steps_left(req))
        burst = min(burst, budget)
        d = 1
        while d * 2 <= burst:
            d *= 2
        return max(d, 1)

    def _decode(self, active: dict[int, GenerationRequest]) -> bool:
        """Returns False iff a device failure wiped the engine state
        (_recover_device_failure ran) — callers mid-tick must then abandon
        the rest of the tick rather than dispatch into rebuilt caches."""
        burst = self._burst_len(active)
        # A model whose step samples on the device has no single step
        # (ServedModel.step): its lone step is a burst of one.
        if burst > 1 or self.model.step is not None:
            ok = self._decode_burst(active, burst)
            # Not pipelined: strictly serial, a tick reads what it
            # dispatched before the next one begins.
            return ok if self._pipelined else ok and self._read_all("serial")
        # A single step takes each line's token from the host: nothing of
        # these lines is in flight (_dispatch_decode read it out).
        try:
            with tracing.phase("engine.decode_dispatch", steps=1,
                               slots=len(active), riders=0):
                positions, write = self._decode_inputs(active)
                self.cache, logits, *counts = self.model.decode_step(
                    self.model_cfg, self.params, self.cache,
                    self._host_tokens(active), jnp.asarray(positions),
                    jnp.asarray(write), kmesh=self.kmesh)
        except Exception as e:  # noqa: BLE001 - cache donated & lost
            logger.exception("decode step failed (%d active)", len(active))
            self._recover_device_failure(f"decode failed: {e!r}")
            return False
        self.decode_dispatches += 1
        self.decode_steps += 1
        self._count_kv_positions(positions, write, 1)
        try:
            reqs = [active.get(s) for s in range(self.max_slots)]
            with tracing.phase("engine.fetch", which="step",
                               why="single_step"):
                sampled, counts = jax.device_get(
                    (self._sample_dispatch(logits, reqs),
                     counts + self._take_counts()))
            self._add_model_counts(*counts)
        except Exception as e:  # noqa: BLE001 - cache survived; only this
            # batch's requests lack tokens — fail them, keep other contexts.
            logger.exception("sampling failed (%d active)", len(active))
            for req in active.values():
                self._fail(req, f"sampling failed: {e!r}")
            return True
        with tracing.phase("engine.emit", tokens=len(active)):
            for slot, req in active.items():
                req.next_pos += 1
                self._emit(req, int(sampled[slot]))
        return True

    def _decode_inputs(self, active: dict[int, GenerationRequest]):
        """(position, write mask) over the static slot array: where each
        line's next token is written once everything in flight is read."""
        positions = np.zeros((self.max_slots,), np.int32)
        write = np.zeros((self.max_slots,), bool)
        for slot, req in active.items():
            positions[slot] = self._position(req)
            write[slot] = True
        return positions, write

    def _host_tokens(self, active: dict[int, GenerationRequest]):
        """Each line's newest token as the host has it, on the device."""
        tokens = np.zeros((self.max_slots,), np.int32)
        for slot, req in active.items():
            if req.out_tokens:
                tokens[slot] = req.out_tokens[-1]
        return jnp.asarray(tokens)

    def _input_tokens(self, active: dict[int, GenerationRequest]):
        """int32[slots] on the device: each line's input to a burst, with
        no host read of anything in flight. A continuing line's is the last
        row of the newest burst in flight (every line that still decodes is
        in it); with no burst in flight the host has them all. A line whose
        first token is in flight and in no burst yet takes it from its
        chunk's sampled device value, so it joins the first burst
        dispatched after that chunk.

        Where prefill gives no token (ServedModel.prefill_token) a step's
        input is no step's output: int32[slots, K], the prompt's tokens
        that lie in the step (a first step's leading places) and -1 at
        every position the step has to decide; with the step before it
        where the model takes one in (_pending_step)."""
        prev = next((e for e in reversed(self._in_flight) if e.steps), None)
        if not self.model.prefill_token:
            k = self._step_positions
            tokens = np.full((self.max_slots, k), -1, np.int32)
            for slot, req in active.items():
                pos = self._position(req)
                given = req.prompt_ids[pos:pos + k]
                tokens[slot, :len(given)] = given
            if self.model.pending_step:
                return (jnp.asarray(tokens),
                        *self._pending_step(active, prev))
            return jnp.asarray(tokens)
        tokens = (prev.last_row if prev is not None
                  else self._host_tokens(active))
        for entry in self._in_flight:
            if entry.steps:
                continue
            for slot, req in entry.reqs.items():
                if active.get(slot) is req and not req.ahead:
                    tokens = _join_token(tokens, entry.toks,
                                         jnp.int32(slot))
        return tokens

    def _pending_step(self, active: dict[int, GenerationRequest], prev):
        """(pending int32[slots, K], has_pending bool[slots]) of a model
        with a ServedModel.pending_step: the step each line decided last,
        handed over like a token, and whether the line has one. Behind a
        burst in flight (``prev``, the newest) it is that burst's last row
        as it lies on the device, for the lines that were in it: not for
        one that joins from its prefill, as a slot's new tenant does
        whatever the burst computed for the slot. With nothing in flight
        the host has every token, and a line that has decoded has its last
        step among them. A finished line's last step goes to nobody, and a
        device failure clears what was in flight with the cache."""
        has_pending = np.zeros((self.max_slots,), bool)
        if prev is not None:
            for slot, req in active.items():
                has_pending[slot] = prev.reqs.get(slot) is req
            return prev.last_row, jnp.asarray(has_pending)
        k = self._step_positions
        pending = np.zeros((self.max_slots, k), np.int32)
        for slot, req in active.items():
            if req.out_tokens:
                pos, p = self._position(req), len(req.prompt_ids)
                has_pending[slot] = True
                pending[slot] = (
                    list(req.prompt_ids[pos - k:pos])
                    + req.out_tokens[max(pos - k - p, 0):pos - p])
        return jnp.asarray(pending), jnp.asarray(has_pending)

    def _count_kv_positions(self, positions, write, steps: int,
                            k: int | None = None) -> None:
        """One decode dispatch's part of kv_positions_read/_reserved:
        ``steps`` steps of ``k`` positions (the model's step, or a verify
        step's), each a kernel call a layer for every forward it costs,
        step i over lines of positions + (i + 1) * k where ``write``, 0
        elsewhere."""
        k, forwards = k or self._step_positions, self._burst_forwards(steps)
        lengths = (positions + k)[None, :] + k * np.arange(steps)[:, None]
        lengths = np.where(write[None, :], np.minimum(lengths, self.max_seq),
                           0)
        self.kv_positions_read += int(
            (forwards * kv_positions_read(lengths, self._kv_block).sum(1))
            .sum())
        self.kv_positions_reserved += (int(forwards.sum()) * self.max_slots
                                       * self.max_seq)

    def _burst_forwards(self, steps: int) -> np.ndarray:
        """int[steps]: the forwards of the stack each step of a dispatch of
        ``steps`` steps costs (ServedModel.burst_forwards; the step's own
        each where the model states nothing of a burst)."""
        if self.model.burst_forwards is None:
            return np.full((steps,), self._step_forwards)
        return np.asarray(self.model.burst_forwards(self.model_cfg, steps))

    def _decode_burst(self, active: dict[int, GenerationRequest],
                      burst: int) -> bool:
        """Dispatch ``burst`` decode steps over the active slots behind
        whatever is in flight; _read emits its tokens. A request finishing
        mid-burst (EOS/stop token) simply stops emitting; the extra KV the
        device wrote past its end sits at positions a later slot reuse
        overwrites (same free-rollback property speculative decoding
        relies on)."""
        forwards = int(self._burst_forwards(burst).sum())
        try:
            with tracing.phase("engine.decode_dispatch", steps=forwards,
                               slots=len(active)) as ph:
                positions, write = self._decode_inputs(active)
                temps = np.zeros((self.max_slots,), np.float32)
                top_ps = np.ones((self.max_slots,), np.float32)
                for slot, req in active.items():
                    temps[slot] = req.sampling.temperature
                    top_ps[slot] = req.sampling.top_p
                need_top_p = bool((top_ps < 1.0).any())
                self._rng_key, sub = jax.random.split(self._rng_key)
                # Chunks left for this burst by _prefill_step ride it.
                riding = self.prefill_chunks_riding
                riders = (self._take_riders(burst)
                          if burst == self._ride_steps else None)
                # Of this burst's steps, those that took a chunk along.
                ph.set(riders=self.prefill_chunks_riding - riding)
                program, carried = (
                    (self.model.decode_burst, ()) if riders is None
                    else (self.model.mixed_burst, (riders,)))
                self.cache, toks, *counts = program(
                    self.model_cfg, self.params, self.cache,
                    self._input_tokens(active), jnp.asarray(positions),
                    jnp.asarray(write), jnp.asarray(temps),
                    jnp.asarray(top_ps), sub, *carried, burst, need_top_p,
                    kmesh=self.kmesh)
                # Every burst leaves its last row on the device, wanted or
                # not: a lone request then walks the helper at every burst
                # length, and no later mix of lengths compiles anything.
                last_row = (_last_row(toks) if self.model.prefill_token
                            or self.model.pending_step else None)
        except Exception as e:  # noqa: BLE001 - cache donated & lost
            logger.exception("burst decode failed (%d active, burst %d)",
                             len(active), burst)
            self._recover_device_failure(f"decode failed: {e!r}")
            return False
        self.decode_dispatches += 1
        self.decode_dispatches_ahead += bool(self._in_flight)
        self.decode_steps += forwards
        self._count_kv_positions(positions, write, burst)
        for req in active.values():
            req.ahead += burst
        self._in_flight.append(_InFlight(
            dict(active), toks, counts + self._take_counts(), burst,
            last_row))
        return True

    def _add_model_counts(self, *fetched) -> None:
        """Add fetched count arrays of the model's programs (none for a
        model without counters) into ``model_counts``."""
        for counts in fetched:
            for name, n in zip(self.model.counters, counts):
                self.model_counts[name] += int(n)

    def _emit_burst(self, active, burst: int, toks) -> None:
        """A burst's tokens to their requests, step by step: every position
        of a line's step in turn, but a position inside the prompt (a first
        step's leading places, where the prompt's tail rode in) is the
        prompt's own and not emitted, and nothing past a line's end is."""
        with tracing.phase("engine.emit") as ph:
            before = self.decode_tokens
            rows = toks.reshape(burst, self.max_slots, -1).tolist()
            for j in range(burst):
                for slot, req in active.items():
                    for tok in rows[j][slot]:
                        if req.done.is_set():
                            break
                        req.next_pos += 1
                        if req.next_pos > len(req.prompt_ids):
                            self._emit(req, tok)
            ph.set(tokens=self.decode_tokens - before)

    def _spec_decode(self, active: dict[int, GenerationRequest]) -> None:
        """One speculative tick: draft proposes spec_k tokens per slot in
        one dispatch, the target verifies them (+ the bonus position) in
        one forward, and each slot advances by accepted+1 tokens. Greedy
        acceptance makes the output IDENTICAL to vanilla greedy decoding
        whatever the draft proposes; stale KV beyond the accepted prefix
        is masked/overwritten by position bookkeeping (free rollback)."""
        k = self.spec_k
        # Requests whose draft catch-up keeps failing are speculation-
        # disabled (bounded blast radius: one bad request must not turn
        # speculation off engine-wide forever) — plain-decode those, then
        # run the speculative tick for the rest.
        spec_active = {s: r for s, r in active.items() if not r.spec_disabled}
        plain_active = {s: r for s, r in active.items() if r.spec_disabled}
        if not spec_active:
            self._decode(active)
            return
        if plain_active and not self._decode(plain_active):
            # The plain half hit a device failure: every slot (including
            # the speculative ones) was failed and both caches rebuilt —
            # nothing valid remains for the speculative half of this tick.
            return
        active = spec_active
        # Draft catch-up: any slot whose draft cache lags (fresh prompt,
        # prefix adoption, PD import, all-k-accepted tail) prefills the
        # missing span — cheap, the draft is small by construction.
        for slot, req in active.items():
            if req.draft_len < req.next_pos and \
                    not self._draft_catch_up(slot, req):
                # The failed dispatch reset the WHOLE draft state (cache
                # rebuilt, every draft_len zeroed) — slots that caught up
                # earlier this tick are invalid too. Plain-decode the whole
                # tick; catch-up re-runs for everyone next tick (minus any
                # request _draft_catch_up just speculation-disabled).
                self._decode(active)
                return
        token0 = np.zeros((self.max_slots,), np.int32)
        pos0 = np.zeros((self.max_slots,), np.int32)
        write = np.zeros((self.max_slots,), bool)
        for slot, req in active.items():
            token0[slot] = req.out_tokens[-1]
            pos0[slot] = req.next_pos
            write[slot] = True
        try:
            # One phase for draft and verify: each is fetched as soon as
            # it is dispatched, so dispatch and fetch do not come apart.
            with tracing.phase("engine.decode_dispatch", steps=k + 1,
                               slots=len(active), riders=0, speculative=1):
                self.draft_cache, proposals = self.draft_model.draft_propose(
                    self.draft_cfg, self.draft_params, self.draft_cache,
                    jnp.asarray(token0), jnp.asarray(pos0), k,
                    jnp.asarray(write), kmesh=self.kmesh)
                proposals = np.asarray(proposals)  # [B, k]
                verify_tokens = np.concatenate(
                    [token0[:, None], proposals], axis=1)  # [B, k+1]
                self.cache, logits = self.model.spec_verify_step(
                    self.model_cfg, self.params, self.cache,
                    jnp.asarray(verify_tokens), jnp.asarray(pos0),
                    jnp.asarray(write), kmesh=self.kmesh)
                greedy = np.asarray(jnp.argmax(logits, axis=-1))  # [B, k+1]
        except Exception as e:  # noqa: BLE001 - caches donated & lost
            logger.exception("speculative step failed (%d active)",
                             len(active))
            self._recover_device_failure(f"speculative decode failed: {e!r}")
            return
        self.spec_ticks += 1
        # The verify step computes k + 1 positions of every slot.
        self.decode_dispatches += 1
        self.decode_steps += k + 1
        self._count_kv_positions(pos0, write, 1, k + 1)
        with tracing.phase("engine.emit") as ph:
            before = self.decode_tokens
            for slot, req in active.items():
                accepted = 0
                while accepted < k and \
                        proposals[slot, accepted] == greedy[slot, accepted]:
                    accepted += 1
                self.spec_proposed += k
                self.spec_accepted += accepted
                emit = [int(t) for t in proposals[slot, :accepted]]
                emit.append(int(greedy[slot, accepted]))  # corrected/bonus
                for tok in emit:
                    if req.done.is_set():
                        break
                    req.next_pos += 1
                    self._emit(req, tok)
                # Draft KV is valid through the accepted prefix;
                # draft_propose writes k+1 entries, covering even the
                # all-accepted case.
                req.draft_len = req.next_pos
            ph.set(tokens=self.decode_tokens - before)

    def _chunk_bucket(self, start: int, remaining: int) -> tuple[int, int]:
        """(bucket, take) for one prefill chunk starting at ``start``:
        power-of-two bucket from prefill_bucket_min, capped at
        prefill_chunk, and CLAMPED to the cache tail — a window crossing
        max_seq would make dynamic_update_slice clamp its start index and
        silently overwrite earlier positions."""
        bucket = self.config.prefill_bucket_min
        while bucket < min(remaining, self.config.prefill_chunk):
            bucket *= 2
        bucket = min(bucket, self.max_seq - start)
        return bucket, min(remaining, bucket)

    def _draft_catch_up(self, slot: int, req: GenerationRequest) -> bool:
        """Prefill the draft cache for positions draft_len..next_pos-1
        (the tokens already consumed by the target)."""
        seq = list(req.prompt_ids) + req.out_tokens[:-1]
        start = req.draft_len
        try:
            while start < req.next_pos:
                bucket, take = self._chunk_bucket(start,
                                                  req.next_pos - start)
                toks = np.zeros((bucket,), np.int32)
                toks[:take] = seq[start:start + take]
                self.draft_cache, _ = self.draft_model.prefill_chunk(
                    self.draft_cfg, self.draft_params, self.draft_cache,
                    jnp.asarray(toks), jnp.int32(start),
                    jnp.int32(start + take), jnp.int32(slot),
                    kmesh=self.kmesh)
                start += take
            req.draft_len = req.next_pos
            req.draft_fail_count = 0
            return True
        except Exception:  # noqa: BLE001 - draft trouble must not kill
            # the request; the caller falls back to plain decode. The
            # failed dispatch DONATED the draft cache — rebuild it, and
            # mark every speculating request's draft state cold. A request
            # that fails catch-up repeatedly (e.g. a span that OOMs the
            # draft prefill every tick) is speculation-disabled so it
            # stops zeroing everyone else's draft state each tick.
            logger.exception("draft catch-up failed for %s", req.request_id)
            req.draft_fail_count += 1
            if req.draft_fail_count >= 3:
                req.spec_disabled = True
                logger.warning("disabling speculation for %s after %d "
                               "failed draft catch-ups", req.request_id,
                               req.draft_fail_count)
            self.draft_cache = self._new_cache(self.draft_cfg)
            for r in self._slots.values():
                if r is not None:
                    r.draft_len = 0
            return False

    def _sample_dispatch(self, logits, reqs):
        """Dispatch sampling on device; returns the (unfetched) token
        array so callers can defer the host roundtrip."""
        b = logits.shape[0]
        temps = np.zeros((b,), np.float32)
        top_ps = np.ones((b,), np.float32)
        top_k = 0
        for i, r in enumerate(reqs):
            if r is None:
                continue
            temps[i] = r.sampling.temperature
            top_ps[i] = r.sampling.top_p
            if r.sampling.top_k:
                top_k = max(top_k, r.sampling.top_k)
        self._rng_key, sub = jax.random.split(self._rng_key)
        return sample_tokens(logits.astype(jnp.float32), jnp.asarray(temps),
                             jnp.asarray(top_ps), top_k, sub,
                             bool((top_ps < 1.0).any()))

    def _emit(self, req: GenerationRequest, token: int) -> None:
        req.out_tokens.append(token)
        if len(req.out_tokens) > 1 or not self.model.prefill_token:
            self.decode_tokens += 1
        if len(req.out_tokens) == 1:
            now = req.first_token_ts = time.time()
            self.first_tokens += 1
            self.first_token_wait_s += now - (req.admit_ts or now)
            if req.trace_ctx is not None:
                self._stamp_first_token_spans(req, now)
        if req.stream_queue is not None:
            req.stream_queue.put(token)
        eos = {self.tokenizer.eos_id, *req.sampling.stop_token_ids}
        finish = None
        if token in eos:
            finish = "stop"
        elif len(req.out_tokens) >= req.sampling.max_tokens:
            finish = "length"
        elif (not req.next_pos % self._step_positions
              and self._steps_to_line_end(req.next_pos) <= 0):
            finish = "length"   # no whole step is left of its cache line
        if finish:
            self._finish(req, finish)

    def _stamp_first_token_spans(self, req: GenerationRequest,
                                 now: float) -> None:
        """The TTFT phase breakdown on the request's own trace: queue wait
        (submit→admit) and the prefill (or P/D KV import) interval ending
        at the first token's emission."""
        if req.admit_ts and req.submit_ts:
            tracing.record_span(
                "engine.queue", req.submit_ts, req.admit_ts,
                ctx=req.trace_ctx,
                attributes={"request_id": req.request_id})
        tracing.record_span(
            "engine.kv_import" if req.kv_imported else "engine.prefill",
            req.admit_ts or req.submit_ts or now, now, ctx=req.trace_ctx,
            attributes={"request_id": req.request_id,
                        "prompt_tokens": len(req.prompt_ids),
                        "prefix_adopted": req.prefilled_len})

    def _fail(self, req: GenerationRequest, err: str) -> None:
        """Fail one request: record the error, free its slot and any staged
        KV payload, and wake its waiter — the engine keeps serving others."""
        self.requests_failed += 1
        req.error = err
        req.preloaded = None
        req.hold_slot = False  # never pin a slot for a failed request
        self._finish(req, "error")

    def _finish(self, req: GenerationRequest, reason: str) -> None:
        req.finish_reason = reason
        req.finish_ts = time.time()
        self.finished += 1
        if req.trace_ctx is not None and req.first_token_ts:
            tracing.record_span(
                "engine.decode", req.first_token_ts, req.finish_ts,
                ctx=req.trace_ctx,
                attributes={"request_id": req.request_id,
                            "tokens": len(req.out_tokens),
                            "finish_reason": reason})
        for slot, r in self._slots.items():
            if r is req:
                req.last_slot = slot
                toks = self._prefix_live.pop(slot, None)
                if not req.hold_slot:
                    self._slots[slot] = None
                    self._slot_freed[slot] = req.finish_ts
                    if toks is not None and reason != "error":
                        # Retire, don't discard: the slot's KV stays intact
                        # until the slot is reclaimed, so an identical or
                        # shared-prefix prompt admits with zero prefill.
                        self._prefix_cached[slot] = (toks, time.monotonic())
        if req.stream_queue is not None:
            req.stream_queue.put(None)
        with self._submit_lock:
            self._requests.pop(req.request_id, None)
        req.done.set()

    def _result(self, req: GenerationRequest) -> GenerationResult:
        toks = req.out_tokens
        if toks and toks[-1] == self.tokenizer.eos_id:
            toks = toks[:-1]
        return GenerationResult(
            request_id=req.request_id, prompt_ids=req.prompt_ids,
            token_ids=list(toks), text=self.tokenizer.decode(toks),
            finish_reason=req.finish_reason or "stop")

    # ---- device placement ----

    def _place_params(self, params, cfg):
        """The tree ``cfg``'s programs take (``ServedModel.program_params``),
        split over the mesh where there is one."""
        model = served_model(cfg)
        if model.program_params is not None:
            params = model.program_params(cfg, params)
        if self.mesh is None:
            return params
        return shard_params(params, self.mesh, model.param_logical_axes(cfg))

    def _new_cache(self, cfg):
        """A zeroed slot cache of ``cfg``'s model, its kv-head dim split
        over tp like the k/v projections that fill it."""
        cache = served_model(cfg).init_cache(cfg, self.max_slots,
                                             self.max_seq)
        if self.mesh is None:
            return cache
        # [layers, slots, Hkv, positions, D]
        return jax.device_put(
            cache, NamedSharding(self.mesh, P(None, None, "tp")))


def _tp_mesh(tp: int):
    devices = jax.devices()[:tp]
    if len(devices) < tp:
        raise ValueError(
            f"tensor_parallel_size={tp} but only {len(devices)} devices")
    return build_mesh(MeshSpec(dp=1, fsdp=1, tp=tp), devices)


def _load_checkpoint(path: str):
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    return ckptr.restore(path)
