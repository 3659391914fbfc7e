"""Prefill/decode disaggregation serving pattern.

Capability parity with the reference's P/D pattern (reference:
python/ray/llm/_internal/serve/serving_patterns/prefill_decode/pd_server.py
— a prefill deployment computes the prompt KV, a KV connector ships it, and
a decode deployment continues generation).

KV hand-off (``LLMConfig.pd_transfer_mode``): in the default ``"store"``
mode the prompt KV never touches a pickler — the prefill server exports the
two device slices as store-backed ndarrays (``ray_tpu.put`` scatter-writes
the raw buffer into the object plane) and the payload carries only
ObjectRefs; the decode server materializes them straight from the plane
(same-host: pinned read-only arena views; cross-host: cut-through transfer
pulls) and imports into a slot. ``"inline"`` keeps the legacy
pickle-through-the-handle-call path for A/B comparison.

Prefill replicas never decode (their slots turn over at prompt rate) and
decode replicas never prefill (steady small-batch decode steps) — the
latency isolation that motivates the pattern.
"""

from __future__ import annotations

import json
import threading
import uuid
from typing import Any

from ray_tpu import serve
from ray_tpu.llm.config import LLMConfig
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.served import require_kv_handoff
from ray_tpu.llm.serving import _sampling_from
from ray_tpu.util import tracing

_kv_metrics = None
_kv_metrics_lock = threading.Lock()


def kv_metrics():
    """KV hand-off accounting, the bench/test proof surface for the
    zero-copy path: ``llm_kv_handoff_bytes{path}`` counts payload tensor
    bytes by transport ("store" = object-plane ndarrays, "inline" =
    pickled through the handle call) and ``llm_kv_serialized_bytes`` counts
    ONLY bytes that took a serialize/deserialize copy — zero on the store
    path by construction."""
    global _kv_metrics
    with _kv_metrics_lock:
        if _kv_metrics is None:
            from ray_tpu.util.metrics import Counter

            _kv_metrics = {
                "bytes": Counter(
                    "llm_kv_handoff_bytes",
                    "prompt-KV bytes handed from prefill to decode engines",
                    tag_keys=("path",)),
                "serialized": Counter(
                    "llm_kv_serialized_bytes",
                    "prompt-KV bytes that crossed a serialize/deserialize "
                    "copy during hand-off (zero on the store path)"),
                "handoffs": Counter(
                    "llm_kv_handoffs_total",
                    "disaggregated prefill->decode hand-offs",
                    tag_keys=("path",)),
            }
    return _kv_metrics


_kv_bound: dict = {}


def kv_bound(mode: str) -> dict:
    """Per-path pre-bound KV hand-off series: the hand-off is on the TTFT
    path, so the tag merge is paid once per process per mode, not per
    request (rtlint R4)."""
    bound = _kv_bound.get(mode)
    if bound is None:
        mtr = kv_metrics()
        bound = _kv_bound[mode] = {
            "bytes": mtr["bytes"].bound({"path": mode}),
            "handoffs": mtr["handoffs"].bound({"path": mode}),
            "serialized": mtr["serialized"].bound(),
        }
    return bound


def export_kv_payload(payload: dict, mode: str) -> dict:
    """Swap the raw KV ndarrays for store-backed ObjectRefs (store mode).

    The put() path tags the arrays as raw-buffer objects (_TAG_NDARRAY):
    the store scatter-writes the memoryview — no pickle framing, and the
    consumer's get() is an arena view (same host) or a transfer-plane pull
    (cross host), never an unpickle."""
    import ray_tpu

    if mode not in ("store", "inline"):
        # A typo'd mode must not silently pickle multi-MB KV per request
        # (the zero-copy path would be off with no error anywhere).
        raise ValueError(
            f"unknown pd_transfer_mode {mode!r}: expected 'store' or "
            f"'inline'")
    mtr = kv_bound(mode)
    nbytes = payload["kv_k"].nbytes + payload["kv_v"].nbytes
    # KV hand-off phase span: nests under the prefill replica's worker
    # span (same thread), so the trace shows how long the export side of
    # the P/D hop took and over which transport.
    with tracing.span("llm.kv_export",
                      attributes={"path": mode, "bytes": nbytes}):
        if mode == "store":
            out = dict(payload)
            kv_k, kv_v = out.pop("kv_k"), out.pop("kv_v")
            out["kv_ref_k"] = ray_tpu.put(kv_k)
            out["kv_ref_v"] = ray_tpu.put(kv_v)
            mtr["bytes"].inc(nbytes)
            mtr["handoffs"].inc()
            return out
        mtr["bytes"].inc(nbytes)
        mtr["serialized"].inc(nbytes)  # will ride the handle call pickled
        mtr["handoffs"].inc()
        return payload


def resolve_kv_payload(payload: dict) -> dict:
    """Materialize a store-mode payload's KV refs into (read-only,
    store-backed) ndarrays; inline payloads pass through unchanged."""
    if "kv_ref_k" not in payload:
        return payload
    import ray_tpu

    out = dict(payload)
    # One batched get: cross-host, the two transfer-plane pulls overlap
    # instead of serializing two multi-MB fetches on the TTFT path.
    with tracing.span("llm.kv_resolve", attributes={"path": "store"}) as s:
        out["kv_k"], out["kv_v"] = ray_tpu.get(
            [out.pop("kv_ref_k"), out.pop("kv_ref_v")])
        if s is not None:
            s.attributes["bytes"] = \
                out["kv_k"].nbytes + out["kv_v"].nbytes
    return out


def _handoff_engine(llm_config: LLMConfig) -> LLMEngine:
    """The engine of one side of the hand-off, refused before anything is
    built for a model whose cache lines are not per-head K/V."""
    require_kv_handoff(llm_config.model_config())
    return LLMEngine(llm_config)


class PrefillServer:
    """Computes prompt KV + the first token; no decode loop runs here."""

    def __init__(self, llm_config: LLMConfig):
        self.engine = _handoff_engine(llm_config)
        self._mode = getattr(llm_config, "pd_transfer_mode", "store")

    def prefill(self, prompt_ids: list[int], sampling_kw: dict) -> dict:
        payload = self.engine.prefill_only(prompt_ids,
                                           _sampling_from(sampling_kw))
        return export_kv_payload(payload, self._mode)

    def router_prefix_blocks(self) -> dict | None:
        """Publish the engine's cached-prefix block hashes so the serve
        router can land shared-prefix bursts here (serve/prefix.py)."""
        return self.engine.router_prefix_blocks()

    def check_health(self) -> None:
        if not self.engine._thread.is_alive():
            raise RuntimeError("prefill engine died")


class DecodeServer:
    """Continues generation from shipped KV; never prefills."""

    def __init__(self, llm_config: LLMConfig):
        self.engine = _handoff_engine(llm_config)

    def decode(self, payload: dict, sampling_kw: dict) -> dict:
        req = self.engine.submit_prefilled(
            resolve_kv_payload(payload), _sampling_from(sampling_kw))
        if not req.done.wait(300):
            raise TimeoutError("decode timed out")
        if req.error:
            raise RuntimeError(req.error)
        res = self.engine._result(req)
        return {"token_ids": res.token_ids, "text": res.text,
                "finish_reason": res.finish_reason}

    def decode_stream(self, payload: dict, sampling_kw: dict):
        req = self.engine.submit_prefilled(
            resolve_kv_payload(payload), _sampling_from(sampling_kw),
            stream=True)
        while True:
            item = req.stream_queue.get()
            if item is None:
                break
            yield self.engine.tokenizer.decode([item])
        yield ("__finish__", req.finish_reason or "stop")

    def check_health(self) -> None:
        if not self.engine._thread.is_alive():
            raise RuntimeError("decode engine died")


class PDServer:
    """OpenAI-style ingress orchestrating prefill → KV hand-off → decode."""

    def __init__(self, prefill_handle, decode_handle, llm_config: LLMConfig):
        # Bind method handles ONCE: routers/long-poll clients are shared
        # per (runtime, deployment) behind the handle, but binding here
        # keeps the per-request path to a cheap options() copy.
        self.prefill = prefill_handle.options(method_name="prefill")
        self.decode = decode_handle.options(method_name="decode")
        self.decode_stream_h = decode_handle.options(
            method_name="decode_stream", stream=True)
        from ray_tpu.llm.tokenizer import get_tokenizer

        self.tokenizer = get_tokenizer(llm_config.tokenizer)
        self._model_id = (llm_config.model
                         if isinstance(llm_config.model, str) else "llama")
        self._block = int(getattr(llm_config, "prefix_block_tokens", 32)
                          or 0)

    def _prefill_handle(self, prompt: list[int]):
        """Prefill handle with this prompt's token-block chain hashes: the
        router lands a shared-prefix burst on the prefill replica whose
        engine already caches those blocks (serve/prefix.py)."""
        if not self._block:
            return self.prefill
        from ray_tpu.serve.prefix import block_hashes

        hashes = block_hashes(prompt, self._block)
        return self.prefill.options(prefix_hashes=hashes) if hashes \
            else self.prefill

    def chat(self, messages: list[dict], **kw) -> dict:
        prompt = self.tokenizer.encode(
            self.tokenizer.apply_chat_template(messages))
        payload = self._prefill_handle(prompt).remote(
            prompt, kw).result(timeout=300)
        out = self.decode.remote(payload, kw).result(timeout=300)
        # token_ids already starts with first_token (the decode engine
        # emits the imported token as its first output) and the engine
        # already stripped/decoded eos — out["text"] is authoritative.
        toks = list(out["token_ids"])
        return {
            "id": f"chatcmpl-{uuid.uuid4().hex[:12]}",
            "object": "chat.completion",
            "model": self._model_id,
            "choices": [{"index": 0,
                         "message": {"role": "assistant",
                                     "content": out["text"]},
                         "finish_reason": out["finish_reason"]}],
            "usage": {"prompt_tokens": len(prompt),
                      "completion_tokens": len(toks),
                      "total_tokens": len(prompt) + len(toks)},
        }

    def chat_stream(self, messages: list[dict], **kw):
        prompt = self.tokenizer.encode(
            self.tokenizer.apply_chat_template(messages))
        payload = self._prefill_handle(prompt).remote(
            prompt, kw).result(timeout=300)
        first = self.tokenizer.decode([payload["first_token"]])
        rid = f"chatcmpl-{uuid.uuid4().hex[:12]}"
        # Frames carry per-request id/model like the single-server OpenAI
        # path (serving.py chat_stream) so strict SDK clients parse both.
        yield ("data: " + json.dumps({
            "id": rid, "object": "chat.completion.chunk",
            "model": self._model_id,
            "choices": [{"index": 0, "delta": {"content": first},
                         "finish_reason": None}]}) + "\n\n")
        gen = self.decode_stream_h.remote(payload, kw)
        skipped_first = False
        finish = "stop"
        for delta in gen:
            if isinstance(delta, (tuple, list)) and delta \
                    and delta[0] == "__finish__":
                finish = delta[1] or "stop"
                continue
            if not skipped_first:
                skipped_first = True  # already streamed as the TTFT chunk
                continue
            yield ("data: " + json.dumps({
                "id": rid, "object": "chat.completion.chunk",
                "model": self._model_id,
                "choices": [{"index": 0, "delta": {"content": delta},
                             "finish_reason": None}]}) + "\n\n")
        # Terminal frame carrying finish_reason — the same contract as the
        # single-server OpenAI streaming path.
        yield ("data: " + json.dumps({
            "id": rid, "object": "chat.completion.chunk",
            "model": self._model_id,
            "choices": [{"index": 0, "delta": {},
                         "finish_reason": finish}]}) + "\n\n")
        yield "data: [DONE]\n\n"

    def __call__(self, request: "serve.Request") -> Any:
        body = request.json() or {}
        stream = bool(body.pop("stream", False))
        messages = body.pop("messages", [])
        if stream:
            return self.chat_stream(messages, **body)
        return self.chat(messages, **body)


def build_pd_openai_app(llm_config: LLMConfig, *,
                        num_prefill_replicas: int = 1,
                        num_decode_replicas: int = 1,
                        name_prefix: str = ""):
    """serve.run(build_pd_openai_app(cfg), route_prefix="/", http=True).

    ``name_prefix`` namespaces the three deployment names so several PD
    apps can coexist in one serve instance (deployment names are global
    — e.g. an A/B bench running both transfer modes side by side)."""
    prefill_dep = serve.deployment(
        name=f"{name_prefix}PrefillServer",
        num_replicas=num_prefill_replicas,
        max_ongoing_requests=llm_config.max_num_seqs,
        health_check_period_s=2.0)(PrefillServer)
    decode_dep = serve.deployment(
        name=f"{name_prefix}DecodeServer", num_replicas=num_decode_replicas,
        max_ongoing_requests=llm_config.max_num_seqs,
        health_check_period_s=2.0)(DecodeServer)
    pd_dep = serve.deployment(name=f"{name_prefix}PDServer", num_replicas=1,
                              max_ongoing_requests=64)(PDServer)
    return pd_dep.bind(prefill_dep.bind(llm_config),
                       decode_dep.bind(llm_config), llm_config)
