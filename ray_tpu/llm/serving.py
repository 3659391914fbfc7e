"""LLM serving: OpenAI-compatible app over serve deployments.

Capability parity with the reference's serve-side LLM stack (reference:
python/ray/llm/_internal/serve/ — LLMServer deployment wrapping the engine,
OpenAI-compatible ingress core/ingress/; deployment options from LLMConfig
llm_config.py:141). The engine here is the JAX continuous-batching engine
(engine.py) instead of a wrapped vLLM.
"""

from __future__ import annotations

import json
import queue
import threading
import time
from typing import Any

from ray_tpu import serve
from ray_tpu.devtools.annotations import guarded_by
from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine


@guarded_by("_lag_lock", "first_frames", "first_frame_lag_s", "last_frames",
            "last_frame_lag_s", "ingress_requests", "ingress_s")
class LLMServer:
    """One replica = one engine instance (the engine batches across the
    replica's concurrent requests)."""

    def __init__(self, llm_config: LLMConfig):
        self.config = llm_config
        self.engine = LLMEngine(llm_config)
        self._model_id = (llm_config.model if isinstance(llm_config.model, str)
                          else "llama")
        # This layer's own share of the time to a first token: from the
        # engine's first-token stamp to the stream method holding that
        # token, ready to frame and yield. Summed over `first_frames`;
        # the replica's pool threads stream concurrently, hence the lock.
        self._lag_lock = threading.Lock()
        self.first_frames = 0
        self.first_frame_lag_s = 0.0
        # The mirror at a line's end: from the engine's finish stamp to the
        # stream method taking the end off the queue, summed over
        # `last_frames`.
        self.last_frames = 0
        self.last_frame_lag_s = 0.0
        # A request's way in: from the proxy's arrival stamp
        # (serve.Request.received_ts) to engine.submit's, summed over
        # `ingress_requests`: the body's read and parse, the route hint,
        # handle and router, the replica's admission and pool thread,
        # tokenisation. A request no proxy stamped (a handle call) counts
        # in neither.
        self.ingress_requests = 0
        self.ingress_s = 0.0

    # -- handle API --

    def _submit(self, prompt, kw: dict, received_ts: float,
                stream: bool = False):
        """engine.submit under ``kw``'s sampling, and the request's way in
        booked where it ends. ``received_ts``, on the four methods below:
        the ``time.time()`` at which an ingress took the request (__call__
        passes the HTTP proxy's stamp); 0.0, no stamp, books nothing."""
        req = self.engine.submit(prompt, _sampling_from(kw), stream=stream)
        if received_ts:
            with self._lag_lock:
                self.ingress_requests += 1
                self.ingress_s += max(req.submit_ts - received_ts, 0.0)
        return req

    def completions(self, prompt: str, *, received_ts: float = 0.0,
                    **kw) -> dict:
        res = self.engine.result(self._submit(prompt, kw, received_ts))
        return {
            "id": f"cmpl-{res.request_id}",
            "object": "text_completion",
            "model": self._model_id,
            "choices": [{"index": 0, "text": res.text,
                         "finish_reason": res.finish_reason}],
            "usage": {"prompt_tokens": len(res.prompt_ids),
                      "completion_tokens": len(res.token_ids),
                      "total_tokens": len(res.prompt_ids) + len(res.token_ids)},
        }

    def chat(self, messages: list[dict], *, received_ts: float = 0.0,
             **kw) -> dict:
        prompt = self.engine.tokenizer.apply_chat_template(messages)
        res = self.engine.result(self._submit(prompt, kw, received_ts))
        return {
            "id": f"chatcmpl-{res.request_id}",
            "object": "chat.completion",
            "model": self._model_id,
            "choices": [{"index": 0,
                         "message": {"role": "assistant", "content": res.text},
                         "finish_reason": res.finish_reason}],
            "usage": {"prompt_tokens": len(res.prompt_ids),
                      "completion_tokens": len(res.token_ids),
                      "total_tokens": len(res.prompt_ids) + len(res.token_ids)},
        }

    def chat_stream(self, messages: list[dict], *,
                    received_ts: float = 0.0, **kw):
        """SSE token stream (reference: OpenAI chat.completion.chunk frames
        through the streaming ingress, serve llm openai compat)."""
        prompt = self.engine.tokenizer.apply_chat_template(messages)
        req = self._submit(prompt, kw, received_ts, stream=True)
        rid = f"chatcmpl-{req.request_id}"
        def frame(item):
            delta = self.engine.tokenizer.decode([item])
            return {"id": rid, "object": "chat.completion.chunk",
                    "model": self._model_id,
                    "choices": [{"index": 0,
                                 "delta": {"content": delta},
                                 "finish_reason": None}]}

        for items in self._stream_tokens(req):
            yield "".join(f"data: {json.dumps(frame(i))}\n\n" for i in items)
        done = {"id": rid, "object": "chat.completion.chunk",
                "model": self._model_id,
                "choices": [{"index": 0, "delta": {},
                             "finish_reason": req.finish_reason or "stop"}]}
        yield f"data: {json.dumps(done)}\n\n"
        yield "data: [DONE]\n\n"

    def completions_stream(self, prompt: str, *, received_ts: float = 0.0,
                           **kw):
        req = self._submit(prompt, kw, received_ts, stream=True)
        rid = f"cmpl-{req.request_id}"
        def frame(item):
            return {"id": rid, "object": "text_completion",
                    "model": self._model_id,
                    "choices": [{"index": 0,
                                 "text": self.engine.tokenizer.decode([item]),
                                 "finish_reason": None}]}

        for items in self._stream_tokens(req):
            yield "".join(f"data: {json.dumps(frame(i))}\n\n" for i in items)
        done = {"id": rid, "object": "text_completion",
                "model": self._model_id,
                "choices": [{"index": 0, "text": "",
                             "finish_reason": req.finish_reason or "stop"}]}
        yield f"data: {json.dumps(done)}\n\n"
        yield "data: [DONE]\n\n"

    def _stream_tokens(self, req):
        """The request's tokens as the engine emits them: each yield is the
        tokens that are there now, one or more (the engine emits a burst's
        tokens of a line together, a block's four or a burst's eight). The
        caller writes one SSE frame a token, and the frames of one yield as
        one chunk: what a chunk costs on its way to the client (the
        replica's streaming call, the proxy's write) is paid once a burst
        and not once a token."""
        first = True
        while True:
            items = [req.stream_queue.get()]
            try:
                while items[-1] is not None:
                    items.append(req.stream_queue.get_nowait())
            except queue.Empty:
                pass
            ended = items[-1] is None
            if ended:
                items.pop()
                lag = time.time() - req.finish_ts
                with self._lag_lock:
                    self.last_frames += 1
                    self.last_frame_lag_s += max(lag, 0.0)
            if items:
                if first:
                    first = False
                    lag = time.time() - req.first_token_ts
                    with self._lag_lock:
                        self.first_frames += 1
                        self.first_frame_lag_s += lag
                yield items
            if ended:
                return

    def stats(self) -> dict:
        with self._lag_lock:
            own = {"first_frames": self.first_frames,
                   "first_frame_lag_s": self.first_frame_lag_s,
                   "last_frames": self.last_frames,
                   "last_frame_lag_s": self.last_frame_lag_s,
                   "ingress_requests": self.ingress_requests,
                   "ingress_s": self.ingress_s}
        return {**self.engine.stats(), **own}

    def router_prefix_blocks(self) -> dict | None:
        """KV-block-aware routing publication (serve/prefix.py): the serve
        controller polls this through ServeReplica.router_meta and
        piggybacks the hashes on the replica snapshot, so routers score
        candidates by matched prefix length. Token domain — handle callers
        pass token-id chain hashes via options(prefix_hashes=...)."""
        return self.engine.router_prefix_blocks()

    def check_health(self) -> None:
        if not self.engine._thread.is_alive():
            raise RuntimeError("engine scheduler thread died")

    # -- HTTP ingress (OpenAI surface) --

    def __call__(self, request: "serve.Request") -> Any:
        path = request.path
        if path.endswith("/v1/models") or path == "/models":
            return {"object": "list",
                    "data": [{"id": self._model_id, "object": "model",
                              "created": int(time.time()),
                              "owned_by": "ray_tpu"}]}
        body = request.json() or {}
        stream = bool(body.pop("stream", False))
        # The proxy's arrival stamp, in place of whatever a client sent
        # under the parameter's name.
        body["received_ts"] = request.received_ts
        if path.endswith("/v1/completions") or path == "/completions":
            prompt = body.pop("prompt", "")
            if stream:
                return self.completions_stream(prompt, **body)
            return self.completions(prompt, **body)
        if path.endswith("/v1/chat/completions") or path == "/chat/completions":
            messages = body.pop("messages", [])
            if stream:
                return self.chat_stream(messages, **body)
            return self.chat(messages, **body)
        return {"error": {"message": f"no route {path}", "code": 404}}


def _sampling_from(kw: dict) -> SamplingParams:
    return SamplingParams(
        max_tokens=int(kw.get("max_tokens", 64)),
        temperature=float(kw.get("temperature", 0.0)),
        top_p=float(kw.get("top_p", 1.0)),
        top_k=int(kw.get("top_k", 0)),
    )


def build_llm_deployment(llm_config: LLMConfig, *,
                         name: str = "LLMServer",
                         num_replicas: int = 1,
                         max_ongoing_requests: int | None = None):
    """The LLMServer as a serve deployment (reference:
    build_llm_deployment / LLMServer.as_deployment). A
    placement_group_config on the LLMConfig gives each replica its own
    gang PG — the multi-host shape where bundle 0 hosts the replica actor
    and the rest reserve the TP/PP worker hosts (reference:
    llm_config.py:181 placement_group_config)."""
    pgc = llm_config.placement_group_config or {}
    return serve.deployment(
        name=name,
        num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests or llm_config.max_num_seqs,
        health_check_period_s=2.0,
        placement_group_bundles=pgc.get("bundles"),
        placement_group_strategy=pgc.get("strategy", "PACK"),
    )(LLMServer)


def build_openai_app(llm_config: LLMConfig, **deploy_kw) -> "serve.Application":
    """OpenAI-compatible application: serve.run(build_openai_app(cfg),
    route_prefix="/", http=True) (reference: serve llm build_openai_app)."""
    dep = build_llm_deployment(llm_config, **deploy_kw)
    return dep.bind(llm_config)
