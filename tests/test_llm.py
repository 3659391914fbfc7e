"""LLM engine + serving tests (reference test model: vLLM-engine stage tests
in ray.llm tests; here the engine itself is under test)."""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams
from ray_tpu.llm.llama_serving import decode_step, prefill, program_params
from ray_tpu.llm.served import init_kv_cache, sample_tokens
from ray_tpu.models.llama import LlamaConfig, forward, init_params


@pytest.fixture(scope="module")
def tiny():
    """The tree as the engine places it: the scheduled programs read its
    fused ``wqkv``; the oracles (``prefill``, models/llama.py's ``forward``)
    the ``wq``, ``wk`` and ``wv`` it was fused from."""
    cfg = LlamaConfig.tiny()
    params = program_params(cfg, init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params


def test_prefill_decode_matches_full_forward(tiny):
    """Incremental decoding must produce the same logits as a full forward
    pass over the concatenated sequence (the KV-cache correctness spec)."""
    cfg, params = tiny
    prompt = np.array([5, 7, 11, 13], np.int32)
    n_extra = 3
    cache = init_kv_cache(cfg, max_slots=2, max_seq=32)

    # Reference: full forward over prompt + extra tokens.
    extra = np.array([17, 19, 23], np.int32)
    full = np.concatenate([prompt, extra])
    ref_logits = np.asarray(
        forward(cfg, params, jnp.asarray(full)[None], attn_impl="blockwise",
                remat=False))[0]

    # Engine path: prefill the prompt, then decode the extra tokens one by
    # one in slot 1 (slot 0 stays empty to catch slot-indexing bugs).
    toks = np.zeros((16,), np.int32)
    toks[:4] = prompt
    cache, last = prefill(cfg, params, cache, jnp.asarray(toks),
                          jnp.int32(4), jnp.int32(1))
    np.testing.assert_allclose(np.asarray(last), ref_logits[3], rtol=2e-4,
                               atol=2e-4)

    for i in range(n_extra):
        tokens = np.zeros((2,), np.int32)
        positions = np.zeros((2,), np.int32)
        tokens[1] = extra[i]
        positions[1] = 4 + i
        cache, logits = decode_step(cfg, params, cache,
                                    jnp.asarray(tokens),
                                    jnp.asarray(positions))
        np.testing.assert_allclose(np.asarray(logits[1]), ref_logits[4 + i],
                                   rtol=2e-4, atol=2e-4)


def test_sample_tokens_greedy_and_topp():
    logits = jnp.asarray([[0.0, 5.0, 1.0, 2.0],
                          [10.0, 0.0, 0.0, 0.0]], jnp.float32)
    # Greedy (temp 0)
    out = sample_tokens(logits, jnp.zeros(2), jnp.ones(2), 0,
                        jax.random.PRNGKey(0))
    assert list(np.asarray(out)) == [1, 0]
    # top_p=tiny keeps only the argmax even at high temperature
    out = sample_tokens(logits, jnp.full((2,), 5.0), jnp.full((2,), 1e-6), 0,
                        jax.random.PRNGKey(1))
    assert list(np.asarray(out)) == [1, 0]
    # top_k=1 likewise
    out = sample_tokens(logits, jnp.full((2,), 5.0), jnp.ones(2), 1,
                        jax.random.PRNGKey(2))
    assert list(np.asarray(out)) == [1, 0]


def test_engine_generate_deterministic():
    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64)
    eng = LLMEngine(cfg)
    try:
        r1 = eng.generate("hello", SamplingParams(max_tokens=8))
        r2 = eng.generate("hello", SamplingParams(max_tokens=8))
        assert r1.token_ids == r2.token_ids  # greedy → deterministic
        assert 0 < len(r1.token_ids) <= 8
        assert r1.finish_reason in ("stop", "length")
    finally:
        eng.shutdown()


def test_engine_continuous_batching_concurrent():
    """More concurrent requests than slots: all must complete, and the
    engine must have had >1 slot active at once (continuous batching)."""
    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64)
    eng = LLMEngine(cfg)
    try:
        peak = [0]
        stop = threading.Event()

        def watch():
            while not stop.is_set():
                peak[0] = max(peak[0], eng.stats()["active"])

        w = threading.Thread(target=watch, daemon=True)
        w.start()
        results = [None] * 5
        def gen(i):
            results[i] = eng.generate(f"prompt number {i}",
                                      SamplingParams(max_tokens=12))
        threads = [threading.Thread(target=gen, args=(i,)) for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        stop.set()
        assert all(r is not None for r in results)
        assert peak[0] >= 2
        # Each result matches its own solo regeneration (no cross-request
        # cache contamination).
        solo = eng.generate("prompt number 3", SamplingParams(max_tokens=12))
        assert solo.token_ids == results[3].token_ids
    finally:
        eng.shutdown()


def test_engine_streaming():
    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64)
    eng = LLMEngine(cfg)
    try:
        chunks = list(eng.generate_stream("stream me",
                                          SamplingParams(max_tokens=6)))
        assert 1 <= len(chunks) <= 6
    finally:
        eng.shutdown()


def test_llm_server_openai_surface():
    ray_tpu.init()
    try:
        from ray_tpu import serve
        from ray_tpu.llm import build_openai_app

        app = build_openai_app(LLMConfig(model="tiny", max_num_seqs=2,
                                         max_seq_len=64))
        handle = serve.run(app, route_prefix=None, _blocking_timeout=120.0)
        out = handle.completions.remote("hi there").result(timeout=120)
        assert out["object"] == "text_completion"
        assert isinstance(out["choices"][0]["text"], str)
        assert out["usage"]["completion_tokens"] > 0

        chat = handle.chat.remote(
            [{"role": "user", "content": "hello"}]).result(timeout=120)
        assert chat["choices"][0]["message"]["role"] == "assistant"
        serve.shutdown()
    finally:
        ray_tpu.shutdown()


# (adopted, [(start, bucket, take), ...]) over a prompt of 30 tokens in a
# line of 32: chunks of one size; a prefill that starts where an adopted
# prefix ends, aligned to nothing, in padded buckets; and a last chunk whose
# bucket is clamped to the cache's tail (12 rows, no power of two).
CHUNK_SCHEDULES = {
    "even": (0, [(0, 8, 8), (8, 8, 8), (16, 8, 8), (24, 8, 6)]),
    "adopted_prefix": (5, [(5, 16, 16), (21, 8, 8), (29, 2, 1)]),
    "clamped_to_tail": (0, [(0, 16, 16), (16, 4, 4), (20, 12, 10)]),
}


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("schedule", sorted(CHUNK_SCHEDULES))
def test_chunked_prefill_matches_full(tiny, schedule, backend):
    """prefill_chunk over N chunks must equal one whole-prompt prefill
    (same cache contents, same last-token logits)."""
    from ray_tpu.llm.llama_serving import prefill_chunk
    from ray_tpu.llm.served import copy_prefix_kv
    from ray_tpu.ops.kernels import force_kernel_backend

    cfg, params = tiny
    prompt = np.arange(1, 31, dtype=np.int32)  # 30 tokens
    p = len(prompt)
    adopted, chunks = CHUNK_SCHEDULES[schedule]

    def whole(slot):
        toks = np.zeros((32,), np.int32)
        toks[:p] = prompt
        return prefill(cfg, params,
                       init_kv_cache(cfg, max_slots=2, max_seq=32),
                       jnp.asarray(toks), jnp.int32(p), jnp.int32(slot))

    cache_full, last_full = whole(1)
    cache_c = init_kv_cache(cfg, max_slots=2, max_seq=32)
    if adopted:
        # Slot 0 holds the prompt; slot 1 adopts its line and prefills
        # from the end of the shared prefix.
        cache_c, _ = whole(0)
        cache_c = copy_prefix_kv(cfg, cache_c, jnp.int32(0), jnp.int32(1))
        donor = np.asarray(cache_c["k"][:, 0]).copy()
    last_c = None
    with force_kernel_backend(backend):
        for start, bucket, take in chunks:
            chunk = np.zeros((bucket,), np.int32)
            chunk[:take] = prompt[start:start + take]
            cache_c, last_c = prefill_chunk(cfg, params, cache_c,
                                            jnp.asarray(chunk),
                                            jnp.int32(start), jnp.int32(p),
                                            jnp.int32(1))
    assert start + take == p
    np.testing.assert_allclose(np.asarray(last_c), np.asarray(last_full),
                               rtol=2e-4, atol=2e-4)
    # cache contents match where real tokens live
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache_c[name][:, 1, :, :p]).astype(np.float32),
            np.asarray(cache_full[name][:, 1, :, :p]).astype(np.float32),
            rtol=2e-3, atol=2e-3)
    if adopted:  # the other slot's line is as it was
        np.testing.assert_array_equal(np.asarray(cache_c["k"][:, 0]), donor)
    else:
        assert not np.asarray(cache_c["k"][:, 0]).any()


def test_prefill_kv_position_counters_grow_a_chunk_at_a_time():
    """prefill_kv_positions_read is what a length-aware prefill attention
    has to visit a chunk (the cached rows and the chunk's bucket),
    prefill_kv_positions_reserved the whole line a dense one scores."""
    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=96)
    cfg.prefill_chunk = 16
    eng = LLMEngine(cfg)
    try:
        st = eng.stats()
        assert st["prefill_kv_positions_read"] == 0
        assert st["prefill_kv_positions_reserved"] == 0
        prompt = [int(t) for t in
                  np.random.default_rng(0).integers(1, 200, 40)]
        eng.generate(prompt, SamplingParams(max_tokens=2, temperature=0.0),
                     timeout=120)
        st = eng.stats()
        # Chunks at 0, 16 and 32, the last one 8 tokens in a bucket of 16
        # (prefill_bucket_min): kv_len + bucket each.
        assert st["prefill_chunks"] == 3
        assert st["prefill_kv_positions_read"] == 16 + 32 + 48
        assert st["prefill_kv_positions_reserved"] == 3 * 96
        # The same prompt again adopts its prefix and prefills the rest in
        # one chunk that starts where the prefix ends.
        eng.generate(prompt[:37] + [7, 8, 9],
                     SamplingParams(max_tokens=2, temperature=0.0),
                     timeout=120)
        now = eng.stats()
        assert now["prefix_hits"] == 1
        saved = now["prefix_tokens_saved"]
        assert 0 < saved <= 37
        chunks = now["prefill_chunks"] - 3
        assert now["prefill_kv_positions_reserved"] == (3 + chunks) * 96
        grew = now["prefill_kv_positions_read"] - st[
            "prefill_kv_positions_read"]
        assert saved * chunks < grew <= 96 * chunks
    finally:
        eng.shutdown()


def test_decode_write_mask_protects_prefilling_slot(tiny):
    """A slot mid-prefill must not be corrupted by the batched decode's
    writes (write_mask=False keeps the cache line)."""
    cfg, params = tiny
    cache = init_kv_cache(cfg, max_slots=2, max_seq=32)
    before = np.asarray(cache["k"][:, 0]).copy()
    tokens = np.array([99, 3], np.int32)
    positions = np.array([0, 0], np.int32)
    write = np.array([False, True])
    cache, _ = decode_step(cfg, params, cache, jnp.asarray(tokens),
                           jnp.asarray(positions), jnp.asarray(write))
    after = np.asarray(cache["k"][:, 0])
    np.testing.assert_array_equal(before, after)  # slot 0 untouched
    assert np.abs(np.asarray(cache["k"][:, 1, :, 0])).sum() > 0  # slot 1 written


def test_engine_long_prompt_chunked():
    """A prompt longer than prefill_chunk completes across chunks."""
    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=96)
    cfg.prefill_chunk = 16
    eng = LLMEngine(cfg)
    try:
        prompt = list(np.random.default_rng(0).integers(1, 200, 40))
        out = eng.generate(prompt, SamplingParams(max_tokens=4,
                                                  temperature=0.0),
                           timeout=120)
        assert len(out.token_ids) >= 1
    finally:
        eng.shutdown()


def test_openai_sse_streaming():
    """stream: true returns chat.completion.chunk SSE frames ending with
    [DONE] (reference: OpenAI-compatible streaming ingress)."""
    import json as _json
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serving import build_openai_app

    ray_tpu.init()
    try:
        cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=128)
        serve.run(build_openai_app(cfg), route_prefix="/", http=True)
        port = serve.http_port()
        body = _json.dumps({
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 5, "temperature": 0.0, "stream": True,
        }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/chat/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.headers["Content-Type"].startswith("text/event-stream")
            text = r.read().decode()
        frames = [ln[6:] for ln in text.splitlines()
                  if ln.startswith("data: ") and ln != "data: [DONE]"]
        assert text.rstrip().endswith("data: [DONE]")
        parsed = [_json.loads(f) for f in frames]
        assert all(p["object"] == "chat.completion.chunk" for p in parsed)
        assert parsed[-1]["choices"][0]["finish_reason"] in ("stop", "length")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_prefill_decode_kv_handoff(tiny):
    """KV exported from one engine and imported into ANOTHER must continue
    greedy generation exactly as a single engine would (reference:
    prefill_decode/pd_server.py + kv_transfer connectors)."""
    from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams

    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=96, seed=3)
    single = LLMEngine(cfg)
    prompt = list(np.random.default_rng(1).integers(1, 200, 12))
    want = single.generate(prompt, SamplingParams(max_tokens=6,
                                                  temperature=0.0),
                           timeout=120)
    single.shutdown()

    pre = LLMEngine(cfg)
    dec = LLMEngine(cfg)
    try:
        payload = pre.prefill_only(prompt)
        assert payload["kv_k"].shape[2] == len(prompt)
        assert payload["first_token"] == want.token_ids[0]
        req = dec.submit_prefilled(payload,
                                   SamplingParams(max_tokens=5,
                                                  temperature=0.0))
        assert req.done.wait(120) and not req.error
        got = req.out_tokens  # [first_token, decoded...]
        assert got[0] == payload["first_token"]
        # the continuation must equal the single-engine greedy sequence
        assert got == want.token_ids[:len(got)]
        assert len(got) == 5
    finally:
        pre.shutdown()
        dec.shutdown()


def test_kv_import_while_bursts_are_in_flight():
    """An imported line's first token is the host's, so it cannot ride
    behind a burst that runs without it: the decode engine reads out what
    is in flight, then carries both lines on, each with the tokens a
    single engine gives it."""
    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=128, seed=3,
                    decode_burst=4)
    pre, dec = LLMEngine(cfg), LLMEngine(cfg)
    try:
        sp = SamplingParams(max_tokens=40)
        prompts = [_ids(12, 1), _ids(20, 2)]
        want = [pre.generate(p, sp, timeout=120).token_ids for p in prompts]
        payload = pre.prefill_only(prompts[1])
        running = dec.submit(prompts[0], sp)
        _wait_for(lambda: len(running.out_tokens) >= 6)
        imported = dec.submit_prefilled(payload, sp)
        for req, toks in zip((running, imported), want):
            assert req.done.wait(120) and not req.error
            assert dec._result(req).token_ids == toks
        assert dec.stats()["decode_dispatches_ahead"] > 4
    finally:
        pre.shutdown()
        dec.shutdown()


def test_engine_bad_kv_payload_fails_cleanly():
    """A decode engine receiving an incompatible KV payload must fail that
    request (error surfaced, waiter woken) without leaking it in _requests
    or wedging the scheduler (engine.py _admit / _fail)."""
    eng = LLMEngine(LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64))
    try:
        bad = {
            "prompt_ids": [1, 2, 3],
            "first_token": 5,
            # wrong layer count -> shape validation failure on import
            "kv_k": np.zeros((99, 1, 3, 4), np.float32),
            "kv_v": np.zeros((99, 1, 3, 4), np.float32),
        }
        req = eng.submit_prefilled(bad, SamplingParams(max_tokens=4))
        assert req.done.wait(60)
        assert req.error and "KV import failed" in req.error
        assert req.finish_reason == "error"
        assert req.request_id not in eng._requests
        assert req.preloaded is None  # staged payload released
        # engine still serves normal traffic afterwards
        res = eng.generate([1, 2, 3], SamplingParams(max_tokens=3,
                                                     temperature=0.0))
        assert len(res.token_ids) > 0
    finally:
        eng.shutdown()


def test_engine_recovers_from_device_failure():
    """decode_step donates the KV cache, so a device-side failure kills the
    cache with it. The engine must fail in-flight requests AND rebuild the
    cache so new traffic still works (engine.py _recover_device_failure)."""
    eng = LLMEngine(LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64))
    real_decode = eng.model.decode_step
    real_burst = eng.model.decode_burst
    boom = {"n": 0}

    def flaky_decode(*a, **kw):
        if boom["n"] == 0:
            boom["n"] += 1
            raise RuntimeError("RESOURCE_EXHAUSTED (simulated)")
        return real_decode(*a, **kw)

    def flaky_burst(*a, **kw):
        if boom["n"] == 0:
            boom["n"] += 1
            raise RuntimeError("RESOURCE_EXHAUSTED (simulated)")
        return real_burst(*a, **kw)

    try:
        # The scheduler reaches every program through ``self.model``.
        eng.model = dataclasses.replace(
            eng.model, decode_step=flaky_decode, decode_burst=flaky_burst)
        req = eng.submit([1, 2, 3], SamplingParams(max_tokens=4))
        assert req.done.wait(60)
        assert req.error and "decode failed" in req.error
        # fresh cache, fresh request: engine serves normally again
        res = eng.generate([1, 2, 3], SamplingParams(max_tokens=3,
                                                     temperature=0.0))
        assert len(res.token_ids) > 0 and boom["n"] == 1
    finally:
        eng.shutdown()


def test_pd_serving_app():
    """Full P/D app through serve: prefill replica -> KV object -> decode
    replica -> ingress answer matches the single-server app (greedy)."""
    import json as _json
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.pd import build_pd_openai_app
    from ray_tpu.llm.serving import build_openai_app

    body = _json.dumps({
        "messages": [{"role": "user", "content": "hello pd"}],
        "max_tokens": 5, "temperature": 0.0,
    }).encode()

    def ask(port):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/chat/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return _json.loads(r.read())

    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=96, seed=5)
    ray_tpu.init()
    try:
        serve.run(build_openai_app(cfg), route_prefix="/", http=True)
        baseline = ask(serve.http_port())["choices"][0]["message"]["content"]
        serve.shutdown()

        ray_tpu.shutdown()
        ray_tpu.init()
        serve.run(build_pd_openai_app(cfg), route_prefix="/", http=True)
        pd_answer = ask(serve.http_port())
        assert pd_answer["choices"][0]["message"]["content"] == baseline
        # usage parity with the single-server OpenAI path
        u = pd_answer["usage"]
        assert u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"]
        # streaming through the P/D path too
        sreq = urllib.request.Request(
            f"http://127.0.0.1:{serve.http_port()}/v1/chat/completions",
            data=_json.dumps({
                "messages": [{"role": "user", "content": "hello pd"}],
                "max_tokens": 4, "temperature": 0.0, "stream": True,
            }).encode(), headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(sreq, timeout=120) as r:
            text = r.read().decode()
        assert text.rstrip().endswith("data: [DONE]")
        # every chunk frame must carry id/model (strict SDK clients require
        # the same frame shape as the single-server path)
        for line in text.splitlines():
            if line.startswith("data: {"):
                frame = _json.loads(line[len("data: "):])
                assert frame["id"] and frame["model"]
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_prefix_cache_exact_rehit_zero_copy():
    """Re-submitting the same prompt adopts the retired slot's KV: only the
    final prompt token is recomputed, and greedy output is identical
    (reference: vLLM automatic prefix caching semantics)."""
    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64)
    eng = LLMEngine(cfg)
    try:
        prompt = list(range(2, 34))  # 32 tokens
        r1 = eng.generate(prompt, SamplingParams(max_tokens=6))
        assert eng.prefix_hits == 0
        r2 = eng.generate(prompt, SamplingParams(max_tokens=6))
        assert eng.prefix_hits == 1
        assert eng.prefix_tokens_saved == len(prompt) - 1
        assert r1.token_ids == r2.token_ids
    finally:
        eng.shutdown()


def test_prefix_cache_shared_prefix_correctness():
    """A request sharing only a PREFIX with a cached prompt must produce
    exactly what a cold engine produces for the same prompt — the adopted
    KV plus the recomputed tail must be equivalent to a full prefill."""
    prefix = list(range(2, 34))            # 32 shared tokens
    prompt_b = prefix + [40, 41, 42, 43]   # diverges after the prefix

    cold = LLMEngine(LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64))
    try:
        expect = cold.generate(prompt_b, SamplingParams(max_tokens=6))
    finally:
        cold.shutdown()

    eng = LLMEngine(LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64))
    try:
        eng.generate(prefix, SamplingParams(max_tokens=4))  # seeds the cache
        got = eng.generate(prompt_b, SamplingParams(max_tokens=6))
        assert eng.prefix_hits == 1
        assert eng.prefix_tokens_saved == len(prefix)  # capped at donor len
        assert got.token_ids == expect.token_ids
    finally:
        eng.shutdown()


def test_prefix_cache_live_donor_copy():
    """Adoption from a donor whose request is STILL RUNNING copies the KV
    line to the new slot; outputs match the cold engine."""
    import time as _t

    prefix = list(range(2, 34))
    prompt_b = prefix + [45, 46]

    cold = LLMEngine(LLMConfig(model="tiny", max_num_seqs=3, max_seq_len=96))
    try:
        expect = cold.generate(prompt_b, SamplingParams(max_tokens=5))
    finally:
        cold.shutdown()

    eng = LLMEngine(LLMConfig(model="tiny", max_num_seqs=3, max_seq_len=96))
    try:
        long_req = eng.submit(prefix, SamplingParams(max_tokens=48))
        deadline = _t.time() + 60
        while not eng._prefix_live and _t.time() < deadline:
            _t.sleep(0.01)  # wait for the donor's prefill to complete
        assert eng._prefix_live, "donor prefill never completed"
        got = eng.generate(prompt_b, SamplingParams(max_tokens=5))
        assert eng.prefix_hits >= 1
        assert got.token_ids == expect.token_ids
        long_req.done.wait(60)
    finally:
        eng.shutdown()


class TestSpeculativeDecoding:
    def test_spec_verify_matches_sequential_decode(self, tiny):
        """spec_verify_step over K tokens produces the same logits and
        cache as K sequential decode_step calls."""
        from ray_tpu.llm.llama_serving import spec_verify_step

        cfg, params = tiny
        K = 3
        prompt = np.array([5, 7, 11, 13], np.int32)
        toks = np.array([17, 19, 23], np.int32)  # K tokens to consume
        c1 = init_kv_cache(cfg, max_slots=2, max_seq=32)
        c1, _ = prefill(cfg, params, c1, jnp.asarray(prompt),
                        jnp.int32(len(prompt)), jnp.int32(0))
        c2 = jax.tree.map(jnp.copy, c1)

        seq_logits = []
        for j, t in enumerate(toks):
            c1, lg = decode_step(
                cfg, params, c1,
                jnp.asarray([t, 0], np.int32),
                jnp.asarray([len(prompt) + j, 0], np.int32),
                jnp.asarray([True, False]))
            seq_logits.append(np.asarray(lg[0]))

        c2, logits = spec_verify_step(
            cfg, params, c2,
            jnp.asarray(np.stack([toks, np.zeros_like(toks)])),
            jnp.asarray([len(prompt), 0], np.int32),
            jnp.asarray([True, False]))
        for j in range(K):
            np.testing.assert_allclose(np.asarray(logits[0, j]),
                                       seq_logits[j], rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(c1["k"]), np.asarray(c2["k"]),
                                   rtol=1e-5, atol=1e-5)

    def test_spec_output_identical_perfect_draft(self):
        """Draft == target: outputs must match vanilla greedy exactly and
        acceptance must be (near) total."""
        from ray_tpu.models.llama import init_params as ip

        tgt_params = ip(LLMConfig(model="tiny").model_config(),
                        jax.random.PRNGKey(3))
        base = LLMEngine(LLMConfig(model="tiny", max_num_seqs=2,
                                   max_seq_len=64), params=tgt_params)
        spec = LLMEngine(LLMConfig(model="tiny", max_num_seqs=2,
                                   max_seq_len=64,
                                   speculative_model="tiny",
                                   speculative_tokens=3),
                         params=tgt_params)
        spec.draft_params = tgt_params  # perfect draft
        try:
            sp = SamplingParams(max_tokens=24, temperature=0.0)
            r0 = base.generate("hello tpu", sampling=sp)
            r1 = spec.generate("hello tpu", sampling=sp)
            assert r1.token_ids == r0.token_ids
            st = spec.stats()
            assert st["spec_ticks"] > 0
            assert st["spec_acceptance"] > 0.9, st
        finally:
            base.shutdown()
            spec.shutdown()

    def test_spec_output_identical_bad_draft(self):
        """The correctness invariant: a DIFFERENT (randomly-initialized)
        draft still yields exactly the vanilla greedy output — speculation
        only changes speed, never results."""
        from ray_tpu.models.llama import init_params as ip

        tgt_params = ip(LLMConfig(model="tiny").model_config(),
                        jax.random.PRNGKey(3))
        base = LLMEngine(LLMConfig(model="tiny", max_num_seqs=2,
                                   max_seq_len=64), params=tgt_params)
        spec = LLMEngine(LLMConfig(model="tiny", max_num_seqs=2,
                                   max_seq_len=64,
                                   speculative_model="tiny",
                                   speculative_tokens=4),
                         params=tgt_params)  # draft params: seed+7 random
        try:
            sp = SamplingParams(max_tokens=20, temperature=0.0)
            for prompt in ("abc", "speculate this"):
                r0 = base.generate(prompt, sampling=sp)
                r1 = spec.generate(prompt, sampling=sp)
                assert r1.token_ids == r0.token_ids, prompt
            st = spec.stats()
            assert st["spec_ticks"] > 0
        finally:
            base.shutdown()
            spec.shutdown()

    def test_spec_disabled_after_repeated_catchup_failure(self):
        """A request whose draft catch-up fails persistently is
        speculation-disabled after 3 attempts (bounded blast radius) —
        it still completes via plain decode, and the engine keeps
        speculating for later requests instead of staying dark."""
        eng = LLMEngine(LLMConfig(model="tiny", max_num_seqs=2,
                                  max_seq_len=64,
                                  speculative_model="tiny",
                                  speculative_tokens=3))
        # Fail the victim's DRAFT prefill dispatches at the device-call
        # layer so the real _draft_catch_up except path (fail counting,
        # disable-at-3, draft-cache rebuild) is what runs — not a stub
        # re-implementing it.
        orig_prefill = eng.draft_model.prefill_chunk

        def failing_prefill(cfg, params, cache, toks, start, end, slot,
                            **kw):
            if cfg is eng.draft_cfg and \
                    eng._slots.get(int(slot)) is victim:
                raise RuntimeError("injected draft prefill failure")
            return orig_prefill(cfg, params, cache, toks, start, end, slot,
                                **kw)

        try:
            eng.draft_model = dataclasses.replace(
                eng.draft_model, prefill_chunk=failing_prefill)
            # max_tokens must span >= 3 fallback ticks: each failed
            # catch-up tick now burst-decodes up to decode_burst tokens, so
            # a short request could finish before the 3rd failure disables
            # speculation.
            victim = eng.submit("doomed draft", sampling=SamplingParams(
                max_tokens=30, temperature=0.0))
            assert victim.done.wait(60) and victim.error is None
            assert victim.spec_disabled
            assert len(victim.out_tokens) == 30
            # Engine must still speculate for a healthy follow-up request.
            healthy = eng.submit("fine", sampling=SamplingParams(
                max_tokens=10, temperature=0.0))
            assert healthy.done.wait(60) and healthy.error is None
            assert not healthy.spec_disabled
            assert eng.stats()["spec_ticks"] > 0
        finally:
            eng.shutdown()

    def test_spec_tick_abandoned_after_plain_decode_device_failure(self):
        """Mixed tick: the plain-decode half hits a device failure, which
        fails every request and rebuilds both caches. The speculative half
        must then be abandoned — dispatching the draft against the rebuilt
        state would emit garbage into already-failed requests."""
        eng = LLMEngine(LLMConfig(model="tiny", max_num_seqs=2,
                                  max_seq_len=64,
                                  speculative_model="tiny",
                                  speculative_tokens=3))
        orig_decode = eng.model.decode_step
        orig_burst = eng.model.decode_burst
        orig_propose = eng.draft_model.draft_propose
        spec_dispatch_after_failure = []
        failed_once = []

        def both_decode_ready():
            return (plain.out_tokens and spec.out_tokens
                    and not plain.done.is_set() and not spec.done.is_set())

        def failing_decode(*a, **kw):
            # Fail only the mixed tick — when both requests decode in the
            # same tick — so the injection deterministically hits the
            # plain half of _spec_decode with the spec half pending.
            if both_decode_ready():
                failed_once.append(True)
                raise RuntimeError("injected device failure")
            return orig_decode(*a, **kw)

        def failing_burst(*a, **kw):
            if both_decode_ready():
                failed_once.append(True)
                raise RuntimeError("injected device failure")
            return orig_burst(*a, **kw)

        def recording_propose(*a, **kw):
            if failed_once:
                spec_dispatch_after_failure.append(True)
            return orig_propose(*a, **kw)

        try:
            eng.model = dataclasses.replace(
                eng.model, decode_step=failing_decode,
                decode_burst=failing_burst)
            eng.draft_model = dataclasses.replace(
                eng.draft_model, draft_propose=recording_propose)
            plain = eng.submit("plain one", sampling=SamplingParams(
                max_tokens=32, temperature=0.0))
            plain.spec_disabled = True  # ride the plain half of the tick
            spec = eng.submit("spec one", sampling=SamplingParams(
                max_tokens=32, temperature=0.0))
            assert plain.done.wait(60) and spec.done.wait(60)
            assert plain.error is not None
            assert spec.error is not None
            assert not spec_dispatch_after_failure, (
                "speculative half dispatched after device recovery")
        finally:
            eng.shutdown()

    def test_spec_mixed_batch_stochastic_falls_back(self):
        """Stochastic requests ride the normal decode path while greedy
        requests speculate — both finish correctly in one engine."""
        eng = LLMEngine(LLMConfig(model="tiny", max_num_seqs=2,
                                  max_seq_len=64,
                                  speculative_model="tiny",
                                  speculative_tokens=3))
        try:
            greedy = eng.submit("aaa", sampling=SamplingParams(
                max_tokens=12, temperature=0.0))
            warm = eng.submit("bbb", sampling=SamplingParams(
                max_tokens=12, temperature=0.8, seed=1))
            assert greedy.done.wait(60) and warm.done.wait(60)
            assert greedy.error is None and warm.error is None
            assert len(greedy.out_tokens) > 0 and len(warm.out_tokens) > 0
            assert eng.stats()["spec_ticks"] > 0
        finally:
            eng.shutdown()


def _wait_for(cond, timeout=60.0):
    import time

    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.002)


def _outcomes(reqs):
    for r in reqs:
        assert r.done.wait(120) and not r.error, r.error
    return [(list(r.out_tokens), r.finish_reason) for r in reqs]


def _ids(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 200, n)]


def _steady(eng):
    """Long generations, one after the other, then two at once."""
    sp = [SamplingParams(max_tokens=n) for n in (40, 21, 33, 37)]
    first = [eng.submit("pipeline me", sp[0])]
    first[0].done.wait(120)
    first.append(eng.submit("zz", sp[1]))
    first[1].done.wait(120)
    return first + [eng.submit("together", sp[2]),
                    eng.submit("with this one", sp[3])]


def _admitted_mid_flight(eng):
    """Prompts of several chunks (16 a chunk) arrive while bursts run."""
    reqs = [eng.submit(_ids(9, 0), SamplingParams(max_tokens=60))]
    for i, (n, m) in enumerate([(50, 30), (37, 25), (70, 12)]):
        _wait_for(lambda: len(reqs[0].out_tokens) >= 6 * (i + 1)
                  or reqs[0].done.is_set())
        reqs.append(eng.submit(_ids(n, i + 1), SamplingParams(max_tokens=m)))
    return reqs


def _stop_then_reuse(eng):
    """One slot: a line stops on a stop token in the middle of a burst
    (its 7th token: bursts of 4 after the first), and the freed slot goes
    to a longer prompt, then to a shorter one, while the look-ahead burst
    that still computes the stopped line is in flight."""
    probe = eng.generate(_ids(24, 7), SamplingParams(max_tokens=12))
    stop = probe.token_ids[6]
    assert stop not in probe.token_ids[:6]
    return [eng.submit(_ids(24, 7), SamplingParams(
                max_tokens=40, stop_token_ids=(stop,))),
            eng.submit(_ids(45, 8), SamplingParams(max_tokens=18)),
            eng.submit(_ids(5, 9), SamplingParams(max_tokens=18))]


def _ends_inside_lookahead(eng):
    """Lines that end at max_tokens at every offset into a burst, and at
    the end of their cache line (max_seq 48) at every offset."""
    reqs = [eng.submit(_ids(6, n), SamplingParams(max_tokens=n))
            for n in (1, 2, 3, 5, 6, 9, 12, 14)]
    reqs += [eng.submit(_ids(p, p), SamplingParams(max_tokens=100))
             for p in (28, 33, 38, 43, 47)]
    return reqs


# name -> (LLMConfig fields, script, least decode dispatches made ahead)
_LOOKAHEAD_CASES = {
    "steady": (dict(max_num_seqs=2, max_seq_len=128), _steady, 20),
    "admitted_mid_flight": (
        dict(max_num_seqs=4, max_seq_len=128, prefill_chunk=16),
        _admitted_mid_flight, 8),
    "stop_then_longer_and_shorter": (
        dict(max_num_seqs=1, max_seq_len=128, prefill_chunk=16),
        _stop_then_reuse, 6),
    "ends_at_max_tokens_and_max_seq": (
        dict(max_num_seqs=3, max_seq_len=48), _ends_inside_lookahead, 4),
}


class TestBurstDecoding:
    """decode_burst: D chained decode+sample steps per dispatch
    (engine.py decode_burst) must be invisible to outputs."""

    def test_burst_matches_single_step_greedy(self):
        base = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64,
                         decode_burst=1)
        burst = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64,
                          decode_burst=4)
        e1, e2 = LLMEngine(base), LLMEngine(burst)
        try:
            for prompt, n in [("hello burst", 13), ("x", 3), ("abc", 8)]:
                r1 = e1.generate(prompt, SamplingParams(max_tokens=n))
                r2 = e2.generate(prompt, SamplingParams(max_tokens=n))
                assert r1.token_ids == r2.token_ids, (prompt, n)
                assert r2.finish_reason == r1.finish_reason
        finally:
            e1.shutdown()
            e2.shutdown()

    def test_burst_concurrent_isolated(self):
        """Burst ticks over a mixed batch: each request's output matches
        its solo regeneration (no cross-slot contamination inside the
        scanned steps)."""
        cfg = LLMConfig(model="tiny", max_num_seqs=4, max_seq_len=64,
                        decode_burst=8)
        eng = LLMEngine(cfg)
        try:
            results = [None] * 4
            def gen(i):
                results[i] = eng.generate(f"burst prompt {i}",
                                          SamplingParams(max_tokens=10))
            threads = [threading.Thread(target=gen, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert all(r is not None for r in results)
            solo = eng.generate("burst prompt 2",
                                SamplingParams(max_tokens=10))
            assert solo.token_ids == results[2].token_ids
        finally:
            eng.shutdown()

    def test_top_k_falls_back_to_single_step(self):
        """top-k sampling can't ride the burst (static k); the engine must
        still serve it correctly via single-step ticks."""
        cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64,
                        decode_burst=8)
        eng = LLMEngine(cfg)
        try:
            r = eng.generate("topk prompt", SamplingParams(
                max_tokens=6, temperature=0.8, top_k=5, seed=1))
            assert 0 < len(r.token_ids) <= 6
        finally:
            eng.shutdown()

    @pytest.mark.parametrize("case", sorted(_LOOKAHEAD_CASES))
    def test_pipelined_bursts_match_unpipelined(self, case):
        """Dispatching the next program before the last is read must be
        output-invisible: under greedy sampling every request gets, token
        for token and with the same finish reason, what the strictly
        serial schedule (decode_pipeline=False) gives it."""
        kwargs, script, ahead = _LOOKAHEAD_CASES[case]
        kwargs = {"model": "tiny", "decode_burst": 4, **kwargs}
        serial = LLMEngine(LLMConfig(**kwargs, decode_pipeline=False))
        piped = LLMEngine(LLMConfig(**kwargs, decode_pipeline=True))
        try:
            want = _outcomes(script(serial))
            got = _outcomes(script(piped))
            assert got == want
            # serial: only a prompt's first burst goes behind anything
            # unread (that prompt's last chunk)
            s = serial.stats()
            assert s["decode_dispatches_ahead"] <= s["admitted"], s
            if ahead:   # the look-ahead engaged, it was not bypassed
                s = piped.stats()
                assert s["decode_dispatches_ahead"] >= ahead, s
            assert not piped._in_flight and not serial._in_flight
        finally:
            serial.shutdown()
            piped.shutdown()

    def test_failure_with_two_programs_in_flight_fails_their_requests(
            self, monkeypatch):
        """A program that fails on the device surfaces when its tokens are
        read, with the next burst already queued behind it: exactly the
        requests in those two programs fail, and the one that waited for a
        slot is served, with the tokens it would have got anyway."""
        import ray_tpu.llm.engine as eng_mod

        cfg = dict(model="tiny", max_num_seqs=2, max_seq_len=128,
                   decode_burst=4)
        oracle = LLMEngine(LLMConfig(**cfg, decode_pipeline=False))
        eng = LLMEngine(LLMConfig(**cfg))
        real_get = jax.device_get
        seen = {"bursts": 0, "in_flight": None}

        def flaky_get(tree):
            toks = tree[0] if isinstance(tree, tuple) else tree
            if threading.current_thread() is eng._thread \
                    and getattr(toks, "ndim", 0) == 2:
                seen["bursts"] += 1
                if seen["bursts"] == 3:
                    # the burst being read and the one behind it
                    seen["in_flight"] = 1 + len(eng._in_flight)
                    raise RuntimeError("injected device failure")
            return real_get(tree)

        try:
            sp = SamplingParams(max_tokens=40)
            want = oracle.generate("the one that waits", sp).token_ids
            monkeypatch.setattr(eng_mod.jax, "device_get", flaky_get)
            a, b = eng.submit("first line", sp), eng.submit("second", sp)
            c = eng.submit("the one that waits", sp)
            for r in (a, b, c):
                assert r.done.wait(120)
            assert seen["in_flight"] == 2
            assert "decode failed" in a.error and "decode failed" in b.error
            assert c.error is None and eng._result(c).token_ids == want
            s = eng.stats()
            assert s["device_failures"] == 1 and s["requests_failed"] == 2
        finally:
            monkeypatch.undo()
            oracle.shutdown()
            eng.shutdown()

    def test_shutdown_reads_out_what_is_in_flight(self):
        """Tokens of programs already dispatched reach their request."""
        cfg = dict(model="tiny", max_num_seqs=2, max_seq_len=256,
                   decode_burst=4)
        oracle = LLMEngine(LLMConfig(**cfg, decode_pipeline=False))
        eng = LLMEngine(LLMConfig(**cfg))
        try:
            sp = SamplingParams(max_tokens=200)
            want = oracle.generate("read me out", sp).token_ids
            req = eng.submit("read me out", sp)
            _wait_for(lambda: len(req.out_tokens) >= 9)
            eng.shutdown()
            assert not eng._thread.is_alive() and not eng._in_flight
            assert req.ahead == 0
            got = list(req.out_tokens)
            assert len(got) >= 9 and got == want[:len(got)]
            # what was dispatched was emitted: steps x 1 line, plus the first
            assert eng.stats()["decode_steps"] == len(got) - 1 \
                or req.done.is_set()
        finally:
            oracle.shutdown()
            eng.shutdown()


def _dispatches(config: LLMConfig):
    """(the programs an engine dispatches, in order, each with its chunk's
    bucket or its burst's steps; the requests' tokens; stats()) for one
    script: a line decodes, then two long prompts arrive beside it. The
    scheduler's thread is stopped and the ticks are made by hand, so the
    sequence is the schedule's alone."""
    eng = LLMEngine(config)
    eng.shutdown()
    calls = []

    def counted(name, size):
        program = getattr(eng.model, name)

        def call(*args, **kw):
            calls.append((name, size(args)))
            return program(*args, **kw)
        return call

    eng.model = dataclasses.replace(
        eng.model,
        prefill_chunk=counted("prefill_chunk", lambda a: a[3].shape[0]),
        decode_step=counted("decode_step", lambda a: 1),
        decode_burst=counted("decode_burst", lambda a: a[9]))
    reqs = [eng.submit(list(range(260, 280)), SamplingParams(max_tokens=30))]
    while not reqs[0].out_tokens:
        eng._tick()
    reqs += [eng.submit(list(range(270, 270 + n)),
                        SamplingParams(max_tokens=m))
             for n, m in ((100, 6), (64, 9))]
    for _ in range(200):
        if all(r.done.is_set() for r in reqs):
            break
        eng._tick()
    assert all(r.done.is_set() and not r.error for r in reqs)
    return calls, [r.out_tokens for r in reqs], eng.stats()


# What the scheduler dispatched for that script before a served model could
# offer ``mixed_burst`` (recorded on the commit before it, PR 46's).
_CHUNK, _BURST, _STEP = "prefill_chunk", "decode_burst", "decode_step"
_DISPATCHED_BEFORE = {
    True: [(_CHUNK, 32), (_BURST, 4), (_BURST, 4)] + [
        (_CHUNK, 32), (_CHUNK, 32), (_BURST, 4)] * 2 + [
        (_CHUNK, 32), (_CHUNK, 16)] + [(_BURST, 4)] * 3 + [(_STEP, 1)],
    False: [(_CHUNK, 32), (_BURST, 4)] + [
        (_CHUNK, 32), (_CHUNK, 32), (_BURST, 4)] * 2 + [
        (_CHUNK, 32), (_CHUNK, 16)] + [(_BURST, 4)] * 4 + [(_STEP, 1)],
}


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["look-ahead", "serial"])
def test_a_model_without_a_mixed_burst_is_scheduled_as_before(monkeypatch,
                                                              pipeline):
    """The tiny Llama with its ``mixed_burst`` taken away (it offered none
    until PR 55): call for call the chunks, bursts and steps it was given
    before the scheduler knew of one, no chunk counted as riding."""
    from ray_tpu.llm import llama_serving

    monkeypatch.setattr(llama_serving, "SERVED", dataclasses.replace(
        llama_serving.SERVED, mixed_burst=None))
    calls, _, stats = _dispatches(LLMConfig(
        model="tiny", max_num_seqs=3, max_seq_len=256, prefill_chunk=32,
        decode_burst=4, prefill_chunks_per_tick=1, decode_pipeline=pipeline))
    assert calls == _DISPATCHED_BEFORE[pipeline]
    assert stats["prefill_chunks"] == 7
    assert stats["prefill_chunks_riding"] == 0
    assert stats["prefill_tokens_riding"] == 0


def test_an_engine_with_a_draft_model_lets_no_chunk_ride():
    """A speculative tick reads the host's tokens and runs the two programs
    of speculation, not a burst: with a draft model the engine never asks
    for ``mixed_burst`` (``_ride_steps`` 0), though the target offers one
    and long prompts arrive while a line decodes."""
    from ray_tpu.llm import llama_serving

    assert llama_serving.SERVED.mixed_burst is llama_serving.mixed_burst
    kw = dict(model="tiny", max_num_seqs=3, max_seq_len=256, prefill_chunk=32,
              decode_burst=4)
    plain = LLMEngine(LLMConfig(**kw))
    spec = LLMEngine(LLMConfig(**kw, speculative_model="tiny",
                               speculative_tokens=3))
    try:
        assert (plain._ride_steps, spec._ride_steps) == (4, 0)
        sp = SamplingParams(max_tokens=40, temperature=0.0)
        first = spec.submit(list(range(5, 25)), sp)
        _wait_for(lambda: first.out_tokens)
        long = spec.submit(list(range(3, 133)),
                           SamplingParams(max_tokens=6, temperature=0.0))
        assert first.done.wait(120) and long.done.wait(120)
        assert not first.error and not long.error
        stats = spec.stats()
        assert stats["prefill_chunks"] >= 5
        assert stats["prefill_chunks_riding"] == 0
        assert stats["prefill_tokens_riding"] == 0
    finally:
        plain.shutdown()
        spec.shutdown()


def test_hf_checkpoint_conversion_numerical_parity(tmp_path):
    """convert_hf_llama vs the transformers reference implementation:
    identical logits on a tiny random-init HF Llama (layout transposes,
    RoPE convention, GQA, norms, tied embeddings all verified at once)."""
    torch = pytest.importorskip("torch")
    tfs = pytest.importorskip("transformers")

    hf_cfg = tfs.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-5, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = tfs.LlamaForCausalLM(hf_cfg).eval()

    from ray_tpu.llm.hf import convert_hf_llama
    from ray_tpu.models.llama import forward

    cfg, params = convert_hf_llama(model, dtype="float32")
    assert cfg.num_kv_heads == 2 and cfg.head_dim == 16

    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 256, (2, 17), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.float().numpy()
    ours = np.asarray(
        forward(cfg, params, jnp.asarray(tokens, jnp.int32), remat=False),
        np.float32)
    np.testing.assert_allclose(ours, ref, atol=2e-3, rtol=2e-3)

    # round-trip through a saved checkpoint directory
    model.save_pretrained(tmp_path / "ck")
    cfg2, params2 = convert_hf_llama(str(tmp_path / "ck"), dtype="float32")
    ours2 = np.asarray(
        forward(cfg2, params2, jnp.asarray(tokens, jnp.int32), remat=False),
        np.float32)
    np.testing.assert_allclose(ours2, ref, atol=2e-3, rtol=2e-3)


def test_engine_loads_hf_checkpoint_dir(tmp_path):
    """LLMConfig(checkpoint_path=<HF dir>) boots the engine with geometry
    AND weights from the checkpoint (byte-tokenizer-compatible vocab)."""
    torch = pytest.importorskip("torch")
    tfs = pytest.importorskip("transformers")

    hf_cfg = tfs.LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=128, rope_theta=10000.0,
        tie_word_embeddings=False, attn_implementation="eager")
    torch.manual_seed(0)
    tfs.LlamaForCausalLM(hf_cfg).save_pretrained(tmp_path / "hf")

    eng = LLMEngine(LLMConfig(model="tiny", dtype="float32",
                              checkpoint_path=str(tmp_path / "hf"),
                              max_num_seqs=2, max_seq_len=64))
    try:
        assert eng.model_cfg.hidden_size == 64  # geometry from checkpoint
        r = eng.generate("hi", SamplingParams(max_tokens=5))
        assert 0 < len(r.token_ids) <= 5
    finally:
        eng.shutdown()


@pytest.mark.parametrize("source", ["init_params", "checkpoint", "hf"])
def test_a_tree_from_anywhere_is_served_through_the_fused_leaf(tmp_path,
                                                                source):
    """The engine fuses ``wqkv`` where it places a tree, whoever made the
    tree: ``init_params``, a saved checkpoint, ``convert_hf_llama``. Its
    greedy tokens, prefilled in two chunks and decoded in bursts, are those
    of models/llama.py's whole forward, which multiplies ``wq``, ``wk`` and
    ``wv`` apart."""
    cfg = dataclasses.replace(LlamaConfig.tiny(), vocab_size=512,
                              max_seq_len=64)
    params, given = init_params(cfg, jax.random.PRNGKey(5)), {}
    if source == "init_params":
        given = {"params": params}
    elif source == "checkpoint":
        import orbax.checkpoint as ocp

        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(tmp_path / "ck", params)
        given = {"checkpoint_path": str(tmp_path / "ck")}
    else:
        torch = pytest.importorskip("torch")
        tfs = pytest.importorskip("transformers")
        from ray_tpu.llm.hf import convert_hf_llama

        torch.manual_seed(0)
        tfs.LlamaForCausalLM(tfs.LlamaConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, max_position_embeddings=64,
            rope_theta=10000.0, tie_word_embeddings=False,
            attn_implementation="eager")).save_pretrained(tmp_path / "hf")
        cfg, params = convert_hf_llama(str(tmp_path / "hf"), dtype="float32")
        given = {"checkpoint_path": str(tmp_path / "hf")}

    prompt, n = list(range(300, 321)), 9
    ids = np.zeros((1, 32), np.int32)
    ids[0, :len(prompt)] = prompt
    whole = jax.jit(lambda ids: forward(cfg, params, ids,
                                        attn_impl="blockwise", remat=False))
    for at in range(len(prompt), len(prompt) + n):
        ids[0, at] = int(jnp.argmax(whole(jnp.asarray(ids))[0, at - 1]))

    eng = LLMEngine(
        LLMConfig(model=cfg, dtype="float32", max_num_seqs=2, max_seq_len=64,
                  prefill_chunk=16, decode_burst=4,
                  checkpoint_path=given.get("checkpoint_path")),
        params=given.get("params"))
    try:
        lay = eng.params["layers"]
        assert lay["wqkv"].shape == (2, 64, lay["wq"].shape[-1]
                                     + 2 * lay["wk"].shape[-1])
        req = eng.submit(prompt, SamplingParams(max_tokens=n))
        assert req.done.wait(120) and req.error is None
        assert req.out_tokens == list(ids[0, len(prompt):len(prompt) + n])
    finally:
        eng.shutdown()


class TestDecodeKernelBody:
    """The decode programs with ops/decode_attention.py's kernel bodies run
    through the Pallas interpreter (the CPU default is their jnp reference).
    A config of its own, so that no program traced elsewhere is reused."""

    @pytest.fixture(scope="class")
    def model(self):
        from dataclasses import replace

        cfg = replace(LlamaConfig.tiny(), vocab_size=264)
        return cfg, program_params(cfg, init_params(cfg,
                                                    jax.random.PRNGKey(0)))

    @pytest.fixture(autouse=True)
    def _interpret(self):
        from ray_tpu.ops.kernels import force_kernel_backend

        with force_kernel_backend("interpret"):
            yield

    def _prefilled(self, cfg, params, prompt, slot, max_seq=32):
        cache = init_kv_cache(cfg, max_slots=2, max_seq=max_seq)
        toks = np.zeros((16,), np.int32)
        toks[:len(prompt)] = prompt
        return prefill(cfg, params, cache, jnp.asarray(toks),
                       jnp.int32(len(prompt)), jnp.int32(slot))

    def test_prefill_then_kernel_decode_matches_full_forward(self, model):
        cfg, params = model
        prompt = np.array([5, 7, 11, 13], np.int32)
        extra = np.array([17, 19, 23], np.int32)
        ref = np.asarray(forward(
            cfg, params, jnp.asarray(np.concatenate([prompt, extra]))[None],
            attn_impl="blockwise", remat=False))[0]
        cache, _ = self._prefilled(cfg, params, prompt, slot=1)
        for i, t in enumerate(extra):
            cache, logits = decode_step(
                cfg, params, cache, jnp.asarray([0, t], np.int32),
                jnp.asarray([0, 4 + i], np.int32),
                jnp.asarray([False, True]))
            np.testing.assert_allclose(np.asarray(logits[1]), ref[4 + i],
                                       rtol=2e-4, atol=2e-4)

    def test_burst_of_eight_is_eight_single_steps(self, model):
        from ray_tpu.llm.llama_serving import decode_burst

        cfg, params = model
        prompt = np.array([5, 7, 11, 13, 17], np.int32)
        c1, last = self._prefilled(cfg, params, prompt, slot=0, max_seq=64)
        c2 = jax.tree.map(jnp.copy, c1)
        tok0 = int(np.argmax(np.asarray(last)))
        write = jnp.asarray([True, False])
        c2, burst = decode_burst(
            cfg, params, c2, jnp.asarray([tok0, 0], np.int32),
            jnp.asarray([len(prompt), 0], np.int32), write,
            jnp.zeros((2,), jnp.float32), jnp.ones((2,), jnp.float32),
            jax.random.PRNGKey(0), 8, False)
        singles, tok = [], tok0
        for j in range(8):
            c1, logits = decode_step(
                cfg, params, c1, jnp.asarray([tok, 0], np.int32),
                jnp.asarray([len(prompt) + j, 0], np.int32), write)
            tok = int(np.argmax(np.asarray(logits[0])))
            singles.append(tok)
        assert np.asarray(burst)[:, 0].tolist() == singles
        np.testing.assert_allclose(np.asarray(c1["k"]), np.asarray(c2["k"]),
                                   rtol=1e-5, atol=1e-5)
        # ... and the whole sequence is what one full forward picks.
        seq = np.concatenate([prompt, [tok0], singles[:-1]]).astype(np.int32)
        ref = np.asarray(forward(cfg, params, jnp.asarray(seq)[None],
                                 attn_impl="blockwise", remat=False))[0]
        assert np.argmax(ref[len(prompt):], -1).tolist() == singles

    def test_verify_of_three_is_three_single_steps(self, model):
        from ray_tpu.llm.llama_serving import spec_verify_step

        cfg, params = model
        prompt = np.array([5, 7, 11, 13], np.int32)
        toks = np.array([17, 19, 23], np.int32)
        c1, _ = self._prefilled(cfg, params, prompt, slot=0)
        c2 = jax.tree.map(jnp.copy, c1)
        write = jnp.asarray([True, False])
        seq_logits = []
        for j, t in enumerate(toks):
            c1, lg = decode_step(cfg, params, c1,
                                 jnp.asarray([t, 0], np.int32),
                                 jnp.asarray([len(prompt) + j, 0], np.int32),
                                 write)
            seq_logits.append(np.asarray(lg[0]))
        c2, logits = spec_verify_step(
            cfg, params, c2,
            jnp.asarray(np.stack([toks, np.zeros_like(toks)])),
            jnp.asarray([len(prompt), 0], np.int32), write)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.stack(seq_logits), rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(np.asarray(c1["k"]),
                                      np.asarray(c2["k"]))

    def test_engine_burst_matches_single_steps(self):
        """Through the scheduler: bursts of 8 (and the 4, 2 and single
        steps a token budget ends on) give the tokens single steps give."""
        from dataclasses import replace

        model = replace(LlamaConfig.tiny(), vocab_size=512,
                        rope_theta=20000.0)
        engines = [LLMEngine(LLMConfig(model=model, max_num_seqs=2,
                                       max_seq_len=64, decode_burst=d))
                   for d in (1, 8)]
        try:
            for prompt, n in [("kernel body", 15), ("x", 3)]:
                r1, r8 = (e.generate(prompt, SamplingParams(max_tokens=n))
                          for e in engines)
                assert r1.token_ids == r8.token_ids, (prompt, n)
                assert len(r8.token_ids) == n
        finally:
            for e in engines:
                e.shutdown()


def test_kv_position_counters_follow_the_block_formula():
    """kv_positions_read is what the decode kernel fetches (lengths rounded
    up to its block, nothing for an idle slot), kv_positions_reserved what
    a kernel blind to lengths would."""
    from ray_tpu.ops.decode_attention import decode_kv_block

    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64,
                    decode_burst=4, decode_pipeline=False)
    eng = LLMEngine(cfg)
    try:
        assert eng.stats()["kv_positions_read"] == 0
        eng.generate("count me", SamplingParams(max_tokens=9))
        st = eng.stats()
        block = decode_kv_block(64, eng.model_cfg.head_dim,
                                eng.model_cfg.jnp_dtype.itemsize)
        assert block == 64  # one block a line at this size
        # One request in two slots: every step fetches its line's one block.
        assert st["kv_positions_read"] == st["decode_steps"] * block
        assert st["kv_positions_reserved"] == st["decode_steps"] * 2 * 64
        assert 0 < st["kv_positions_read"] <= st["kv_positions_reserved"]

        # Scripted, with blocks shorter than the line: a burst of 4 from
        # positions 125 and 300 (slot 2 idle), in blocks of 128 of 512.
        eng.max_seq, eng.max_slots, eng._kv_block = 512, 3, 128
        before = (eng.kv_positions_read, eng.kv_positions_reserved)
        eng._count_kv_positions(np.array([125, 300, 0]),
                                np.array([True, True, False]), steps=4)
        # lengths 126..129 -> 128, 128, 128, 256; 301..304 -> 384 each.
        assert eng.kv_positions_read - before[0] == 128 * 3 + 256 + 384 * 4
        assert eng.kv_positions_reserved - before[1] == 4 * 3 * 512
        # A verify step of 5 tokens is one kernel call over position + 5.
        eng._count_kv_positions(np.array([125, 300, 0]),
                                np.array([True, False, False]), steps=1, k=5)
        assert eng.kv_positions_read - before[0] == (
            128 * 3 + 256 + 384 * 4 + 256)
    finally:
        eng.shutdown()
