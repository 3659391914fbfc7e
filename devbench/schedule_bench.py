"""The engine's schedule alone, timed on the chip: no HTTP, no router.

    chiprun -- python3 devbench/schedule_bench.py [reason] [chat]

``reason``: 32 lines of 12 Mistral-7B layers decode steadily over 32 x 3,072
(the shape of ``mistral7b-serve-reason``) for WINDOW_S seconds; a new line of
256 prompt tokens replaces each that ends. ``chat``: 4 of 32 lines decode
over 32 x 2,048 and a prompt of 256 arrives every 0.7 s. Printed for each:
tokens a second, wall milliseconds a decode step (the device's step plus
whatever gap the host leaves: compare ``decode_ms_per_step`` of a traced
cell), the share of decode dispatches made while an earlier result was
unread, and the time from ``submit`` to the first token of the lines that
arrived in the window.

The script reads nothing but ``LLMEngine``'s public surface, so the same
file runs against an older tree laid beside it (``git archive`` into
``.parent/``, copy this file in, run that copy). One JSON object, last line.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WINDOW_S = 20.0
WIDTHS = dict(hidden_size=4096, intermediate_size=14336, num_heads=32,
              num_kv_heads=8, head_dim=128, vocab_size=32768,
              num_layers=12, dtype="bfloat16", tie_embeddings=False,
              rope_theta=1e6)
# slots, max_seq, lines kept decoding, tokens an answer, seconds between
# arrivals on top of the replacements (0: none)
SHAPES = {"reason": (32, 3072, 32, 1400, 0.0),
          "chat": (32, 2048, 4, 128, 0.7)}


def _engine(slots: int, max_seq: int):
    import jax

    from ray_tpu.llm import LLMConfig, LLMEngine, engine as engine_mod
    from ray_tpu.models.llama import LlamaConfig

    jitted = jax.jit(engine_mod.init_params, static_argnums=0)
    engine_mod.init_params = lambda cfg, key: jitted(cfg, key)
    cfg = LlamaConfig(max_seq_len=max_seq, **WIDTHS)
    return LLMEngine(LLMConfig(model=cfg, max_num_seqs=slots,
                               max_seq_len=max_seq, dtype="bfloat16",
                               prefix_block_tokens=0))


def _run(name: str) -> dict:
    import numpy as np

    from ray_tpu.llm import SamplingParams

    slots, max_seq, lines, answer, gap_s = SHAPES[name]
    eng = _engine(slots, max_seq)
    rng = np.random.default_rng(0)
    stop = threading.Event()
    waits: list[float] = []

    def prompt():
        return [int(t) for t in rng.integers(3, 30000, 256)]

    def client(first_tokens: int):
        """One line kept busy: a request, then the next."""
        tokens = first_tokens
        while not stop.is_set():
            t0 = time.monotonic()
            req = eng.submit(prompt(), SamplingParams(max_tokens=tokens),
                             stream=True)
            req.stream_queue.get()
            waits.append((t0, time.monotonic() - t0))
            req.done.wait()
            tokens = answer

    try:
        # every program the run will use, on lone requests (the lengths the
        # traffic files' warm-ups walk)
        for n in (2, 3, 4, 5, 8, 9, 16):
            eng.generate(prompt(), SamplingParams(max_tokens=n), timeout=900)
        # lines at different depths of their answers
        threads = [threading.Thread(
            target=client, daemon=True,
            args=(answer * (i + 1) // lines + 8,)) for i in range(lines)]
        for t in threads:
            t.start()
            time.sleep(0.05)
        time.sleep(5.0)
        s0, t0 = eng.stats(), time.monotonic()
        while time.monotonic() - t0 < WINDOW_S:
            time.sleep(gap_s or 0.5)
            if gap_s:
                threading.Thread(target=lambda: eng.generate(
                    prompt(), SamplingParams(max_tokens=answer),
                    timeout=900), daemon=True).start()
        s1, t1 = eng.stats(), time.monotonic()
    finally:
        stop.set()
        eng.shutdown()
    d = {k: s1[k] - s0[k] for k in s1
         if isinstance(s1[k], (int, float)) and k in s0}
    took = t1 - t0
    inside = [w for at, w in waits if t0 <= at <= t1]
    return {
        "tokens_per_s": (d["decode_tokens"] + d["first_tokens"]) / took,
        "wall_ms_per_decode_step": 1e3 * took / max(d["decode_steps"], 1),
        "decode_dispatches": d["decode_dispatches"],
        "decode_ahead_share": (d["decode_dispatches_ahead"]
                               / max(d["decode_dispatches"], 1)
                               if "decode_dispatches_ahead" in d else None),
        "slot_use": d["decode_tokens"] / max(d["decode_steps"] * slots, 1),
        "prefill_chunks": d["prefill_chunks"],
        "first_tokens": d["first_tokens"],
        "admit_to_first_token_ms": 1e3 * d["first_token_wait_s"]
        / max(d["first_tokens"], 1),
        "submit_to_first_token_ms_mean": (1e3 * sum(inside) / len(inside)
                                          if inside else None),
        "failed": s1["requests_failed"],
    }


def main(argv: list[str]) -> int:
    import jax

    out = {"device_kind": jax.devices()[0].device_kind,
           "platform": jax.devices()[0].platform}
    if out["platform"] != "tpu":
        print(json.dumps({**out, "error": "needs a TPU"}))
        return 1
    for name in SHAPES:
        if name in argv:
            out[name] = _run(name)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
