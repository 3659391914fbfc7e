"""The looped stack (models/ouro.py, llm/ouro_serving.py) against the plain
reference (benchmark/reference/ouro.py) on seeded random weights, at a tiny
size on the CPU: the plain forward, the cached programs, a whole
``LLMEngine.generate``; logits and never sampled tokens.

Both sides compute in float32 here, so they differ by the order of sums
alone: over 12 layer applications logits of magnitude ~1 agree to 3e-6
(measured), and ``LOGIT_ATOL`` leaves a factor of thirty. The same program
in bfloat16, the next precision down, misses it by three orders of magnitude
(asserted below), and so does a program whose passes share a cache line.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams
from ray_tpu.llm import ouro_serving
from ray_tpu.models import ouro
from ray_tpu.models.ouro import OuroConfig
from ray_tpu.ops.kernels import force_kernel_backend

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import ouro as reference  # noqa: E402
from rtbench.adapters import ouro as adapter  # noqa: E402

LOGIT_ATOL = 1e-4
SLOTS, MAX_SEQ, CHUNK = 3, 64, 16
I32 = jnp.int32


def config_json(cfg: OuroConfig) -> dict:
    """``cfg`` as the benchmark's configuration file would state it."""
    return {"hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "vocab_size": cfg.vocab_size,
            "num_hidden_layers": cfg.num_layers,
            "total_ut_steps": cfg.total_ut_steps,
            "early_exit_threshold": cfg.early_exit_threshold,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "torch_dtype": cfg.dtype}


def case(**kw):
    cfg = OuroConfig.tiny(max_seq_len=MAX_SEQ, **kw)
    params = ouro.init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (40,), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def want_logits(cfg, params, tokens):
    return np.asarray(reference.logits(
        config_json(cfg), adapter.reference_weights(params),
        jnp.asarray(tokens, I32)))


# ----------------------------------------------------------- plain forward

@pytest.mark.parametrize("steps,threshold", [(4, 1.0), (1, 1.0), (4, 0.8)],
                         ids=["T4", "T1", "T4-exit-at-0.8"])
def test_forward_matches_the_reference(steps, threshold):
    """Logits and the exit distribution. Under a threshold the head reads
    the pass the rule picks, an earlier one for some tokens and the last for
    others, on both sides."""
    cfg, params, tokens = case(total_ut_steps=steps,
                               early_exit_threshold=threshold)
    got, pdf = ouro.forward(cfg, params, tokens[None])
    c, w = config_json(cfg), adapter.reference_weights(params)
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(reference.logits(c, w, tokens)),
                               atol=LOGIT_ATOL, rtol=0)
    want_pdf = np.asarray(reference.exit_distribution(c, w, tokens))
    assert want_pdf.shape == (40, steps)
    np.testing.assert_allclose(want_pdf.sum(axis=1), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pdf[0]), want_pdf, atol=1e-5,
                               rtol=0)
    if threshold < 1:
        picked = (np.cumsum(want_pdf, axis=1)[:, :-1] >= threshold).any(1)
        assert 0 < picked.sum() < len(picked)   # both branches taken


def test_the_tolerance_fails_the_next_precision_down():
    cfg, params, tokens = case()
    low = dataclasses.replace(cfg, dtype="bfloat16")
    got, _ = ouro.forward(
        low, jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
        tokens[None])
    gap = np.abs(np.asarray(got[0]) - want_logits(cfg, params, tokens)).max()
    assert gap > 100 * LOGIT_ATOL


# ------------------------------------------------------ the cached programs

def through_the_cache(cfg, params, tokens, prompt=24, decode=8):
    """Logits of positions prompt - 1 .. prompt + decode - 1 through
    ``prefill_chunk`` (two chunks, the second padded) and ``decode_step`` in
    slot 1 of three, and the counts of every call."""
    cache = ouro_serving.init_cache(cfg, SLOTS, MAX_SEQ)
    rows, counts = [], []
    for start in range(0, prompt, CHUNK):
        cache, row, n = ouro_serving.prefill_chunk(
            cfg, params, cache, tokens[start:start + CHUNK], I32(start),
            I32(prompt), I32(1))
        counts.append(np.asarray(n))
    rows.append(row)
    write = jnp.array([False, True, False])
    for pos in range(prompt, prompt + decode):
        cache, out, n = ouro_serving.decode_step(
            cfg, params, cache, jnp.array([0, tokens[pos], 0], I32),
            jnp.array([0, pos, 0], I32), write)
        rows.append(out[1])
        counts.append(np.asarray(n))
    return np.stack([np.asarray(r) for r in rows]), counts, cache


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_prefill_in_chunks_then_decoding_matches_the_reference(backend):
    """The programs of llm/ouro_serving.py against the reference's full
    forward pass; under ``interpret`` the kernels' own bodies run, at one
    query row a KV head (group 1)."""
    cfg, params, tokens = case()
    with force_kernel_backend(backend):
        got, counts, _ = through_the_cache(cfg, params, tokens)
    want = want_logits(cfg, params, tokens)[23:32]
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    # Valid tokens only: 16 and 8 of the two chunks (the second is padded
    # to 16), one a decode step (two idle slots), each counted 4 passes.
    assert [tuple(n) for n in counts] == [(16, 64), (8, 32)] + [(1, 4)] * 8


@dataclasses.dataclass(frozen=True)
class TiedLines(OuroConfig):
    """The mistake this model invites: every pass of a layer on one line."""

    def cache_line(self, step, layer):
        return layer


def test_two_passes_that_share_a_cache_line_are_seen():
    """Within one chunk a shared line is harmless (a pass overwrites the
    rows it then reads), so the case has a second chunk and decoding: there
    the earlier tokens' rows are the last pass's, whatever pass reads them.
    That program is whole tenths away from the reference; the real one is
    on the right side of the gap."""
    cfg, params, tokens = case()
    tied = TiedLines(**dataclasses.asdict(cfg))
    want = want_logits(cfg, params, tokens)[23:32]
    wrong, _, _ = through_the_cache(tied, params, tokens)
    right, _, cache = through_the_cache(cfg, params, tokens)
    assert np.abs(wrong - want).max() > 0.05
    assert np.abs(right - want).max() < LOGIT_ATOL
    # every one of the T x L lines of the slot was written, each its own
    lines = np.asarray(cache["k"][:, 1, :, :32])
    assert lines.shape[0] == cfg.cache_lines == 12
    assert all(np.abs(lines[a] - lines[b]).max() > 1e-3
               for a in range(12) for b in range(a))
    assert not np.asarray(cache["k"][:, 0]).any()     # an idle slot's lines


def test_copy_prefix_kv_moves_every_line_of_the_slot():
    cfg, params, tokens = case()
    _, _, cache = through_the_cache(cfg, params, tokens, decode=0)
    src = {k: np.asarray(v[:, 1]) for k, v in cache.items()}
    cache = ouro_serving.copy_prefix_kv(cfg, cache, I32(1), I32(2))
    for k, v in cache.items():
        assert v.shape[0] == cfg.cache_lines
        np.testing.assert_array_equal(np.asarray(v[:, 2]), src[k])
        np.testing.assert_array_equal(np.asarray(v[:, 1]), src[k])


# ------------------------------------------------------- through the engine

def margin(cfg, params, prompt, out):
    """How far, at worst, a generated token's logit lies under the
    reference's top logit of its position (the benchmark's ``correct``)."""
    rows = want_logits(cfg, params, prompt + out)[len(prompt) - 1:-1]
    return float((rows.max(axis=1) - rows[np.arange(len(out)), out]).max())


@pytest.fixture(scope="module")
def served():
    cfg = OuroConfig.tiny(max_seq_len=MAX_SEQ)
    eng = LLMEngine(LLMConfig(model=cfg, max_num_seqs=SLOTS,
                              max_seq_len=MAX_SEQ, prefill_chunk=CHUNK,
                              decode_burst=4))
    yield cfg, eng
    eng.shutdown()


def test_a_whole_generate_chooses_the_references_top_logits(served):
    """Chunked prefill, bursts and the look-ahead, three lines at once,
    greedy: every token the engine gives is the reference's top token of
    its position, to the logits' tolerance."""
    cfg, eng = served
    prompts = [[300 + i for i in range(n)] for n in (37, 5, 21)]
    reqs = [eng.submit(p, SamplingParams(max_tokens=n))
            for p, n in zip(prompts, (14, 20, 9))]
    assert all(r.done.wait(180) and not r.error for r in reqs)
    for p, r in zip(prompts, reqs):
        assert margin(cfg, eng.params, p, r.out_tokens) <= LOGIT_ATOL
    stats = eng.stats()
    assert stats["loop_steps"] == cfg.total_ut_steps == 4
    assert stats["loop_exit_steps"] == 4 * stats["loop_tokens"]
    # prompt tokens and decoded tokens went through the loop, padding and
    # idle slots did not (a burst's steps past a line's end still count)
    assert stats["loop_tokens"] >= \
        stats["prompt_tokens_prefilled"] + stats["decode_tokens"]
    assert stats["loop_tokens"] <= stats["prompt_tokens_prefilled"] \
        + stats["decode_steps"] * SLOTS


def test_an_adopted_prefix_decodes_to_the_same_logits(served):
    cfg, eng = served
    first = [400 + i for i in range(40)]
    second = first[:33] + [7, 8, 9]
    hits = eng.stats()["prefix_hits"]
    a = eng.submit(first, SamplingParams(max_tokens=12))
    assert a.done.wait(180) and not a.error
    b = eng.submit(second, SamplingParams(max_tokens=12))
    assert b.done.wait(180) and not b.error
    assert eng.stats()["prefix_hits"] == hits + 1
    assert margin(cfg, eng.params, second, b.out_tokens) <= LOGIT_ATOL


def test_a_shipped_line_has_the_caches_lines_not_the_models_layers(served):
    """The prefill/decode hand-off: a line leaves with one entry a (pass,
    layer) and comes back into another slot; decoding goes on as if the
    prompt had been prefilled here."""
    cfg, eng = served
    prompt = [350 + i for i in range(19)]
    whole = eng.generate(prompt, SamplingParams(max_tokens=8))
    payload = eng.prefill_only(prompt)
    assert payload["kv_k"].shape == (cfg.cache_lines, cfg.num_kv_heads, 19,
                                     cfg.head_dim)
    req = eng.submit_prefilled(payload, SamplingParams(max_tokens=8))
    assert req.done.wait(180) and not req.error
    assert req.out_tokens == whole.token_ids
    short = dict(payload, kv_k=payload["kv_k"][:cfg.num_layers],
                 kv_v=payload["kv_v"][:cfg.num_layers])
    bad = eng.submit_prefilled(short, SamplingParams(max_tokens=2))
    assert bad.done.wait(60) and "KV import failed" in bad.error


@pytest.mark.parametrize("kw,message", [
    (dict(model=OuroConfig.tiny(early_exit_threshold=0.9)),
     r"early_exit_threshold 0\.9 \(under 1\).*owes its later passes"),
    (dict(model=OuroConfig.tiny(), speculative_model="tiny"),
     r"speculative draft"),
    (dict(model=OuroConfig.tiny(), tensor_parallel_size=2),
     r"tensor_parallel_size > 1"),
], ids=["threshold", "draft", "tp"])
def test_what_the_looped_stack_does_not_serve_is_refused(kw, message):
    with pytest.raises(ValueError, match=message):
        LLMEngine(LLMConfig(max_num_seqs=2, max_seq_len=32, **kw))
