"""Phi-4-mini-flash (SambaY) on the CPU at tiny sizes, float32, seeded
weights: ``models/phi4flash.forward`` and the programs of
``llm/phi4flash_serving.py`` against the plain reference
(benchmark/reference/phi4flash.py), which shares no code with them: the
scan as the token-by-token recurrence, the two softmaxes apart on unpacked
heads, every layer at every position.

One tolerance, ``ATOL`` 1e-4 on logits of about unit size: everything is
float32 here, the program and the reference order the same sums differently
(a packed head's product sums 2 d terms of which d are zeros; a chunk's
attention sums ring and chunk; XLA's CPU matmuls block), which leaves a few
1e-6 (3.8e-6 observed); 1e-4 is far under anything a wrong mechanism
moves (every one tested below moves the logits by over 1e-2).
"""

import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm import phi4flash_serving as serving
from ray_tpu.llm.config import SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import phi4flash
from ray_tpu.models.phi4flash import Phi4FlashConfig
from ray_tpu.ops.kernels import force_kernel_backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import phi4flash as reference  # noqa: E402
from rtbench.adapters import phi4flash as adapter  # noqa: E402

CFG = Phi4FlashConfig.tiny()
W = CFG.sliding_window            # 8
PROMPT = 29                       # past three turns of the ring, no multiple
SLOTS, MAX_SEQ = 3, 64
ATOL = 1e-4


def config_json(cfg: Phi4FlashConfig) -> dict:
    """The benchmark's configuration keys for ``cfg``."""
    return {"hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "num_hidden_layers": cfg.num_layers,
            "sliding_window": cfg.sliding_window,
            "mb_per_layer": cfg.mb_per_layer,
            "vocab_size": cfg.vocab_size,
            "layer_norm_eps": cfg.norm_eps,
            "mamba_d_state": cfg.mamba_d_state,
            "mamba_d_conv": cfg.mamba_d_conv,
            "mamba_expand": cfg.mamba_expand,
            "mamba_dt_rank": cfg.dt_rank,
            "hidden_act": "silu", "tie_word_embeddings": True,
            "mlp_bias": False, "lm_head_bias": False,
            "torch_dtype": cfg.dtype}


@pytest.fixture(scope="module")
def params():
    return phi4flash.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (PROMPT + 14,),
                                         259, CFG.vocab_size), np.int32)


def reference_logits(cfg, params, tokens):
    return reference.logits(config_json(cfg),
                            adapter.reference_weights(params),
                            jnp.asarray(tokens))


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's logits over the whole sequence, float32."""
    return reference_logits(CFG, params, tokens)


def forward(cfg, params, tokens):
    return np.asarray(jax.jit(phi4flash.forward, static_argnums=0)(
        cfg, params, jnp.asarray(tokens)[None])[0])


def _with(params, **leaves):
    return {**params, "layers": {**params["layers"], **leaves}}


def test_the_tiny_config_has_every_kind_of_layer_and_the_whole_its_count():
    assert (CFG.half, CFG.ssm_lines, CFG.window_lines, CFG.cross_lines,
            CFG.line_readers) == (4, 3, 2, 1, 2)
    assert (CFG.head_dim, CFG.pair_dim, CFG.kv_pairs, CFG.d_inner,
            CFG.dt_rank) == (8, 16, 2, 128, 4)
    full = Phi4FlashConfig()
    assert (full.half, full.ssm_lines, full.window_lines, full.cross_lines,
            full.line_readers, full.head_dim, full.pair_dim, full.kv_pairs,
            full.d_inner, full.dt_rank, full.ssm_state_bytes) == \
        (16, 9, 8, 7, 8, 64, 128, 10, 5120, 160, 327_680)
    # the row's "3.8B", as ISSUE 52 and the adapter count it
    assert full.num_params() == 3_852_562_944
    assert adapter.params_held(
        {**config_json(full), "mamba_dt_rank": "auto"}) == 3_852_562_944
    leaves = jax.eval_shape(lambda: phi4flash.init_params(
        CFG, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(leaves)) == \
        CFG.num_params()
    assert jax.tree.structure(leaves) == jax.tree.structure(
        phi4flash.param_logical_axes(CFG), is_leaf=lambda x: isinstance(
            x, tuple))
    with pytest.raises(ValueError, match="multiple of 4"):
        Phi4FlashConfig.tiny(num_layers=10)
    with pytest.raises(ValueError, match="pairs"):
        Phi4FlashConfig.tiny(num_heads=6, num_kv_heads=3)


def test_forward_is_the_reference(params, tokens, want):
    np.testing.assert_allclose(forward(CFG, params, tokens), want, atol=ATOL)


# What each mechanism is held by: the leaf (or the configuration) that
# carries it, made neutral or moved. The program with that change must
# leave the reference (which keeps the seeded weights) by far more than the
# tolerance; that the program follows the reference with them is the test
# above.
NEUTRAL = {
    "lambda": lambda lay: {"lam": jnp.zeros_like(lay["lam"]),
                           "cross_lam": jnp.zeros_like(lay["cross_lam"])},
    "the norm over a pair": lambda lay: {
        "subln": jnp.ones_like(lay["subln"]),
        "cross_subln": jnp.ones_like(lay["cross_subln"])},
    "D": lambda lay: {"d": jnp.zeros_like(lay["d"])},
    "the convolution's bias": lambda lay: {
        "conv_b": jnp.zeros_like(lay["conv_b"])},
    "a tap": lambda lay: {"conv_w": lay["conv_w"].at[:, 0].set(0.0)},
    "the step's bias": lambda lay: {
        "dt_bias": jnp.zeros_like(lay["dt_bias"])},
    "the decay": lambda lay: {"a_log": jnp.zeros_like(lay["a_log"])},
    "the attention's biases": lambda lay: {
        k: jnp.zeros_like(lay[k]) for k in ("bq", "bk", "bv", "bo",
                                            "cross_bq", "cross_bo")},
    "a LayerNorm's bias": lambda lay: {
        "norm1_b": jnp.zeros_like(lay["norm1_b"])},
    "the gated memory unit": lambda lay: {
        "gmu_in": jnp.zeros_like(lay["gmu_in"])},
}


@pytest.mark.parametrize("part", list(NEUTRAL))
def test_the_seeded_weights_make_every_part_visible(params, tokens, want,
                                                    part):
    got = forward(CFG, _with(params, **NEUTRAL[part](params["layers"])),
                  tokens)
    assert np.abs(got - want).max() > 1e-2


def test_the_head_is_the_embedding_transposed(params, tokens, want):
    """Tied: another embedding row for a token that is not in the sequence
    moves that token's logit and nothing else."""
    absent = 7
    assert absent not in tokens
    moved = {**params, "embed_tokens":
             params["embed_tokens"].at[absent].multiply(2.0)}
    got = forward(CFG, moved, tokens)
    others = np.arange(CFG.vocab_size) != absent
    np.testing.assert_allclose(got[:, others], want[:, others], atol=ATOL)
    assert np.abs(got[:, absent] - want[:, absent]).max() > 1e-2


def test_the_window_s_edge(params, tokens, want):
    """A position attends the ``sliding_window`` positions that end at
    itself (itself and the ``W - 1`` before it), no more and no fewer; a
    key position under 0 (a ring's row that holds nothing yet) is nobody's.
    A window one position wider leaves the reference."""
    vis = np.asarray(phi4flash.window_visible(jnp.arange(20), jnp.arange(20),
                                              W))
    for t in range(20):
        assert vis[t].nonzero()[0].tolist() == list(range(max(0, t - W + 1),
                                                          t + 1))
    assert not np.asarray(phi4flash.window_visible(
        jnp.arange(4), jnp.arange(-3, 1), W))[:, :3].any()
    wider = forward(replace(CFG, sliding_window=W + 1), params, tokens)
    np.testing.assert_allclose(wider[:W], want[:W], atol=ATOL)
    assert np.abs(wider[W:] - want[W:]).max() > 1e-2


def test_lambda_init_follows_the_layer_s_index(params):
    """``0.8 - 0.6 exp(-0.3 l)`` with ``l`` the layer's index among all
    layers, in ``lambda`` and in ``1 - lambda_init``: the same weights and
    inputs at another index give another output, the one the reference
    gives for that index."""
    for l in (1, 5, 31):
        want = 0.8 - 0.6 * np.exp(-0.3 * l)
        assert abs(float(CFG.lambda_init(l)) - want) < 1e-6
        assert abs(reference.lambda_init(l) - want) < 1e-12
    ap = {k: params["layers"][k][0] for k in phi4flash.ATTN_LEAVES}
    o = jax.random.normal(jax.random.PRNGKey(2),
                          (1, CFG.num_heads, 5, CFG.pair_dim))
    out = {l: np.asarray(phi4flash.attention_output(CFG, ap, o, l,
                                                    jnp.float32))
           for l in (1, 3)}
    assert np.abs(out[1] - out[3]).max() > 1e-2
    w = {"lam": ap["lam"], "subln": ap["subln"], "o": ap["wo"],
         "o_bias": ap["bo"]}
    pairs = o[0].reshape(CFG.num_heads // 2, 2, 5, CFG.pair_dim)
    for l in (1, 3):
        ref = reference._differential(
            pairs[:, 0], pairs[:, 1], w["lam"], w["subln"], w["o"],
            w["o_bias"], jnp.float32(reference.lambda_init(l)),
            eps=CFG.norm_eps)
        np.testing.assert_allclose(out[l][0], np.asarray(ref), atol=1e-5)


def test_the_packed_pairs_are_the_two_softmaxes_apart(params):
    """``[q1 | 0]`` and ``[0 | q2]`` against ``[k1 | k2]`` at scale
    ``d^-1/2`` are ``softmax(q1 k1^T / sqrt(d))`` and ``softmax(q2 k2^T /
    sqrt(d))``, each times ``[v1 | v2]``: the program's packed attention
    against the reference's unpacked one, query pair ``i`` on KV pair ``i
    // 2``."""
    ap = {k: params["layers"][k][1] for k in phi4flash.ATTN_LEAVES}
    xn = jax.random.normal(jax.random.PRNGKey(3), (1, 11, CFG.hidden_size))
    q, k, v = phi4flash.attention_heads(CFG, ap, xn)
    assert q.shape == (1, CFG.num_heads, 11, CFG.pair_dim)
    assert k.shape == v.shape == (1, CFG.kv_pairs, 11, CFG.pair_dim)
    d = CFG.head_dim
    # the zeros are where they belong
    assert not np.asarray(q[0, 0::2, :, d:]).any()
    assert not np.asarray(q[0, 1::2, :, :d]).any()
    pos = jnp.arange(11)
    got = phi4flash.packed_attention(q, k, v, pos[None, :] <= pos[:, None],
                                     d ** -0.5)[0]
    proj = lambda n: xn[0] @ ap["w" + n] + ap["b" + n]  # noqa: E731
    q1, q2 = reference.split_pairs(proj("q"), CFG.num_heads)
    k1, k2 = reference.split_pairs(proj("k"), CFG.num_kv_heads)
    vv = jnp.concatenate(reference.split_pairs(proj("v"), CFG.num_kv_heads),
                         axis=-1)
    np.testing.assert_allclose(
        np.asarray(got[0::2]),
        np.asarray(reference._softmax_attention(q1, k1, vv, 0)), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(got[1::2]),
        np.asarray(reference._softmax_attention(q2, k2, vv, 0)), atol=1e-5)


def test_the_memory_is_taken_before_the_gate(params, tokens, want):
    """The gated memory units read the memory layer's ``y``, not ``y *
    silu(z)``: a program that handed on the gated output leaves the
    reference."""
    real = phi4flash.ssm_layer

    def gated(cfg, layers, index, line, x, operator, state):
        seen = {}

        def spy(line, sp, xn, state):
            y, z, state = operator(line, sp, xn, state)
            seen["z"] = z
            return y, z, state

        x, y, state = real(cfg, layers, index, line, x, spy, state)
        return x, y * jax.nn.silu(seen["z"]), state

    phi4flash.ssm_layer = gated
    try:
        got = np.asarray(phi4flash.forward(CFG, params,
                                           jnp.asarray(tokens)[None])[0])
    finally:
        phi4flash.ssm_layer = real
    assert np.abs(got - want).max() > 1e-2


# ---- the cache: the line, the rings, the states and the windows -------------

def _prefill(params, tokens, cuts, slot=1, bucket=None, cache=None,
             always_cross=False):
    """The prompt ``tokens`` through ``prefill_chunk`` in chunks that end at
    ``cuts``, the last padded to ``bucket`` where one is given (the engine
    pads a prompt's last chunk and no other). Returns (cache, the last
    chunk's logits, the counts summed)."""
    cache = cache if cache is not None else serving.init_cache(
        CFG, SLOTS, MAX_SEQ)
    program = serving.prefill_chunk if not always_cross else jax.jit(
        lambda *a: serving._prefill_impl(*a, always_cross=True),
        static_argnums=0)
    start, total = 0, 0
    for end in cuts:
        size = bucket if bucket and end == cuts[-1] else end - start
        chunk = np.zeros(size, np.int32)
        chunk[:end - start] = tokens[start:end]
        cache, logits, counts = program(
            CFG, params, cache, jnp.asarray(chunk), jnp.int32(start),
            jnp.int32(len(tokens)), jnp.int32(slot))
        start, total = end, total + np.asarray(counts)
    return cache, np.asarray(logits), total


def _ring_in_order(cache, leaf, end):
    """A slot's ring rows by position: the last ``min(end, W)`` positions
    before ``end``, oldest first. [lines, pairs, rows, 2 d] of slot 1."""
    ring = np.asarray(cache[leaf][:, 1])
    held = [p for p in range(max(0, end - W), end)]
    return ring[:, :, [p % W for p in held]]


# a prompt shorter than the window (5 < 8) and ones longer; chunks shorter
# and longer than the window; a padded last chunk
CUTS = {"one pass": (PROMPT, [PROMPT], None),
        "chunks of 1 and 2": (PROMPT, [1, 3, 4, 12, 14, 15, PROMPT], None),
        "chunks of the window": (PROMPT, [8, 16, 24, PROMPT], 8),
        "a padded last chunk": (PROMPT, [16, PROMPT], 16),
        "chunks longer than the window": (PROMPT, [20, PROMPT], 32),
        "a prompt shorter than the window": (5, [5], 16),
        "a lone padded token": (PROMPT, [16, 28, PROMPT], 16)}


@pytest.mark.parametrize("name", list(CUTS))
def test_prefill_in_chunks_cut_anywhere_gives_one_pass_s_logits_and_cache(
        params, tokens, want, name):
    """What a chunk leaves is what stands after the prompt's last token, not
    after the chunk's last (padded) row: the state, the window, and the
    rings' last ``W`` valid rows each in the row of its position."""
    n, cuts, bucket = CUTS[name]
    prompt = tokens[:n]
    cache, logits, counts = _prefill(params, prompt, cuts, bucket=bucket)
    whole, _, _ = _prefill(params, prompt, [n])
    np.testing.assert_allclose(logits, want[n - 1], atol=ATOL)
    for leaf in ("state", "conv"):
        np.testing.assert_allclose(np.asarray(cache[leaf]),
                                   np.asarray(whole[leaf]), atol=5e-5,
                                   err_msg=leaf)
        assert not np.asarray(cache[leaf][:, [0, 2]]).any()
    for leaf in ("k", "v"):
        np.testing.assert_allclose(np.asarray(cache[leaf][:, 1, :, :n]),
                                   np.asarray(whole[leaf][:, 1, :, :n]),
                                   atol=5e-5)
        assert not np.asarray(cache[leaf][:, [0, 2]]).any()
    for leaf in ("rk", "rv"):
        np.testing.assert_allclose(_ring_in_order(cache, leaf, n),
                                   _ring_in_order(whole, leaf, n), atol=5e-5)
        assert not np.asarray(cache[leaf][:, [0, 2]]).any()
    named = dict(zip(serving.COUNTERS, counts))
    assert named["ssm_chunk_tokens"] == n * CFG.ssm_lines
    assert named["ssm_state_updates"] == 0
    assert named["cross_decoder_chunks_skipped"] == len(cuts) - 1


def test_a_skipped_chunk_and_a_forced_one_leave_the_same_cache_and_logits(
        params, tokens, want):
    """Layers ``L/2 + 2`` on write nothing that a later position reads: a
    prompt whose chunks all ran them and one whose chunks skipped them (all
    but the last) hold the same cache bit for bit, give the same first
    token's logits, and decode the same logits afterwards."""
    cuts = [8, 16, 24, PROMPT]
    skipped, logits_s, counts_s = _prefill(params, tokens[:PROMPT], cuts,
                                           bucket=8)
    forced, logits_f, counts_f = _prefill(params, tokens[:PROMPT], cuts,
                                          bucket=8, always_cross=True)
    assert counts_s[2] == 3
    for leaf in skipped:
        np.testing.assert_array_equal(np.asarray(skipped[leaf]),
                                      np.asarray(forced[leaf]), err_msg=leaf)
    np.testing.assert_allclose(logits_s, logits_f, atol=1e-6)
    np.testing.assert_allclose(logits_s, want[PROMPT - 1], atol=ATOL)
    # and a chunk that is not the last gives the logits nobody reads
    _, unread, _ = _prefill(params, tokens[:PROMPT], [16])
    assert unread.shape == (CFG.vocab_size,) and not unread.any()
    write = jnp.asarray([False, True, False])
    for p in range(PROMPT, PROMPT + 3):
        tok = jnp.zeros((SLOTS,), jnp.int32).at[1].set(int(tokens[p]))
        pos = jnp.zeros((SLOTS,), jnp.int32).at[1].set(p)
        skipped, got_s, _ = serving.decode_step(CFG, params, skipped, tok,
                                                pos, write)
        forced, got_f, _ = serving.decode_step(CFG, params, forced, tok, pos,
                                               write)
        np.testing.assert_array_equal(np.asarray(got_s[1]),
                                      np.asarray(got_f[1]))


def test_a_chunk_at_the_start_of_a_prompt_starts_from_zeros(params, tokens,
                                                            want):
    """Whatever the slot held before: a longer request's state, window,
    rows and rings (a ring's rows past the prompt's length are nobody's)."""
    junk = jax.tree.map(lambda a: jnp.full_like(a, 3.0),
                        serving.init_cache(CFG, SLOTS, MAX_SEQ))
    cache, logits, _ = _prefill(params, tokens[:5], [5], bucket=16,
                                cache=junk)
    short = reference_logits(CFG, params, tokens[:8])
    np.testing.assert_allclose(logits, short[4], atol=ATOL)
    # and decoding on, while the ring is still not full
    write = jnp.asarray([False, True, False])
    for p in range(5, 8):
        tok = jnp.zeros((SLOTS,), jnp.int32).at[1].set(int(tokens[p]))
        pos = jnp.zeros((SLOTS,), jnp.int32).at[1].set(p)
        cache, got, _ = serving.decode_step(CFG, params, cache, tok, pos,
                                            write)
        np.testing.assert_allclose(np.asarray(got[1]), short[p], atol=ATOL)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_prefill_then_decode_agrees_with_the_reference_past_a_turn_of_the_ring(
        params, tokens, want, backend):
    """Through the line, the rings, the states and the windows,
    teacher-forced over 14 positions (the ring of 8 turns once and most of
    a second time); the other slots of the decode batch are idle
    (``write_mask`` false) and keep what they hold bit for bit.
    ``interpret`` runs the attention kernels' own bodies."""
    with force_kernel_backend(backend):
        cache, logits, _ = _prefill(params, tokens[:PROMPT], [16, PROMPT],
                                    bucket=16)
        np.testing.assert_allclose(logits, want[PROMPT - 1], atol=ATOL)
        # slot 2 holds another request's state, which no step may touch
        cache, _, _ = _prefill(params, tokens[:11], [11], slot=2, cache=cache)
        held = {k: np.asarray(cache[k][:, 2]) for k in cache}
        assert all(held[k].any() for k in held)
        write = jnp.asarray([False, True, False])
        for p in range(PROMPT, len(tokens)):
            tok = jnp.zeros((SLOTS,), jnp.int32).at[1].set(int(tokens[p]))
            pos = jnp.zeros((SLOTS,), jnp.int32).at[1].set(p)
            cache, logits, counts = serving.decode_step(
                CFG, params, cache, tok, pos, write)
            np.testing.assert_allclose(np.asarray(logits[1]), want[p],
                                       atol=ATOL)
            assert [int(c) for c in counts] == [CFG.ssm_lines, 0, 0]
    for k in cache:
        np.testing.assert_array_equal(np.asarray(cache[k][:, 2]), held[k])
        assert not np.asarray(cache[k][:, 0]).any()


def test_a_burst_is_its_steps_and_keeps_idle_slots_state(params, tokens):
    cache, _, _ = _prefill(params, tokens[:PROMPT], [PROMPT])
    cache, _, _ = _prefill(params, tokens[:11], [11], slot=2, cache=cache)
    held = {k: np.asarray(cache[k][:, 2]) for k in cache}
    write = jnp.asarray([False, True, False])
    tok = jnp.zeros((SLOTS,), jnp.int32).at[1].set(int(tokens[PROMPT]))
    pos = jnp.zeros((SLOTS,), jnp.int32).at[1].set(PROMPT)
    zeros, ones = jnp.zeros((SLOTS,)), jnp.ones((SLOTS,))
    burst, toks, counts = serving.decode_burst(
        CFG, params, jax.tree.map(jnp.copy, cache), tok, pos, write, zeros,
        ones, jax.random.PRNGKey(0), 4, False)
    got = []
    for j in range(4):
        cache, logits, _ = serving.decode_step(CFG, params, cache, tok,
                                               pos + j, write)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        got.append(int(tok[1]))
    assert [int(t) for t in toks[:, 1]] == got
    assert [int(c) for c in counts] == [4 * CFG.ssm_lines, 0, 0]
    for leaf in burst:
        np.testing.assert_allclose(np.asarray(burst[leaf]),
                                   np.asarray(cache[leaf]), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(burst[leaf][:, 2]),
                                      held[leaf])


def test_a_state_kept_below_float32_does_not_pass(params, tokens, want):
    """The departure the configuration states (the state in float32, as the
    published kernels keep it) is held here: a state rounded to bfloat16
    after every chunk of 4 tokens moves the logits past the tolerance
    (6.4e-4 observed, against 2.5e-6 for the float32 state)."""
    cache = serving.init_cache(CFG, SLOTS, MAX_SEQ)
    assert cache["state"].dtype == jnp.float32
    for start in range(0, PROMPT, 4):
        chunk = jnp.asarray(tokens[start:min(start + 4, PROMPT)])
        cache, logits, _ = serving.prefill_chunk(
            CFG, params, cache, chunk, jnp.int32(start), jnp.int32(PROMPT),
            jnp.int32(1))
        cache["state"] = cache["state"].astype(jnp.bfloat16).astype(
            jnp.float32)
    assert np.abs(np.asarray(logits) - want[PROMPT - 1]).max() > 5 * ATOL


def test_a_ring_s_size_does_not_depend_on_the_line_s_length():
    for max_seq in (64, 256):
        cache = jax.eval_shape(lambda: serving.init_cache(CFG, SLOTS,
                                                          max_seq))
        assert cache["rk"].shape == cache["rv"].shape == \
            (CFG.window_lines, SLOTS, CFG.kv_pairs, W, CFG.pair_dim)
        assert cache["k"].shape == (1, SLOTS, CFG.kv_pairs, max_seq,
                                    CFG.pair_dim)
        assert cache["state"].shape == (CFG.ssm_lines, SLOTS,
                                        CFG.mamba_d_state, CFG.d_inner)
        assert cache["state"].dtype == jnp.float32
    assert set(cache) == {"k", "v", "rk", "rv", "state", "conv"}


# ---- through the scheduler ---------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    eng = LLMEngine(LLMConfig(model=Phi4FlashConfig.tiny(max_seq_len=MAX_SEQ),
                              max_num_seqs=SLOTS, max_seq_len=MAX_SEQ,
                              prefill_chunk=16, decode_burst=4,
                              dtype="float32", seed=0))
    yield eng
    eng.shutdown()


def test_the_engine_serves_it_and_its_tokens_are_the_reference_s(engine):
    """Greedy requests through ``LLMEngine``: prompts of several chunks (a
    padded last one), one shorter than the window, bursts beside a slot
    mid-prefill, a reused slot, answers past a turn of the ring. Every token
    has the reference's top logit to within the tolerance, whatever else was
    in the batch."""
    cfg = engine.config.model
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(259, cfg.vocab_size, n)))
               for n in (29, 45, 5, 33, 17)]
    reqs = [engine.submit(p, SamplingParams(max_tokens=12)) for p in prompts]
    for r in reqs:
        assert r.done.wait(120) and r.error is None, r.error
    for prompt, out in zip(prompts, (list(r.out_tokens) for r in reqs)):
        assert len(out) == 12
        rows = reference_logits(cfg, engine.params, prompt + out)
        rows = rows[len(prompt) - 1:len(prompt) + 11]
        chosen = rows[np.arange(12), out]
        assert (rows.max(-1) - chosen).max() <= ATOL
    stats = engine.stats()
    assert (stats["ssm_lines"], stats["window_lines"], stats["full_lines"],
            stats["line_readers"], stats["window"]) == (3, 2, 1, 2, 8)
    assert stats["ssm_state_bytes"] == 4 * 128 * 4
    assert stats["ssm_chunk_tokens"] == sum(map(len, prompts)) * 3
    # a token a request comes from prefill, the others from decode steps
    assert stats["ssm_state_updates"] == 5 * 11 * 3
    # chunks of 16: every chunk but a prompt's last skips the cross-decoder
    chunks = sum(-(-len(p) // 16) for p in prompts)
    assert stats["prefill_chunks"] == chunks
    assert stats["cross_decoder_chunks_skipped"] == chunks - 5
    assert stats["prefix_hits"] == 0


def test_a_common_prefix_is_not_adopted(engine):
    """The state at an earlier length is nowhere: two prompts with a long
    common prefix are both prefilled whole."""
    before = engine.stats()
    base = list(range(300, 332))
    for tail in ([7, 8, 9], [10, 11]):
        engine.generate(base + tail, SamplingParams(max_tokens=2))
    after = engine.stats()
    assert after["prefix_hits"] == before["prefix_hits"] == 0
    assert after["ssm_chunk_tokens"] - before["ssm_chunk_tokens"] == \
        (35 + 34) * 3


@pytest.mark.parametrize("bad,match", [
    ({"tensor_parallel_size": 2}, "tensor_parallel_size"),
    ({"speculative_model": Phi4FlashConfig.tiny()}, "speculative draft")])
def test_what_it_does_not_run_is_refused_at_construction(bad, match):
    with pytest.raises(ValueError, match=match):
        LLMEngine(LLMConfig(model=Phi4FlashConfig.tiny(), max_num_seqs=2,
                            max_seq_len=64, dtype="float32", **bad))


def test_the_block_pool_is_refused_by_the_model_too():
    with pytest.raises(ValueError, match="kv_block_size"):
        serving.SERVED.refuse(replace(
            LLMConfig(model=Phi4FlashConfig.tiny()), kv_block_size=16))


def test_the_reference_pads_a_long_sequence_and_gives_the_rows_asked_for(
        params):
    """A sequence longer than one query block is padded inside the reference
    to a multiple of ``PAD_TO`` (one compiled length for many requests); the
    padding is after every position that was asked for, which no earlier
    position sees: 515 rows come back and they are the program's."""
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (515,), 259,
                                           CFG.vocab_size), np.int32)
    assert 515 > reference.QUERY_BLOCK and reference.PAD_TO % 512 == 0
    got = reference_logits(CFG, params, tokens)
    assert got.shape == (515, CFG.vocab_size) and isinstance(got, np.ndarray)
    np.testing.assert_allclose(forward(CFG, params, tokens), got, atol=ATOL)
