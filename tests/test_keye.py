"""Keye-VL-2.0's language model: ``models/keye.py`` and
``llm/keye_serving.py`` against the plain reference of the benchmark, at a
small size on the CPU.

What is held here is what the family adds to the repository: an indexer in
every layer (a LayerNorm on its one key, a rotary over half of an index
head, a ReLU a head and a weighted sum), a selection that is part of the
result (the sets are the reference's exactly, a constructed tie among
them), a second cache leaf written with a chunk's and a step's rows, and
the shares of the routed experts adding up.

Tolerances: float32 against float32 at ``highest``; what is left is the
order of the sums (observed 2e-6 on logits of about 4). Each part left out
below moves them by more than 1e-2.
"""

import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm import keye_serving as serving
from ray_tpu.llm.config import SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import keye, routed
from ray_tpu.models.keye import KeyeConfig
from ray_tpu.ops import sparse_attention as sa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import keye as reference  # noqa: E402
from rtbench.adapters import keye as adapter  # noqa: E402

CFG = KeyeConfig.tiny()
PROMPT = 45           # several times the 8 positions a query keeps
SLOTS, MAX_SEQ = 3, 64
ATOL = 1e-4


@pytest.fixture(scope="module", autouse=True)
def release_the_compiled_programs():
    yield
    jax.clear_caches()


def config_json(cfg: KeyeConfig) -> dict:
    """The benchmark's configuration keys for ``cfg``: ``num_experts`` is
    the number held, as in the configuration file."""
    return {"hidden_size": cfg.hidden_size,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "num_experts": cfg.experts_held,
            "expert_shard": cfg.expert_shard,
            "published": {"num_experts": cfg.num_experts},
            "sa_config": {"indexer_head_dim": cfg.index_head_dim,
                          "indexer_num_heads": cfg.index_heads,
                          "indexer_num_kv_heads": 1,
                          "topk": cfg.index_topk}}


# (jitted: called op by op the initialiser costs a worker 5 s)
INIT = jax.jit(keye.init_params, static_argnums=0)


@pytest.fixture(scope="module")
def params():
    return INIT(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (PROMPT + 8,),
                                         259, CFG.vocab_size), np.int32)


def reference_logits(cfg, params, tokens, sets=None):
    return np.asarray(reference.logits(
        config_json(cfg), adapter.reference_weights(params),
        jnp.asarray(tokens), sets))


@pytest.fixture(scope="module")
def want(params, tokens):
    """(the reference's logits over the whole sequence, every layer's
    sets [S, S])."""
    sets = []
    logits = reference_logits(CFG, params, tokens, sets)
    return logits, [np.concatenate([np.asarray(b) for b in layer])
                    for layer in sets]


def forward(cfg, params, tokens):
    picks = []
    got, counts = keye.forward(cfg, params, jnp.asarray(tokens)[None],
                               picks=picks)
    return np.asarray(got[0]), counts, [np.asarray(p[0]) for p in picks]


def test_the_tiny_config_has_the_mechanism_and_the_cut_its_count():
    assert CFG.index_topk == 8 < PROMPT
    assert CFG.index_rope_dim * 2 == CFG.index_head_dim
    full = KeyeConfig()
    assert (full.index_heads, full.index_head_dim, full.index_rope_dim,
            full.index_topk, full.rope_theta) == (16, 64, 32, 2048, 1e7)
    assert full.indexer_params() == 2_261_120
    assert full.num_params() == 30_640_656_384
    rule = full.router_rule
    assert (rule.outputs, rule.topk, rule.score, rule.use_bias,
            rule.renormalize, rule.renorm_eps, rule.held) == \
        (128, 8, "softmax", False, True, 0.0, 128)
    # the benchmark's cut: 12 layers, share 0 of 8, an eighth of the
    # vocabulary, as ISSUE 64 and the adapter count it
    cut = replace(full, num_layers=12, expert_shards=8, vocab_size=18992)
    assert cut.experts_held == 16
    assert cut.num_params() == 1_240_586_752
    with pytest.raises(ValueError, match="shards"):
        replace(full, expert_shards=7)
    with pytest.raises(ValueError, match="index_rope_dim"):
        replace(full, index_rope_dim=96)


def test_init_params_has_a_leaf_an_axis_list_and_a_unit_embedding(params):
    axes = keye.param_logical_axes(CFG)
    shapes = jax.tree.map(lambda a: a.ndim, params)
    assert jax.tree.map(len, axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == shapes
    assert sum(a.size for a in jax.tree.leaves(params)) == CFG.num_params()
    lay = params["layers"]
    assert lay["router"].dtype == jnp.float32
    assert lay["router"].shape[-1] == CFG.num_experts
    held = jax.eval_shape(lambda: keye.init_params(
        KeyeConfig.tiny(expert_shards=4), jax.random.PRNGKey(0)))["layers"]
    assert held["we_gate"].shape[1] == 2 and held["router"].shape[-1] == 8
    for name in ("ik_norm", "q_norm", "k_norm"):
        w = np.asarray(lay[name])
        assert 0.05 < w.std() < 0.2 and abs(w.mean() - 1.0) < 0.06, name
    assert 0.05 < np.asarray(lay["ik_bias"]).std() < 0.2
    # a token's own row carries the stream: the embedding is of unit size
    assert abs(np.asarray(params["embed_tokens"]).std()
               / keye.EMBED_SIZE - 1.0) < 0.05


def test_forward_matches_the_reference_and_its_sets_exactly(params, tokens,
                                                            want):
    logits, sets = want
    got, counts, picks = forward(CFG, params, tokens)
    np.testing.assert_allclose(got, logits, atol=ATOL)
    for layer in range(CFG.num_layers):
        np.testing.assert_array_equal(picks[layer], sets[layer])
        # a row keeps all it sees up to 8 positions, then 8
        assert list(sets[layer].sum(axis=1)) == [
            min(t + 1, CFG.index_topk) for t in range(len(tokens))]
    # the sets are no window and no prefix: some late row reaches back
    # past its last 8 and skips some of them
    late = sets[0][-1]
    assert late[:-CFG.index_topk].any() and not late[-CFG.index_topk:].all()
    n = len(tokens) * CFG.num_experts_per_tok * CFG.num_layers
    assert [int(c) for c in counts[:3]] == [n, n, 0]


def _with(params, **leaves):
    return {**params, "layers": {**params["layers"], **leaves}}


def _first_half_zero(a):
    """A projection whose index heads lose their rotated half."""
    shaped = a.reshape(*a.shape[:-1], -1, CFG.index_head_dim)
    return shaped.at[..., :CFG.index_rope_dim].set(0.0).reshape(a.shape)


NEUTRAL = {
    # the key's LayerNorm: its weight and its bias
    "key_norm_weight": lambda lay: {"ik_norm": 0 * lay["ik_norm"] + 1.0},
    "key_norm_bias": lambda lay: {"ik_bias": 0 * lay["ik_bias"]},
    # the heads' weights: one head alone decides
    "head_weights": lambda lay: {"wi_w": lay["wi_w"].at[..., 1:].set(0.0)},
    # the rotated half of the index heads
    "rotated_half": lambda lay: {"wi_q": _first_half_zero(lay["wi_q"])},
    "q_norm": lambda lay: {"q_norm": 0 * lay["q_norm"] + 1.0},
}


@pytest.mark.parametrize("part", list(NEUTRAL))
def test_the_seeded_weights_make_every_new_part_visible(params, tokens, want,
                                                        part):
    """A program that dropped the key's norm, a head's weight or the
    indexer's rotary does not pass for right: with that leaf neutral other
    positions are chosen and the logits move by far more than the parity
    tolerance."""
    got, _, picks = forward(
        CFG, _with(params, **NEUTRAL[part](params["layers"])), tokens)
    assert np.abs(got - want[0]).max() > 1e-2
    if part != "q_norm":
        assert (picks[0] != want[1][0]).any()


def test_a_context_no_longer_than_topk_is_dense_attention_a_longer_is_not(
        params, tokens, want):
    """With ``index_topk`` at the sequence's length every row keeps all it
    sees and the indexer decides nothing: the logits are those of the model
    whose indexer is another (random) one. At 8 they are not, from the 9th
    position on."""
    dense = replace(CFG, index_topk=len(tokens))
    other = _with(params, wi_q=params["layers"]["wi_q"][::-1])
    a, _, picks = forward(dense, params, tokens)
    b, _, _ = forward(dense, other, tokens)
    np.testing.assert_array_equal(a, b)
    causal = np.tri(len(tokens), dtype=bool)
    np.testing.assert_array_equal(picks[0], causal)
    np.testing.assert_allclose(
        a, reference_logits(dense, params, tokens), atol=ATOL)
    sparse = want[0]
    k = CFG.index_topk
    np.testing.assert_allclose(sparse[:k], a[:k], atol=ATOL)
    assert np.abs(sparse[k:] - a[k:]).max() > 1e-2
    c, _, _ = forward(CFG, other, tokens)
    assert np.abs(c[k:] - sparse[k:]).max() > 1e-2


def test_a_tie_at_the_last_place_goes_to_the_lower_position(tokens):
    """Constructed: with the heads' weights at zero every score is zero,
    plus or minus (a weight of either sign times a ReLU), so every row's
    scores are all equal and its set is the first 8 positions, as
    ``lax.top_k`` gives them among equal numbers: in the reference, in the
    whole-sequence pass, and through the cache, where the threshold and the
    tie's cut decide. (0.0 and -0.0 are equal: ``top_k`` alone would put
    the positive zeros first.)"""
    p = INIT(CFG, jax.random.PRNGKey(3))
    p = _with(p, wi_w=0 * p["layers"]["wi_w"])
    seq = tokens[:24]
    sets = []
    want = reference_logits(CFG, p, seq, sets)
    got, _, picks = forward(CFG, p, seq)
    first = np.zeros((24, 24), bool)
    for t in range(24):
        first[t, :min(t + 1, CFG.index_topk)] = True
    for layer in range(CFG.num_layers):
        np.testing.assert_array_equal(np.asarray(sets[layer][0]), first)
        np.testing.assert_array_equal(picks[layer], first)
    np.testing.assert_allclose(got, want, atol=ATOL)
    cache, logits, _ = _prefill(p, seq[:20], [16, 20], bucket=16)
    np.testing.assert_allclose(logits, want[19], atol=ATOL)
    _, logits, _ = serving.decode_step(
        CFG, p, cache, jnp.array([0, seq[20], 0], jnp.int32),
        jnp.array([0, 20, 0], jnp.int32), jnp.array([False, True, False]))
    np.testing.assert_allclose(np.asarray(logits[1]), want[20], atol=ATOL)


def test_the_shares_add_up(params):
    """Four shares of the routed experts, each computed by a program that
    holds a quarter, are the uncut layer's feed-forward: what an
    expert-parallel deployment sums (no shared expert to count once; the
    cell's eight shares of 16 are the same rule at another count)."""
    shards = 4
    whole, p = CFG, params
    lay = p["layers"]
    u = jax.random.normal(jax.random.PRNGKey(5), (40, whole.hidden_size))
    valid = jnp.ones((40,), bool)
    layer = 2
    total, picks = 0.0, 0
    block = jax.jit(routed.moe_block, static_argnums=0)
    c = reference._static(config_json(whole))
    w = adapter.reference_weights(p)["layers"]
    for s in range(shards):
        cfg = KeyeConfig.tiny(expert_shard=s, expert_shards=shards)
        held = cfg.experts_held
        assert held == 2
        part = {**lay, **{k: lay[k][:, s * held:(s + 1) * held]
                          for k in ("we_gate", "we_up", "we_down")}}
        y, counts = block(cfg.router_rule, part, layer, u, valid)
        total = total + y.astype(jnp.float32)
        picks += int(counts[1])
        if s == 3:
            # one share alone is that share of the reference: the held
            # experts are the router's outputs 6 and 7, not 0 and 1
            mine = reference.moe(
                reference._static(config_json(cfg)), u,
                {**w, **{k: w[k][:, 6:8]
                         for k in ("e_gate", "e_up", "e_down")}}, layer)
            np.testing.assert_allclose(np.asarray(y), np.asarray(mine),
                                       atol=1e-5)
            assert np.abs(np.asarray(mine)).max() > 1e-3
    assert picks == 40 * whole.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(reference.moe(c, u, w, layer)),
                               atol=1e-5)


# ---- the cache: lines and index keys ----------------------------------------

def _prefill(params, tokens, cuts, slot=1, bucket=None, cache=None, cfg=CFG):
    """The prompt ``tokens`` through ``prefill_chunk`` in chunks that end at
    ``cuts``, the last padded to ``bucket`` where one is given. Returns
    (cache, the last chunk's logits, the counts summed)."""
    cache = cache if cache is not None else serving.init_cache(
        cfg, SLOTS, MAX_SEQ)
    start, total = 0, 0
    for end in cuts:
        size = bucket if bucket and end == cuts[-1] else end - start
        chunk = np.zeros(size, np.int32)
        chunk[:end - start] = tokens[start:end]
        cache, logits, counts = serving.prefill_chunk(
            cfg, params, cache, jnp.asarray(chunk), jnp.int32(start),
            jnp.int32(len(tokens)), jnp.int32(slot))
        start, total = end, total + np.asarray(counts)
    return cache, np.asarray(logits), total


CUTS = {"chunks of 16": ([16, 32, PROMPT], 16),
        "a chunk of one token": ([16, 17, 33, PROMPT], 16)}


@pytest.fixture(scope="module")
def one_pass(params, tokens):
    """The prompt in one padded chunk of 48."""
    return _prefill(params, tokens[:PROMPT], [PROMPT], bucket=48)


@pytest.mark.parametrize("name", list(CUTS))
def test_prefill_in_chunks_gives_one_pass_s_logits_and_leaves(params, tokens,
                                                              want, one_pass,
                                                              name):
    cuts, bucket = CUTS[name]
    prompt = tokens[:PROMPT]
    cache, logits, counts = _prefill(params, prompt, cuts, bucket=bucket)
    whole, whole_logits, whole_counts = one_pass
    # (a padded sequence's last row is the prompt's: the reference's row
    # PROMPT - 1 over the longer sequence, since the model is causal)
    np.testing.assert_allclose(logits, want[0][PROMPT - 1], atol=ATOL)
    np.testing.assert_allclose(whole_logits, want[0][PROMPT - 1], atol=ATOL)
    for leaf in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache[leaf][:, 1, :, :PROMPT]),
            np.asarray(whole[leaf][:, 1, :, :PROMPT]), atol=2e-5,
            err_msg=leaf)
    np.testing.assert_allclose(
        np.asarray(cache["index_k"][:, 1, :, :, :PROMPT]),
        np.asarray(whole["index_k"][:, 1, :, :, :PROMPT]), atol=2e-5)
    for leaf in ("k", "v", "index_k"):
        assert not np.asarray(cache[leaf][:, [0, 2]]).any()
    # the selection's counts: a row a layer, what it saw, what it kept
    rows = PROMPT * CFG.num_layers
    seen = sum(range(1, PROMPT + 1)) * CFG.num_layers
    kept = sum(min(t, CFG.index_topk) for t in range(1, PROMPT + 1)) \
        * CFG.num_layers
    assert [int(c) for c in counts[6:]] == [rows, seen, kept, 0, 0]
    assert [int(c) for c in whole_counts[6:]] == [rows, seen, kept, 0, 0]


def test_decoding_through_the_cache_is_the_full_forward_pass(params, tokens,
                                                             want):
    """Prefill and then a token a step, beside a slot that does not decode
    and one mid-prefill: the reference's logits at every position, the
    index keys written where the rows are, nothing elsewhere."""
    cache, logits, _ = _prefill(params, tokens[:PROMPT], [16, 32, PROMPT],
                                bucket=16)
    np.testing.assert_allclose(logits, want[0][PROMPT - 1], atol=ATOL)
    # slot 2 holds another prompt's first chunk: it neither decodes nor is
    # disturbed
    cache, _, _ = _prefill(params, tokens[::-1][:20], [16], slot=2,
                           cache=cache)
    other = np.asarray(cache["index_k"][:, 2])
    write = jnp.array([False, True, False])
    for i in range(6):
        tok = jnp.array([7, tokens[PROMPT + i], 9], jnp.int32)
        cache, logits, counts = serving.decode_step(
            CFG, params, cache, tok,
            jnp.array([5, PROMPT + i, 16], jnp.int32), write)
        np.testing.assert_allclose(np.asarray(logits[1]),
                                   want[0][PROMPT + i], atol=ATOL)
        scored = (PROMPT + i + 1) * CFG.num_layers
        kept = CFG.index_topk * CFG.num_layers
        assert [int(c) for c in counts[6:]] == [
            CFG.num_layers, scored, kept, scored, kept]
    np.testing.assert_array_equal(np.asarray(cache["index_k"][:, 2]), other)
    assert not np.asarray(cache["index_k"][:, 0]).any()
    assert np.asarray(cache["index_k"][:, 1, 0, :, PROMPT + 5]).any()
    assert not np.asarray(cache["index_k"][:, 1, 0, :, PROMPT + 6:]).any()


def test_a_burst_is_its_steps(params, tokens):
    cache, logits, _ = _prefill(params, tokens[:PROMPT], [16, 32, PROMPT],
                                bucket=16)
    first = int(np.argmax(logits))
    args = (jnp.array([0, first, 0], jnp.int32),
            jnp.array([0, PROMPT, 0], jnp.int32),
            jnp.array([False, True, False]))
    zeros = jnp.zeros((SLOTS,), jnp.float32)
    burst_cache, toks, counts = serving.decode_burst(
        CFG, params, jax.tree.map(jnp.copy, cache), *args, zeros, zeros + 1,
        jax.random.PRNGKey(0), 4, False)
    tok, got = args[0], []
    for i in range(4):
        cache, logits, _ = serving.decode_step(
            CFG, params, cache, tok, args[1] + i, args[2])
        tok = tok.at[1].set(int(np.argmax(logits[1])))
        got.append(int(tok[1]))
    assert [int(t) for t in toks[:, 1]] == got
    assert int(counts[6]) == 4 * CFG.num_layers
    for leaf in ("k", "v", "index_k"):
        np.testing.assert_allclose(np.asarray(burst_cache[leaf]),
                                   np.asarray(cache[leaf]), atol=1e-6)


# ---- through the engine -------------------------------------------------------

def test_the_engine_serves_it_and_refuses_what_it_cannot(params, tokens):
    """``LLMConfig`` -> ``LLMEngine``: greedy tokens are the reference's
    argmax chain, ``stats()`` carries the selection's counters, no prefix
    is adopted, and a draft or ``tensor_parallel_size`` 2 is refused."""
    cfg = LLMConfig(model=CFG, max_num_seqs=SLOTS, max_seq_len=MAX_SEQ,
                    prefill_chunk=16, decode_burst=4, dtype="float32")
    eng = LLMEngine(cfg)
    try:
        p = jax.tree.map(np.asarray, eng.params)
        prompt = [int(t) for t in tokens[:PROMPT]]
        reqs = [eng.submit(prompt, SamplingParams(max_tokens=6)),
                eng.submit(prompt[:30], SamplingParams(max_tokens=3)),
                eng.submit(prompt, SamplingParams(max_tokens=2))]
        assert all(r.done.wait(180) and not r.error for r in reqs)
        stats = eng.stats()
    finally:
        eng.shutdown()
    seq = prompt + reqs[0].out_tokens
    ref = reference_logits(CFG, jax.tree.map(jnp.asarray, p),
                           np.asarray(seq, np.int32))
    rows = ref[PROMPT - 1:len(seq) - 1]
    chosen = rows[np.arange(6), reqs[0].out_tokens]
    assert (rows.max(axis=1) - chosen).max() < ATOL
    assert reqs[2].out_tokens == reqs[0].out_tokens[:2]
    assert stats["prefix_hits"] == 0
    assert stats["index_topk"] == CFG.index_topk
    assert stats["moe_experts_held"] == CFG.num_experts
    # every token's row scored in every layer: the prompts' and the decoded
    rows = (2 * PROMPT + 30 + (6 + 3 + 2 - 3)) * CFG.num_layers
    # (and the steps a burst of 4 runs past a request's last token)
    assert rows <= stats["index_rows"] <= rows + 3 * 3 * CFG.num_layers
    assert stats["index_positions_selected"] < \
        stats["index_positions_scored"]
    assert stats["index_positions_selected"] <= rows * CFG.index_topk
    with pytest.raises(ValueError, match="tensor_parallel_size"):
        LLMEngine(replace(cfg, tensor_parallel_size=2))
    with pytest.raises(ValueError, match="speculative draft"):
        LLMEngine(replace(cfg, speculative_model=CFG))


def test_the_kernels_bodies_give_the_reference_forms_logits(params, tokens,
                                                            want):
    """The three Pallas bodies through the interpreter, in the serving
    programs: a chunk and a step give the jnp forms' logits."""
    from ray_tpu.ops.kernels import force_kernel_backend

    jax.clear_caches()
    try:
        with force_kernel_backend("interpret"):
            cache, logits, _ = _prefill(params, tokens[:PROMPT],
                                        [16, 32, PROMPT], bucket=16)
            np.testing.assert_allclose(logits, want[0][PROMPT - 1],
                                       atol=ATOL)
            cache, logits, _ = serving.decode_step(
                CFG, params, cache,
                jnp.array([0, tokens[PROMPT], 0], jnp.int32),
                jnp.array([0, PROMPT, 0], jnp.int32),
                jnp.array([False, True, False]))
            np.testing.assert_allclose(np.asarray(logits[1]),
                                       want[0][PROMPT], atol=ATOL)
    finally:
        jax.clear_caches()


def test_kept_is_what_the_two_numbers_stand_for():
    scores = jnp.asarray([[3.0, 1.0, 3.0, 3.0, -jnp.inf]])
    thr, pcut = sa.topk_threshold_reference(scores, 2)
    np.testing.assert_array_equal(
        np.asarray(sa.kept(scores, thr, pcut)),
        [[True, False, True, False, False]])
