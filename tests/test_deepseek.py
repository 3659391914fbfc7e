"""DeepSeek-V2 at a small size on the CPU: the model, the serving programs
through the latent cache, the grouped rule, YaRN, the expert shares and the
engine, against the plain reference (benchmark/reference/deepseek.py) on
seeded random weights.

Both sides compute in float32 here, so they differ only by the order of
sums and by the absorbed form's reassociation: logits of magnitude ~4 agree
to LOGIT_ATOL (measured 6e-6). A wrong mask, scale, rotation, group or
expert term moves logits by 1e-2 and more, and bfloat16 by 1e-2. On the
chip the program runs bfloat16 and a run compares with the margin its
traffic file states.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import deepseek as reference  # noqa: E402
from rtbench.adapters import deepseek as adapter  # noqa: E402

from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm import deepseek_serving as serving  # noqa: E402
from ray_tpu.llm.config import SamplingParams  # noqa: E402
from ray_tpu.llm.engine import LLMEngine  # noqa: E402
from ray_tpu.models import deepseek, routed  # noqa: E402
from ray_tpu.models.deepseek import DeepseekV2Config  # noqa: E402
from ray_tpu.models.mla import mla_full  # noqa: E402
from ray_tpu.ops import latent_attention as la  # noqa: E402
from ray_tpu.ops import rope  # noqa: E402
from ray_tpu.ops.kernels import force_kernel_backend  # noqa: E402


LOGIT_ATOL = 5e-5


def ref_config(cfg: DeepseekV2Config) -> dict:
    """The configuration as a benchmark file states it (published names,
    the experts held and the deployment beside them)."""
    return {"hidden_size": cfg.hidden_size,
            "num_attention_heads": cfg.num_heads,
            "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
            "rope_scaling": {
                "type": "yarn", "factor": cfg.rope_factor,
                "beta_fast": cfg.rope_beta_fast,
                "beta_slow": cfg.rope_beta_slow, "mscale": cfg.rope_mscale,
                "mscale_all_dim": cfg.rope_mscale_all_dim,
                "original_max_position_embeddings":
                    cfg.rope_original_max_position},
            "rms_norm_eps": cfg.norm_eps,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "norm_topk_prob": cfg.norm_topk_prob,
            "first_k_dense_replace": cfg.first_k_dense_replace,
            "n_routed_experts": cfg.experts_held,
            "published": {"n_routed_experts": cfg.n_routed_experts},
            "expert_shard": cfg.expert_shard,
            "expert_shards": cfg.expert_shards}


def ref_logits(cfg, params, tokens):
    return np.asarray(reference.logits(
        ref_config(cfg), adapter.reference_weights(params),
        jnp.asarray(tokens, jnp.int32)))


@pytest.fixture(scope="module", params=[(1, 0), (4, 1)],
                ids=["uncut", "group-1-of-4"])
def case(request):
    shards, shard = request.param
    cfg = DeepseekV2Config.tiny(expert_shards=shards, expert_shard=shard)
    params = deepseek.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (40,), 0,
                                cfg.vocab_size)
    return cfg, params, tokens, ref_logits(cfg, params, tokens)


def test_forward_matches_the_reference(case):
    cfg, params, tokens, want = case
    got, counts = jax.jit(deepseek.forward, static_argnums=0)(
        cfg, params, tokens[None])
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=LOGIT_ATOL,
                               rtol=0)
    picks, local, zero, touched, layer_steps, tiles, tokens_local = (
        int(c) for c in counts)
    nm = cfg.num_routed_layers
    assert picks == 40 * cfg.num_experts_per_tok * nm
    assert layer_steps == nm and zero == 0
    assert 0 < touched <= cfg.experts_held * nm
    if cfg.expert_shards == 1:
        assert local == picks and tokens_local == 40 * nm
    else:
        # One group of four here, two kept: a token reaches this shard in
        # at most half the (token, layer) pairs, with 1 to 3 picks.
        assert 0 < tokens_local <= local <= 3 * tokens_local
        assert tokens_local < 40 * nm


def test_the_reference_sees_a_wrong_mask(case):
    cfg, params, tokens, want = case
    flipped = ref_logits(cfg, params, tokens[::-1])[::-1]
    assert np.abs(want - flipped).max() > 0.05


@pytest.mark.parametrize("field,value", [
    ("rope_factor", 1.0),            # no YaRN: other frequencies and scale
    ("topk_group", 4),               # every group kept: the ungrouped top 3
    ("n_shared_experts", 1),         # half the shared SwiGLU
])
def test_the_reference_sees_a_mechanism_left_out(field, value):
    """What separates this model from its siblings moves the logits by far
    more than the tolerance: a program without it would not pass."""
    import dataclasses

    cfg = DeepseekV2Config.tiny()
    params = deepseek.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (40,), 0,
                                cfg.vocab_size)
    want = ref_logits(cfg, params, tokens)
    other = dataclasses.replace(cfg, **{field: value})
    if field == "n_shared_experts":
        half = cfg.moe_intermediate_size
        lay = dict(params["layers"])
        lay.update(ws_gate=lay["ws_gate"][..., :half],
                   ws_up=lay["ws_up"][..., :half],
                   ws_down=lay["ws_down"][:, :half])
        params = {**params, "layers": lay}
    got, _ = jax.jit(deepseek.forward, static_argnums=0)(
        other, params, tokens[None])
    assert np.abs(np.asarray(got[0]) - want).max() > 100 * LOGIT_ATOL


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_prefill_then_decode_through_the_cache_matches_the_reference(
        case, backend):
    """A prompt of 28 in chunks of 16 (the second half full), six single
    decode steps, then a burst of 4: logits at every step against one full
    forward pass of the reference; the burst's tokens against the
    reference's own top logit at their positions."""
    cfg, params, tokens, want = case
    t = np.asarray(tokens)
    slots, slot, prompt = 3, 1, 28
    nm = cfg.num_routed_layers
    with force_kernel_backend(backend):
        cache = serving.init_cache(cfg, slots, 64)
        for start in (0, 16):
            chunk = np.zeros(16, np.int32)
            take = min(16, prompt - start)
            chunk[:take] = t[start:start + take]
            cache, last, counts = serving.prefill_chunk(
                cfg, params, cache, jnp.asarray(chunk), jnp.int32(start),
                jnp.int32(prompt), jnp.int32(slot))
        # The padded tail of the last chunk is routed nowhere.
        assert int(counts[0]) == 12 * cfg.num_experts_per_tok * nm
        np.testing.assert_allclose(np.asarray(last), want[prompt - 1],
                                   atol=LOGIT_ATOL, rtol=0)
        write = np.zeros(slots, bool)
        write[slot] = True
        for p in range(prompt, prompt + 6):
            tok = np.zeros(slots, np.int32)
            pos = np.zeros(slots, np.int32)
            tok[slot], pos[slot] = t[p], p
            cache, logits, counts = serving.decode_step(
                cfg, params, cache, jnp.asarray(tok), jnp.asarray(pos),
                jnp.asarray(write))
            np.testing.assert_allclose(np.asarray(logits[slot]), want[p],
                                       atol=LOGIT_ATOL, rtol=0)
        # One live slot, one token: exactly topk picks a routed layer.
        assert int(counts[0]) == cfg.num_experts_per_tok * nm
        assert int(counts[6]) <= nm
        p = prompt + 6
        tok = np.zeros(slots, np.int32)
        pos = np.zeros(slots, np.int32)
        tok[slot], pos[slot] = t[p], p
        cache, toks, counts = serving.decode_burst(
            cfg, params, cache, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(write), jnp.zeros(slots, jnp.float32),
            jnp.ones(slots, jnp.float32), jax.random.PRNGKey(0), 4, False)
    assert int(counts[0]) == 4 * cfg.num_experts_per_tok * nm
    burst = [int(x) for x in np.asarray(toks)[:, slot]]
    seq = list(t[:p + 1]) + burst
    rows = ref_logits(cfg, params, seq)[p:p + 4]
    chosen = rows[np.arange(4), burst]
    assert (rows.max(axis=1) - chosen).max() < LOGIT_ATOL


# ----------------------------------------------------------------- the rule

def test_the_grouped_rule_keeps_three_groups_and_the_ungrouped_top_differs():
    """Scores made by hand: the six largest lie in five groups, so the
    ungrouped top 6 cannot be the grouped choice; the grouped rule keeps
    the three groups with the largest single score and takes its six from
    their experts, weights by score alone, times the factor."""
    rule = routed.RouterRule(experts=16, topk=6, use_bias=False,
                             scaling_factor=16.0, groups=8, topk_groups=3)
    plain = routed.RouterRule(experts=16, topk=6, use_bias=False,
                              scaling_factor=16.0)
    # groups of two: (0,1) (2,3) ... ; one token, logits by hand.
    logits = np.full(16, -4.0, np.float32)
    logits[[0, 2, 4, 6, 8]] = [3.0, 2.9, 2.8, 2.7, 2.6]   # five groups' bests
    logits[[1, 3, 5]] = [1.0, 0.9, 0.8]                   # their partners
    logits[10] = 2.5                                      # a sixth group
    router = jnp.eye(16, dtype=jnp.float32)
    u = jnp.asarray(logits)[None]
    idx, w = routed.route(rule, router, None, u)
    free, _ = routed.route(plain, router, None, u)
    assert sorted(np.asarray(free[0]).tolist()) == [0, 2, 4, 6, 8, 10]
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 2, 3, 4, 5]
    assert len({int(i) // 2 for i in np.asarray(idx[0])}) == 3
    p = np.exp(logits) / np.exp(logits).sum()
    np.testing.assert_allclose(np.asarray(w[0]),
                               16.0 * p[np.asarray(idx[0])], rtol=1e-5)
    # The reference's own code for the rule agrees, expert by expert.
    c = reference._static({**ref_config(DeepseekV2Config.tiny()),
                           "n_group": 8, "topk_group": 3,
                           "num_experts_per_tok": 6})
    want = np.asarray(reference.gate_weights(c, u, router))[0]
    got = np.zeros(16, np.float32)
    got[np.asarray(idx[0])] = np.asarray(w[0])
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("bad,says", [
    (dict(groups=3), "do not divide"),
    (dict(groups=4, zero_experts=4), "do not divide"),
    (dict(groups=8, topk_groups=1, topk=3), "do not hold"),
    (dict(groups=4, topk_groups=5), "do not hold"),
    # with a bias a group scores as its two best: groups of one have none
    # (groups of two or more are Ling's rule, tests/test_ling.py)
    (dict(groups=16, topk_groups=4, use_bias=True), "selection bias"),
])
def test_a_grouped_rule_that_cannot_be_is_refused(bad, says):
    kw = dict(experts=16, topk=2, use_bias=False)
    kw.update(bad)
    with pytest.raises(ValueError, match=says):
        routed.RouterRule(**kw)


def test_a_rule_without_groups_routes_as_before():
    """``groups`` 1 (the default) leaves ``route`` what it was: the jaxpr
    has one top_k and no comparison of kept groups."""
    rule = routed.RouterRule(experts=16, topk=4, use_bias=False)
    text = str(jax.make_jaxpr(lambda r, u: routed.route(rule, r, None, u))(
        jnp.zeros((8, 16)), jnp.zeros((5, 8))))
    assert text.count("top_k") == 1 and "reduce_or" not in text
    grouped = routed.RouterRule(experts=16, topk=4, use_bias=False,
                                groups=4, topk_groups=2)
    text = str(jax.make_jaxpr(lambda r, u: routed.route(grouped, r, None,
                                                        u))(
        jnp.zeros((8, 16)), jnp.zeros((5, 8))))
    assert text.count("top_k") == 2 and "reduce_or" in text


# --------------------------------------------------------------------- YaRN

def test_yarn_frequencies_and_scale_are_the_hand_values():
    """The published rope_scaling: the ramp runs from pair 10 to pair 23 of
    32, the first ten frequencies are the published ones, the last nine are
    divided by 40, and the softmax scale is multiplied by 1.5896."""
    sc = DeepseekV2Config().rope_scaling
    assert rope.yarn_ramp_bounds(64, 1e4, sc) == (10, 23)
    inv = np.asarray(rope.rope_frequencies(64, 1e4, sc))
    plain = np.asarray(rope.rope_frequencies(64, 1e4))
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert len(inv[23:]) == 9
    mid = (inv / plain)[11:23]
    assert np.all(np.diff(mid) < 0) and mid[0] < 1 and mid[-1] > 1 / 40
    m = rope.yarn_mscale(40, 0.707)
    assert abs(m - 1.2608) < 1e-4 and abs(m * m - 1.5896) < 1e-4
    assert rope.yarn_mscale(1.0, 0.707) == 1.0
    cfg = DeepseekV2Config()
    assert abs(cfg.sm_scale - 1.5896 / np.sqrt(192)) < 1e-5
    # The reference's own YaRN agrees.
    ref_inv, bounds = reference.yarn_inv_freq(64, 1e4, dict(
        sc, mscale=0.707, mscale_all_dim=0.707))
    assert bounds == (10, 23)
    np.testing.assert_allclose(np.asarray(ref_inv), inv, rtol=1e-6)


def test_a_rotary_factor_on_cos_and_sin_is_refused():
    with pytest.raises(ValueError, match="cos and sin"):
        DeepseekV2Config.tiny(rope_mscale=1.0, rope_mscale_all_dim=0.5)


# ------------------------------------------------------------- the kernels

@pytest.mark.parametrize("k", [1, 2])
def test_latent_decode_attention_at_128_heads_interpret_against_reference(k):
    """The kernel's tile at this model's head count: 128 rows a token."""
    b, h, s, rank, dr = 3, 128, 256, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(ks[0], (b, k, h, rank + dr), jnp.float32)
    cache = jax.random.normal(ks[1], (2, b, s, 128), jnp.float32)
    lengths = jnp.array([200 + k, 0, 77 + k], jnp.int32)
    pos = jnp.array([200, 0, 77], jnp.int32)
    want = la.latent_decode_attention_reference(q, cache, 1, lengths, pos,
                                                rank, 0.3)
    with force_kernel_backend("interpret"):
        got = la.latent_decode_attention(q, cache, 1, lengths, pos,
                                         rank=rank, sm_scale=0.3, block=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)
    assert not np.asarray(got[1]).any()


# ---------------------------------------------------------------- the share

def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Section 4 of the model-configs guide, in the published layout: 8
    groups, one a shard. The routed parts that all 8 shares give, with what
    every chip computes alike (the attention, the shared experts) counted
    once, add up to the uncut layer of the reference; every pick has one
    home and a token reaches at most 3 shards."""
    shards = 8
    kw = dict(n_routed_experts=16, n_group=8, topk_group=3,
              num_experts_per_tok=4)
    full = DeepseekV2Config.tiny(**kw)
    params = deepseek.init_params(full, jax.random.PRNGKey(8))
    h = jax.random.normal(jax.random.PRNGKey(9), (1, 12, full.hidden_size))
    want = reference.layer(
        reference._static(ref_config(full)), h[0],
        adapter.reference_weights(params)["layers"], 2)

    def attn(index, ap, xn, state):
        return mla_full(full, ap, xn, None,
                        deepseek.kv_up_projections), state

    def layer_of(cfg, layers):
        out, _, counts = deepseek.layer(
            cfg, layers, 2, True, h, attn, None, jnp.ones((1, 12), bool))
        return out[0], counts

    held = full.n_routed_experts // shards
    no_experts = {k: (jnp.zeros_like(v[:, :held]) if k.startswith("we_")
                      else v) for k, v in params["layers"].items()}
    # The attention, the residual and the shared experts: what every chip
    # computes alike.
    once, _ = layer_of(DeepseekV2Config.tiny(expert_shards=shards, **kw),
                       no_experts)
    total, local_picks, tokens_local = once, 0, 0
    for s in range(shards):
        cfg = DeepseekV2Config.tiny(expert_shards=shards, expert_shard=s,
                                    **kw)
        layers = {k: (v[:, s * held:(s + 1) * held] if k.startswith("we_")
                      else v) for k, v in params["layers"].items()}
        out, counts = layer_of(cfg, layers)
        total = total + (out - once)
        local_picks += int(counts[1])
        tokens_local += int(counts[6])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=0)
    _, uncut = layer_of(full, params["layers"])
    assert local_picks == int(uncut[1]) == 12 * 4   # every pick has a home
    assert 12 * 2 <= tokens_local <= 12 * 3         # 4 picks in <= 3 groups
    # Without the shared experts' term the sum falls short by their part.
    u = jax.random.normal(jax.random.PRNGKey(10), (12, full.hidden_size))
    shared, _, _ = deepseek.routed_ffn(full, params["layers"], 1, u,
                                       jnp.ones(12, bool))
    want_shared = reference.shared_experts(
        u, adapter.reference_weights(params)["layers"], 1)
    np.testing.assert_allclose(np.asarray(shared), np.asarray(want_shared),
                               atol=2e-5, rtol=0)
    assert np.abs(np.asarray(shared)).max() > 0.1


# --------------------------------------------------------------- the engine

@pytest.mark.parametrize("bad,says", [
    (dict(kv_block_size=16), "block pool"),
    (dict(speculative_model="tiny"), "speculative draft"),
    (dict(tensor_parallel_size=2), "tensor_parallel_size"),
])
def test_the_engine_refuses_what_deepseek_does_not_support(bad, says):
    cfg = DeepseekV2Config.tiny(max_seq_len=64)
    with pytest.raises(ValueError, match=says):
        LLMEngine(LLMConfig(model=cfg, max_num_seqs=2, max_seq_len=64, **bad))


def test_the_prefill_decode_handoff_is_refused_before_an_engine_is_built():
    from ray_tpu.llm.pd import DecodeServer, PrefillServer

    llm = LLMConfig(model=DeepseekV2Config.tiny(max_seq_len=64),
                    max_num_seqs=2, max_seq_len=64)
    for server in (PrefillServer, DecodeServer):
        with pytest.raises(ValueError, match="hand-off"):
            server(llm)


def test_the_engine_serves_deepseek_and_counts_its_routing():
    """Four requests through the one LLMEngine (two chunks, a tail bucket,
    bursts, prefix adoption between the first and the last): at every
    generated position the engine's token has the reference's top logit
    (the reference's full forward pass over prompt + answer), and stats()
    carries the router's counters, this model's own among them."""
    cfg = DeepseekV2Config.tiny(expert_shards=4, max_seq_len=128)
    eng = LLMEngine(LLMConfig(model=cfg, max_num_seqs=3, max_seq_len=128,
                              prefill_chunk=32))
    try:
        prompts = [list(range(260, 300)), list(range(300, 370)),
                   [261, 262, 263, 264, 265], list(range(260, 293))]
        budgets = [12, 9, 20, 3]
        reqs = [eng.submit(p, SamplingParams(max_tokens=m))
                for p, m in zip(prompts, budgets)]
        for r in reqs:
            assert r.done.wait(120) and r.error is None
        stats = eng.stats()
        params = eng.params
    finally:
        eng.shutdown()
    for p, r in zip(prompts, reqs):
        rows = ref_logits(cfg, params, p + r.out_tokens)[len(p) - 1:-1]
        chosen = rows[np.arange(len(r.out_tokens)), r.out_tokens]
        assert (rows.max(axis=1) - chosen).max() < LOGIT_ATOL
    assert stats["requests_failed"] == 0 and stats["device_failures"] == 0
    assert stats["moe_experts_held"] == 4
    assert stats["prefix_hits"] >= 1
    nm = cfg.num_routed_layers
    tokens = stats["prompt_tokens_prefilled"] + stats["decode_tokens"]
    assert stats["moe_picks"] >= tokens * cfg.num_experts_per_tok * nm
    assert stats["moe_picks_zero"] == 0
    assert 0 < stats["moe_tokens_local"] <= stats["moe_picks_local"] \
        < stats["moe_picks"]
    # Two of four groups kept: at most half the (token, layer) pairs.
    assert stats["moe_tokens_local"] * cfg.num_experts_per_tok \
        <= stats["moe_picks"] // 2 + 1
    # a routed layer is counted once a program's step, a riding chunk's
    # with the step that carried it
    assert stats["moe_layer_steps"] == nm * (
        stats["prefill_chunks"] - stats["prefill_chunks_riding"]
        + stats["decode_steps"])
    assert stats["kv_positions_read"] > 0
