"""The Granite 4.0-H family: ``models/granite.py`` and
``llm/granite_serving.py`` against the plain reference of the benchmark, at
a small size on the CPU.

What is held here is what the family adds to the repository: Mamba-2's state
(stored two heads to a lane row, handed from chunk to chunk through the
cache, kept through padded chunks, reset at a prompt's start, untouched in a
slot that does not decode), its convolution's bias, the gate before the
norm over all channels, an attention without positions at a scale that is
not ``head_dim^-1/2``, the four multipliers each in its place, the tied head,
and the shares of the routed experts adding up beside the shared SwiGLU.

Tolerances: float32 against float32 at ``highest``; what is left is the
order of the sums (observed 2e-6 on logits of about 1 to 9, the rule's
chunked form among them). 1e-4 would pass none of the parts moved or left
out below: each moves the logits by more than 1e-2.
"""

import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm import granite_serving as serving
from ray_tpu.llm.config import SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import granite, routed
from ray_tpu.models.granite import ATTENTION, MAMBA, GraniteConfig
from ray_tpu.ops import ssd
from ray_tpu.ops.kernels import force_kernel_backend
from ray_tpu.ops.rope import apply_rope, rope_frequencies

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import granite as reference  # noqa: E402
from rtbench.adapters import granite as adapter  # noqa: E402

CFG = GraniteConfig.tiny()
PROMPT = 77           # past one sub-chunk of the rule (64), not a multiple
SLOTS, MAX_SEQ = 3, 128
ATOL = 1e-4


@pytest.fixture(scope="module", autouse=True)
def release_the_compiled_programs():
    """After the module: this file's programs are its own (their
    configuration is a static argument), and a compiled program keeps its
    memory mappings as long as JAX's caches hold it: 17,000 of them after
    tests/test_granite.py alone, where a process may have 65,530
    (``vm.max_map_count``) and a worker of the suite runs some seventy
    files. Past the limit XLA's CPU compile dies of a segmentation fault
    under whichever test comes next (PERF.md section 7, PR 62)."""
    yield
    jax.clear_caches()


def config_json(cfg: GraniteConfig) -> dict:
    """The benchmark's configuration keys for ``cfg``:
    ``num_local_experts`` is the number held, as in the configuration
    file."""
    return {"hidden_size": cfg.hidden_size,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "rms_norm_eps": cfg.norm_eps,
            "mamba_n_heads": cfg.mamba_n_heads,
            "mamba_d_head": cfg.mamba_d_head,
            "mamba_d_state": cfg.mamba_d_state,
            "mamba_n_groups": cfg.mamba_n_groups,
            "mamba_d_conv": cfg.mamba_d_conv,
            "mamba_conv_bias": True, "mamba_proj_bias": False,
            "attention_bias": False, "tie_word_embeddings": True,
            "position_embedding_type": "nope", "rope_scaling": None,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "layer_types": list(cfg.layer_types),
            "num_local_experts": cfg.experts_held,
            "expert_shard": cfg.expert_shard}


@pytest.fixture(scope="module")
def params():
    return granite.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (PROMPT + 6,),
                                         259, CFG.vocab_size), np.int32)


def reference_logits(cfg, params, tokens, **moved):
    return np.asarray(reference.logits(
        {**config_json(cfg), **moved}, adapter.reference_weights(params),
        jnp.asarray(tokens)))


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's logits over the whole sequence, float32."""
    return reference_logits(CFG, params, tokens)


def forward(cfg, params, tokens):
    got, counts = jax.jit(granite.forward, static_argnums=0)(
        cfg, params, jnp.asarray(tokens)[None])
    return np.asarray(got[0]), counts


def test_the_tiny_config_has_every_mechanism_and_the_cut_its_count():
    assert CFG.period == 3 and CFG.periods == 2
    assert (CFG.linear_lines, CFG.attention_lines) == (4, 2)
    assert [CFG.kind(i) for i in range(3)] == [MAMBA, ATTENTION, MAMBA]
    assert [CFG.rank(i) for i in range(3)] == [0, 0, 1]
    # eight heads of 16 lie eight to a lane row: one group of [N, 128]
    assert CFG.state_shape == (1, 16, 128)
    assert CFG.attention_multiplier != CFG.head_dim ** -0.5
    # the published model and the benchmark's cut, by the program's count
    whole = GraniteConfig()
    assert whole.period == 10 and whole.periods == 4
    assert (whole.linear_lines, whole.attention_lines) == (36, 4)
    assert whole.state_shape == (64, 128, 128)
    assert whole.linear_state_bytes == 4 << 20
    assert whole.num_params() == 32_207_337_984
    cut = replace(whole, num_layers=10,
                  layer_types=whole.layer_types[:10], expert_shards=2,
                  vocab_size=50176)
    assert cut.period == 10 and cut.experts_held == 36
    assert cut.num_params() == 4_757_211_776
    rule = cut.router_rule
    assert (rule.experts, rule.topk, rule.held, rule.score, rule.use_bias,
            rule.renormalize, rule.renorm_eps) == (
                72, 10, 36, "softmax", False, True, 0.0)


@pytest.mark.parametrize("bad,match", [
    (dict(layer_types=(MAMBA,) * 5), "layer_types"),
    (dict(layer_types=(MAMBA, "window") * 3), "layer_types"),
    (dict(mamba_n_groups=2), "mamba_n_groups"),
    (dict(mamba_n_heads=6), "heads of"),
    (dict(expert_shards=3), "shards")])
def test_a_configuration_it_cannot_run_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        GraniteConfig.tiny(**bad)


def test_init_params_has_a_leaf_an_axis_list_and_the_decays_spread(params):
    axes = granite.param_logical_axes(CFG)
    assert set(axes["layers"]) == set(params["layers"])
    for name, leaf in params["layers"].items():
        assert len(axes["layers"][name]) == leaf.ndim, name
    assert "lm_head" not in params                 # the head is tied
    assert sum(a.size for a in jax.tree.leaves(params)) == CFG.num_params()
    lay = params["layers"]
    for name in ("router", "dt_bias", "a_log", "d_skip"):
        assert lay[name].dtype == jnp.float32, name
    decay = np.exp(-np.asarray(jax.nn.softplus(lay["dt_bias"]))
                   * np.exp(np.asarray(lay["a_log"])))
    # heads that remember a thousand tokens beside heads that forget in one
    assert decay.max() > 0.95 and decay.min() < 1e-2
    # h_0 a fiftieth of a branch's size after its multiplier (a tied head
    # reads a token's own embedding back: models/granite.init_params)
    h0 = np.asarray(params["embed_tokens"], np.float32) \
        * CFG.embedding_multiplier
    assert 0.9 * granite.EMBED_SIZE < h0.std() < 1.1 * granite.EMBED_SIZE


def test_forward_matches_the_reference(params, tokens, want):
    got, counts = forward(CFG, params, tokens)
    np.testing.assert_allclose(got, want, atol=ATOL)
    assert 0.5 < want.std() < 2.0
    # the seeded model does not hand its input token back: the tied head
    # reads it out of the stream, and ``h_0`` is seeded small for that
    assert (want.argmax(-1) == tokens).mean() < 0.2
    n = len(tokens)
    assert int(counts[0]) == n * CFG.num_experts_per_tok * CFG.num_layers
    assert int(counts[1]) == int(counts[0])        # every expert held


# ---- each part in its place -------------------------------------------------

MOVED = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
         "attention_multiplier": CFG.head_dim ** -0.5, "logits_scaling": 1.0}


@pytest.mark.parametrize("name", list(MOVED))
def test_each_multiplier_moved_from_its_place_fails_the_comparison(
        params, tokens, want, name):
    """The four Granite multipliers (12, 0.22, 1/128 and 16 published; 6,
    0.3, 1/8 and 4 here) each at the value a model without it would have:
    the program's logits leave the reference's, and the reference given the
    same value follows them (so the key is read where the program reads
    it)."""
    cfg = replace(CFG, **{name: MOVED[name]})
    got, _ = forward(cfg, params, tokens)
    assert np.abs(got - want).max() > 100 * ATOL
    np.testing.assert_allclose(
        got, reference_logits(cfg, params, tokens), atol=ATOL)


def test_a_rotary_applied_fails_the_comparison(params, tokens, want,
                                               monkeypatch):
    """``position_embedding_type`` is ``nope``: queries and keys rotated as
    every other attention here rotates them no longer give the reference's
    logits (the seeded ``W_q`` and ``W_k`` make a score of unit variance
    under the multiplier, so the attention is no mean and a position
    shows)."""
    plain = granite.attention_heads

    def rotated(cfg, ap, xn):
        q, k, v = plain(cfg, ap, xn)
        inv = rope_frequencies(cfg.head_dim, 10000.0)
        pos = jnp.arange(q.shape[2])
        return apply_rope(q, pos, inv), apply_rope(k, pos, inv), v

    monkeypatch.setattr(granite, "attention_heads", rotated)
    got, _ = jax.jit(granite.forward, static_argnums=0)(
        replace(CFG, max_seq_len=77), params, jnp.asarray(tokens)[None])
    assert np.abs(np.asarray(got[0]) - want).max() > 100 * ATOL


def _with(params, **leaves):
    return {**params, "layers": {**params["layers"], **leaves}}


NEUTRAL = {
    "the convolution's bias": lambda lay: {
        "conv_b": jnp.zeros_like(lay["conv_b"])},
    "the skip D": lambda lay: {"d_skip": jnp.zeros_like(lay["d_skip"])},
    "the step's bias": lambda lay: {
        "dt_bias": jnp.zeros_like(lay["dt_bias"])},
    "the rate A": lambda lay: {"a_log": jnp.zeros_like(lay["a_log"])},
    "the gated norm's weight": lambda lay: {
        "ssm_norm": jnp.ones_like(lay["ssm_norm"])},
    "the shared SwiGLU": lambda lay: {
        "ws_down": jnp.zeros_like(lay["ws_down"])},
    "the routed experts": lambda lay: {
        "we_down": jnp.zeros_like(lay["we_down"])},
}


@pytest.mark.parametrize("part", list(NEUTRAL))
def test_the_seeded_weights_make_every_part_visible(params, tokens, want,
                                                    part):
    """A part at its neutral value moves the logits past the tolerance: the
    seeded weights would show it missing or misplaced."""
    got, _ = forward(CFG, _with(params, **NEUTRAL[part](params["layers"])),
                     tokens)
    assert np.abs(got - want).max() > 10 * ATOL, part


def test_the_gate_comes_before_the_norm_over_all_channels(params):
    """``norm_before_gate`` false, one group: ``N(y silu(z))`` over
    ``d_inner`` at once, not a head and not the norm first."""
    lp = {k: params["layers"][k][0] for k in granite.MAMBA_LEAVES}
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, s = 1, 5
    y = jax.random.normal(ks[0], (b, s, CFG.mamba_n_heads, CFG.mamba_d_head))
    x = jnp.zeros_like(y)
    z = jax.random.normal(ks[1], (b, s, CFG.d_inner))
    got = granite.mamba_output(CFG, lp, y, x, z, jnp.float32)
    g = y.reshape(b, s, -1) * jax.nn.silu(z)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + CFG.norm_eps)
    want = (g * lp["ssm_norm"]) @ lp["out_proj"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_shares_add_up():
    """Two shares of the routed experts, each computed by a program that
    holds a half, plus the shared SwiGLU once, are the uncut layer's
    feed-forward: what an expert-parallel deployment sums."""
    shards = 2
    whole = GraniteConfig.tiny()
    p = granite.init_params(whole, jax.random.PRNGKey(4))
    lay = p["layers"]
    u = jax.random.normal(jax.random.PRNGKey(5), (40, whole.hidden_size))
    valid = jnp.ones((40,), bool)
    layer = 2
    total = granite.shared_expert(lay, layer, u).astype(jnp.float32)
    picks = 0
    for s in range(shards):
        cfg = GraniteConfig.tiny(expert_shard=s, expert_shards=shards)
        held = cfg.experts_held
        assert held == 4
        part = {**lay, **{k: lay[k][:, s * held:(s + 1) * held]
                          for k in ("we_gate", "we_up", "we_down")}}
        y, counts = routed.moe_block(cfg.router_rule, part, layer, u, valid)
        total = total + y
        picks += int(counts[1])
    assert picks == 40 * whole.num_experts_per_tok
    c = reference._static(config_json(whole))
    w = adapter.reference_weights(p)["layers"]
    want = reference._glu(u, w["s_gate"][layer], w["s_up"][layer],
                          w["s_down"][layer]) \
        + reference.routed_experts(c, u, w, layer)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)
    # and one share alone is that share of the reference
    cfg = GraniteConfig.tiny(expert_shard=1, expert_shards=shards)
    part = {**lay, **{k: lay[k][:, 4:8]
                      for k in ("we_gate", "we_up", "we_down")}}
    got, _ = forward(cfg, {**p, "layers": part}, np.arange(300, 340))
    np.testing.assert_allclose(
        got, reference_logits(cfg, {**p, "layers": part},
                              np.arange(300, 340)), atol=ATOL)


# ---- the cache: states, windows and the line ---------------------------------

def _prefill(params, tokens, cuts, slot=1, bucket=None, cache=None):
    """The prompt ``tokens`` through ``prefill_chunk`` in chunks that end at
    ``cuts``, the last padded to ``bucket`` where one is given (the engine
    pads a prompt's last chunk and no other). Returns (cache, the last
    chunk's logits, the counts summed)."""
    cache = cache if cache is not None else serving.init_cache(
        CFG, SLOTS, MAX_SEQ)
    start, total = 0, 0
    for end in cuts:
        size = bucket if bucket and end == cuts[-1] else end - start
        chunk = np.zeros(size, np.int32)
        chunk[:end - start] = tokens[start:end]
        cache, logits, counts = serving.prefill_chunk(
            CFG, params, cache, jnp.asarray(chunk), jnp.int32(start),
            jnp.int32(len(tokens)), jnp.int32(slot))
        start, total = end, total + np.asarray(counts)
    return cache, np.asarray(logits), total


CUTS = {"one pass": ([PROMPT], None),
        "chunks of 1 and 2": ([1, 3, 4, 40, 42, 43, PROMPT], None),
        "a padded last chunk": ([32, 64, PROMPT], 32),
        "a lone padded token, a chunk that ends inside a sub-chunk": (
            [50, 76, PROMPT], 16)}


@pytest.mark.parametrize("name", list(CUTS))
def test_prefill_in_chunks_cut_anywhere_gives_one_pass_s_logits_and_state(
        params, tokens, want, name):
    """The state a chunk leaves is the one after the prompt's last token,
    not after the chunk's last (padded) row; a chunk of 1 or 2 tokens is
    shorter than the convolution and reaches back into the window."""
    cuts, bucket = CUTS[name]
    prompt = tokens[:PROMPT]
    cache, logits, counts = _prefill(params, prompt, cuts, bucket=bucket)
    whole, _, _ = _prefill(params, prompt, [PROMPT])
    np.testing.assert_allclose(logits, want[PROMPT - 1], atol=ATOL)
    # (the sums are ordered by the cuts)
    for leaf in ("state", "conv"):
        np.testing.assert_allclose(np.asarray(cache[leaf]),
                                   np.asarray(whole[leaf]), atol=5e-5,
                                   err_msg=leaf)
        assert not np.asarray(cache[leaf][:, [0, 2]]).any()
    for leaf in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache[leaf][:, 1, :, :PROMPT]),
            np.asarray(whole[leaf][:, 1, :, :PROMPT]), atol=5e-5)
        assert not np.asarray(cache[leaf][:, [0, 2]]).any()
    named = dict(zip(serving.COUNTERS, counts))
    assert named["linear_chunk_tokens"] == PROMPT * CFG.linear_lines
    assert named["linear_state_updates"] == 0
    assert named["moe_picks"] == \
        PROMPT * CFG.num_experts_per_tok * CFG.num_layers


def test_the_cache_is_the_leaves_the_module_says(params):
    cache = serving.init_cache(CFG, SLOTS, MAX_SEQ)
    assert cache["state"].shape == (4, SLOTS, 1, 16, 128)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (4, SLOTS, 3 * CFG.conv_dim)
    assert cache["k"].shape == cache["v"].shape == (2, SLOTS, 2, MAX_SEQ, 16)


def test_a_chunk_at_the_start_of_a_prompt_starts_from_zeros(params, tokens,
                                                            want):
    """Whatever the slot held before: a longer request's state, window and
    rows."""
    junk = jax.tree.map(lambda a: jnp.full_like(a, 3.0),
                        serving.init_cache(CFG, SLOTS, MAX_SEQ))
    _, logits, _ = _prefill(params, tokens[:PROMPT], [32, PROMPT], bucket=64,
                            cache=junk)
    np.testing.assert_allclose(logits, want[PROMPT - 1], atol=ATOL)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_prefill_then_decode_agrees_with_the_reference_at_every_position(
        params, tokens, want, backend):
    """Through the states, the windows and the line, teacher-forced; the
    other slots of the decode batch are idle (``write_mask`` false) and keep
    what they hold bit for bit. ``interpret`` runs the attention kernels'
    own bodies."""
    with force_kernel_backend(backend):
        cache, logits, _ = _prefill(params, tokens[:PROMPT], [32, 64, PROMPT],
                                    bucket=16)
        np.testing.assert_allclose(logits, want[PROMPT - 1], atol=ATOL)
        # slot 2 holds another request's state, which no step may touch
        cache, _, _ = _prefill(params, tokens[:9], [9], slot=2, cache=cache)
        held = {k: np.asarray(cache[k][:, 2]) for k in cache}
        assert held["state"].any() and held["conv"].any()
        write = jnp.asarray([False, True, False])
        for p in range(PROMPT, len(tokens)):
            tok = jnp.zeros((SLOTS,), jnp.int32).at[1].set(int(tokens[p]))
            pos = jnp.zeros((SLOTS,), jnp.int32).at[1].set(p)
            cache, logits, counts = serving.decode_step(
                CFG, params, cache, tok, pos, write)
            np.testing.assert_allclose(np.asarray(logits[1]), want[p],
                                       atol=ATOL)
            named = dict(zip(serving.COUNTERS, (int(c) for c in counts)))
            # one live slot: a state a Mamba layer, topk picks a layer
            assert named["linear_state_updates"] == CFG.linear_lines
            assert named["linear_chunk_tokens"] == 0
            assert named["moe_picks"] == \
                CFG.num_experts_per_tok * CFG.num_layers
    for k in cache:
        np.testing.assert_array_equal(np.asarray(cache[k][:, 2]), held[k])
        assert not np.asarray(cache[k][:, 0]).any()


def test_a_burst_is_its_steps_and_keeps_idle_slots_state(params, tokens):
    cache, _, _ = _prefill(params, tokens[:PROMPT], [PROMPT])
    cache, _, _ = _prefill(params, tokens[:9], [9], slot=2, cache=cache)
    held = np.asarray(cache["state"][:, 2])
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
    named = dict(zip(serving.COUNTERS, (int(c) for c in counts)))
    assert named["moe_layer_steps"] == 4 * CFG.num_layers
    assert named["linear_state_updates"] == 4 * CFG.linear_lines
    for leaf in ("state", "conv"):
        np.testing.assert_allclose(np.asarray(burst[leaf]),
                                   np.asarray(cache[leaf]), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(burst["state"][:, 2]), held)


def test_a_state_kept_below_float32_does_not_pass(params, tokens, want):
    """The departure the configuration states (the state in float32, as the
    published kernels keep it) is held by the comparison: a state rounded to
    bfloat16 between a prompt's chunks moves the logits past the
    tolerance."""
    cache, _, _ = _prefill(params, tokens[:64], [64])
    assert cache["state"].dtype == jnp.float32
    cache["state"] = cache["state"].astype(jnp.bfloat16).astype(jnp.float32)
    chunk = jnp.asarray(tokens[64:PROMPT])
    _, logits, _ = serving.prefill_chunk(
        CFG, params, cache, chunk, jnp.int32(64), jnp.int32(PROMPT),
        jnp.int32(1))
    # Seeded as Mamba-2 seeds it (a step of 0.001 to 0.1) the state is a
    # small part of a layer's output beside the skip ``D x``: the rounding
    # moves the logits by 1e-4 where the sound chunks leave 2e-6 (both
    # observed), so the line between them is drawn at 2e-5 and 5e-5, tighter
    # than ATOL.
    _, kept, _ = _prefill(params, tokens[:PROMPT], [64, PROMPT])
    assert np.abs(kept - want[PROMPT - 1]).max() < 2e-5
    assert np.abs(np.asarray(logits) - want[PROMPT - 1]).max() > 5e-5


def test_the_stored_state_is_the_recurrence_s(params, tokens):
    """What a prefill leaves in the ``state`` leaf is the reference
    recurrence's ``[heads, N, P]`` of each Mamba layer, heads side by side
    in the lanes (ops/ssd.to_stored)."""
    cache, _, _ = _prefill(params, tokens[:PROMPT], [PROMPT])
    lp = {k: params["layers"][k][0] for k in granite.MAMBA_LEAVES}
    x = granite.embed(CFG, params, jnp.asarray(tokens[:PROMPT]))[None]
    xn = granite.rms_norm_reference(x, params["layers"]["input_norm"][0],
                                    CFG.norm_eps)
    xbc, _, dt = granite.mamba_inputs(CFG, lp, xn)
    prior = jnp.zeros((1, 3, CFG.conv_dim))
    xs, bs, cs = granite.mamba_heads(
        CFG, lp, jnp.concatenate([prior, xbc], axis=1), PROMPT)
    _, state = ssd.ssd_recurrence(
        xs[0], dt[0], -jnp.exp(lp["a_log"]), bs[0], cs[0],
        jnp.zeros(CFG.state_shape))
    np.testing.assert_allclose(np.asarray(cache["state"][0, 1]),
                               np.asarray(state), atol=1e-5)


# ---- through the scheduler ---------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    eng = LLMEngine(LLMConfig(model=GraniteConfig.tiny(max_seq_len=MAX_SEQ),
                              max_num_seqs=SLOTS, max_seq_len=MAX_SEQ,
                              prefill_chunk=32, decode_burst=4,
                              dtype="float32", seed=0))
    yield eng
    eng.shutdown()


def test_the_engine_serves_it_and_its_tokens_are_the_reference_s(engine):
    """Greedy requests through ``LLMEngine``: prompts of several chunks (a
    padded last one), bursts beside a slot mid-prefill, a reused slot. Every
    token has the reference's top logit to within the tolerance, whatever
    else was in the batch."""
    cfg = engine.config.model
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(259, cfg.vocab_size, n)))
               for n in (77, 45, 9, 70, 33)]
    reqs = [engine.submit(p, SamplingParams(max_tokens=6)) for p in prompts]
    for r in reqs:
        assert r.done.wait(120) and r.error is None, r.error
    for prompt, out in zip(prompts, (list(r.out_tokens) for r in reqs)):
        assert len(out) == 6
        rows = reference_logits(cfg, engine.params, prompt + out)
        rows = rows[len(prompt) - 1:len(prompt) + 5]
        chosen = rows[np.arange(6), out]
        assert (rows.max(-1) - chosen).max() <= ATOL
    stats = engine.stats()
    assert stats["linear_lines"] == cfg.linear_lines == 4
    assert stats["attention_lines"] == 2
    assert stats["moe_experts_held"] == 8
    assert stats["linear_state_bytes"] == 8 * 16 * 16 * 4
    assert stats["linear_chunk_tokens"] == sum(map(len, prompts)) * 4
    # a token a request comes from prefill, the others from decode steps
    assert stats["linear_state_updates"] == 5 * 5 * 4
    assert stats["moe_picks"] == (sum(map(len, prompts)) + 25) * 3 * 6
    assert stats["prefix_hits"] == 0


def test_a_common_prefix_is_not_adopted(engine):
    """The state at an earlier length is nowhere: two prompts with a long
    common prefix are both prefilled whole."""
    before = engine.stats()
    base = list(range(300, 364))
    for tail in ([7, 8, 9], [10, 11]):
        engine.generate(base + tail, SamplingParams(max_tokens=2))
    after = engine.stats()
    assert after["prefix_hits"] == before["prefix_hits"] == 0
    assert after["linear_chunk_tokens"] - before["linear_chunk_tokens"] == \
        (67 + 66) * 4


@pytest.mark.parametrize("bad,match", [
    ({"tensor_parallel_size": 2}, "tensor_parallel_size"),
    ({"kv_block_size": 16}, "kv_block_size"),
    ({"speculative_model": GraniteConfig.tiny()}, "speculative draft")])
def test_what_it_does_not_run_is_refused_at_construction(bad, match):
    with pytest.raises(ValueError, match=match):
        LLMEngine(LLMConfig(model=GraniteConfig.tiny(), max_num_seqs=2,
                            max_seq_len=64, dtype="float32", **bad))
