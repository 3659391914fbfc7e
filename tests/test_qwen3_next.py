"""The Qwen3-Next family: ``models/qwen3_next.py`` and
``llm/qwen3_next_serving.py`` against the plain reference of the benchmark,
at a small size on the CPU.

What is held here is what the family adds to the repository: a state that a
scan over the sequence makes (handed from chunk to chunk through the cache,
kept through padded chunks, reset at a prompt's start, untouched in a slot
that does not decode), the attention's gate and its rotary over part of a
head, norms whose weight is ``1 + w``, the L2 norm's ``1e-6``, the shared
expert's gate, and the shares of the routed experts adding up.

Tolerances: float32 against float32 at ``highest``; what is left is the
order of the sums (observed 1e-5 on logits of about 4, the rule's chunked
form among them). 1e-4 would pass none of the parts left out below: each
moves the logits by more than 1e-2.
"""

import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm import qwen3_next_serving as serving
from ray_tpu.llm.config import SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import qwen3_next, routed
from ray_tpu.models.qwen3_next import ATTENTION, LINEAR, Qwen3NextConfig
from ray_tpu.ops.kernels import force_kernel_backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import qwen3_next as reference  # noqa: E402
from rtbench.adapters import qwen3_next as adapter  # noqa: E402

CFG = Qwen3NextConfig.tiny()
PROMPT = 77           # past one sub-chunk of the rule (64), not a multiple
SLOTS, MAX_SEQ = 3, 128
ATOL = 1e-4


def config_json(cfg: Qwen3NextConfig) -> dict:
    """The benchmark's configuration keys for ``cfg``: ``num_experts`` is
    the number held, as in the configuration file."""
    return {"hidden_size": cfg.hidden_size,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim,
            "partial_rotary_factor": cfg.partial_rotary_factor,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "linear_num_key_heads": cfg.linear_num_key_heads,
            "linear_num_value_heads": cfg.linear_num_value_heads,
            "linear_key_head_dim": cfg.linear_key_head_dim,
            "linear_value_head_dim": cfg.linear_value_head_dim,
            "linear_conv_kernel_dim": cfg.linear_conv_kernel_dim,
            "full_attention_interval": cfg.full_attention_interval,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "num_experts": cfg.experts_held,
            "expert_shard": cfg.expert_shard}


@pytest.fixture(scope="module")
def params():
    return qwen3_next.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (PROMPT + 6,),
                                         259, CFG.vocab_size), np.int32)


def reference_logits(cfg, params, tokens):
    return np.asarray(reference.logits(
        config_json(cfg), adapter.reference_weights(params),
        jnp.asarray(tokens)))


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's logits over the whole sequence, float32."""
    return reference_logits(CFG, params, tokens)


def forward(cfg, params, tokens):
    got, counts = jax.jit(qwen3_next.forward, static_argnums=0)(
        cfg, params, jnp.asarray(tokens)[None])
    return np.asarray(got[0]), counts


def test_the_tiny_config_has_every_mechanism_and_the_cut_its_count():
    assert [CFG.kind(l) for l in range(CFG.num_layers)] == \
        [LINEAR, ATTENTION, LINEAR, ATTENTION]
    assert (CFG.periods, CFG.linear_lines, CFG.attention_lines) == (2, 2, 2)
    assert CFG.linear_num_value_heads == 2 * CFG.linear_num_key_heads
    assert CFG.rotary_dim == 4 < CFG.head_dim
    full = Qwen3NextConfig()
    assert [full.kind(l) for l in range(8)] == \
        [LINEAR] * 3 + [ATTENTION] + [LINEAR] * 3 + [ATTENTION]
    assert (full.linear_lines, full.attention_lines, full.rotary_dim,
            full.conv_dim, full.linear_state_bytes) == \
        (36, 12, 64, 8192, 2 * 2 ** 20)
    rule = full.router_rule
    assert (rule.outputs, rule.topk, rule.score, rule.use_bias,
            rule.renormalize, rule.renorm_eps, rule.held) == \
        (512, 10, "softmax", False, True, 0.0, 512)
    # the benchmark's cut: 16 layers, share 0 of 8, an eighth of the
    # vocabulary, as ISSUE 48 and the adapter count it
    cut = replace(full, num_layers=16, expert_shards=8, vocab_size=18992)
    assert cut.experts_held == 64
    assert cut.num_params() == 3_879_901_440
    with pytest.raises(ValueError, match="whole periods"):
        replace(full, num_layers=18)
    with pytest.raises(ValueError, match="shards"):
        replace(full, expert_shards=7)


def test_init_params_has_a_leaf_an_axis_list_and_no_norm_at_its_default(
        params):
    axes = qwen3_next.param_logical_axes(CFG)
    shapes = jax.tree.map(lambda a: a.ndim, params)
    assert jax.tree.map(len, axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == shapes
    assert sum(a.size for a in jax.tree.leaves(params)) == CFG.num_params()
    lay = params["layers"]
    assert lay["router"].dtype == jnp.float32
    for name in ("input_norm", "post_norm", "q_norm", "k_norm"):
        w = np.asarray(lay[name])
        assert 0.05 < w.std() < 0.2 and abs(w.mean()) < 0.05, name
    assert abs(np.asarray(lay["gdn_norm"]).mean() - 1.0) < 0.05
    # the decay a head is centred on spreads over exp(-0.7) to exp(-1e-3)
    rate = np.exp(np.asarray(lay["a_log"])) * np.log1p(
        np.exp(np.asarray(lay["dt_bias"])))
    assert 1e-3 <= rate.min() and rate.max() <= 0.7
    assert rate.max() / rate.min() > 5


def test_forward_matches_the_reference(params, tokens, want):
    got, counts = forward(CFG, params, tokens)
    np.testing.assert_allclose(got, want, atol=ATOL)
    n = len(tokens) * CFG.num_experts_per_tok * CFG.num_layers
    assert [int(c) for c in counts[:3]] == [n, n, 0]
    assert int(counts[4]) == CFG.num_layers


def _with(params, **leaves):
    return {**params, "layers": {**params["layers"], **leaves}}


NEUTRAL = {
    # the attention's gate: a zero gate half is sigmoid 0.5 on every head
    "attention_gate": lambda lay: {"wq": lay["wq"].reshape(
        *lay["wq"].shape[:2], CFG.num_heads, 2, CFG.head_dim
    ).at[..., 1, :].set(0.0).reshape(lay["wq"].shape)},
    # the norms' ``1 + w``: with w read as the weight itself the stream
    # would be scaled by about 0.1; with w dropped, by 1
    "input_norm": lambda lay: {"input_norm": 0 * lay["input_norm"]},
    "head_norms": lambda lay: {"q_norm": 0 * lay["q_norm"],
                               "k_norm": 0 * lay["k_norm"]},
    "rule_norm": lambda lay: {"gdn_norm": 0 * lay["gdn_norm"] + 1.0},
    # the shared expert's gate: a zero vector is sigmoid 0.5 on every token
    "shared_gate": lambda lay: {"shared_gate": 0 * lay["shared_gate"]},
    "shared_expert": lambda lay: {"ws_down": 0 * lay["ws_down"]},
    "first_tap": lambda lay: {"conv_w": lay["conv_w"].at[:, 0].set(0.0)},
    "last_tap": lambda lay: {"conv_w": lay["conv_w"].at[:, -1].set(0.0)},
    "decay": lambda lay: {"a_log": lay["a_log"] - 20.0},
    "step": lambda lay: {"in_ba": lay["in_ba"].at[
        ..., :CFG.linear_num_value_heads].set(0.0)},
}


@pytest.mark.parametrize("part", list(NEUTRAL))
def test_the_seeded_weights_make_every_new_part_visible(params, tokens, want,
                                                        part):
    """A program that dropped the gate of the attention or of the shared
    expert, read a norm's ``1 + w`` as ``1``, lost a tap, the decay or the
    step does not pass for right: with that leaf neutral the logits move by
    far more than the parity tolerance."""
    got, _ = forward(CFG, _with(params, **NEUTRAL[part](params["layers"])),
                     tokens)
    assert np.abs(got - want).max() > 1e-2


def test_the_rotary_turns_a_quarter_of_a_head_and_leaves_the_rest(params):
    """``partial_rotary_factor`` 0.25: the first 4 of 16 values of a head
    depend on the position, the other 12 do not; a rotary over the whole
    head gives other logits."""
    ap = {k: params["layers"][k][0] for k in qwen3_next.ATTENTION_LEAVES}
    xn = jax.random.normal(jax.random.PRNGKey(2), (1, 5, CFG.hidden_size))
    inv_freq = qwen3_next.rope_frequencies(CFG.rotary_dim, CFG.rope_theta)
    at = lambda p: qwen3_next.attention_heads(  # noqa: E731
        CFG, ap, xn, jnp.arange(5) + p, inv_freq)
    (q0, k0, _, _), (q9, k9, _, _) = at(0), at(9)
    r = CFG.rotary_dim
    for a, b in ((q0, q9), (k0, k9)):
        np.testing.assert_array_equal(np.asarray(a[..., r:]),
                                      np.asarray(b[..., r:]))
        assert np.abs(np.asarray(a[..., :r] - b[..., :r])).max() > 0.1
    # ``rotate_half`` over the rotated part alone: value i pairs with
    # i + r/2, at theta^(-2i/r)
    q = np.asarray(qwen3_next.rms_norm_reference(
        (xn @ ap["wq"]).reshape(1, 5, CFG.num_heads, 2, CFG.head_dim)[
            ..., 0, :], qwen3_next.unit_offset(ap["q_norm"]), CFG.norm_eps))
    ang = 9.0 + np.arange(5)[:, None] * 1.0
    ang = ang * (CFG.rope_theta ** (-np.arange(0, r, 2) / r))[None, :]
    a, b = q[0, :, 1, :r // 2], q[0, :, 1, r // 2:r]
    np.testing.assert_allclose(
        np.asarray(q9[0, 1, :, :r]),
        np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                        b * np.cos(ang) + a * np.sin(ang)], -1), atol=1e-5)


def test_the_l2_norm_adds_1e_6_under_the_root(params):
    """``x rsqrt(sum x^2 + 1e-6)``: on a head whose values are about 1e-3
    the sum is about 1e-5 and the 1e-6 shortens the unit vector by 5%; on
    zeros it gives zeros where a plain division gives NaN."""
    lp = {k: params["layers"][k][0] for k in qwen3_next.LINEAR_LEAVES}
    lp["conv_w"] = jnp.zeros_like(lp["conv_w"]).at[-1].set(1.0)
    small = 1e-3 * jax.random.normal(jax.random.PRNGKey(3),
                                     (1, 3 + 6, CFG.conv_dim))
    small = small.at[:, -1].set(0.0)
    q, k, _ = qwen3_next.linear_heads(CFG, lp, small, 6)
    x = np.asarray(jax.nn.silu(small[0, 3:, CFG.key_dim:2 * CFG.key_dim])
                   ).reshape(6, CFG.linear_num_key_heads, -1)
    want_k = x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    got_k = np.asarray(k[0, :, ::2])        # a key head's first value head
    np.testing.assert_allclose(got_k, want_k, rtol=1e-5, atol=1e-9)
    norms = np.linalg.norm(got_k[:-1], axis=-1)
    assert (norms < 0.99).all() and (norms > 0.5).all()
    assert not np.asarray(k[0, -1]).any() and np.isfinite(np.asarray(q)).all()
    # a value head takes its key head's q and k (``repeat_interleave``)
    np.testing.assert_array_equal(np.asarray(k[0, :, 0::2]),
                                  np.asarray(k[0, :, 1::2]))


def test_the_shares_add_up():
    """Four shares of the routed experts, each computed by a program that
    holds a quarter, plus the shared expert once, are the uncut layer's
    feed-forward: what an expert-parallel deployment sums."""
    shards = 4
    whole = Qwen3NextConfig.tiny()
    p = qwen3_next.init_params(whole, jax.random.PRNGKey(4))
    lay = p["layers"]
    u = jax.random.normal(jax.random.PRNGKey(5), (40, whole.hidden_size))
    valid = jnp.ones((40,), bool)
    layer = 2
    total = qwen3_next.shared_expert(lay, layer, u).astype(jnp.float32)
    picks = 0
    for s in range(shards):
        cfg = Qwen3NextConfig.tiny(expert_shard=s, expert_shards=shards)
        held = cfg.experts_held
        assert held == 2
        part = {**lay, **{k: lay[k][:, s * held:(s + 1) * held]
                          for k in ("we_gate", "we_up", "we_down")}}
        y, counts = routed.moe_block(cfg.router_rule, part, layer, u, valid)
        total = total + y
        picks += int(counts[1])
    assert picks == 40 * whole.num_experts_per_tok
    c = reference._static(config_json(whole))
    w = adapter.reference_weights(p)["layers"]
    want = reference.shared_expert(u, w, layer) \
        + reference.routed_experts(c, u, w, layer)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)
    # and one share alone is that share of the reference
    cfg = Qwen3NextConfig.tiny(expert_shard=1, expert_shards=shards)
    part = {**lay, **{k: lay[k][:, 2:4]
                      for k in ("we_gate", "we_up", "we_down")}}
    got, _ = forward(cfg, {**p, "layers": part}, np.arange(300, 340))
    np.testing.assert_allclose(
        got, reference_logits(cfg, {**p, "layers": part},
                              np.arange(300, 340)), atol=ATOL)


# ---- the cache: lines, states and windows ------------------------------------

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
        "a chunk that ends inside a sub-chunk": ([50, 70, PROMPT], 16),
        "a lone padded token": ([64, 76, PROMPT], 16)}


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
    # (a state's entries reach 3 and its sums are ordered by the cuts:
    # 1.5e-5 observed between chunks of one token and one pass)
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
    """Through the lines, the states and the windows, teacher-forced; the
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
            # one live slot: a state a linear layer, topk picks a layer
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
    assert np.abs(np.asarray(logits) - want[PROMPT - 1]).max() > 10 * ATOL


# ---- through the scheduler ---------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    eng = LLMEngine(LLMConfig(model=Qwen3NextConfig.tiny(max_seq_len=MAX_SEQ),
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
    assert stats["linear_lines"] == cfg.linear_lines == 2
    assert stats["attention_lines"] == 2
    assert stats["moe_experts_held"] == 8
    assert stats["linear_state_bytes"] == 4 * 16 * 8 * 4
    assert stats["linear_chunk_tokens"] == sum(map(len, prompts)) * 2
    # a token a request comes from prefill, the others from decode steps
    assert stats["linear_state_updates"] == 5 * 5 * 2
    assert stats["moe_picks"] == (sum(map(len, prompts)) + 25) * 2 * 4
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
        (67 + 66) * 2


@pytest.mark.parametrize("bad,match", [
    ({"tensor_parallel_size": 2}, "tensor_parallel_size"),
    ({"speculative_model": Qwen3NextConfig.tiny()}, "speculative draft")])
def test_what_it_does_not_run_is_refused_at_construction(bad, match):
    with pytest.raises(ValueError, match=match):
        LLMEngine(LLMConfig(model=Qwen3NextConfig.tiny(), max_num_seqs=2,
                            max_seq_len=64, dtype="float32", **bad))
