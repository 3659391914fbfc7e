"""The LFM2-MoE family: ``models/lfm2.py`` and ``llm/lfm2_serving.py``
against the plain reference of the benchmark, at a small size on the CPU.

What is held here is what the family adds to the repository: a state of
fixed size in the slot cache beside attention lines (kept through padded
chunks, reset at a prompt's start, untouched in a slot that does not
decode), heads of half a lane row with their own norms, and the router's
rule (sigmoid, a bias for the choice alone, the renormalisation's 1e-6, the
factor).
"""

import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm import lfm2_serving as serving
from ray_tpu.llm.config import SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.served import served_model
from ray_tpu.models import lfm2, routed
from ray_tpu.models.lfm2 import ATTENTION, CONV, Lfm2Config, Segment
from ray_tpu.ops.kernels import force_kernel_backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import lfm2 as reference  # noqa: E402
from rtbench.adapters import lfm2 as adapter  # noqa: E402


CFG = Lfm2Config.tiny()
PROMPT = 45
SLOTS, MAX_SEQ = 3, 64


def config_json(cfg: Lfm2Config) -> dict:
    """The benchmark's configuration keys for ``cfg``."""
    return {"hidden_size": cfg.hidden_size,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "norm_eps": cfg.norm_eps, "conv_L_cache": cfg.conv_L_cache,
            "conv_bias": False, "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "use_expert_bias": cfg.use_expert_bias,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "rope_parameters": {"rope_theta": cfg.rope_theta},
            "layer_types": list(cfg.layer_types),
            "num_dense_layers": cfg.num_dense_layers}


@pytest.fixture(scope="module")
def params():
    return lfm2.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (PROMPT + 6,),
                                         259, CFG.vocab_size), np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's logits over the whole sequence, float32."""
    return np.asarray(reference.logits(
        config_json(CFG), adapter.reference_weights(params),
        jnp.asarray(tokens)))


def test_the_tiny_config_has_every_mechanism_and_the_published_one_its_segments():
    assert set(CFG.layer_types) == {CONV, ATTENTION}
    assert CFG.num_dense_layers == 1 and CFG.num_routed_layers == 4
    assert (CFG.conv_lines, CFG.attention_lines) == (3, 2)
    # one dense layer alone, then the period (attention, conv) twice
    assert CFG.segments == (Segment(0, 1, 1), Segment(1, 2, 2))
    full = Lfm2Config()
    assert (full.num_layers, full.conv_lines, full.attention_lines) == \
        (40, 30, 10)
    assert full.segments == (Segment(0, 1, 2), Segment(2, 4, 9),
                             Segment(38, 1, 1), Segment(39, 1, 1))
    cut = replace(full, layer_types=full.layer_types[:10])
    assert cut.segments == (Segment(0, 1, 2), Segment(2, 4, 2))
    assert [cut.rank(l) for l in (0, 1, 2, 3, 6, 9)] == \
        [(0, 0), (1, 1), (0, 0), (2, 1), (1, 4), (7, 7)]
    # 24B parameters whole, 5.27B in the cut, as the adapter counts them
    assert round(full.num_params() / 1e9, 1) == 23.8
    assert cut.num_params() == 5_267_090_176


def test_forward_matches_the_reference(params, tokens, want):
    """float32 against float32 at ``highest``: what is left is the order of
    the sums (observed 4e-7 on logits of 0.7); 1e-4 would not pass a bias
    left out (test below) nor a norm of a head (its weights are 1 +- 10%)."""
    got, counts = jax.jit(lfm2.forward, static_argnums=0)(
        CFG, params, jnp.asarray(tokens)[None])
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=1e-4)
    n = len(tokens)
    assert [int(c) for c in counts] == [
        n * CFG.num_experts_per_tok * CFG.num_routed_layers,
        n * CFG.num_experts_per_tok * CFG.num_routed_layers, 0,
        int(counts[3]), CFG.num_routed_layers, int(counts[5])]
    assert int(counts[5]) >= int(counts[3]) > 0    # a tile a touched expert


@pytest.mark.parametrize("leaf,index", [
    ("router_bias", None), ("q_norm", None), ("k_norm", None),
    ("conv_w", (slice(None), 0)), ("conv_w", (slice(None), 2))],
    ids=["expert_bias", "q_norm", "k_norm", "first_tap", "last_tap"])
def test_the_seeded_weights_make_every_new_part_visible(params, tokens, want,
                                                        leaf, index):
    """A program that dropped the selection bias, a head norm's weights or
    a tap of the convolution does not pass for right: with that leaf
    neutral the logits move by far more than the parity tolerance."""
    neutral = {"router_bias": 0.0, "q_norm": 1.0, "k_norm": 1.0,
               "conv_w": 0.0}[leaf]
    value = params["layers"][leaf]
    changed = (jnp.full_like(value, neutral) if index is None
               else value.at[index].set(neutral))
    got, _ = jax.jit(lfm2.forward, static_argnums=0)(
        CFG, {**params, "layers": {**params["layers"], leaf: changed}},
        jnp.asarray(tokens)[None])
    assert np.abs(np.asarray(got[0]) - want).max() > 1e-2


def _prefill(params, tokens, cuts, slot=1, bucket=None, cache=None):
    """The prompt ``tokens`` through ``prefill_chunk`` in chunks that end at
    ``cuts``, the last padded to ``bucket`` where one is given (the engine
    pads a prompt's last chunk and no other). Returns (cache, the last
    chunk's logits)."""
    cache = cache if cache is not None else serving.init_cache(
        CFG, SLOTS, MAX_SEQ)
    start = 0
    for end in cuts:
        size = bucket if bucket and end == cuts[-1] else end - start
        chunk = np.zeros(size, np.int32)
        chunk[:end - start] = tokens[start:end]
        cache, logits, _ = serving.prefill_chunk(
            CFG, params, cache, jnp.asarray(chunk), jnp.int32(start),
            jnp.int32(len(tokens)), jnp.int32(slot))
        start = end
    return cache, np.asarray(logits)


CUTS = {"one pass": ([PROMPT], None),
        "chunks of 1 and 2": ([1, 3, 4, 20, 22, 23, PROMPT], None),
        "a padded last chunk": ([16, 32, PROMPT], 16),
        "a padded chunk of two": ([7, 19, 43, PROMPT], 16),
        "a lone padded token": ([32, 44, PROMPT], 16)}


@pytest.mark.parametrize("name", list(CUTS))
def test_prefill_in_chunks_cut_anywhere_gives_one_pass_s_logits_and_state(
        params, tokens, want, name):
    """The state a chunk leaves is the one after the prompt's last token,
    not after the chunk's last (padded) row; a chunk of 1 or 2 tokens is
    shorter than the convolution and reaches back into the state."""
    cuts, bucket = CUTS[name]
    prompt = tokens[:PROMPT]
    cache, logits = _prefill(params, prompt, cuts, bucket=bucket)
    whole, _ = _prefill(params, prompt, [PROMPT])
    np.testing.assert_allclose(logits, want[PROMPT - 1], atol=1e-4)
    np.testing.assert_allclose(np.asarray(cache["conv"]),
                               np.asarray(whole["conv"]), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(cache["kv"][:, 1, :, :PROMPT]),
        np.asarray(whole["kv"][:, 1, :, :PROMPT]), atol=1e-5)
    # the other slots' state and lines were left alone
    assert not np.asarray(cache["conv"][:, [0, 2]]).any()
    assert not np.asarray(cache["kv"][:, [0, 2]]).any()


def test_a_chunk_at_the_start_of_a_prompt_starts_from_zeros(params, tokens,
                                                            want):
    """Whatever the slot held before: a longer request's state and rows."""
    junk = jax.tree.map(lambda a: jnp.full_like(a, 3.0),
                        serving.init_cache(CFG, SLOTS, MAX_SEQ))
    _, logits = _prefill(params, tokens[:PROMPT], [16, PROMPT], bucket=32,
                         cache=junk)
    np.testing.assert_allclose(logits, want[PROMPT - 1], atol=1e-4)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_prefill_then_decode_agrees_with_the_reference_at_every_position(
        params, tokens, want, backend):
    """Through the cache and the state, teacher-forced; other slots in the
    decode batch are idle (``write_mask`` false) and keep what they hold.
    ``interpret`` runs the kernels' own bodies on the packed stack."""
    with force_kernel_backend(backend):
        cache, logits = _prefill(params, tokens[:PROMPT], [16, 32, PROMPT],
                                 bucket=16)
        np.testing.assert_allclose(logits, want[PROMPT - 1], atol=1e-4)
        # slot 2 holds another request's state, which no step may touch
        cache, _ = _prefill(params, tokens[:9], [9], slot=2, cache=cache)
        held = np.asarray(cache["conv"][:, 2]), np.asarray(cache["kv"][:, 2])
        write = jnp.asarray([False, True, False])
        for p in range(PROMPT, len(tokens)):
            tok = jnp.zeros((SLOTS,), jnp.int32).at[1].set(int(tokens[p]))
            pos = jnp.zeros((SLOTS,), jnp.int32).at[1].set(p)
            cache, logits, counts = serving.decode_step(
                CFG, params, cache, tok, pos, write)
            np.testing.assert_allclose(np.asarray(logits[1]), want[p],
                                       atol=1e-4)
            # one live slot, one token: topk picks a routed layer
            assert int(counts[0]) == \
                CFG.num_experts_per_tok * CFG.num_routed_layers
    np.testing.assert_array_equal(np.asarray(cache["conv"][:, 2]), held[0])
    np.testing.assert_array_equal(np.asarray(cache["kv"][:, 2]), held[1])
    assert not np.asarray(cache["conv"][:, 0]).any()


def test_a_burst_is_its_steps_and_keeps_idle_slots_state(params, tokens):
    cache, _ = _prefill(params, tokens[:PROMPT], [PROMPT])
    cache, _ = _prefill(params, tokens[:9], [9], slot=2, cache=cache)
    held = np.asarray(cache["conv"][:, 2])
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
    assert int(counts[4]) == 4 * CFG.num_routed_layers
    np.testing.assert_allclose(np.asarray(burst["conv"]),
                               np.asarray(cache["conv"]), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(burst["conv"][:, 2]), held)


# ---- the router's rule ------------------------------------------------------

def _hand_route(gate, bias, u, topk, renormalize=True, factor=1.0):
    """The rule as the configuration file states it, in numpy float64."""
    s = 1.0 / (1.0 + np.exp(-(u.astype(np.float64) @ gate.astype(np.float64))))
    idx = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :topk]
    w = np.take_along_axis(s, idx, axis=-1)
    if renormalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return idx, w * factor


@pytest.mark.parametrize("renormalize,factor", [(True, 1.0), (True, 2.5),
                                                (False, 1.0)])
def test_the_router_is_the_hand_written_rule(renormalize, factor):
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    gate = np.asarray(jax.random.normal(keys[0], (32, 16))) / np.sqrt(32)
    bias = np.asarray(jax.random.normal(keys[1], (16,))) * 0.3
    u = np.asarray(jax.random.normal(keys[2], (64, 32)))
    rule = routed.RouterRule(experts=16, topk=4, score="sigmoid",
                             use_bias=True, renormalize=renormalize,
                             scaling_factor=factor)
    idx, w = routed.route(rule, jnp.asarray(gate), jnp.asarray(bias),
                          jnp.asarray(u))
    want_idx, want_w = _hand_route(gate, bias, u, 4, renormalize, factor)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    # The bias moves the choice and not the weights: the chosen's weights
    # are their own scores', whatever the bias added.
    plain, _ = _hand_route(gate, 0 * bias, u, 4, renormalize, factor)
    assert (np.sort(want_idx, -1) != np.sort(plain, -1)).any()
    s = 1.0 / (1.0 + np.exp(-(u.astype(np.float64) @ gate)))
    picked = np.take_along_axis(s, np.asarray(idx), axis=-1)
    if renormalize:
        # the 1e-6: the weights sum to a little under the factor
        total = np.asarray(w, np.float64).sum(-1) / factor
        np.testing.assert_allclose(
            total, picked.sum(-1) / (picked.sum(-1) + 1e-6), rtol=1e-6)
        assert (total < 1.0).all()
    else:
        np.testing.assert_allclose(np.asarray(w), picked * factor, rtol=1e-5)


def test_the_seeded_bias_changes_the_choice_for_the_share_the_file_states():
    """At the published router widths (2,048 -> 64, four a token) on
    unit-variance input, gate and bias as ``init_params`` draws them:
    ``assumed.init`` of benchmark/configs/lfm2-24b-a2b.json says about a
    quarter of tokens, this holds it between 0.15 and 0.40."""
    rule = Lfm2Config().router_rule
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    gate = jax.random.normal(keys[0], (2048, 64)) / np.sqrt(2048)
    bias = jax.random.normal(keys[1], (64,)) * lfm2.EXPERT_BIAS_SCALE
    u = jax.random.normal(keys[2], (4096, 2048))
    with_bias, _ = routed.route(rule, gate, bias, u)
    without, _ = routed.route(rule, gate, jnp.zeros_like(bias), u)
    share = (np.sort(np.asarray(with_bias), -1)
             != np.sort(np.asarray(without), -1)).any(-1).mean()
    assert 0.15 < share < 0.40, share


def test_init_params_draws_the_bias_at_its_scale_and_no_norm_at_one():
    params = lfm2.init_params(CFG, jax.random.PRNGKey(5))
    lay = params["layers"]
    bias = np.asarray(lay["router_bias"])
    assert bias.dtype == np.float32 and lay["router"].dtype == jnp.float32
    assert 0.5 < bias.std() / lfm2.EXPERT_BIAS_SCALE < 1.5
    for leaf in ("q_norm", "k_norm", "operator_norm", "ffn_norm"):
        assert np.abs(np.asarray(lay[leaf]) - 1.0).max() > 0.05, leaf
    assert lay["conv_w"].shape == (CFG.conv_lines, CFG.conv_L_cache,
                                   CFG.hidden_size)
    assert "lm_head" not in params      # tied
    axes = lfm2.param_logical_axes(CFG)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda a: 0, axes, is_leaf=lambda x: isinstance(x, tuple)))


# ---- through the engine -----------------------------------------------------

def _prompts():
    rng = np.random.default_rng(7)
    make = lambda n: [int(t) for t in rng.integers(259, CFG.vocab_size, n)]  # noqa: E731
    shared = make(60)
    return {"long": make(90), "short": make(7), "mid": make(37),
            "shared_a": shared + make(5), "shared_b": shared + make(9)}


def _alone(params, prompt, n):
    """The request alone: greedy tokens by ``forward`` over the growing
    sequence, and the logits that chose them."""
    fwd = jax.jit(lfm2.forward, static_argnums=0)
    seq, rows = list(prompt), []
    for _ in range(n):
        logits, _ = fwd(CFG, params, jnp.asarray(seq)[None])
        rows.append(np.asarray(logits[0, -1]))
        seq.append(int(rows[-1].argmax()))
    return seq[len(prompt):], np.stack(rows)


@pytest.fixture(scope="module")
def engine():
    eng = LLMEngine(LLMConfig(model=CFG, max_num_seqs=2, max_seq_len=128,
                              prefill_chunk=32, decode_burst=4, seed=3))
    yield eng
    eng.shutdown()


def _engine_logits(eng, prompt, out):
    """The logits behind an answer, from ``forward`` on the engine's own
    weights over prompt + answer (the engine hands out tokens only)."""
    logits, _ = jax.jit(lfm2.forward, static_argnums=0)(
        CFG, eng.params, jnp.asarray(prompt + out)[None])
    return np.asarray(logits[0, len(prompt) - 1:-1])


def test_the_engine_serves_each_request_as_if_alone(engine):
    """A slot reused after a longer request, a request admitted while
    another decodes in bursts (its slot's state untouched between its
    chunks), and two requests with a long common prefix, none adopted:
    each gives the tokens and the logits of the request alone."""
    p = _prompts()
    n = 10
    # the long request first, alone: both slots then hold old state
    first = engine.generate(p["long"], SamplingParams(max_tokens=n))
    # mid decodes in bursts while short, then the two shared ones, are
    # admitted beside it into the slot the long request left
    reqs = {name: engine.submit(p[name], SamplingParams(
        max_tokens=3 * n if name == "mid" else n))
        for name in ("mid", "short", "shared_a", "shared_b")}
    outs = {"long": first.token_ids}
    for name, req in reqs.items():
        assert req.done.wait(120), name
        assert req.error is None, req.error
        outs[name] = list(req.out_tokens)
    for name, out in outs.items():
        want_tokens, want_rows = _alone(engine.params, p[name], len(out))
        assert out == want_tokens, name
        np.testing.assert_allclose(_engine_logits(engine, p[name], out),
                                   want_rows, atol=1e-4, err_msg=name)
    stats = engine.stats()
    assert stats["prefix_hits"] == 0 and stats["prefix_tokens_saved"] == 0
    assert stats["prefix_block"] == 0 and engine.router_prefix_blocks() is None
    assert stats["requests_failed"] == 0 and stats["device_failures"] == 0
    assert (stats["moe_experts_held"], stats["attention_lines"],
            stats["conv_lines"]) == (8, 2, 3)
    assert stats["moe_picks_zero"] == 0
    assert stats["moe_picks"] == stats["moe_picks_local"] > 0
    assert stats["decode_dispatches"] < stats["decode_steps"]   # bursts ran


def test_the_served_model_says_what_it_cannot_do():
    served = served_model(CFG)
    assert served is serving.SERVED
    assert not served.prefix_from_line and not served.kv_handoff
    assert served.copy_prefix_kv is None
    assert served.kv_block(Lfm2Config(), 8192) == 512
    cache = jax.eval_shape(lambda: serving.init_cache(Lfm2Config(
        layer_types=lfm2.PUBLISHED_LAYER_TYPES[:10]), 64, 8192))
    assert cache["kv"].shape == (2, 64, 8, 8192, 128)
    assert cache["conv"].shape == (8, 64, 2 * 2048)
    # a cached position costs its 4 KiB
    assert cache["kv"].size * 2 // (64 * 8192) == 4096


@pytest.mark.parametrize("kw,message", [
    ({"speculative_model": "tiny"}, "Lfm2Config does not support a "
                                    "speculative draft"),
    ({"tensor_parallel_size": 2}, "Lfm2Config does not support "
                                  "tensor_parallel_size > 1"),
], ids=["draft", "tp"])
def test_refuse_names_each_thing_refused(kw, message):
    with pytest.raises(ValueError, match=message):
        LLMEngine(LLMConfig(model=CFG, max_num_seqs=2, max_seq_len=32, **kw))


def test_the_hand_off_is_refused_by_name():
    from ray_tpu.llm.served import require_kv_handoff

    with pytest.raises(ValueError, match="Lfm2Config does not support the "
                                         "prefill/decode hand-off"):
        require_kv_handoff(CFG)


def test_a_configuration_that_is_no_lfm2_is_refused_at_construction():
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2Config.tiny(layer_types=("conv", "window"))
    with pytest.raises(ValueError, match="do not divide"):
        Lfm2Config.tiny(expert_shards=3)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        Lfm2Config.tiny(router_score="tanh")
