"""The Ling-3.0 family (the language model of Ling-3.0-flash-VL):
``models/ling.py`` and ``llm/ling_serving.py`` against the plain reference
of the benchmark, at a small size on the CPU.

What is held here is what the family adds to the repository: a delta rule
whose decay is a number a key channel under a bounded gate (handed from
chunk to chunk through the cache beside a latent line, kept through padded
chunks, reset at a prompt's start, untouched in a slot that does not
decode), a latent attention without a low-rank query under a head-wise gate
and a rotary by halves, dense and routed feed-forwards under both kinds of
mixer, and the grouped rule with a selection bias, its shares adding up.

Tolerances: float32 against float32 at ``highest``; what is left is the
order of the sums (observed 2e-5 on logits of about 4, the rule's chunked
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
from ray_tpu.llm import ling_serving as serving
from ray_tpu.llm.config import SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import ling, routed
from ray_tpu.models.ling import KDA, LATENT, LingConfig
from ray_tpu.ops.kernels import force_kernel_backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import ling as reference  # noqa: E402
from rtbench.adapters import ling as adapter  # noqa: E402

CFG = LingConfig.tiny()
# The tiny model's state leaves: the first group's KDA layer, written out,
# and the two scanned groups' (llm/ling_serving._state_leaves).
STATES = ("state0", "state1")
PROMPT = 77           # past one sub-chunk of the rule (64), not a multiple
SLOTS, MAX_SEQ = 3, 128
ATOL = 1e-4


def config_json(cfg: LingConfig) -> dict:
    """The benchmark's configuration keys for ``cfg``: ``num_experts`` is
    the number held, as in the configuration file."""
    return {"hidden_size": cfg.hidden_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "q_lora_rank": None, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "head_dim": cfg.linear_head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "layer_group_size": cfg.layer_group_size,
            "short_conv_kernel_size": cfg.short_conv_kernel_size,
            "kda_lower_bound": cfg.kda_lower_bound,
            "first_k_dense_replace": cfg.first_k_dense_replace,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "expert_swiglu_limit_list": [0] * cfg.num_layers,
            "share_expert_swiglu_limit_list": [0] * cfg.num_layers,
            "num_experts": cfg.experts_held,
            "published": {"num_experts": cfg.num_experts},
            "expert_shard": cfg.expert_shard,
            "expert_shards": cfg.expert_shards}


@pytest.fixture(scope="module")
def params():
    return ling.init_params(CFG, jax.random.PRNGKey(0))


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
    got, counts = jax.jit(ling.forward, static_argnums=0)(
        cfg, params, jnp.asarray(tokens)[None])
    return np.asarray(got[0]), counts


def test_the_tiny_config_has_every_kind_of_layer_and_the_cut_its_count():
    assert [CFG.kind(l) for l in range(CFG.num_layers)] == [KDA, LATENT] * 3
    assert (CFG.groups, CFG.linear_lines, CFG.latent_lines,
            CFG.num_dense_layers, CFG.num_routed_layers) == (3, 3, 3, 2, 4)
    full = LingConfig()
    assert [full.kind(l) for l in range(12)] == ([KDA] * 5 + [LATENT]) * 2
    assert (full.linear_lines, full.latent_lines, full.conv_dim,
            full.linear_state_bytes, full.latent_row, full.qk_head_dim) == \
        (35, 7, 12288, 2 * 2 ** 20, 640, 192)
    rule = full.router_rule
    assert (rule.outputs, rule.topk, rule.score, rule.use_bias,
            rule.renormalize, rule.renorm_eps, rule.scaling_factor,
            rule.groups, rule.topk_groups, rule.held) == \
        (512, 8, "sigmoid", True, True, 1e-20, 2.5, 8, 4, 512)
    # 124.41B in all and the benchmark's cut (12 layers, share 0 of 8, an
    # eighth of the vocabulary), as ISSUE 58 and the adapter count them,
    # two norms a layer and the final norm beside
    norms = lambda c: (2 * c.num_layers + 1) * c.hidden_size  # noqa: E731
    assert full.num_params() - norms(full) == (
        35 * 63_049_888 + 7 * 31_965_696 + 2 * 47_185_920
        + 40 * 513 * 5_898_240 + 40 * 1_311_232 + 804_782_080)
    cut = replace(full, num_layers=12, expert_shards=8, vocab_size=19648)
    assert cut.experts_held == 64
    assert cut.num_params() == 4_736_432_192
    with pytest.raises(ValueError, match="whole groups"):
        replace(full, num_layers=15)
    with pytest.raises(ValueError, match="shards"):
        replace(full, expert_shards=7)
    # the clamp of the published model's last layers is not computed here
    with pytest.raises(ValueError, match="clamped SwiGLU"):
        replace(full, expert_swiglu_limits=(0,) * 35 + (4,) * 7)
    replace(full, num_layers=12, expert_swiglu_limits=(0,) * 35 + (4,) * 7)


def test_init_params_has_a_leaf_an_axis_list_and_a_decay_that_spreads(
        params):
    axes = ling.param_logical_axes(CFG)
    shapes = jax.tree.map(lambda a: a.ndim, params)
    assert jax.tree.map(len, axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == shapes
    assert sum(a.size for a in jax.tree.leaves(params)) == CFG.num_params()
    lay = params["layers"]
    assert lay["router"].dtype == lay["router_bias"].dtype == jnp.float32
    for name in ("input_norm", "post_norm", "kv_a_norm", "kda_norm"):
        w = np.asarray(lay[name])
        assert 0.05 < w.std() < 0.2 and abs(w.mean() - 1.0) < 0.05, name
    bias = np.asarray(lay["router_bias"])
    assert (bias < 0).any() and (bias > 0).any() and 0 < bias.std() < 0.05
    # the decay a channel is centred on spreads over exp(-4.6) to
    # exp(-1e-3), inside the gate's (-5, 0)
    amount = np.repeat(np.exp(np.asarray(lay["a_log"])), CFG.linear_head_dim,
                       axis=-1)
    rate = -CFG.kda_lower_bound / (
        1.0 + np.exp(-amount * np.asarray(lay["dt_bias"])))
    assert 1e-3 <= rate.min() * 1.001 and rate.max() <= 4.6 * 1.001
    assert np.exp(-rate).min() < 0.05 and np.exp(-rate).max() > 0.99


@pytest.mark.parametrize("shape", ["a group of 2, the first dense",
                                   "a group of 3, one dense layer"])
def test_forward_matches_the_reference(shape):
    """Whole sequences, both ways a dense layer can lie in its group: the
    first group all dense (dense under both mixers), and a group that is
    part dense and part routed (the published model's: 2 of 6)."""
    cfg = CFG if shape.startswith("a group of 2") else LingConfig.tiny(
        layer_group_size=3, first_k_dense_replace=1)
    p = ling.init_params(cfg, jax.random.PRNGKey(0))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (PROMPT + 6,),
                                         259, cfg.vocab_size), np.int32)
    got, counts = forward(cfg, p, toks)
    np.testing.assert_allclose(got, reference_logits(cfg, p, toks), atol=ATOL)
    n = len(toks) * cfg.num_experts_per_tok * cfg.num_routed_layers
    assert [int(c) for c in counts[:3]] == [n, n, 0]
    assert int(counts[4]) == cfg.num_routed_layers


def test_a_sequence_of_several_query_blocks_matches_too(params):
    """Past one query block the reference pads the sequence (to a multiple
    of 2,048, which no earlier position sees) and attends a block at a
    time: 300 positions are two blocks and a padded tail."""
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (300,), 259,
                                         CFG.vocab_size), np.int32)
    assert len(toks) > reference.QUERY_BLOCK
    got, _ = forward(CFG, params, toks)
    want = reference_logits(CFG, params, toks)
    assert want.shape == got.shape == (300, CFG.vocab_size)
    np.testing.assert_allclose(got, want, atol=ATOL)


def _with(params, **leaves):
    return {**params, "layers": {**params["layers"], **leaves}}


LD = CFG.linear_dim
NEUTRAL = {
    # the attention's head-wise gate: zero is sigmoid 0.5 on every head
    "attention_gate": lambda lay: {"wg": 0 * lay["wg"]},
    # KDA's output gate a channel
    "kda_output_gate": lambda lay: {"in_qkvz": lay["in_qkvz"].at[
        ..., 3 * LD:].set(0.0)},
    "kda_norm": lambda lay: {"kda_norm": 0 * lay["kda_norm"] + 1.0},
    "latent_norm": lambda lay: {"kv_a_norm": 0 * lay["kv_a_norm"] + 1.0},
    "shared_expert": lambda lay: {"ws_down": 0 * lay["ws_down"]},
    "dense_ffn": lambda lay: {"w_down": 0 * lay["w_down"]},
    "first_tap": lambda lay: {"conv_w": lay["conv_w"].at[:, 0].set(0.0)},
    "last_tap": lambda lay: {"conv_w": lay["conv_w"].at[:, -1].set(0.0)},
    # no decay at all, and a decay the same in every channel of a head
    "decay": lambda lay: {"dt_bias": lay["dt_bias"] - 100.0},
    "decay_a_channel": lambda lay: {
        "dt_bias": 0 * lay["dt_bias"] + lay["dt_bias"].reshape(
            *lay["a_log"].shape, -1)[..., :1].repeat(
                CFG.linear_head_dim, -1).reshape(lay["dt_bias"].shape),
        "in_f": 0 * lay["in_f"]},
    "decay_input": lambda lay: {"in_f": 0 * lay["in_f"]},
    "step": lambda lay: {"in_b": 0 * lay["in_b"]},
    "selection_bias": lambda lay: {"router_bias": 0 * lay["router_bias"]
                                   + jnp.linspace(-0.3, 0.3, 16)},
}


@pytest.mark.parametrize("part", list(NEUTRAL))
def test_the_seeded_weights_make_every_new_part_visible(params, tokens, want,
                                                        part):
    """A program that dropped a gate, a norm's weight, a tap, the decay, its
    spread over a head's channels, its dependence on the token, the step or
    the selection bias does not pass for right: with that leaf neutral (or
    the bias another) the logits move by far more than the parity
    tolerance."""
    got, _ = forward(CFG, _with(params, **NEUTRAL[part](params["layers"])),
                     tokens)
    assert np.abs(got - want).max() > 1e-2


def test_the_gate_is_bounded_and_a_number_a_channel(params):
    """``g`` lies in (kda_lower_bound, 0) whatever the input, differs along
    a head's channels, and follows the token."""
    lp = {k: params["layers"][k][0] for k in ling.KDA_LEAVES}
    xn = 5.0 * jax.random.normal(jax.random.PRNGKey(2),
                                 (2, 9, CFG.hidden_size))
    _, _, g, beta = ling.kda_inputs(CFG, lp, xn)
    g = np.asarray(g)
    assert g.shape == (2, 9, CFG.linear_num_heads, CFG.linear_head_dim)
    assert g.dtype == np.float32 and np.asarray(beta).shape == g.shape[:3]
    assert (g > CFG.kda_lower_bound).all() and (g < 0).all()
    assert g.std(axis=-1).min() > 0.1 and g.std(axis=1).max() > 0.05


def test_the_latent_rotary_turns_a_half_against_the_other(params):
    """The shared rotated key of ``mla_project`` under this configuration:
    value i of the rope part pairs with i + Dr/2 at theta^(-2i/Dr), not
    with its neighbour; and there is no low-rank query (``wq`` alone)."""
    from ray_tpu.models.mla import mla_project

    ap = {k: params["layers"][k][0] for k in ling.LATENT_LEAVES}
    assert "wq_a" not in params["layers"]
    xn = jax.random.normal(jax.random.PRNGKey(2), (1, 5, CFG.hidden_size))
    _, _, rows = mla_project(CFG, ap, xn, jnp.arange(5) + 9)
    dr, rank = CFG.qk_rope_head_dim, CFG.kv_lora_rank
    raw = np.asarray(xn[0] @ ap["wkv_a"])[:, rank:]
    ang = (9.0 + np.arange(5))[:, None] * (
        CFG.rope_theta ** (-np.arange(0, dr, 2) / dr))[None, :]
    a, b = raw[:, :dr // 2], raw[:, dr // 2:]
    np.testing.assert_allclose(
        np.asarray(rows[0, :, rank:rank + dr]),
        np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                        b * np.cos(ang) + a * np.sin(ang)], -1), atol=1e-5)


# ---- the grouped rule with a selection bias ---------------------------------

def _rule(**kw):
    base = dict(experts=16, topk=4, score="sigmoid", use_bias=True,
                renormalize=True, renorm_eps=1e-20, scaling_factor=2.5,
                groups=4, topk_groups=2)
    base.update(kw)
    return routed.RouterRule(**base)


def test_a_group_scores_as_its_two_best_and_a_pick_never_leaves_the_kept():
    """Scores by hand: group 0 holds the single largest ``s + b`` and a
    small second, groups 1 and 2 two good ones each. By its best alone group
    0 would be kept; by the sum of two, groups 1 and 2 are, and all four
    picks fall in them, though group 0's best outranks them all. The
    reference's own code for the rule agrees, expert by expert."""
    logits = np.full(16, -4.0, np.float32)
    logits[0] = 3.0                                  # group 0: one star
    logits[[4, 5]] = 1.5                             # group 1
    logits[[8, 9]] = 1.0                             # group 2
    logits[6], logits[10] = 0.5, 0.2
    router = jnp.eye(16, dtype=jnp.float32)
    u = jnp.asarray(logits)[None]
    idx, w = routed.route(_rule(), router, jnp.zeros((16,)), u)
    assert sorted(np.asarray(idx[0]).tolist()) == [4, 5, 8, 9]
    s = 1.0 / (1.0 + np.exp(-logits))
    picked = s[np.asarray(idx[0])]
    np.testing.assert_allclose(np.asarray(w[0]), 2.5 * picked / picked.sum(),
                               rtol=1e-5)
    c = reference._static({**config_json(CFG), "n_group": 4,
                           "topk_group": 2})
    want = np.asarray(reference.gate_weights(c, u, router,
                                             jnp.zeros((16,))))[0]
    got = np.zeros(16, np.float32)
    got[np.asarray(idx[0])] = np.asarray(w[0])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_a_negative_bias_changes_the_choice_and_not_the_weights():
    """A bias under an expert moves it out of the choice (and its group out
    of the kept ones); the weights of what is chosen are the scores alone,
    as with no bias. A bias so negative that a kept group's ``s + b`` are
    all under 0 still keeps the picks inside the kept groups: outside is
    -inf, not 0."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    router = jnp.eye(16, dtype=jnp.float32)
    free, w_free = routed.route(_rule(), router, jnp.zeros((16,)), u)
    bias = jnp.zeros((16,)).at[jnp.arange(4)].set(-2.0)
    idx, w = routed.route(_rule(), router, bias, u)
    assert not (np.asarray(idx) < 4).any() and (np.asarray(free) < 4).any()
    s = np.asarray(jax.nn.sigmoid(u))
    picked = np.take_along_axis(s, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)
    # every ``s + b`` under 0 (the same bias under all: the choice is the
    # free one): the picks still lie in two groups
    idx, _ = routed.route(_rule(), router, jnp.full((16,), -3.0), u)
    groups = [len(set(row // 4)) for row in np.asarray(idx)]
    assert max(groups) <= 2
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(np.asarray(free), -1))


def test_the_shares_add_up():
    """At the published routing (512 outputs in 8 groups, 4 kept, 8 a
    token) on small widths: the 8 shares of a routed layer, each computed by
    a program that holds one group, plus the shared expert once, are the
    uncut layer's feed-forward: what an expert-parallel deployment sums. A
    token's picks fall in 4 groups at most, so a share gets none of a
    token's picks or up to 8."""
    shards = 8
    kw = dict(num_experts=512, num_experts_per_tok=8, n_group=8,
              topk_group=4, moe_intermediate_size=8)
    whole = LingConfig.tiny(**kw)
    p = ling.init_params(whole, jax.random.PRNGKey(4))
    lay = p["layers"]
    u = jax.random.normal(jax.random.PRNGKey(5), (40, whole.hidden_size))
    valid = jnp.ones((40,), bool)
    layer = 1
    total = ling.shared_expert(lay, layer, u).astype(jnp.float32)
    picks, per_token = 0, []
    for s in range(shards):
        cfg = LingConfig.tiny(expert_shard=s, expert_shards=shards, **kw)
        held = cfg.experts_held
        assert held == 64
        part = {**lay, **{k: lay[k][:, s * held:(s + 1) * held]
                          for k in ("we_gate", "we_up", "we_down")}}
        y, counts, local = routed.moe_block_picks(cfg.router_rule, part,
                                                  layer, u, valid)
        total = total + y
        picks += int(counts[1])
        per_token.append(np.asarray(local).sum(axis=1))
    assert picks == 40 * 8
    per_token = np.stack(per_token)                  # [shares, tokens]
    assert ((per_token > 0).sum(axis=0) <= 4).all()
    assert (per_token.sum(axis=0) == 8).all() and per_token.max() <= 8
    c = reference._static(config_json(whole))
    w = adapter.reference_weights(p)["layers"]
    want = reference.shared_expert(u, w, layer) \
        + reference.routed_experts(c, u, w, layer)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)
    # and one share alone is that share of the reference
    cfg = LingConfig.tiny(expert_shard=1, expert_shards=4)
    p4 = ling.init_params(LingConfig.tiny(), jax.random.PRNGKey(4))
    part = {**p4["layers"], **{k: p4["layers"][k][:, 4:8]
                               for k in ("we_gate", "we_up", "we_down")}}
    got, _ = forward(cfg, {**p4, "layers": part}, np.arange(300, 340))
    np.testing.assert_allclose(
        got, reference_logits(cfg, {**p4, "layers": part},
                              np.arange(300, 340)), atol=ATOL)


# ---- the cache: latent lines, states and windows -----------------------------

def test_no_state_leaf_is_updated_twice_in_a_step():
    """A KDA layer of a group written out has a state leaf of its own (one
    line); the scanned groups share a leaf a place in the group, a line a
    group. At the cell's depth (two groups, the first written out, the
    second a scan of one) that is ten float32 leaves of [1, slots, heads,
    D, D] beside one leaf of windows over all ten KDA layers and the latent
    lines; the published depth has five leaves of one line and five of six;
    the tiny model one of each."""
    tiny_shapes = jax.eval_shape(lambda: serving.init_cache(CFG, SLOTS,
                                                            MAX_SEQ))
    assert sorted(tiny_shapes) == ["conv", "latent", "state0", "state1"]
    assert [tiny_shapes[k].shape[0] for k in STATES] == [1, 2]
    full = LingConfig()
    assert serving._state_leaves(full) == [1] * 5 + [6] * 5
    assert serving._state_at(full, 0, 3) == (3, 0)
    cut = replace(full, num_layers=12, expert_shards=8, vocab_size=19648)
    assert serving._state_leaves(cut) == [1] * 10
    shapes = jax.eval_shape(lambda: serving.init_cache(cut, 96, 8192))
    assert sorted(shapes) == sorted(["conv", "latent"]
                                    + [f"state{i}" for i in range(10)])
    for i in range(10):
        leaf = shapes[f"state{i}"]
        assert (leaf.shape, leaf.dtype) == ((1, 96, 32, 128, 128),
                                            jnp.float32)
    assert shapes["conv"].shape == (10, 96, 3 * 12288)
    assert shapes["latent"].shape == (2, 96, 8192, 640)
    # all dense, no group written out: a leaf a place, a line a group
    assert serving._state_leaves(replace(cut, first_k_dense_replace=0)) \
        == [2] * 5


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
        "a chunk that ends inside a block of 16": ([50, 70, PROMPT], 16),
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
    for leaf in STATES + ("conv",):
        np.testing.assert_allclose(np.asarray(cache[leaf]),
                                   np.asarray(whole[leaf]), atol=5e-5,
                                   err_msg=leaf)
        assert not np.asarray(cache[leaf][:, [0, 2]]).any()
    np.testing.assert_allclose(
        np.asarray(cache["latent"][:, 1, :PROMPT]),
        np.asarray(whole["latent"][:, 1, :PROMPT]), atol=5e-5)
    assert not np.asarray(cache["latent"][:, [0, 2]]).any()
    named = dict(zip(serving.COUNTERS, counts))
    assert named["linear_chunk_tokens"] == PROMPT * CFG.linear_lines
    assert named["linear_state_updates"] == 0
    assert named["moe_picks"] == \
        PROMPT * CFG.num_experts_per_tok * CFG.num_routed_layers


def test_a_padded_chunk_leaves_the_state_bit_for_bit(params, tokens):
    """A chunk of 16 rows of which 13 are the prompt's leaves the state
    that a chunk of exactly 13 leaves, to the bit: a padded row enters the
    rule with ``g = 0`` and ``beta = 0``."""
    exact, _, _ = _prefill(params, tokens[:13], [13])
    padded, _, _ = _prefill(params, tokens[:13], [13], bucket=16)
    for leaf in STATES + ("conv",):
        np.testing.assert_array_equal(np.asarray(padded[leaf]),
                                      np.asarray(exact[leaf]), err_msg=leaf)


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
    """Through the latent lines, the states and the windows, teacher-forced;
    the other slots of the decode batch are idle (``write_mask`` false) and
    keep what they hold bit for bit. ``interpret`` runs the latent kernels'
    own bodies."""
    with force_kernel_backend(backend):
        cache, logits, _ = _prefill(params, tokens[:PROMPT], [32, 64, PROMPT],
                                    bucket=16)
        np.testing.assert_allclose(logits, want[PROMPT - 1], atol=ATOL)
        # slot 2 holds another request's state, which no step may touch
        cache, _, _ = _prefill(params, tokens[:9], [9], slot=2, cache=cache)
        held = {k: np.asarray(cache[k][:, 2]) for k in cache}
        assert all(held[k].any() for k in STATES + ("conv",))
        write = jnp.asarray([False, True, False])
        for p in range(PROMPT, len(tokens)):
            tok = jnp.zeros((SLOTS,), jnp.int32).at[1].set(int(tokens[p]))
            pos = jnp.zeros((SLOTS,), jnp.int32).at[1].set(p)
            cache, logits, counts = serving.decode_step(
                CFG, params, cache, tok, pos, write)
            np.testing.assert_allclose(np.asarray(logits[1]), want[p],
                                       atol=ATOL)
            named = dict(zip(serving.COUNTERS, (int(c) for c in counts)))
            # one live slot: a state a KDA layer, topk picks a routed layer
            assert named["linear_state_updates"] == CFG.linear_lines
            assert named["linear_chunk_tokens"] == 0
            assert named["moe_picks"] == \
                CFG.num_experts_per_tok * CFG.num_routed_layers
    for k in cache:
        np.testing.assert_array_equal(np.asarray(cache[k][:, 2]), held[k])
        assert not np.asarray(cache[k][:, 0]).any()


def test_a_burst_is_its_steps_and_keeps_idle_slots_state(params, tokens):
    cache, _, _ = _prefill(params, tokens[:PROMPT], [PROMPT])
    cache, _, _ = _prefill(params, tokens[:9], [9], slot=2, cache=cache)
    held = [np.asarray(cache[k][:, 2]) for k in STATES]
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
    assert named["moe_layer_steps"] == 4 * CFG.num_routed_layers
    assert named["linear_state_updates"] == 4 * CFG.linear_lines
    for leaf in STATES + ("conv",):
        np.testing.assert_allclose(np.asarray(burst[leaf]),
                                   np.asarray(cache[leaf]), atol=1e-6)
    for k, was in zip(STATES, held):
        np.testing.assert_array_equal(np.asarray(burst[k][:, 2]), was)


def test_a_state_kept_below_float32_does_not_pass(params, tokens, want):
    """The departure the configuration states (the state in float32, as the
    published kernels keep it) is held by the comparison: a state rounded to
    bfloat16 between a prompt's chunks moves the logits past the
    tolerance."""
    cache, _, _ = _prefill(params, tokens[:64], [64])
    for k in STATES:
        assert cache[k].dtype == jnp.float32
        cache[k] = cache[k].astype(jnp.bfloat16).astype(jnp.float32)
    chunk = jnp.asarray(tokens[64:PROMPT])
    _, logits, _ = serving.prefill_chunk(
        CFG, params, cache, chunk, jnp.int32(64), jnp.int32(PROMPT),
        jnp.int32(1))
    assert np.abs(np.asarray(logits) - want[PROMPT - 1]).max() > 10 * ATOL


# ---- through the scheduler ---------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    eng = LLMEngine(LLMConfig(model=LingConfig.tiny(max_seq_len=MAX_SEQ),
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
    assert stats["linear_lines"] == cfg.linear_lines == 3
    assert stats["latent_lines"] == 3
    assert stats["moe_experts_held"] == 16
    assert stats["linear_state_bytes"] == 4 * 16 * 16 * 4
    assert stats["linear_chunk_tokens"] == sum(map(len, prompts)) * 3
    # a token a request comes from prefill, the others from decode steps
    assert stats["linear_state_updates"] == 5 * 5 * 3
    assert stats["moe_picks"] == (sum(map(len, prompts)) + 25) * 4 * 4
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
        (67 + 66) * 3


@pytest.mark.parametrize("bad,match", [
    ({"tensor_parallel_size": 2}, "tensor_parallel_size"),
    ({"kv_block_size": 16}, "block pool"),
    ({"speculative_model": LingConfig.tiny()}, "speculative draft")])
def test_what_it_does_not_run_is_refused_at_construction(bad, match):
    with pytest.raises(ValueError, match=match):
        LLMEngine(LLMConfig(model=LingConfig.tiny(), max_num_seqs=2,
                            max_seq_len=64, dtype="float32", **bad))
