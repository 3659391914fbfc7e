"""MiMo-V2 on the CPU at tiny sizes, float32, seeded weights:
``models/mimo.forward`` and the programs of ``llm/mimo_serving.py`` against
the plain reference (benchmark/reference/mimo.py), which shares no code
with them: unpacked keys and values, the window's mask and the sink written
again, every expert on every token.

One tolerance, ``ATOL`` 1e-4 on logits of about unit size: everything is
float32 here, the program and the reference order the same sums differently
(a packed row's product sums 2 D terms of which D are zeros; a chunk's
window attention sums ring and chunk in bands; XLA's CPU matmuls block),
which leaves a few 1e-6 (4.3e-6 observed); 1e-4 is far under anything a
wrong mechanism moves: a missing sink, a window off by one, a whole-head
rotary, one rotary base for both kinds and a bfloat16 run each move the
logits by over 1e-3 (tested below).
"""

import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm import mimo_serving as serving
from ray_tpu.llm.config import SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import mimo, routed
from ray_tpu.models.mimo import FULL, WINDOW, MimoConfig
from ray_tpu.ops.decode_attention import (
    decode_attention,
    decode_attention_reference,
    kv_row_write,
)
from ray_tpu.ops.kernels import force_kernel_backend
from ray_tpu.ops.prefill_attention import (
    prefill_attention,
    prefill_attention_reference,
)
from ray_tpu.ops.rope import apply_rope, apply_rope_partial, rope_frequencies

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import mimo as reference  # noqa: E402
from rtbench.adapters import mimo as adapter  # noqa: E402

CFG = MimoConfig.tiny()
W = CFG.sliding_window            # 8
PROMPT = 29                       # past three turns of a ring, no multiple
SLOTS, MAX_SEQ = 3, 64
ATOL = 1e-4


def config_json(cfg: MimoConfig) -> dict:
    """The benchmark's configuration keys for ``cfg``."""
    return {"hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "hybrid_layer_pattern": list(cfg.kinds),
            "moe_layer_freq": list(cfg.routed),
            "num_attention_heads": cfg.num_heads,
            "swa_num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "swa_num_key_value_heads": cfg.swa_num_kv_heads,
            "head_dim": cfg.head_dim, "swa_head_dim": cfg.head_dim,
            "v_head_dim": cfg.v_head_dim, "swa_v_head_dim": cfg.v_head_dim,
            "partial_rotary_factor": cfg.partial_rotary_factor,
            "rope_theta": cfg.rope_theta,
            "swa_rope_theta": cfg.swa_rope_theta,
            "rope_scaling": {"rope_type": "default", "type": "default"},
            "sliding_window": cfg.sliding_window,
            "attention_value_scale": cfg.attention_value_scale,
            "attention_bias": False,
            "add_swa_attention_sink_bias": cfg.window_sink,
            "add_full_attention_sink_bias": False,
            "layernorm_epsilon": cfg.norm_eps,
            "n_routed_experts": cfg.experts_held,
            "published": {"n_routed_experts": cfg.n_routed_experts},
            "expert_shard": cfg.expert_shard,
            "expert_shards": cfg.expert_shards,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": None, "n_shared_experts": None,
            "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
            "scoring_func": "sigmoid", "hidden_act": "silu",
            "tie_word_embeddings": False, "vocab_size": cfg.vocab_size,
            "torch_dtype": cfg.dtype}


@pytest.fixture(scope="module", autouse=True)
def compiled_programs_are_given_back():
    """This module compiles some hundreds of small programs (every cut of a
    prompt is a shape, the kernels' bodies run through the interpreter),
    and XLA's CPU backend keeps memory maps for each: 14,373 after 13 of
    these tests, 701 once the caches are cleared. A test worker that ran
    this file between two others of the kind passed the kernel's limit of
    65,530 maps a process and its next compile died (three whole runs of
    the suite, PR 54). The caches go when the module does."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def params():
    return mimo.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (PROMPT + 14,),
                                         259, CFG.vocab_size), np.int32)


def reference_logits(cfg, params, tokens, **keys):
    return reference.logits({**config_json(cfg), **keys},
                            adapter.reference_weights(params),
                            jnp.asarray(tokens))


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's logits over the whole sequence, float32."""
    return reference_logits(CFG, params, tokens)


def forward(cfg, params, tokens):
    return np.asarray(jax.jit(mimo.forward, static_argnums=0)(
        cfg, params, jnp.asarray(tokens)[None])[0][0])


def _with(params, **leaves):
    return {**params, "layers": {**params["layers"], **leaves}}


def test_the_tiny_config_has_every_kind_of_layer_and_the_whole_its_count():
    assert (CFG.full_lines, CFG.window_lines, CFG.num_dense_layers,
            CFG.num_routed_layers) == (2, 4, 1, 5)
    assert [(r.kind, r.routed, r.first, r.n, r.line, r.ffn)
            for r in CFG.runs] == [(FULL, False, 0, 1, 0, 0),
                                   (WINDOW, True, 1, 3, 0, 0),
                                   (FULL, True, 4, 1, 1, 3),
                                   (WINDOW, True, 5, 1, 3, 4)]
    assert (CFG.rotary_dim, CFG.kv_row) == (8, 48)
    full = MimoConfig()
    assert [l for l, k in enumerate(full.kinds) if k == FULL] == \
        [0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert full.routed == (0,) + (1,) * 47 and len(full.runs) == 17
    assert (full.rotary_dim, full.kv_row, full.qkv_width(FULL),
            full.qkv_width(WINDOW)) == (64, 384, 13568, 14848)
    # the row's "309B-A15B", as ISSUE 54 counts it: 308.8B in all
    assert round(full.num_params() / 1e9, 1) == 308.8
    # the cell's share: 7 layers, 16 experts held, an eighth of the
    # vocabulary, as the adapter counts it from the configuration's file
    cut = MimoConfig(num_layers=7, vocab_size=19072, expert_shards=16)
    assert cut.kinds == (0, 1, 1, 1, 1, 0, 1) and len(cut.runs) == 4
    held = adapter.params_held({**config_json(cut)})
    assert held == cut.num_params() - 5 * 64 - 15 * 4096 - 6 * 256
    assert round(held * 2 / 2 ** 30, 2) == 6.39
    leaves = jax.eval_shape(lambda: mimo.init_params(
        CFG, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(leaves)) == \
        CFG.num_params()
    assert jax.tree.structure(leaves) == jax.tree.structure(
        mimo.param_logical_axes(CFG), is_leaf=lambda x: isinstance(x, tuple))
    with pytest.raises(ValueError, match="v_head_dim"):
        MimoConfig.tiny(v_head_dim=32)
    with pytest.raises(ValueError, match="layer_kinds"):
        MimoConfig.tiny(layer_kinds=(0, 1))


def test_forward_is_the_reference(params, tokens, want):
    np.testing.assert_allclose(forward(CFG, params, tokens), want, atol=ATOL)
    assert 0.5 < want.std() < 2.0


# What the comparison must see: each is the seeded model with one mechanism
# taken out or bent, by weights or by configuration, run through the SAME
# program and held against the reference of the true model.
def _no_sink(params):
    return CFG, _with(params, sink=jnp.full_like(params["layers"]["sink"],
                                                 -1e30))


BENT = {
    "the sinks left out": _no_sink,
    "a window of 7": lambda p: (replace(CFG, sliding_window=W - 1), p),
    "a window of 9": lambda p: (replace(CFG, sliding_window=W + 1), p),
    "a whole-head rotary": lambda p: (
        replace(CFG, partial_rotary_factor=1.0), p),
    "one base for both kinds": lambda p: (
        replace(CFG, swa_rope_theta=CFG.rope_theta), p),
    "values not scaled": lambda p: (replace(CFG, attention_value_scale=1.0),
                                    p),
    "the bias left out of the choice": lambda p: (CFG, _with(
        p, router_bias=jnp.zeros_like(p["layers"]["router_bias"]))),
}


@pytest.mark.parametrize("name", list(BENT))
def test_a_bent_mechanism_does_not_pass(params, tokens, want, name):
    cfg, bent = BENT[name](params)
    got = forward(cfg, bent, tokens)
    assert np.abs(got - want).max() > 10 * ATOL, name


def test_a_bfloat16_run_of_a_float32_configuration_does_not_pass(
        params, tokens, want):
    """Every leaf but the router's, its bias and the sinks (float32 in any
    model) in bfloat16, as ``dtype="bfloat16"`` initialises them."""
    keep = ("router", "router_bias", "sink")
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    low = _with(low, **{k: params["layers"][k] for k in keep})
    got = forward(replace(CFG, dtype="bfloat16"), low, tokens)
    assert np.abs(got - want).max() > 30 * ATOL


def test_sinks_at_minus_infinity_are_a_model_without_them(params, tokens):
    """The sink enters through one term of one denominator: at -1e30 that
    term is exactly 0, in the program and in the reference alike."""
    bare = replace(CFG, window_sink=False)
    without = {**params, "layers": {k: v for k, v in params["layers"].items()
                                    if k != "sink"}}
    _, sunk = _no_sink(params)
    got = forward(CFG, sunk, tokens)
    np.testing.assert_allclose(got, forward(bare, without, tokens),
                               atol=1e-6)
    np.testing.assert_allclose(got, reference_logits(bare, without, tokens),
                               atol=ATOL)
    assert np.abs(got - forward(CFG, params, tokens)).max() > 10 * ATOL


def test_the_window_s_edge():
    """Query p of a window layer sees key p - W + 1 and not key p - W."""
    p = np.arange(20)
    seen = np.asarray(mimo.window_visible(jnp.asarray(p), jnp.asarray(p), W))
    for q in (0, 3, W - 1, W, 19):
        keys = np.flatnonzero(seen[q])
        assert keys.tolist() == list(range(max(0, q - W + 1), q + 1))
    assert seen[12, 12 - W + 1] and not seen[12, 12 - W]


def test_a_window_layer_forgets_what_left_its_window(params, tokens):
    """Only a full layer carries what lies ``W`` or more back: with the full
    layers' values at zero (their attention adds nothing), the last
    position's logits do not move when a token ``W`` back or further
    changes, and do when the one at ``W - 1`` back does. The stack is 6
    layers deep, so the reach is 4 window layers x (W - 1) = 28 positions:
    tested on a model of one window layer after the dense full one."""
    cfg = MimoConfig.tiny(num_layers=2, layer_kinds=(0, 1))
    p = mimo.init_params(cfg, jax.random.PRNGKey(3))
    wq = p["layers"]["wqkv_full"]
    cut = (cfg.num_heads + cfg.num_kv_heads) * cfg.head_dim
    p = _with(p, wqkv_full=wq.at[..., cut:].set(0.0))
    base = forward(cfg, p, tokens[:20])[-1]
    far, near = tokens[:20].copy(), tokens[:20].copy()
    far[19 - W] = (far[19 - W] + 1) % cfg.vocab_size
    near[19 - W + 1] = (near[19 - W + 1] + 1) % cfg.vocab_size
    np.testing.assert_allclose(forward(cfg, p, far)[-1], base, atol=1e-6)
    assert np.abs(forward(cfg, p, near)[-1] - base).max() > 1e-3


def test_the_partial_rotary_turns_the_first_lanes_and_two_bases():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 5, CFG.head_dim))
    pos = jnp.arange(3, 8)
    rot = CFG.rotary_dim
    for kind, theta in ((FULL, CFG.rope_theta), (WINDOW, CFG.swa_rope_theta)):
        inv = mimo.inv_frequencies(CFG, kind)
        np.testing.assert_allclose(
            inv, theta ** (-np.arange(0, rot, 2) / rot), rtol=1e-6)
        got = apply_rope_partial(x, pos, inv)
        # lanes from ``rotary_dim`` on are left as they are, bit for bit
        np.testing.assert_array_equal(got[..., rot:], x[..., rot:])
        # the first lanes turn as a whole head of that size would: lane i
        # with lane i + rot / 2
        np.testing.assert_array_equal(got[..., :rot],
                                      apply_rope(x[..., :rot], pos, inv))
        # and as the reference turns them
        ref = reference.rotary_part(x[0].transpose(1, 0, 2), 3, theta, rot)
        np.testing.assert_allclose(got[0].transpose(1, 0, 2), ref, atol=1e-6)
    assert not np.allclose(mimo.inv_frequencies(CFG, FULL)[1:],
                           mimo.inv_frequencies(CFG, WINDOW)[1:])
    # the published geometry: a third of 192 is 64 lanes, 32 pairs
    assert MimoConfig().rotary_dim == int(192 * 0.334) == 64
    assert rope_frequencies(64, 1e7).shape == (32,)


# ---- the kernels' bodies: keys wider than values, and the sink --------------

def _packed_case(key, b=3, hkv=2, g=4, s=32, d=24, dv=16):
    ks = jax.random.split(key, 5)
    k = jax.random.normal(ks[0], (b, hkv, s, d))
    v = jnp.pad(jax.random.normal(ks[1], (b, hkv, s, dv)),
                ((0, 0),) * 3 + ((0, d - dv),))
    stack = jnp.concatenate([k, v], -1)[None]             # [1, B, Hkv, S, 2D]
    q = jax.random.normal(ks[2], (b, hkv * g, 1, d))
    sink = jax.random.normal(ks[3], (hkv * g,))
    return stack, k, v[..., :dv], q, sink


def _dense(q, k, v, visible, sink):
    """Plain softmax attention with a sink, a head at a time."""
    b, h, _, d = q.shape
    g = h // k.shape[1]
    out = np.zeros((b, h, v.shape[-1]))
    for i in range(b):
        for j in range(h):
            s = (k[i, j // g] @ q[i, j, 0]) / np.sqrt(d)
            s = np.where(visible[i], s, -np.inf)
            top = max(s.max(), sink[j]) if sink is not None else s.max()
            if not np.isfinite(top):        # an empty slot: nothing is seen
                continue
            e = np.exp(s - top)
            den = e.sum() + (np.exp(sink[j] - top) if sink is not None else 0)
            out[i, j] = (e[:, None] * v[i, j // g]).sum(0) / den
    return out


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("sunk", [False, True])
def test_decode_attention_with_keys_of_24_values_of_16_and_a_sink(backend,
                                                                  sunk):
    """A packed row of a key and a narrower value padded to the key's
    width, through the decode kernel's own body: the values' mix is the
    first ``Dv`` of the output's lanes; the sink is one more term of the
    denominator. An empty slot gives zeros either way."""
    stack, k, v, q, sink = _packed_case(jax.random.PRNGKey(2))
    sink = sink if sunk else None
    lengths = jnp.asarray([32, 0, 11])
    pos = jnp.asarray([31, 0, 10])
    with force_kernel_backend(backend):
        got = decode_attention(q, stack, None, 0, lengths, pos, block=8,
                               sink=sink)
    visible = np.arange(32)[None, :] < np.asarray(lengths)[:, None]
    want = _dense(*(np.asarray(a) for a in (q, k, v)), visible,
                  None if sink is None else np.asarray(sink))
    want[1] = 0
    np.testing.assert_allclose(np.asarray(got[:, :, 0, :16]), want,
                               atol=2e-6)
    np.testing.assert_array_equal(np.asarray(got[:, :, 0, 16:]), 0)
    if sunk:
        bare = decode_attention_reference(q, stack, None, 0, lengths, pos)
        assert np.abs(np.asarray(got - bare)).max() > 1e-2


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_prefill_attention_with_keys_of_24_and_values_of_16(backend):
    stack, k, v, _, _ = _packed_case(jax.random.PRNGKey(4))
    q = jax.random.normal(jax.random.PRNGKey(5), (8, 16, 24))
    with force_kernel_backend(backend):
        got = prefill_attention(q, stack, None, 0, 2, 8, 24, block_k=8)
    want = prefill_attention_reference(
        q, jnp.asarray(k)[None], jnp.pad(v, ((0, 0),) * 3 + ((0, 8),))[None],
        0, 2, 8, 24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(got[..., 16:]), 0)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_the_row_writes_of_both_geometries(backend):
    """One ``kv_row_write`` serves a full line of 2 KV heads and a ring of
    4: a row is a key and a padded value side by side."""
    with force_kernel_backend(backend):
        cache = serving.init_cache(CFG, SLOTS, MAX_SEQ)
        for leaf, heads, at in (("kv", CFG.num_kv_heads, 37),
                                ("ring", CFG.swa_num_kv_heads, 37 % W)):
            k = jax.random.normal(jax.random.PRNGKey(heads),
                                  (SLOTS, heads, 1, CFG.head_dim))
            v = jnp.pad(k[..., :CFG.v_head_dim] + 1,
                        ((0, 0),) * 3 + ((0, CFG.head_dim - CFG.v_head_dim),))
            stack, _ = kv_row_write(
                cache[leaf], None, k, v, 1, jnp.full((SLOTS,), at),
                jnp.asarray([True, False, True]))
            row = np.asarray(stack[1, :, :, at])
            np.testing.assert_array_equal(row[0, :, :CFG.head_dim], k[0, :, 0])
            np.testing.assert_array_equal(row[2, :, CFG.head_dim:], v[2, :, 0])
            assert not row[1].any() and not np.asarray(stack[0]).any()


# ---- the routed layer: the shares add up, the router's float32 --------------

def test_the_shares_routed_results_sum_to_the_uncut_layer_s(params):
    """Every share routes over all 16 outputs and computes its own experts'
    part: the 4 shares' results sum to the whole layer's, and the whole
    layer's is the reference's sum over all experts."""
    u = jax.random.normal(jax.random.PRNGKey(7), (24, CFG.hidden_size))
    valid = jnp.ones((24,), bool)
    layers = params["layers"]
    whole, counts = routed.moe_block(CFG.router_rule, layers, 2, u, valid)
    total, picks = jnp.zeros_like(whole), 0
    for shard in range(4):
        cfg = replace(CFG, expert_shard=shard, expert_shards=4)
        held = slice(shard * 4, shard * 4 + 4)
        part, c = routed.moe_block(
            cfg.router_rule, {**layers, **{k: layers[k][:, held] for k in
                                           ("we_gate", "we_up", "we_down")}},
            2, u, valid)
        total, picks = total + part, picks + int(c[1])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    assert picks == int(counts[0]) == 24 * CFG.num_experts_per_tok
    w = adapter.reference_weights(params)["layers"]
    want = reference.routed_experts(config_json(CFG), u, w, 2)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=1e-5)


def test_the_rule_is_sigmoid_top_k_by_score_plus_bias_weights_by_score(
        params):
    rule = CFG.router_rule
    assert (rule.score, rule.use_bias, rule.renormalize, rule.renorm_eps,
            rule.scaling_factor, rule.groups) == ("sigmoid", True, True,
                                                  1e-20, 1.0, 1)
    full = MimoConfig(expert_shards=16).router_rule
    assert (full.experts, full.topk, full.held) == (256, 8, 16)
    u = jax.random.normal(jax.random.PRNGKey(8), (16, CFG.hidden_size))
    router = params["layers"]["router"][0]
    bias = jnp.zeros((16,)).at[3].set(10.0)       # expert 3 always chosen
    idx, w = routed.route(rule, router, bias, u)
    assert (np.asarray(idx) == 3).any(axis=1).all()
    s = jax.nn.sigmoid(u @ router)
    picked = jnp.take_along_axis(s, idx, axis=1)
    # the bias is in the choice and not in the weights, which sum to one
    np.testing.assert_allclose(np.asarray(w), np.asarray(
        picked / picked.sum(1, keepdims=True)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(reference.gate_weights(
        u, router, bias, 4, True, 1.0)).sum(1), 1.0, atol=1e-6)


def test_the_router_stays_float32_in_a_bfloat16_model():
    cfg = MimoConfig.tiny(dtype="bfloat16")
    leaves = jax.eval_shape(lambda: mimo.init_params(cfg,
                                                     jax.random.PRNGKey(0)))
    lay = leaves["layers"]
    assert lay["router"].dtype == lay["router_bias"].dtype == \
        lay["sink"].dtype == jnp.float32
    assert lay["we_gate"].dtype == lay["wqkv_window"].dtype == jnp.bfloat16
    text = str(jax.make_jaxpr(lambda p, t: mimo.forward(cfg, p, t))(
        leaves, jax.ShapeDtypeStruct((1, 8), jnp.int32)))
    # the router's product: float32 operands at true float32 precision
    assert "precision=(Precision.HIGHEST, Precision.HIGHEST)" in text \
        or "precision=HIGHEST" in text


# ---- the serving programs ---------------------------------------------------

def _prefill(params, prompt, cuts, bucket=None, slot=1, cache=None,
             cfg=CFG):
    """Prefill ``prompt`` into ``slot`` in chunks that end at ``cuts``,
    each padded to ``bucket`` rows (None: its own length)."""
    if cache is None:
        cache = serving.init_cache(cfg, SLOTS, MAX_SEQ)
    start, total = 0, 0
    for end in cuts:
        chunk = np.zeros((bucket or end - start,), np.int32)
        chunk[:end - start] = prompt[start:end]
        cache, logits, counts = serving.prefill_chunk(
            cfg, params, cache, jnp.asarray(chunk), jnp.int32(start),
            jnp.int32(len(prompt)), jnp.int32(slot))
        start, total = end, total + np.asarray(counts)
    return cache, np.asarray(logits), total


def _ring_in_order(cache, end):
    """Slot 1's ring rows by position: the last ``min(end, W)`` positions
    before ``end``, oldest first."""
    ring = np.asarray(cache["ring"][:, 1])
    return ring[:, :, [p % W for p in range(max(0, end - W), end)]]


# prompts shorter than, equal to and several times the window; chunks that
# end inside a ring's turn; chunks of whole windows (the banded form) and
# not; a padded last chunk
CUTS = {"one pass": (PROMPT, [PROMPT], None),
        "chunks of 1 and 2": (PROMPT, [1, 3, 4, 12, 14, 15, PROMPT], None),
        "chunks of the window": (PROMPT, [8, 16, 24, PROMPT], 8),
        "chunks of two windows, banded": (PROMPT, [16, PROMPT], 16),
        "chunks of four windows, banded": (PROMPT + 10, [32, PROMPT + 10],
                                           32),
        "chunks no window divides": (PROMPT, [20, PROMPT], 20),
        "a prompt shorter than the window": (5, [5], 16),
        "a prompt of the window": (W, [W], 16),
        "a lone padded token": (PROMPT, [16, 28, PROMPT], 16)}


@pytest.mark.parametrize("name", list(CUTS))
def test_prefill_in_chunks_cut_anywhere_gives_one_pass_s_logits_and_cache(
        params, tokens, want, name):
    """What a chunk leaves is what stands after the prompt's last token, not
    after the chunk's last (padded) row: the full lines' rows, and the
    rings' last ``W`` valid rows each in the row of its position."""
    n, cuts, bucket = CUTS[name]
    prompt = tokens[:n]
    cache, logits, counts = _prefill(params, prompt, cuts, bucket=bucket)
    whole, _, _ = _prefill(params, prompt, [n])
    np.testing.assert_allclose(logits, want[n - 1], atol=ATOL)
    np.testing.assert_allclose(np.asarray(cache["kv"][:, 1, :, :n]),
                               np.asarray(whole["kv"][:, 1, :, :n]),
                               atol=5e-5)
    np.testing.assert_allclose(_ring_in_order(cache, n),
                               _ring_in_order(whole, n), atol=5e-5)
    for leaf in cache:
        assert not np.asarray(cache[leaf][:, [0, 2]]).any()
        # a value's padding lanes hold zeros
        assert not np.asarray(cache[leaf][..., CFG.head_dim
                                          + CFG.v_head_dim:]).any()
    named = dict(zip(serving.COUNTERS, counts))
    # every row of a chunk that lies before the prompt's end is routed (a
    # padded chunk that is not the last one has such rows twice)
    rows = sum(min(bucket or end - start, n - start)
               for start, end in zip([0] + cuts, cuts))
    assert named["moe_picks"] == rows * CFG.num_experts_per_tok \
        * CFG.num_routed_layers
    assert named["window_positions_read"] == len(cuts) * W \
        * CFG.window_lines * CFG.swa_num_kv_heads
    # MAX_SEQ 64 has no block of 128: a chunk reads the whole line
    assert named["full_positions_read"] == len(cuts) * MAX_SEQ \
        * CFG.full_lines * CFG.num_kv_heads
    assert named["attn_positions_read"] == named["window_positions_read"] \
        + named["full_positions_read"]


def test_a_chunk_at_the_start_of_a_prompt_sees_none_of_the_ring_s_rows(
        params, tokens, want):
    """A slot that held a longer request: the new prompt's first chunk
    (``kv_len`` 0) sees nothing of it."""
    cache, _, _ = _prefill(params, tokens[:PROMPT], [PROMPT])
    cache, logits, _ = _prefill(params, tokens[:5], [5], bucket=16,
                                cache=cache)
    np.testing.assert_allclose(logits, want[4], atol=ATOL)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_prefill_then_decode_agrees_with_the_reference_past_a_turn_of_the_ring(
        params, tokens, want, backend):
    """Through the full lines and the rings, teacher-forced over 14
    positions (a ring of 8 turns once and most of a second time); the other
    slots of the decode batch are idle (``write_mask`` false) and keep what
    they hold bit for bit. ``interpret`` runs the attention kernels' own
    bodies: both geometries' row writes, the two plans, the sink."""
    with force_kernel_backend(backend):
        cache, logits, _ = _prefill(params, tokens[:PROMPT], [16, PROMPT],
                                    bucket=16)
        np.testing.assert_allclose(logits, want[PROMPT - 1], atol=ATOL)
        # slot 2 holds another request's rows, which no step may touch
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
            named = dict(zip(serving.COUNTERS, (int(c) for c in counts)))
            assert named["window_positions_read"] == W * 4 * 4
            assert named["full_positions_read"] == MAX_SEQ * 2 * 2
            assert named["moe_layer_steps"] == CFG.num_routed_layers
    for k in cache:
        np.testing.assert_array_equal(np.asarray(cache[k][:, 2]), held[k])
        assert not np.asarray(cache[k][:, 0]).any()


def test_a_step_before_the_ring_is_full_reads_what_is_written(params, tokens,
                                                              want):
    """A prompt of 3 and then 4 steps: the ring holds 4 to 7 rows, the
    rest of it zeros from another time, masked by the ring's length."""
    with force_kernel_backend("interpret"):
        cache, _, _ = _prefill(params, tokens[:PROMPT], [PROMPT])  # stale
        cache, logits, _ = _prefill(params, tokens[:3], [3], bucket=16,
                                    cache=cache)
        np.testing.assert_allclose(logits, want[2], atol=ATOL)
        for p in range(3, 7):
            cache, logits, counts = serving.decode_step(
                CFG, params, cache, jnp.asarray([0, int(tokens[p]), 0]),
                jnp.asarray([0, p, 0]), jnp.asarray([False, True, False]))
            np.testing.assert_allclose(np.asarray(logits[1]), want[p],
                                       atol=ATOL)


def test_a_burst_is_its_steps_and_keeps_idle_slots_rows(params, tokens):
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
    named = dict(zip(serving.COUNTERS, (int(c) for c in counts)))
    assert named["moe_layer_steps"] == 4 * CFG.num_routed_layers
    assert named["window_positions_read"] == 4 * W * 4 * 4
    for leaf in burst:
        np.testing.assert_allclose(np.asarray(burst[leaf]),
                                   np.asarray(cache[leaf]), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(burst[leaf][:, 2]),
                                      held[leaf])


def test_a_ring_s_size_does_not_depend_on_the_line_s_length():
    short = jax.eval_shape(lambda: serving.init_cache(CFG, 4, 64))
    long = jax.eval_shape(lambda: serving.init_cache(CFG, 4, 256))
    assert short["ring"].shape == long["ring"].shape == (4, 4, 4, W, 48)
    assert short["kv"].shape == (2, 4, 2, 64, 48)
    assert long["kv"].shape == (2, 4, 2, 256, 48)
    full = MimoConfig(num_layers=7, expert_shards=16)
    cell = jax.eval_shape(lambda: serving.init_cache(full, 24, 32768))
    assert cell["kv"].shape == (2, 24, 4, 32768, 384)
    assert cell["ring"].shape == (5, 24, 8, 128, 384)
    # a step's fetch of a full line's block: 4 heads x 512 x 768 bytes
    assert serving.full_kv_block(full, 32768) == 512
    assert serving.full_kv_block(CFG, 64) == 64


# ---- through the scheduler ---------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    eng = LLMEngine(LLMConfig(model=MimoConfig.tiny(max_seq_len=MAX_SEQ),
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
    assert (stats["window_lines"], stats["full_lines"], stats["window"],
            stats["window_kv_heads"], stats["full_kv_heads"],
            stats["kv_row_lanes"], stats["moe_experts_held"]) == \
        (4, 2, 8, 4, 2, 48, 16)
    tokens_in = sum(map(len, prompts))
    # a token a request comes from prefill, the others from decode steps
    assert stats["moe_picks"] == (tokens_in + 5 * 11) * 4 * 5
    assert stats["moe_picks_local"] == stats["moe_picks"]
    chunks = sum(-(-len(p) // 16) for p in prompts)
    assert stats["prefill_chunks"] == chunks
    assert stats["window_positions_read"] == (chunks + 5 * 11) * W * 4 * 4
    assert stats["attn_positions_read"] == stats["window_positions_read"] \
        + stats["full_positions_read"]
    assert stats["prefix_hits"] == 0


def test_a_common_prefix_is_not_adopted(engine):
    """A ring at an earlier length is nowhere: two prompts with a long
    common prefix are both prefilled whole."""
    before = engine.stats()
    base = list(range(300, 332))
    for tail in ([7, 8, 9], [10, 11]):
        engine.generate(base + tail, SamplingParams(max_tokens=2))
    after = engine.stats()
    assert after["prefix_hits"] == before["prefix_hits"] == 0
    assert after["moe_picks"] - before["moe_picks"] == (35 + 34 + 2) * 4 * 5


@pytest.mark.parametrize("bad,match", [
    ({"tensor_parallel_size": 2}, "tensor_parallel_size"),
    ({"speculative_model": MimoConfig.tiny()}, "speculative draft")])
def test_what_it_does_not_run_is_refused_at_construction(bad, match):
    with pytest.raises(ValueError, match=match):
        LLMEngine(LLMConfig(model=MimoConfig.tiny(), max_num_seqs=2,
                            max_seq_len=64, dtype="float32", **bad))


def test_the_block_pool_is_refused_by_the_model_too():
    with pytest.raises(ValueError, match="kv_block_size"):
        serving.SERVED.refuse(replace(
            LLMConfig(model=MimoConfig.tiny()), kv_block_size=16))


def test_the_reference_pads_a_long_sequence_and_gives_the_rows_asked_for(
        params):
    """A sequence longer than one query block is padded inside the reference
    to whole blocks; the padding is after every position that was asked
    for, which no earlier position sees: 515 rows come back, on the host,
    and they are the program's. A window layer's block is given only the
    keys its mask can show."""
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (515,), 259,
                                           CFG.vocab_size), np.int32)
    assert 515 > reference.QUERY_BLOCK
    got = reference_logits(CFG, params, tokens)
    assert got.shape == (515, CFG.vocab_size) and isinstance(got, np.ndarray)
    np.testing.assert_allclose(forward(CFG, params, tokens), got, atol=ATOL)
