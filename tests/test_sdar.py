"""The SDAR-MoE family: ``models/sdar.py`` and ``llm/sdar_serving.py``
through the one ``llm/engine.py``, against the plain reference of the
benchmark, at a small size on the CPU.

What is held here is what the family adds to the repository: a step that
decides a block of 4 positions by 4 forwards (the scheduler counts in such
steps, a prompt's tail rides into the first one, a request's surplus is not
emitted, and a burst hands its last block to the next burst's first forward,
which commits it), a prefill that yields no token, the block-causal mask in
both attention ops, and the three rules by which a block's positions take
their tokens.
"""

import os
import sys
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm import sdar_serving as serving
from ray_tpu.llm.config import SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.served import (
    require_kv_handoff,
    sample_tokens,
    served_model,
)
from ray_tpu.models import sdar
from ray_tpu.models.sdar import RULES, SdarConfig
from ray_tpu.ops.decode_attention import (
    decode_attention,
    decode_attention_reference,
    decode_plan_of,
)
from ray_tpu.ops.kernels import force_kernel_backend
from ray_tpu.ops.prefill_attention import (
    prefill_attention,
    prefill_attention_reference,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import sdar as reference  # noqa: E402
from rtbench.adapters import sdar as adapter  # noqa: E402

CFG = SdarConfig.tiny(max_seq_len=64)
MASK = CFG.mask_token_id
SLOTS, MAX_SEQ, CHUNK = 3, 64, 16


def config_json(cfg: SdarConfig) -> dict:
    """The benchmark's configuration keys for ``cfg``."""
    return {"hidden_size": cfg.hidden_size, "head_dim": cfg.head_dim,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "block_length": cfg.block_length,
            "denoising_steps": cfg.denoising_steps,
            "remasking_strategy": cfg.remasking_strategy,
            "confidence_threshold": cfg.confidence_threshold,
            "mask_token_id": cfg.mask_token_id}


@pytest.fixture(scope="module", autouse=True)
def release_the_compiled_programs():
    """After the module: a compiled program keeps its memory mappings for as
    long as JAX's caches hold it, and a worker of the suite that never lets
    one go runs into ``vm.max_map_count`` (tests/test_granite.py, PERF.md
    section 7: a worker died under this file in two whole runs)."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def params():
    return sdar.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def weights(params):
    return adapter.reference_weights(params)


def _prompt(n: int, salt: int = 0) -> list[int]:
    return [int(t) for t in np.random.RandomState(100 * salt + n).randint(
        259, CFG.vocab_size, n)]


def _want(weights, rule, prompt, n):
    return reference.generate(config_json(CFG), weights, prompt, n, rule)


# ---- the model --------------------------------------------------------------

def test_forward_matches_the_reference(params, weights):
    tokens = np.asarray(_prompt(23), np.int32)
    got, counts = jax.jit(sdar.forward, static_argnums=0)(
        CFG, params, jnp.asarray(tokens)[None])
    want = reference.forward(config_json(CFG), weights, tokens)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-4)
    # every row of every layer picks its experts, all of them held here
    assert int(counts[0]) == int(counts[1]) == \
        23 * CFG.num_experts_per_tok * CFG.num_layers
    assert int(counts[4]) == CFG.num_layers


def test_the_mask_lets_a_block_see_itself_and_nothing_after(params):
    """Changing a token changes the logits of its own block's rows and of
    every later row, and of no row of an earlier block."""
    fwd = jax.jit(sdar.forward, static_argnums=0)
    tokens = np.asarray(_prompt(16), np.int32)
    base = np.asarray(fwd(CFG, params, jnp.asarray(tokens)[None])[0][0])
    tokens[9] = (tokens[9] + 1) % CFG.vocab_size           # block 2: 8..11
    moved = np.abs(np.asarray(
        fwd(CFG, params, jnp.asarray(tokens)[None])[0][0]) - base).max(-1)
    assert not moved[:8].any()
    assert (moved[8:] > 1e-6).all()


def test_the_published_defaults_and_the_rule():
    cfg = SdarConfig()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim) == (48, 2048, 32, 4, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.vocab_size) == (128, 8, 768, 151936)
    assert (cfg.block_length, cfg.denoising_steps, cfg.opened_a_forward) == \
        (4, 4, 1)
    # the rule states the rows a forward can read: what `sequential` opens,
    # every row where confidences decide
    assert (cfg.read_a_forward, cfg.reads_confidence) == (1, False)
    assert replace(cfg, denoising_steps=2).read_a_forward == 2
    for rule in RULES[1:]:
        assert (replace(cfg, remasking_strategy=rule).read_a_forward,
                replace(cfg, remasking_strategy=rule).reads_confidence) == \
            (4, True)
    rule = cfg.router_rule
    assert (rule.score, rule.use_bias, rule.renormalize, rule.renorm_eps,
            rule.outputs, rule.topk, rule.held) == \
        ("softmax", False, True, 0.0, 128, 8, 128)
    # 30.5B parameters published; a layer is 623,120,640
    assert cfg.num_params() == 48 * 623_120_640 + 2 * 311_164_928 + 2048
    assert replace(cfg, num_layers=6).num_params() == 4_361_055_744


@pytest.mark.parametrize("kw,message", [
    ({"remasking_strategy": "random"}, "remasking_strategy"),
    ({"denoising_steps": 3}, "do not divide"),
    ({"mask_token_id": 512}, "outside the vocabulary"),
], ids=["rule", "steps", "mask"])
def test_a_configuration_that_is_no_sdar_is_refused_at_construction(
        kw, message):
    with pytest.raises(ValueError, match=message):
        SdarConfig.tiny(**kw)


def _open(rule, confidence, is_open, **kw):
    cfg = SdarConfig.tiny(remasking_strategy=rule, **kw)
    return np.asarray(sdar.open_positions(
        cfg, jnp.asarray(confidence, jnp.float32),
        jnp.asarray(is_open))).astype(int).tolist()


def test_the_three_rules_open_what_they_say():
    conf = [[0.2, 0.95, 0.5, 0.97], [0.3, 0.3, 0.1, 0.3]]
    is_open = [[True, True, False, True], [False, True, True, True]]
    assert _open("sequential", conf, is_open) == [[1, 0, 0, 0], [0, 1, 0, 0]]
    # the highest confidence among the open; the earlier of two equal
    assert _open("low_confidence_static", conf, is_open) == \
        [[0, 0, 0, 1], [0, 1, 0, 0]]
    # every open position over the threshold, the static choice where none
    assert _open("low_confidence_dynamic", conf, is_open) == \
        [[0, 1, 0, 1], [0, 1, 0, 0]]
    # two a forward: the two leftmost, the two best
    assert _open("sequential", conf, is_open, denoising_steps=2) == \
        [[1, 1, 0, 0], [0, 1, 1, 0]]
    assert _open("low_confidence_static", conf, is_open,
                 denoising_steps=2) == [[0, 1, 0, 1], [0, 1, 0, 1]]
    # nothing open: nothing opened
    assert _open("low_confidence_static", conf, [[False] * 4] * 2) == \
        [[0] * 4] * 2


def test_the_rows_read_are_the_leftmost_open_ones():
    is_open = jnp.asarray([[False, True, False, True], [False] * 4,
                           [True] * 4, [False, False, False, True]])
    one = SdarConfig.tiny()
    two = SdarConfig.tiny(denoising_steps=2)
    assert np.asarray(sdar.read_positions(one, is_open)).tolist() == \
        [[1], [0], [0], [3]]
    read = sdar.read_positions(two, is_open)
    assert np.asarray(read).tolist() == [[1, 3], [0, 0], [0, 1], [3, 0]]
    # what `sequential` opens lies among them, and gets its own row's value
    rows = jnp.asarray([[10, 11], [20, 21], [30, 31], [40, 41]])
    placed = np.asarray(sdar.at_positions(is_open, rows))
    take = np.asarray(sdar.open_positions(two, None, is_open))
    assert take.astype(int).tolist() == \
        [[0, 1, 0, 1], [0] * 4, [1, 1, 0, 0], [0, 0, 0, 1]]
    assert placed[take].tolist() == [10, 11, 30, 31, 40]
    # a line with none open takes nothing, whatever its rows chose
    assert not take[1].any()
    # a rule that reads confidences reads every row: nothing to gather
    for rule in RULES[1:]:
        cfg = SdarConfig.tiny(remasking_strategy=rule)
        assert sdar.read_positions(cfg, is_open) is None
    whole = jnp.arange(16).reshape(4, 4)
    assert sdar.at_positions(is_open, whole) is whole


# ---- the two attention ops --------------------------------------------------

def _dense_attention(q, k, v, visible):
    """softmax(q k / sqrt(d)) v under a dense mask, in float64: q
    [H, R, D], k and v [Hkv, S, D], visible [R, S]."""
    h, hkv = q.shape[0], k.shape[0]
    k, v = (np.repeat(np.asarray(a, np.float64), h // hkv, axis=0)
            for a in (k, v))
    s = np.einsum("hrd,hsd->hrs", np.asarray(q, np.float64), k) \
        / np.sqrt(q.shape[-1])
    s = np.where(visible[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hrs,hsd->hrd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("block", [1, 4])
def test_prefill_attention_s_diagonal_in_blocks(backend, block):
    """A chunk of 16 after 8 cached rows, of a prompt of 20: query t sees
    the keys through its block's end and below the prompt's length. At
    ``block`` 1 that is the causal mask, by the same code."""
    h, hkv, d, c, s, kv_len, length = 4, 2, 16, 16, 64, 8, 20
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (h, c, d), jnp.float32)
    k_cache = jax.random.normal(key[1], (2, SLOTS, hkv, s, d), jnp.float32)
    v_cache = jax.random.normal(key[2], (2, SLOTS, hkv, s, d), jnp.float32)
    qpos = kv_len + np.arange(c)
    visible = ((np.arange(s)[None] // block <= qpos[:, None] // block)
               & (np.arange(s)[None] < length))
    want = _dense_attention(q, k_cache[1, 2], v_cache[1, 2], visible)
    with force_kernel_backend(backend):
        got = prefill_attention(q, k_cache, v_cache, 1, 2, kv_len, length,
                                block=block, block_k=16)
    rows = length - kv_len      # the padded rows past the prompt mean nothing
    np.testing.assert_allclose(np.asarray(got)[:, :rows], want[:, :rows],
                               atol=1e-5)
    if block == 1:
        with force_kernel_backend(backend):
            default = prefill_attention(q, k_cache, v_cache, 1, 2, kv_len,
                                        length, block_k=16)
        np.testing.assert_array_equal(np.asarray(default), np.asarray(got))
        np.testing.assert_array_equal(
            np.asarray(prefill_attention_reference(
                q, k_cache, v_cache, 1, 2, kv_len, length)),
            np.asarray(prefill_attention_reference(
                q, k_cache, v_cache, 1, 2, kv_len, length, block=1)))


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_four_rows_a_line_all_see_their_block_through_decode_attention(
        backend):
    """The mask's position at the block's last: every one of the 4 rows of
    a line sees the line through the block's end, a line of length 0
    nothing."""
    b, h, hkv, d, k, s = 3, 4, 2, 16, 4, 64
    key = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(key[0], (b, h, k, d), jnp.float32)
    k_cache = jax.random.normal(key[1], (2, b, hkv, s, d), jnp.float32)
    v_cache = jax.random.normal(key[2], (2, b, hkv, s, d), jnp.float32)
    start = jnp.asarray([12, 0, 40], jnp.int32)
    write = jnp.asarray([True, True, False])
    lengths = jnp.where(write, start + k, 0)
    with force_kernel_backend(backend):
        got = np.asarray(decode_attention(q, k_cache, v_cache, 1, lengths,
                                          start + (k - 1), block=16))
    for line in range(2):
        visible = np.broadcast_to(
            np.arange(s)[None] < int(lengths[line]), (k, s))
        # rows of the op are [H, K, D]; of the dense form, head-major too
        want = _dense_attention(q[line], k_cache[1, line], v_cache[1, line],
                                visible)
        np.testing.assert_allclose(got[line], want, atol=1e-5)
    assert not got[2].any()
    np.testing.assert_allclose(
        got, np.asarray(decode_attention_reference(
            q, k_cache, v_cache, 1, lengths, start + (k - 1))), atol=1e-5)


# ---- the programs -----------------------------------------------------------

def _prefill(params, tokens, slot=0, cache=None, chunk=CHUNK):
    """The whole blocks of ``tokens`` into ``slot``, chunk by chunk."""
    cache = cache if cache is not None else serving.init_kv_cache(
        CFG, SLOTS, MAX_SEQ)
    whole = len(tokens) - len(tokens) % CFG.block_length
    for at in range(0, whole, chunk):
        toks = np.zeros((chunk,), np.int32)
        take = min(chunk, whole - at)
        toks[:take] = tokens[at:at + take]
        cache, logits, counts = serving.prefill_chunk(
            CFG, params, cache, jnp.asarray(toks), jnp.int32(at),
            jnp.int32(whole), jnp.int32(slot))
        assert logits is None and counts.shape == (len(serving.COUNTERS),)
    return cache


def _burst(params, cache, given: dict, starts: dict, steps, cfg=CFG,
           temps=None, top_ps=None, burst=None, pending=None, slots=SLOTS):
    """A burst over the lines of ``starts`` (slot -> block start); ``given``
    (slot -> the tokens its first block has decided); ``pending`` (slot ->
    the block before it, decided and not committed: no line has one where
    None, and a row of ``tokens [slots, K]`` (a burst's last) stands for
    every line of ``starts``). Greedy unless ``temps`` [slots] says
    otherwise; ``burst`` stands in for the program."""
    k = cfg.block_length
    tok = np.full((slots, k), -1, np.int32)
    pos = np.zeros((slots,), np.int32)
    write = np.zeros((slots,), bool)
    for slot, start in starts.items():
        pos[slot], write[slot] = start, True
        tok[slot, :len(given.get(slot, []))] = given.get(slot, [])
    if not isinstance(pending, dict):
        pending = {} if pending is None else {
            slot: np.asarray(pending)[slot] for slot in starts}
    last, has = np.zeros((slots, k), np.int32), np.zeros((slots,), bool)
    for slot, block in pending.items():
        last[slot], has[slot] = block, True
    zeros, ones = jnp.zeros((slots,)), jnp.ones((slots,))
    return (burst or serving.decode_burst)(
        cfg, params, cache,
        (jnp.asarray(tok), jnp.asarray(last), jnp.asarray(has)),
        jnp.asarray(pos), jnp.asarray(write),
        zeros if temps is None else jnp.asarray(temps),
        ones if top_ps is None else jnp.asarray(top_ps),
        jax.random.PRNGKey(0), steps, top_ps is not None)


def _commit(params, cache, blocks: dict, starts: dict, cfg=CFG, slots=SLOTS):
    """A commit forward of its own, as every block had one before a commit
    could ride: the clean ``blocks`` (slot -> tokens) at ``starts`` once
    through the stack, their K/V left in the cache."""
    k = cfg.block_length
    tok = np.zeros((slots, k), np.int32)
    pos = np.zeros((slots,), np.int32)
    write = np.zeros((slots,), bool)
    for slot, start in starts.items():
        tok[slot], pos[slot], write[slot] = blocks[slot], start, True
    plan = decode_plan_of(jnp.where(jnp.asarray(write),
                                    jnp.asarray(pos) + k, 0), cache["k"])
    return serving._forward(cfg, params, cache, jnp.asarray(tok),
                            jnp.asarray(pos), jnp.asarray(write), plan)[0]


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_a_burst_decides_blocks_as_the_reference_does_and_commits_them(
        params, weights, backend):
    """Two lines at different depths and an idle one in one burst of two
    blocks, the kernels' own bodies included: the tokens are the
    reference's, the idle line's cache is untouched, a line's K/V through
    its first block are those of the clean pass over what it now holds, and
    its last block's are once the next burst has taken that block in."""
    a, b = _prompt(22), _prompt(9, salt=1)
    with force_kernel_backend(backend):
        cache = _prefill(params, a, slot=0)
        cache = _prefill(params, b, slot=2, cache=cache)
        cache = _prefill(params, _prompt(12, salt=2), slot=1, cache=cache)
        held = np.asarray(cache["k"][:, 1]), np.asarray(cache["v"][:, 1])
        cache, toks, counts = _burst(params, cache, {0: a[20:], 2: b[8:]},
                                     {0: 20, 2: 8}, steps=2)
        toks = np.asarray(toks)
        assert toks.shape == (2, SLOTS, 4)
        out_a = toks[:, 0].reshape(-1).tolist()
        out_b = toks[:, 2].reshape(-1).tolist()
        # a first block gives the prompt's tail back in its places
        assert out_a[:2] == a[20:] and out_b[:1] == b[8:]
        assert out_a[2:] == _want(weights, "sequential", a, 6)
        assert out_b[1:] == _want(weights, "sequential", b, 7)
        np.testing.assert_array_equal(np.asarray(cache["k"][:, 1]), held[0])
        np.testing.assert_array_equal(np.asarray(cache["v"][:, 1]), held[1])
        # the next burst's first forward commits the block handed to it
        later, more, _ = _burst(params, jax.tree.map(jnp.copy, cache), {},
                                {0: 28, 2: 16}, steps=1, pending=toks[-1])
        out_a += np.asarray(more)[0, 0].tolist()
        assert out_a[2:] == _want(weights, "sequential", a, 10)
        # the clean pass over the whole of line 0, into the idle slot
        clean = _prefill(params, a[:20] + out_a, slot=1,
                         cache=jax.tree.map(jnp.copy, later), chunk=32)
    for leaf in ("k", "v"):
        want = np.asarray(clean[leaf][:, 1])
        np.testing.assert_allclose(np.asarray(cache[leaf][:, 0, :, :24]),
                                   want[:, :, :24], atol=1e-5)
        # the burst's last block stood as its last denoising forward left
        # it (a position still masked), until the next burst came
        assert np.abs(np.asarray(cache[leaf][:, 0, :, 24:28])
                      - want[:, :, 24:28]).max() > 1e-3
        np.testing.assert_allclose(np.asarray(later[leaf][:, 0, :, :28]),
                                   want[:, :, :28], atol=1e-5)
    counts = dict(zip(serving.COUNTERS, np.asarray(counts).tolist()))
    # two lines x two blocks; a line's first commit rode its second block's
    # first forward, its second is the next burst's, and its first block's
    # first forward carried a dead half: 4 + 8 + 6 x 4 rows a line
    assert counts["diffusion_blocks"] == 4
    assert counts["diffusion_commits"] == 0
    assert counts["diffusion_commits_riding"] == 2
    assert counts["diffusion_forwards"] == 16 and counts["diffusion_given"] == 3
    assert counts["moe_layer_steps"] == 8 * CFG.num_layers
    assert counts["moe_picks"] == \
        2 * 36 * CFG.num_experts_per_tok * CFG.num_layers


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_a_burst_leaves_what_its_blocks_leave_one_by_one(params, weights,
                                                         backend, steps):
    """The commits that ride the next block's first forward are the commits,
    across bursts too: a chain of bursts of 1, 2 or 4 blocks, each handed
    the last block of the one before, gives the reference's tokens, and the
    tokens and every committed block's K/V of the same four blocks run one
    at a time, each committed by a forward of its own; the idle line's
    cache is untouched by either."""
    a, b = _prompt(22), _prompt(9, salt=1)
    given, starts, blocks = {0: a[20:], 2: b[8:]}, {0: 20, 2: 8}, 4
    totals = np.zeros((len(serving.COUNTERS),), np.int64)
    with force_kernel_backend(backend):
        cache = _prefill(params, b, slot=2, cache=_prefill(params, a))
        alone, want = jax.tree.map(jnp.copy, cache), []
        for j in range(blocks):
            at = {slot: start + 4 * j for slot, start in starts.items()}
            alone, toks, _ = _burst(params, alone, given if j == 0 else {},
                                    at, steps=1)
            want.append(np.asarray(toks)[0])
            alone = _commit(params, alone, want[-1], at)
        got, last = [], None
        for j in range(0, blocks, steps):
            cache, toks, counts = _burst(
                params, cache, given if j == 0 else {},
                {slot: start + 4 * j for slot, start in starts.items()},
                steps=steps, pending=last)
            got += list(np.asarray(toks))
            last, totals = np.asarray(toks)[-1], totals + np.asarray(counts)
    got, want = np.stack(got), np.stack(want)
    np.testing.assert_array_equal(got[:, [0, 2]], want[:, [0, 2]])
    assert got[:, 0].reshape(-1)[2:].tolist() == \
        _want(weights, "sequential", a, 14)
    assert got[:, 2].reshape(-1)[1:].tolist() == \
        _want(weights, "sequential", b, 15)
    for leaf in ("k", "v"):
        for slot, at in starts.items():
            # every block but the chain's last, which nobody has committed
            np.testing.assert_allclose(
                np.asarray(cache[leaf][:, slot, :, :at + 4 * (blocks - 1)]),
                np.asarray(alone[leaf][:, slot, :, :at + 4 * (blocks - 1)]),
                atol=1e-5)
        assert not np.asarray(cache[leaf][:, 1]).any()
    counts = dict(zip(serving.COUNTERS, totals.tolist()))
    assert counts["diffusion_blocks"] == 2 * blocks
    assert counts["diffusion_commits_riding"] == 2 * (blocks - 1)
    assert counts["diffusion_commits"] == 0
    assert counts["moe_layer_steps"] == (blocks // steps) * \
        sum(serving.burst_forwards(CFG, steps)) * CFG.num_layers


def _lines_kv(cache, slot):
    return [np.asarray(cache[leaf][:, slot]) for leaf in ("k", "v")]


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_a_dead_clean_half_touches_nothing(params, weights, backend):
    """Beside a line with a block pending: a line fresh from its prefill, a
    line whose prompt is shorter than a block (it stands at position 0, its
    dead half before the line) and a slot's new tenant (the slot's cache
    and the pending row hold the old tenant's) go through the same wide
    first forward. Each line's tokens are the reference's and its K/V those
    of the line run alone; the prompt's last whole block is bit-equal
    before and after; and whatever stands in a dead half's ``pending`` row,
    every line's tokens and the whole cache come out bit-equal."""
    slots = 4
    a, b, c, d = (_prompt(22), _prompt(9, salt=1), _prompt(3, salt=2),
                  _prompt(14, salt=3))
    old = _prompt(21, salt=4)
    with force_kernel_backend(backend):
        cache = serving.init_kv_cache(CFG, slots, MAX_SEQ)
        # slot 3's old tenant, then line a, each through a burst of two
        cache = _prefill(params, old, slot=3, cache=_prefill(params, a,
                                                             cache=cache))
        cache, toks, _ = _burst(params, cache, {0: a[20:], 3: old[20:]},
                                {0: 20, 3: 20}, steps=2, slots=slots)
        last = np.asarray(toks)[-1]
        out_a = np.asarray(toks)[:, 0].reshape(-1).tolist()
        # the new tenant's prompt over the old line; b's; c has no block
        cache = _prefill(params, d, slot=3, cache=cache)
        cache = _prefill(params, b, slot=1, cache=cache)
        before = {slot: _lines_kv(cache, slot) for slot in range(slots)}
        given = {1: b[8:], 2: c, 3: d[12:]}
        starts = {0: 28, 1: 8, 2: 0, 3: 12}

        def run(junk):
            rows = np.full((slots, 4), junk, np.int32)
            # a's own last block, and what the burst before left at slot 3
            rows[0], rows[3] = last[0], last[3]
            tok = np.full((slots, 4), -1, np.int32)
            for slot, tail in given.items():
                tok[slot, :len(tail)] = tail
            return serving.decode_burst(
                CFG, params, jax.tree.map(jnp.copy, cache),
                (jnp.asarray(tok), jnp.asarray(rows),
                 jnp.asarray([True, False, False, False])),
                jnp.asarray([starts[s] for s in range(slots)], jnp.int32),
                jnp.ones((slots,), bool), jnp.zeros((slots,)),
                jnp.ones((slots,)), jax.random.PRNGKey(0), 2, False)

        after, got, counts = run(0)
        other, got_other, _ = run(MASK)
        got = np.asarray(got)
        np.testing.assert_array_equal(got, np.asarray(got_other))
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(after[leaf]),
                                          np.asarray(other[leaf]))
        # each line alone in the same cache, the others idle
        for slot in range(slots):
            alone, toks, _ = _burst(
                params, jax.tree.map(jnp.copy, cache),
                {slot: given.get(slot, [])}, {slot: starts[slot]}, steps=2,
                pending={0: last[0]} if slot == 0 else None, slots=slots)
            np.testing.assert_array_equal(np.asarray(toks)[:, slot],
                                          got[:, slot])
            for mine, theirs in zip(_lines_kv(after, slot),
                                    _lines_kv(alone, slot)):
                np.testing.assert_allclose(mine, theirs, atol=1e-5)
    for slot, prompt, n in ((1, b, 7), (2, c, 5), (3, d, 6)):
        out = got[:, slot].reshape(-1).tolist()
        assert out[:len(given[slot])] == given[slot]
        assert out[len(given[slot]):] == _want(weights, "sequential",
                                               prompt, n)
    assert (out_a + got[:, 0].reshape(-1).tolist())[2:] == \
        _want(weights, "sequential", a, 14)
    for slot, at in starts.items():
        # a burst writes its own two blocks' positions, the pending line
        # its pending block's too, and nothing else of any line: the
        # prompt's last whole block least of all
        wrote = slice(at - 4 if slot == 0 else at, at + 8)
        for was, now in zip(before[slot], _lines_kv(after, slot)):
            was, now = was.copy(), now.copy()
            was[:, :, wrote], now[:, :, wrote] = 0, 0
            np.testing.assert_array_equal(was, now)
    counts = dict(zip(serving.COUNTERS, np.asarray(counts).tolist()))
    # the one pending block, and every line's first block inside the burst
    assert counts["diffusion_commits_riding"] == 1 + 4
    assert counts["diffusion_commits"] == 0
    assert counts["diffusion_blocks"] == 8
    assert counts["diffusion_forwards"] == 32
    # rows routed: 3 dead halves of 4 rows are not
    assert counts["moe_picks"] == (4 * (8 + 3 * 4 + 8 + 3 * 4) - 3 * 4) * \
        CFG.num_experts_per_tok * CFG.num_layers


@pytest.mark.parametrize("pending", [False, True], ids=["fresh", "pending"])
@pytest.mark.parametrize("steps", [1, 2, 3])
@pytest.mark.parametrize("rule,rows", [("sequential", 2),
                                       ("low_confidence_static", 4)])
def test_the_counters_count_the_forwards_that_ran(params, rule, rows, steps,
                                                  pending):
    """Forwards are counted where a forward runs and commits where the
    commit does: at 2 denoising forwards a block a burst of n blocks reads
    2 n forwards a line, none of them a commit of its own, n - 1 that
    carried the commit of the block before and one more where the line
    came with a block pending, with no edit of a formula. The head's rows
    are counted where the head runs: lines x the rows the rule can read x
    denoising forwards, the idle line's none."""
    cfg = replace(CFG, denoising_steps=2, remasking_strategy=rule)
    a, b = _prompt(13), _prompt(18, salt=1)
    cache = _prefill(params, b, slot=2, cache=_prefill(params, a))
    _, _, counts = _burst(
        params, cache, {0: a[12:], 2: b[16:]}, {0: 12, 2: 16}, steps=steps,
        cfg=cfg, pending={0: a[8:12], 2: b[12:16]} if pending else None)
    counts = dict(zip(serving.COUNTERS, np.asarray(counts).tolist()))
    assert counts["diffusion_blocks"] == 2 * steps
    assert counts["diffusion_commits"] == 0
    assert counts["diffusion_commits_riding"] == 2 * (steps - 1 + pending)
    assert counts["diffusion_forwards"] == 2 * (2 * steps)
    assert sum(serving.burst_forwards(cfg, steps)) == 2 * steps
    assert counts["moe_layer_steps"] == 2 * steps * cfg.num_layers
    assert counts["diffusion_head_rows"] == 2 * rows * (2 * steps)
    assert counts["diffusion_head_rows"] == rows * (
        counts["diffusion_forwards"] - counts["diffusion_commits"])
    # the rows routed: a forward's 4 a line, 4 more where a commit rode
    assert counts["moe_picks"] == 4 * (
        counts["diffusion_forwards"] + counts["diffusion_commits_riding"]) \
        * cfg.num_experts_per_tok * cfg.num_layers


def test_the_head_s_rows_are_a_metric_of_the_cell():
    """``diffusion_head_rows_per_forward``: a data file over the reader the
    benchmark has, read from ``BENCHMARK.json`` as the harness reads it."""
    from rtbench import manifest

    cell = manifest.load_cell("sdar-30b-serve-generate-512", REPO)
    spec = next(x for x in cell["per_layer"]
                if x["name"] == "diffusion_head_rows_per_forward")
    commit = next(x for x in cell["per_layer"]
                  if x["name"] == "diffusion_commit_share")
    assert spec["reader"] == "counter_ratio"
    assert spec["params"] == {"num": "diffusion_head_rows",
                              "den": "diffusion_forwards"}
    assert {spec["params"]["num"], spec["params"]["den"]} <= \
        set(serving.COUNTERS)
    assert (spec["moves"], spec["better"], spec["source"], spec["unit"]) == \
        ("serve_tok_s", "lower", "program_counter", "rows")
    assert spec["layer"] == commit["layer"]
    assert spec["workloads"] == ["sdar-30b-serve-generate-512"]
    assert manifest.check(manifest.load(REPO), REPO) == []
    # 4 denoising forwards of 1 row and a commit of none: 0.8 a forward
    from rtbench.readers import counter_ratio

    polls = [(t, {"diffusion_head_rows": 4 * n, "diffusion_forwards": 5 * n})
             for t, n in ((1.0, 10), (2.0, 30))]
    obs = {"polls": polls, "t_open": 0.0, "t_close": 3.0}
    assert counter_ratio.read(obs, spec["params"]) == 0.8
    # a program without the counter (the parent) reads nothing
    assert counter_ratio.read(
        {**obs, "polls": [(t, {"diffusion_forwards": 5}) for t, _ in polls]},
        spec["params"]) is None


def test_the_riding_share_is_a_metric_of_the_cell():
    """``diffusion_commit_riding_share`` (PR 63): a data file over the
    reader the benchmark has. Of the blocks run in a window, those whose
    commit rode a forward: 1 in 2 where only a burst of 2's first block's
    does (PR 61: 88.9 in the cell once first blocks are rare), every block
    but a line's last once bursts hand their last block on."""
    from rtbench import manifest
    from rtbench.readers import counter_ratio

    cell = manifest.load_cell("sdar-30b-serve-generate-512", REPO)
    spec = next(x for x in cell["per_layer"]
                if x["name"] == "diffusion_commit_riding_share")
    commit = next(x for x in cell["per_layer"]
                  if x["name"] == "diffusion_commit_share")
    assert spec["reader"] == "counter_ratio"
    assert spec["params"] == {"num": "diffusion_commits_riding",
                              "den": "diffusion_blocks", "scale": 100.0}
    assert {spec["params"]["num"], spec["params"]["den"]} <= \
        set(serving.COUNTERS)
    assert (spec["moves"], spec["better"], spec["source"], spec["unit"]) == \
        ("serve_tok_s", "higher", "program_counter", "%")
    assert spec["layer"] == commit["layer"]
    assert spec["workloads"] == ["sdar-30b-serve-generate-512"]
    # appended after the share it replaced (never "the last": a later PR
    # appends its own, as PR 64 did)
    names = [x["name"] for x in manifest.load(REPO)["per_layer"]]
    assert names.index(spec["name"]) > names.index(commit["name"])
    # 128 lines, each 127 blocks of 128 with a commit riding
    polls = [(t, {"diffusion_commits_riding": 127 * n,
                  "diffusion_blocks": 128 * n})
             for t, n in ((1.0, 10), (2.0, 30))]
    obs = {"polls": polls, "t_open": 0.0, "t_close": 3.0}
    assert counter_ratio.read(obs, spec["params"]) == 100.0 * 127 / 128
    # a program without the counter (before PR 61) reads nothing
    assert counter_ratio.read(
        {**obs, "polls": [(t, {"diffusion_blocks": 5}) for t, _ in polls]},
        spec["params"]) is None


def test_open_positions_are_tracked_by_place_not_by_the_mask_s_id(params,
                                                                  weights):
    """A prompt whose tail *is* the mask id, and one with it inside."""
    a = _prompt(9)
    a[8] = MASK
    a[3] = MASK
    cache = _prefill(params, a)
    _, toks, counts = _burst(params, cache, {0: a[8:]}, {0: 8}, steps=1)
    out = np.asarray(toks)[0, 0].tolist()
    assert out[0] == MASK
    assert out[1:] == _want(weights, "sequential", a, 3)
    assert int(counts[serving.COUNTERS.index("diffusion_given")]) == 1


def _choose_every_row(logits, temps, top_ps, key, need_top_p):
    """``serving._choose`` as it stood while every row of the block went
    through the head (PR 41, 42): logits [B, K, V]."""
    b, k, v = logits.shape
    flat = logits.reshape(b * k, v)
    x0 = sample_tokens(flat, jnp.repeat(temps, k), jnp.repeat(top_ps, k), 0,
                       key, need_top_p).astype(jnp.int32)
    chosen = jnp.take_along_axis(flat, x0[:, None], axis=-1)[:, 0]
    confidence = jnp.exp(chosen - jax.nn.logsumexp(flat, axis=-1))
    return x0.reshape(b, k), confidence.reshape(b, k)


@partial(jax.jit, static_argnums=(0, 9, 10))
def _burst_every_row(cfg, params, cache, inputs, positions0, write_mask,
                     temps, top_ps, key, steps, need_top_p):
    """``decode_burst`` in plain loops as it stood before a commit could
    ride (PR 41 to 60): the head and the choice of every row of the block
    at every denoising forward, every block committed by a forward of its
    own, nothing taken in but ``token0``."""
    token0, k, out = inputs[0], cfg.block_length, []
    for j in range(steps):
        pos = positions0 + j * k
        is_open = (token0 < 0) | (j > 0)
        tokens = jnp.where(is_open, cfg.mask_token_id, token0)
        plan = decode_plan_of(jnp.where(write_mask, pos + k, 0), cache["k"])
        for d in range(cfg.denoising_steps):
            cache, x, _ = serving._forward(cfg, params, cache, tokens, pos,
                                           write_mask, plan)
            x0, confidence = _choose_every_row(
                sdar.lm_head(cfg, params, x), temps, top_ps,
                jax.random.fold_in(jax.random.fold_in(key, j), d),
                need_top_p)
            take = sdar.open_positions(cfg, confidence, is_open)
            tokens, is_open = jnp.where(take, x0, tokens), is_open & ~take
        cache, _, _ = serving._forward(cfg, params, cache, tokens, pos,
                                       write_mask, plan)
        out.append(tokens)
    return cache, jnp.stack(out), None


def _same_as_every_row(params, cfg, steps, given):
    """Two lines whose first blocks come with ``given`` and ``given + 1``
    positions decided, and an idle one: the program's tokens against the
    transcription's."""
    a, b = _prompt(8 + given), _prompt(12 + (given + 1) % 4, salt=1)
    cache = _prefill(params, b, slot=2, cache=_prefill(params, a))
    args = ({0: a[8:], 2: b[12:]}, {0: 8, 2: 12}, steps)
    _, want, _ = _burst(params, jax.tree.map(jnp.copy, cache), *args,
                        cfg=cfg, burst=_burst_every_row)
    _, got, _ = _burst(params, cache, *args, cfg=cfg)
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_array_equal(got[:, [0, 2]], want[:, [0, 2]])
    # what the prompt decided stands where it stood, through forwards in
    # which the line had nothing open any more
    assert got[0, 0, :given].tolist() == a[8:]
    assert got[0, 2, :len(b) - 12].tolist() == b[12:]
    assert (got[:, [0, 2]] != MASK).all()


@pytest.mark.parametrize("given", [0, 1, 2, 3])
@pytest.mark.parametrize("steps", [1, 2])
@pytest.mark.parametrize("rule", RULES)
def test_the_rows_read_decide_what_every_row_decided(params, rule, steps,
                                                     given):
    """The head on the rows the rule can read gives, token for token, what
    the head on every row gave."""
    _same_as_every_row(params, replace(CFG, remasking_strategy=rule), steps,
                       given)


@pytest.mark.parametrize("given", [0, 1, 2, 3])
def test_two_rows_a_line_go_back_to_their_own_positions(params, given):
    """At 2 denoising forwards a block ``sequential`` reads two rows a
    line, and a line with one position open reads one that counts."""
    _same_as_every_row(params, replace(CFG, denoising_steps=2), 2, given)


def _top_p_set(logits, temperature, top_p):
    """The ids ``sample_tokens`` may draw: the smallest prefix of the
    sorted probabilities whose mass before each is under ``top_p``."""
    z = np.asarray(logits, np.float64) / temperature
    p = np.exp(z - z.max())
    p /= p.sum()
    order = np.argsort(-p)
    before = np.cumsum(p[order]) - p[order]
    return set(order[before < top_p].tolist())


def test_a_greedy_line_beside_a_drawn_one_keeps_its_tokens(params):
    """A request's tokens do not depend on its neighbours: beside a line at
    temperature 0.8 and top-p 0.9 (so the draw is made for the batch) the
    greedy line's tokens are those of the all-greedy batch, and each of the
    other line's lies in the top-p set of the logits that chose it."""
    a, b = _prompt(9), _prompt(14, salt=1)
    cache = _prefill(params, b, slot=2, cache=_prefill(params, a))
    args = ({0: a[8:], 2: b[12:]}, {0: 8, 2: 12}, 2)
    _, cold, _ = _burst(params, jax.tree.map(jnp.copy, cache), *args)
    _, mixed, _ = _burst(params, jax.tree.map(jnp.copy, cache), *args,
                         temps=[0.0, 0.0, 0.8], top_ps=[1.0, 1.0, 0.9])
    cold, mixed = np.asarray(cold), np.asarray(mixed)
    np.testing.assert_array_equal(mixed[:, 0], cold[:, 0])
    assert mixed[0, 2, :2].tolist() == b[12:]
    # replay line 2 with its own tokens: position i of a block was chosen
    # with the positions before it decided and the rest masked
    k, write = CFG.block_length, jnp.asarray([False, False, True])
    drawn_off_the_top = 0
    for j in range(2):
        pos = jnp.asarray([0, 0, 12 + j * k], jnp.int32)
        plan = decode_plan_of(jnp.where(write, pos + k, 0), cache["k"])
        block = mixed[j, 2]
        for i in range(2 if j == 0 else 0, k):
            tokens = np.full((SLOTS, k), MASK, np.int32)
            tokens[2, :i] = block[:i]
            cache, x, _ = serving._forward(CFG, params, cache,
                                           jnp.asarray(tokens), pos, write,
                                           plan)
            logits = np.asarray(sdar.lm_head(CFG, params, x))[2, i]
            assert int(block[i]) in _top_p_set(logits, 0.8, 0.9), (j, i)
            drawn_off_the_top += int(block[i]) != int(logits.argmax())
        tokens = np.full((SLOTS, k), MASK, np.int32)
        tokens[2] = block
        cache, _, _ = serving._forward(CFG, params, cache,
                                       jnp.asarray(tokens), pos, write, plan)
    # six draws from a flat distribution: some leave the arg-max
    assert drawn_off_the_top > 0


def test_under_a_confidence_rule_no_row_is_gathered_before_the_head(params):
    """With every row read the head's program is the plain head: the same
    jaxpr, no gather in it."""
    x = jnp.zeros((SLOTS, CFG.block_length, CFG.hidden_size))
    plain = jax.make_jaxpr(lambda p, x: sdar.lm_head(CFG, p, x))(params, x)
    whole = jax.make_jaxpr(
        lambda p, x: serving._logits(CFG, p, x, None))(params, x)
    assert str(whole) == str(plain) and "gather" not in str(whole)
    read = jnp.zeros((SLOTS, 1), jnp.int32)
    some = jax.make_jaxpr(
        lambda p, x, r: serving._logits(CFG, p, x, r))(params, x, read)
    assert "gather" in str(some)
    assert some.out_avals[0].shape == (SLOTS, 1, CFG.vocab_size)


# ---- the reference's logits -------------------------------------------------

@pytest.mark.parametrize("p", [8, 10, 3])
def test_the_reference_s_logits_are_those_its_generation_chose_by(weights,
                                                                  p):
    """``logits`` is given a finished sequence and nothing else (not the
    prompt's length), padded as the harness pads it: row ``i - 1`` is the
    row that chose position ``i``'s token under the sequential rule."""
    c = config_json(CFG)
    prompt, n, trace = _prompt(p, salt=3), 9, []
    out = reference.generate(c, weights, prompt, n, "sequential", trace)
    seq = prompt + out
    rows = np.asarray(reference.logits(
        c, weights, jnp.asarray(seq + [0] * (-len(seq) % 16), jnp.int32)))
    assert len(trace) >= n
    for position, chose in trace:
        if position >= len(seq):
            continue           # the last block's surplus
        np.testing.assert_allclose(rows[position - 1], np.asarray(chose),
                                   atol=1e-4)
        assert int(rows[position - 1].argmax()) == seq[position]
    # and the harness's own comparison reads 0 on the reference's tokens
    from rtbench.kinds.serve_common import worst_margin

    def logits_of(s):
        return np.asarray(reference.logits(
            c, weights, jnp.asarray(s + [0] * (-len(s) % 16), jnp.int32)))

    assert worst_margin(prompt, out, logits_of) == 0.0


# ---- through the engine -----------------------------------------------------

def _engine(params, rule="sequential", **kw):
    base = dict(model=replace(CFG, remasking_strategy=rule), max_num_seqs=3,
                max_seq_len=MAX_SEQ, prefill_chunk=16, decode_burst=2, seed=3)
    base.update(kw)
    return LLMEngine(LLMConfig(**base), params=params)


@pytest.fixture(scope="module", params=RULES)
def engine(request, params):
    eng = _engine(params, request.param)
    yield request.param, eng
    eng.shutdown()


def test_the_engine_s_tokens_are_the_reference_s(engine, weights):
    """Prompts of every length mod 4 (one shorter than a block), answers
    that are no multiple of 4, a prompt across a chunk boundary, one that
    contains the mask id; the long one first and alone, so that the rest
    reuse its slot while several lines stand at different depths."""
    rule, eng = engine
    prompts = {"crosses": _prompt(22), "short": _prompt(3, 1),
               "whole": _prompt(8, 2), "one": _prompt(9, 3),
               "three": _prompt(11, 4), "masked": _prompt(14, 5)}
    prompts["masked"][13] = prompts["masked"][6] = MASK
    n = {"crosses": 7, "short": 5, "whole": 8, "one": 13, "three": 6,
         "masked": 10}
    first = eng.generate(prompts["crosses"],
                         SamplingParams(max_tokens=n["crosses"]))
    outs = {"crosses": first.token_ids}
    reqs = {name: eng.submit(prompts[name],
                             SamplingParams(max_tokens=n[name]), stream=True)
            for name in prompts if name != "crosses"}
    for name, req in reqs.items():
        assert req.done.wait(120), name
        assert req.error is None, req.error
        assert req.finish_reason == "length"
        outs[name] = list(req.out_tokens)
        # one frame a token, then the end
        frames = []
        while (item := req.stream_queue.get(timeout=5)) is not None:
            frames.append(item)
        assert frames == outs[name]
    for name, out in outs.items():
        assert out == _want(weights, rule, prompts[name], n[name]), name
    stats = eng.stats()
    assert stats["requests_failed"] == 0 and stats["device_failures"] == 0
    # four denoising forwards a block and none for a commit: every block's
    # rode the next block's first forward, in its burst or in the next,
    # but each line's last block's, which nobody commits (a finished
    # line's surplus blocks in a burst already queued ride on, unread)
    assert stats["diffusion_forwards"] == 4 * stats["diffusion_blocks"]
    assert stats["diffusion_commits"] == 0
    assert stats["diffusion_commits_riding"] == \
        stats["diffusion_blocks"] - len(prompts)
    # every token streamed is a decode's: prefill gives none
    assert stats["decode_tokens"] == sum(n.values())
    assert stats["first_tokens"] == len(prompts)
    # the tails of 22, 3, 9, 11 and 14: 2 + 3 + 1 + 3 + 2
    assert stats["diffusion_given"] == 11
    assert stats["prompt_tokens_prefilled"] == 20 + 0 + 8 + 8 + 8 + 12
    # a burst of n blocks is 4 n forwards
    assert stats["decode_steps"] % 4 == 0
    assert stats["decode_dispatches"] < stats["decode_steps"] // 4
    assert (stats["moe_experts_held"], stats["attention_lines"],
            stats["diffusion_block_length"]) == (8, 3, 4)
    assert stats["prefix_hits"] == 0 and eng.router_prefix_blocks() is None
    assert stats["decode_dispatches_ahead"] > 0      # bursts behind bursts


@pytest.mark.parametrize("burst,pipeline", [(1, True), (1, False),
                                            (2, False), (4, True)])
def test_every_schedule_gives_the_same_tokens(params, weights, burst,
                                              pipeline):
    """Bursts of one block and of more, behind one another or strictly
    serial: the default's tokens (bursts of two, pipelined) are held to the
    reference above, and these to it too."""
    eng = _engine(params, decode_burst=burst, decode_pipeline=pipeline)
    try:
        prompts = [_prompt(10, 6), _prompt(17, 7), _prompt(4, 8)]
        reqs = [eng.submit(p, SamplingParams(max_tokens=11))
                for p in prompts]
        for p, req in zip(prompts, reqs):
            assert req.done.wait(120) and req.error is None
            assert list(req.out_tokens) == _want(weights, "sequential", p,
                                                 11)
        stats = eng.stats()
        assert stats["decode_steps"] == 4 * burst * stats["decode_dispatches"]
        # handed over on the device or made by the host from its own
        # tokens, whatever the burst's length: every block but a line's
        # last is committed by the forward after it
        assert stats["diffusion_commits"] == 0
        assert stats["diffusion_commits_riding"] == \
            stats["diffusion_blocks"] - len(prompts)
        if not pipeline:
            assert stats["decode_dispatches_ahead"] == 0
    finally:
        eng.shutdown()


def test_the_engine_hands_a_burst_s_last_block_to_the_next(params, weights):
    """The scheduler's thread stopped and the ticks made by hand: a burst
    queued behind one in flight takes that burst's last row as it lies on
    the device (the look-ahead holds: nothing in flight is read before the
    dispatch), a line that joins from its prefill has nothing pending
    whatever the burst in flight computed for its slot, and with nothing in
    flight the host makes the pending blocks from its own tokens; either
    way the tokens are the reference's and no block pays a commit."""
    outs = {}
    for pipeline in (True, False):
        eng = _engine(params, decode_pipeline=pipeline, max_num_seqs=2)
        eng.shutdown()
        program, seen = eng.model.decode_burst, []

        def burst(cfg, params, cache, inputs, positions0, write, *rest,
                  program=program, seen=seen, eng=eng, **kw):
            _, pending, has = inputs
            prev = next((e for e in reversed(eng._in_flight) if e.steps),
                        None)
            seen.append({
                "behind": prev is not None,
                "handed_on_the_device": prev is not None
                and pending is prev.last_row,
                "has": np.asarray(has).tolist(),
                "steps_before": eng.decode_steps, "burst": rest[3]})
            return program(cfg, params, cache, inputs, positions0, write,
                           *rest, **kw)

        eng.model = replace(eng.model, decode_burst=burst)
        prompts = [_prompt(10, 6), _prompt(17, 7), _prompt(3, 8)]
        budgets = [22, 9, 10]
        reqs = [eng.submit(p, SamplingParams(max_tokens=n))
                for p, n in zip(prompts, budgets)]
        for _ in range(200):
            if all(r.done.is_set() for r in reqs):
                break
            eng._tick()
        for p, n, r in zip(prompts, budgets, reqs):
            assert r.done.is_set() and r.error is None
            assert list(r.out_tokens) == _want(weights, "sequential", p, n)
        outs[pipeline] = [list(r.out_tokens) for r in reqs]
        stats = eng.stats()
        assert stats["diffusion_commits"] == 0
        assert stats["diffusion_commits_riding"] == \
            stats["diffusion_blocks"] - len(prompts)
        # ``decode_steps`` grows by 4 forwards a block of the burst
        grown = [b["steps_before"] - a["steps_before"]
                 for a, b in zip(seen, seen[1:])]
        assert grown == [4 * a["burst"] for a in seen[:-1]]
        assert stats["decode_steps"] == 4 * sum(a["burst"] for a in seen)
        # the first burst of all finds nothing pending; the third request
        # takes a slot whose old tenant was in the burst before
        assert seen[0]["has"] == [False, False]
        assert any(a["has"] == [True, True] for a in seen)
        assert any(sorted(a["has"]) == [False, True] for a in seen[1:])
        if pipeline:
            behind = [a for a in seen if a["behind"]]
            assert behind and stats["decode_dispatches_ahead"] == len(behind)
            assert all(a["handed_on_the_device"] for a in behind)
        else:
            assert not any(a["behind"] for a in seen)
    assert outs[True] == outs[False]


def test_a_stop_token_ends_a_line_inside_its_block(params, weights):
    eng = _engine(params)
    try:
        prompt = _prompt(10, 9)
        want = _want(weights, "sequential", prompt, 12)
        stop = want[4]
        cut = want.index(stop) + 1
        req = eng.submit(prompt, SamplingParams(max_tokens=12,
                                                stop_token_ids=(stop,)))
        assert req.done.wait(120) and req.error is None
        assert req.finish_reason == "stop"
        assert list(req.out_tokens) == want[:cut]
        # sampling by temperature and top-p runs through the same programs
        hot = eng.generate(prompt, SamplingParams(max_tokens=6,
                                                  temperature=0.8, top_p=0.9))
        assert len(hot.token_ids) == 6
    finally:
        eng.shutdown()


@pytest.mark.parametrize("max_seq,tokens", [(32, 32 - 21), (30, 28 - 21)])
def test_a_line_ends_with_its_last_whole_block(params, weights, max_seq,
                                               tokens):
    """A request that would run past its cache line ends where the line's
    last whole block does: the line's last position is used (a block's
    tokens are its own positions'), a block that would cross the end is
    not begun."""
    eng = _engine(params, max_seq_len=max_seq)
    try:
        prompt = _prompt(21, 10)
        out = eng.generate(prompt, SamplingParams(max_tokens=40))
        assert out.finish_reason == "length"
        assert out.token_ids == _want(weights, "sequential", prompt, tokens)
    finally:
        eng.shutdown()


# ---- what it says of itself -------------------------------------------------

def test_the_served_model_says_what_it_is():
    served = served_model(CFG)
    assert served is serving.SERVED
    assert served.step(CFG) == (4, 4) and not served.prefill_token
    # a burst hands its last block on; so nobody else may read a line
    assert served.pending_step
    with pytest.raises(ValueError, match="pending_step"):
        replace(served, prefix_from_line=True)
    with pytest.raises(ValueError, match="pending_step"):
        replace(served_model(LLMConfig(model="tiny").model_config()),
                pending_step=True)
    assert served.decode_step is None and served.copy_prefix_kv is None
    assert not served.prefix_from_line and not served.kv_handoff
    assert served.counters == serving.COUNTERS and len(served.counters) == 12
    # no block pays a commit forward: the next block's first one carries it
    assert served.burst_forwards(CFG, 1) == [4]
    assert served.burst_forwards(CFG, 4) == [4, 4, 4, 4]
    full = replace(SdarConfig(), num_layers=6)
    assert served.kv_block(full, 1536) == 512
    cache = jax.eval_shape(lambda: served.init_cache(full, 128, 1536))
    assert cache["k"].shape == cache["v"].shape == (6, 128, 4, 1536, 128)
    # a cached position costs its 12 KiB
    assert 2 * cache["k"].size * 2 // (128 * 1536) == 12 * 1024


@pytest.mark.parametrize("kw,message", [
    ({"speculative_model": "tiny"}, "SdarConfig does not support a "
                                    "speculative draft"),
    ({"tensor_parallel_size": 2}, "SdarConfig does not support "
                                  "tensor_parallel_size > 1"),
], ids=["draft", "tp"])
def test_refuse_names_each_thing_refused(kw, message):
    with pytest.raises(ValueError, match=message):
        LLMEngine(LLMConfig(model=CFG, max_num_seqs=2, max_seq_len=32, **kw))


def test_top_k_and_the_hand_off_are_refused_by_name(params):
    with pytest.raises(ValueError, match="SdarConfig does not support the "
                                         "prefill/decode hand-off"):
        require_kv_handoff(CFG)
    eng = _engine(params)
    try:
        with pytest.raises(ValueError, match="does not support top_k"):
            eng.submit(_prompt(5), SamplingParams(max_tokens=4, top_k=5))
        with pytest.raises(ValueError, match="hand-off"):
            eng.prefill_only(_prompt(5))
    finally:
        eng.shutdown()


# ---- the stream -------------------------------------------------------------

def test_a_block_s_tokens_reach_the_client_as_one_chunk_of_frames():
    """The engine emits a block's tokens of a line together; the server
    writes a frame a token and the frames that are there as one chunk, so
    the path to the client is walked once a block and not once a token."""
    import queue
    import threading
    import time
    import types

    from ray_tpu.llm.serving import LLMServer

    server = object.__new__(getattr(LLMServer, "func_or_class", LLMServer))
    server._lag_lock = threading.Lock()
    server.first_frames, server.first_frame_lag_s = 0, 0.0
    server.last_frames, server.last_frame_lag_s = 0, 0.0
    req = types.SimpleNamespace(stream_queue=queue.Queue(),
                                first_token_ts=time.time(),
                                finish_ts=time.time())
    for tok in (11, 12, 13):             # a first block of three
        req.stream_queue.put(tok)
    chunks = server._stream_tokens(req)
    assert next(chunks) == [11, 12, 13]
    for tok in (21, 22, 23, 24, None):   # a block and the end with it
        req.stream_queue.put(tok)
    assert next(chunks) == [21, 22, 23, 24]
    assert list(chunks) == []
    assert server.first_frames == 1
    # the end is counted where it is taken off the queue, once a stream
    assert server.last_frames == 1 and server.last_frame_lag_s >= 0.0
    # the end alone yields nothing
    req.stream_queue.put(None)
    assert list(server._stream_tokens(req)) == []
    assert server.last_frames == 2 and server.first_frames == 1
