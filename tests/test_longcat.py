"""LongCat-Flash at a small size on the CPU: the model, the serving programs
through the latent cache, the kernels, the expert shares and the engine,
against the plain reference (benchmark/reference/longcat.py) on seeded
random weights.

Both sides compute in float32 here, so they differ only by the order of
sums and by the absorbed form's reassociation: logits of magnitude ~4 agree
to LOGIT_ATOL (measured 5e-6). A wrong mask, scale, rotation or expert term
moves logits by 1e-2 and more, and bfloat16 by 1e-2. On the chip the program
runs bfloat16 and a run compares with the margin its traffic file states.
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

from reference import longcat as reference  # noqa: E402
from rtbench.adapters import longcat as adapter  # noqa: E402

from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm import longcat_serving as serving  # noqa: E402
from ray_tpu.llm.config import SamplingParams  # noqa: E402
from ray_tpu.llm.engine import LLMEngine  # noqa: E402
from ray_tpu.models import longcat, mla  # noqa: E402
from ray_tpu.models.longcat import LongcatConfig  # noqa: E402
from ray_tpu.ops import grouped_matmul as gmm  # noqa: E402
from ray_tpu.ops import latent_attention as la  # noqa: E402
from ray_tpu.ops.kernels import force_kernel_backend  # noqa: E402
from ray_tpu.ops.rope import apply_rope, apply_rope_interleaved  # noqa: E402

LOGIT_ATOL = 5e-5


def ref_config(cfg: LongcatConfig) -> dict:
    """The configuration as a benchmark file states it (published names,
    the experts held and the deployment beside them)."""
    return {"hidden_size": cfg.hidden_size,
            "num_attention_heads": cfg.num_heads,
            "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps,
            "mla_scale_q_lora": cfg.mla_scale_q_lora,
            "mla_scale_kv_lora": cfg.mla_scale_kv_lora,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "moe_topk": cfg.moe_topk, "zero_expert_num": cfg.zero_expert_num,
            "n_routed_experts": cfg.experts_held,
            "published": {"n_routed_experts": cfg.n_routed_experts},
            "expert_shard": cfg.expert_shard,
            "expert_shards": cfg.expert_shards}


@pytest.fixture(scope="module", params=[(1, 0), (2, 1)],
                ids=["uncut", "shard-1-of-2"])
def case(request):
    shards, shard = request.param
    cfg = LongcatConfig.tiny(expert_shards=shards, expert_shard=shard)
    params = longcat.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (40,), 0,
                                cfg.vocab_size)
    want = np.asarray(reference.logits(
        ref_config(cfg), adapter.reference_weights(params), tokens))
    return cfg, params, tokens, want


def test_forward_matches_the_reference(case):
    cfg, params, tokens, want = case
    got, counts = jax.jit(longcat.forward, static_argnums=0)(
        cfg, params, tokens[None])
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=LOGIT_ATOL,
                               rtol=0)
    picks, local, zero, touched, layer_steps, tiles = (
        int(c) for c in counts)
    assert picks == 40 * cfg.moe_topk * cfg.num_layers
    assert layer_steps == cfg.num_layers
    assert 0 < local < picks and 0 < zero < picks
    assert 0 < touched <= cfg.experts_held * cfg.num_layers
    assert touched <= tiles <= touched + local // 16


def test_the_reference_sees_a_wrong_mask(case):
    cfg, params, tokens, want = case
    flipped = np.asarray(reference.logits(
        ref_config(cfg), adapter.reference_weights(params),
        tokens[::-1]))[::-1]
    assert np.abs(want - flipped).max() > 0.05


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
    with force_kernel_backend(backend):
        cache = serving.init_cache(cfg, slots, 64)
        for start in (0, 16):
            chunk = np.zeros(16, np.int32)
            take = min(16, prompt - start)
            chunk[:take] = t[start:start + take]
            cache, last, _ = serving.prefill_chunk(
                cfg, params, cache, jnp.asarray(chunk), jnp.int32(start),
                jnp.int32(prompt), jnp.int32(slot))
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
        # One live slot, one token: the router saw exactly topk picks a layer.
        assert int(counts[0]) == cfg.moe_topk * cfg.num_layers
        p = prompt + 6
        tok = np.zeros(slots, np.int32)
        pos = np.zeros(slots, np.int32)
        tok[slot], pos[slot] = t[p], p
        cache, toks, counts = serving.decode_burst(
            cfg, params, cache, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(write), jnp.zeros(slots, jnp.float32),
            jnp.ones(slots, jnp.float32), jax.random.PRNGKey(0), 4, False)
    assert int(counts[0]) == 4 * cfg.moe_topk * cfg.num_layers
    burst = [int(x) for x in np.asarray(toks)[:, slot]]
    seq = list(t[:p + 1]) + burst
    rows = np.asarray(reference.logits(
        ref_config(cfg), adapter.reference_weights(params),
        jnp.asarray(seq, jnp.int32)))[p:p + 4]
    chosen = rows[np.arange(4), burst]
    assert (rows.max(axis=1) - chosen).max() < LOGIT_ATOL


def test_absorbed_attention_equals_the_unabsorbed():
    """One attention alone: every position's output by the absorbed form
    over cached rows (what decode runs) against the up-projected causal
    attention of models/mla.mla_full (what the reference writes)."""
    cfg = LongcatConfig.tiny()
    params = longcat.init_params(cfg, jax.random.PRNGKey(2))
    ap = {k: v[1] for k, v in params["layers"].items()
          if k in longcat.SUBLAYER_LEAVES}
    s = 24
    xn = jax.random.normal(jax.random.PRNGKey(3), (1, s, cfg.hidden_size))
    want = mla.mla_full(cfg, ap, xn)
    q_n, q_r, rows = mla.mla_project(cfg, ap, xn, jnp.arange(s))
    w_kb, w_vb = mla.kv_up_projections(cfg, ap["wkv_b"])
    q = jnp.concatenate([jnp.einsum("bkhd,rhd->bkhr", q_n, w_kb), q_r], -1)
    # Every position as a one-token decode of a slot of its own.
    cache = jnp.broadcast_to(rows[0][None, None], (1, s, s, cfg.latent_row))
    lat = la.latent_decode_attention_reference(
        q[0][:, None], cache, 0, jnp.arange(s) + 1, jnp.arange(s),
        cfg.kv_lora_rank, cfg.sm_scale)
    got = jnp.einsum("bkhr,rhd->bkhd", lat, w_vb).reshape(1, s, -1) @ ap["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)


def test_adjacent_pair_rotary_is_the_half_split_one_permuted():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 5, 8))
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]])
    inv = 1.0 / (1e4 ** (jnp.arange(0, 8, 2) / 8))
    got = apply_rope_interleaved(x, pos, inv)
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    want = apply_rope(halves, pos, inv)
    np.testing.assert_allclose(np.asarray(got[..., 0::2]),
                               np.asarray(want[..., :4]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[..., 1::2]),
                               np.asarray(want[..., 4:]), atol=1e-6)
    ref = reference.rotary_pairs(x[0].transpose(1, 0, 2), 1e4)
    np.testing.assert_allclose(np.asarray(got[0].transpose(1, 0, 2)),
                               np.asarray(ref), atol=1e-6)


# ------------------------------------------------------------- the kernels

@pytest.mark.parametrize("k", [1, 2])
def test_latent_decode_attention_interpret_against_reference(k):
    """Lengths 0, 1, a block edge, one past it and the full line, in blocks
    of 128 of a line of 256."""
    b, h, rank, dr, s = 5, 4, 32, 8, 256
    key = jax.random.PRNGKey(5)
    cache = jax.random.normal(key, (2, b, s, 128), jnp.float32)
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, k, h, rank + dr))
    lengths = jnp.asarray([0, k, 128, 129, 256], jnp.int32)
    pos0 = jnp.maximum(lengths - k, 0)
    kw = dict(rank=rank, sm_scale=0.2)
    with force_kernel_backend("reference"):
        want = la.latent_decode_attention(q, cache, 1, lengths, pos0, **kw)
    with force_kernel_backend("interpret"):
        got = la.latent_decode_attention(q, cache, 1, lengths, pos0,
                                         block=128, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert not np.asarray(got[0]).any()      # an empty slot gives zeros
    # The row's padding (lanes rank + dr and beyond) is never read.
    noisy = cache.at[..., rank + dr:].set(1e9)
    with force_kernel_backend("interpret"):
        again = la.latent_decode_attention(q, noisy, 1, lengths, pos0,
                                           block=128, **kw)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


@pytest.mark.parametrize("k", [1, 3])
def test_latent_row_write_interpret_against_reference(k):
    b, s, d = 4, 64, 128
    key = jax.random.PRNGKey(6)
    cache = jax.random.normal(key, (2, b, s, d), jnp.float32)
    new = jax.random.normal(jax.random.fold_in(key, 1), (b, k, d))
    pos0 = jnp.asarray([0, 15, 30, s - k], jnp.int32)   # 15: across a window
    mask = jnp.asarray([True, True, False, True])
    with force_kernel_backend("reference"):
        want = la.latent_row_write(cache, new, 1, pos0, mask)
    with force_kernel_backend("interpret"):
        got = la.latent_row_write(cache, new, 1, pos0, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(cache[0]))
    np.testing.assert_array_equal(np.asarray(got[1, 2]),
                                  np.asarray(cache[1, 2]))
    np.testing.assert_array_equal(np.asarray(got[1, 1, 15:15 + k]),
                                  np.asarray(new[1]))


@pytest.mark.parametrize("swiglu", [False, True])
def test_grouped_matmul_interpret_against_reference(swiglu):
    """Experts 1 gets nothing, expert 2 two tiles; two dead tiles."""
    key = jax.random.PRNGKey(7)
    w = jax.random.normal(key, (2, 4, 64, 32)) * 0.1
    w2 = jax.random.normal(jax.random.fold_in(key, 1), (2, 4, 64, 32)) * 0.1
    x = jax.random.normal(jax.random.fold_in(key, 2), (48, 64))
    te = jnp.asarray([0, 2, 2, 3, 3, 3], jnp.int32)
    kw = dict(tm=8, w2=w2 if swiglu else None)
    with force_kernel_backend("reference"):
        want = gmm.grouped_matmul(x, w, 1, te, 4, **kw)
    with force_kernel_backend("interpret"):
        got = gmm.grouped_matmul(x, w, 1, te, 4, **kw)
    np.testing.assert_allclose(np.asarray(got[:32]), np.asarray(want[:32]),
                               atol=1e-5)
    by_hand = x[8:16] @ w[1, 2]
    if swiglu:
        by_hand = jax.nn.silu(by_hand) * (x[8:16] @ w2[1, 2])
    np.testing.assert_allclose(np.asarray(want[8:16]), np.asarray(by_hand),
                               atol=1e-5)


@pytest.mark.parametrize("keys", [
    [4, 4, 4, 4, 4, 4, 4, 4],           # nothing local
    [2, 2, 2, 2, 2, 2, 2, 2],           # everything on one expert
    [0, 4, 3, 3, 1, 4, 0, 3],           # a mix
], ids=["none", "one-expert", "mix"])
def test_dispatch_plan_places_every_local_pick_once_and_drops_none(keys):
    held, tm = 4, 2
    keys = jnp.asarray(keys, jnp.int32)
    pick_of_row, row_of_pick, tile_expert, n_live, sizes = \
        longcat.dispatch_plan(keys, held, tm)
    pick_of_row, row_of_pick = np.asarray(pick_of_row), np.asarray(row_of_pick)
    local = [i for i, k in enumerate(np.asarray(keys)) if k < held]
    assert sorted(p for p in pick_of_row if p >= 0) == local
    for i in local:
        row = row_of_pick[i]
        assert pick_of_row[row] == i
        assert int(tile_expert[row // tm]) == int(keys[i])
        assert row // tm < int(n_live)
    assert int(n_live) == sum(-(-int(s) // tm) for s in np.asarray(sizes))
    assert len(pick_of_row) == (len(keys) // tm + held) * tm


# --------------------------------------------------------------- the shares

def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Section 4 of the model-configs guide: the routed parts that all the
    shares give, with the zero experts and the dense paths counted once,
    add up to the uncut layer of the reference."""
    shards = 4
    full = LongcatConfig.tiny()
    params = longcat.init_params(full, jax.random.PRNGKey(8))
    h = jax.random.normal(jax.random.PRNGKey(9), (1, 12, full.hidden_size))
    want = reference.double_layer(
        reference._static(ref_config(full)), h[0],
        adapter.reference_weights(params)["layers"], 1)

    def attn(i, ap, xn, state):
        return mla.mla_full(full, ap, xn), state

    def layer_of(cfg, layers):
        out, _, counts = longcat.double_layer(
            cfg, layers, 1, h, attn, None, jnp.ones((1, 12), bool))
        return out[0], counts

    held = full.n_routed_experts // shards
    no_experts = {k: (jnp.zeros_like(v[:, :held]) if k.startswith("we_")
                      else v) for k, v in params["layers"].items()}
    cut = LongcatConfig.tiny(expert_shards=shards)
    once, _ = layer_of(cut, no_experts)     # dense paths and zero experts
    total, local_picks = once, 0
    for s in range(shards):
        cfg = LongcatConfig.tiny(expert_shards=shards, expert_shard=s)
        layers = {k: (v[:, s * held:(s + 1) * held] if k.startswith("we_")
                      else v) for k, v in params["layers"].items()}
        out, counts = layer_of(cfg, layers)
        total = total + (out - once)
        local_picks += int(counts[1])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=0)
    _, uncut = layer_of(full, params["layers"])
    assert local_picks == int(uncut[1])      # every routed pick has a home


# --------------------------------------------------------------- the engine

@pytest.mark.parametrize("bad,says", [
    (dict(kv_block_size=16), "block pool"),
    (dict(speculative_model="tiny"), "speculative draft"),
    (dict(tensor_parallel_size=2), "tensor_parallel_size"),
])
def test_the_engine_refuses_what_longcat_does_not_support(bad, says):
    cfg = LongcatConfig.tiny(max_seq_len=64)
    with pytest.raises(ValueError, match=says):
        LLMEngine(LLMConfig(model=cfg, max_num_seqs=2, max_seq_len=64, **bad))


def test_the_prefill_decode_handoff_is_refused_before_an_engine_is_built():
    from ray_tpu.llm.pd import DecodeServer, PrefillServer

    llm = LLMConfig(model=LongcatConfig.tiny(max_seq_len=64), max_num_seqs=2,
                    max_seq_len=64)
    for server in (PrefillServer, DecodeServer):
        with pytest.raises(ValueError, match="hand-off"):
            server(llm)


def test_the_engine_serves_longcat_and_counts_its_routing():
    """Four requests through the one LLMEngine (two chunks, a tail bucket,
    bursts, prefix adoption between the first and the last): greedy tokens
    are those of the model's own full forward pass, and stats() carries the
    router's counters."""
    cfg = LongcatConfig.tiny(expert_shards=2, max_seq_len=128)
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
    fwd = jax.jit(longcat.forward, static_argnums=0)
    for p, r in zip(prompts, reqs):
        seq = jnp.asarray(p + r.out_tokens, jnp.int32)
        logits, _ = fwd(cfg, params, seq[None])
        rows = np.asarray(logits[0])[len(p) - 1:-1]
        chosen = rows[np.arange(len(r.out_tokens)), r.out_tokens]
        assert (rows.max(axis=1) - chosen).max() < LOGIT_ATOL
    assert stats["requests_failed"] == 0 and stats["device_failures"] == 0
    assert stats["moe_experts_held"] == 8
    tokens = stats["prompt_tokens_prefilled"] + stats["decode_tokens"]
    # Decode steps route every decoding slot, finished ones' spare steps
    # included: at least the tokens that counted.
    assert stats["moe_picks"] >= tokens * cfg.moe_topk * cfg.num_layers
    assert 0 < stats["moe_picks_local"] < stats["moe_picks"]
    assert 0 < stats["moe_picks_zero"] < stats["moe_picks"]
    assert stats["moe_layer_steps"] == cfg.num_layers * (
        stats["prefill_chunks"] + stats["decode_steps"])
    assert stats["moe_experts_touched"] <= 8 * stats["moe_layer_steps"]
    assert stats["kv_positions_read"] > 0
