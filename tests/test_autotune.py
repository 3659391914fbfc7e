"""Train-step autotuner: HBM estimator (hlo_stats liveness), analytic
memory model, candidate space, search driver, per-layer remat.

The estimator tests run against hand-written HLO (tuple results, TPU tiled
layouts, while/fusion nesting — the shapes that broke earlier parsers) and
one recorded real fixture with its memory_analysis ground truth; the
analytic model is gated by the chip-verified fit/OOM table from bench
rounds r04/r05. Everything here is analysis-only — no TPU, no execution.
"""

import gzip
import json
import os

import pytest

from ray_tpu.autotune.model import (
    POLICY_FLOPS_FACTOR,
    device_hbm_budget_bytes,
    predict_hbm,
    remat_flops_factor,
)
from ray_tpu.autotune.search import (
    AutotuneCache,
    autotune_train_configs,
    geometry_sig,
)
from ray_tpu.autotune.space import Candidate, candidate_space
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.parallel.hlo_stats import (
    _padded_shape_bytes,
    compiled_hbm_bytes,
    hbm_stats,
)

FIXTURE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixtures", "hlo")


def _bench_cfg():
    return LlamaConfig(
        vocab_size=32128, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
        max_seq_len=2048, tie_embeddings=True, dtype="bfloat16")


# ---------------------------------------------------------------------------
# HBM estimator (hlo_stats.hbm_stats)
# ---------------------------------------------------------------------------

def test_padded_shape_bytes_tiled_layouts():
    # plain: no padding
    assert _padded_shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    # TPU tiling pads the physical dims up to tile multiples:
    # [130, 260] -> [136, 384] under T(8,128)
    assert _padded_shape_bytes("f32[130,260]{1,0:T(8,128)}") == 136 * 384 * 4
    # transposed minor-to-major permutes the physical dims before tiling:
    # {0,1} means dim0 is minor -> physical [260, 130] -> [264, 256]
    assert _padded_shape_bytes("f32[130,260]{0,1:T(8,128)}") == 264 * 256 * 4
    # tuples sum; bf16 is 2 bytes; scalars are itemsize
    assert _padded_shape_bytes("(bf16[8,128]{1,0}, f32[])") == \
        8 * 128 * 2 + 4


def test_hbm_stats_synthetic_straight_line():
    """a and b feed the dot; b dies there, the dot result and a feed the
    ROOT tuple. Peak temp = a + b + dot live together at the dot."""
    hlo = """HloModule m, is_scheduled=true

ENTRY %main (p0: f32[64,64]) -> (f32[64,64], f32[64,64]) {
  %p0 = f32[64,64]{1,0} parameter(0)
  %a = f32[64,64]{1,0} negate(f32[64,64]{1,0} %p0)
  %b = f32[64,64]{1,0} exponential(f32[64,64]{1,0} %p0)
  %d = f32[64,64]{1,0} dot(f32[64,64]{1,0} %a, f32[64,64]{1,0} %b)
  ROOT %t = (f32[64,64]{1,0}, f32[64,64]{1,0}) tuple(f32[64,64]{1,0} %a, f32[64,64]{1,0} %d)
}
"""
    st = hbm_stats(hlo)
    buf = 64 * 64 * 4
    assert st.parameter_bytes == buf
    assert st.peak_temp_bytes == 3 * buf  # a + b + d at the dot
    assert st.n_computations == 1


def test_hbm_stats_tuple_alias_extends_liveness():
    """Buffers packed into a tuple must stay live until the tuple's last
    use — the failure mode that undercounted scan carries 2-3x."""
    hlo = """HloModule m, is_scheduled=true

ENTRY %main (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  %a = f32[256]{0} negate(f32[256]{0} %p0)
  %b = f32[256]{0} exponential(f32[256]{0} %p0)
  %t = (f32[256]{0}, f32[256]{0}) tuple(f32[256]{0} %a, f32[256]{0} %b)
  %c = f32[256]{0} add(f32[256]{0} %p0, f32[256]{0} %p0)
  %g = f32[256]{0} get-tuple-element((f32[256]{0}, f32[256]{0}) %t), index=0
  ROOT %r = f32[256]{0} add(f32[256]{0} %g, f32[256]{0} %c)
}
"""
    st = hbm_stats(hlo)
    # a and b stay alive through the tuple -> get-tuple-element chain (the
    # element-level split is deliberately NOT modeled: a GTE keeps the
    # whole tuple's buffers alive — conservative, the safe direction for
    # OOM pruning), so at ROOT: a + b + c + r.
    assert st.peak_temp_bytes == 4 * 256 * 4


def test_hbm_stats_while_body_recursion():
    """A while's peak = live carry + the body's own temp peak; the while
    result aliases its operand (no double count)."""
    hlo = """HloModule m, is_scheduled=true

%cond (p: (f32[1024], s32[])) -> pred[] {
  %p = (f32[1024]{0}, s32[]) parameter(0)
  %i = s32[] get-tuple-element((f32[1024]{0}, s32[]) %p), index=1
  %k = s32[] constant(8)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %k), direction=LT
}

%body (p: (f32[1024], s32[])) -> (f32[1024], s32[]) {
  %p = (f32[1024]{0}, s32[]) parameter(0)
  %x = f32[1024]{0} get-tuple-element((f32[1024]{0}, s32[]) %p), index=0
  %i = s32[] get-tuple-element((f32[1024]{0}, s32[]) %p), index=1
  %big = f32[2048]{0} concatenate(f32[1024]{0} %x, f32[1024]{0} %x), dimensions={0}
  %y = f32[1024]{0} slice(f32[2048]{0} %big), slice={[0:1024]}
  %one = s32[] constant(1)
  %j = s32[] add(s32[] %i, s32[] %one)
  ROOT %r = (f32[1024]{0}, s32[]) tuple(f32[1024]{0} %y, s32[] %j)
}

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %zero = s32[] constant(0)
  %init = (f32[1024]{0}, s32[]) tuple(f32[1024]{0} %p0, s32[] %zero)
  %w = (f32[1024]{0}, s32[]) while((f32[1024]{0}, s32[]) %init), condition=%cond, body=%body
  ROOT %out = f32[1024]{0} get-tuple-element((f32[1024]{0}, s32[]) %w), index=0
}
"""
    st = hbm_stats(hlo)
    # body peak: big (8 KB) + y (4 KB) + j; entry adds nothing live beyond
    # the aliased carry (parameters are counted separately)
    assert st.peak_temp_bytes >= 2048 * 4 + 1024 * 4
    assert st.peak_temp_bytes < 2 * (2048 * 4 + 1024 * 4)
    assert st.n_computations == 3


def test_hbm_stats_async_tuple_and_tiled_result():
    """Async-start-style nested tuple results with TPU tiled layouts parse
    and price without truncation at the inner parens."""
    hlo = """HloModule m, is_scheduled=true

ENTRY %main (p0: f32[256,128]) -> f32[256,128] {
  %p0 = f32[256,128]{1,0:T(8,128)} parameter(0)
  %s = ((f32[256,128]{1,0:T(8,128)}), (f32[256,128]{1,0:T(8,128)})) custom-call(f32[256,128]{1,0:T(8,128)} %p0), custom_call_target="x"
  %g = f32[256,128]{1,0:T(8,128)} get-tuple-element(((f32[256,128]{1,0:T(8,128)}), (f32[256,128]{1,0:T(8,128)})) %s), index=1
  ROOT %r = f32[256,128]{1,0:T(8,128)} add(f32[256,128]{1,0:T(8,128)} %g, f32[256,128]{1,0:T(8,128)} %g)
}
"""
    st = hbm_stats(hlo)
    buf = 256 * 128 * 4
    assert st.parameter_bytes == buf
    # custom-call result tuple (2 bufs) + ROOT add
    assert st.peak_temp_bytes == 3 * buf


def test_hbm_stats_fixture_within_gate():
    """The 15% acceptance gate on a recorded real train-step module
    (captured by devbench/autotune_bench.py write_fixtures with its
    memory_analysis ground truth)."""
    meta_path = os.path.join(FIXTURE_DIR, "meta.json")
    if not os.path.exists(meta_path):
        pytest.skip("no recorded HLO fixtures")
    meta = json.load(open(meta_path))
    checked = 0
    for name, m in meta.items():
        path = os.path.join(FIXTURE_DIR, f"{name}.hlo.gz")
        if not os.path.exists(path):
            continue
        with gzip.open(path, "rt") as f:
            st = hbm_stats(f.read())
        err = abs(st.peak_bytes - m["measured_total_bytes"]) \
            / m["measured_total_bytes"]
        assert err <= 0.15, f"{name}: estimator off by {err:.1%}"
        # the estimator must overestimate or track closely — an
        # UNDERestimate is the dangerous direction for OOM pruning
        assert st.peak_bytes >= m["measured_total_bytes"] * 0.97, name
        checked += 1
    assert checked >= 3


def test_compiled_hbm_bytes_cpu_memory_analysis():
    """compiled_hbm_bytes prefers the backend's memory_analysis and agrees
    with the text estimator within the documented band."""
    import jax
    import jax.numpy as jnp

    def f(x, y):
        return jnp.tanh(x @ y).sum()

    c = jax.jit(f).lower(jnp.ones((128, 256)), jnp.ones((256, 128))).compile()
    total, source = compiled_hbm_bytes(c)
    assert source == "memory_analysis"
    est = hbm_stats(c.as_text()).peak_bytes
    assert 0.8 <= est / total <= 1.3


# ---------------------------------------------------------------------------
# Analytic memory model
# ---------------------------------------------------------------------------

def test_predict_hbm_chip_verified_boundary():
    """r04/r05 chip ground truth at the 1.1B bench geometry: every config
    that fit must predict under the 15.75 GB v5e budget, every
    compile-time OOM must predict over it."""
    cfg = _bench_cfg()
    budget = 15.75
    fits = [(4, "attn"), (4, "attn+"), (5, "attn"), (8, "attn"),
            (4, "dots")]
    ooms = [(16, "attn"), (8, "dots"), (4, "dots+")]
    for b, r in fits:
        p = predict_hbm(cfg, 2048, Candidate(batch=b, remat=r))
        assert p.total_gb <= budget, f"b{b}/{r}: {p.total_gb} GB (chip fit)"
    for b, r in ooms:
        p = predict_hbm(cfg, 2048, Candidate(batch=b, remat=r))
        assert p.total_gb > budget, f"b{b}/{r}: {p.total_gb} GB (chip OOM)"


def test_predict_hbm_monotonicity():
    cfg = _bench_cfg()

    def gb(**kw):
        return predict_hbm(cfg, 2048, Candidate(**kw)).total_gb

    # batch grows HBM
    assert gb(batch=4, remat="attn") < gb(batch=8, remat="attn")
    # richer save-lists grow HBM
    assert gb(batch=4, remat="full") < gb(batch=4, remat="attn") \
        < gb(batch=4, remat="attn+") < gb(batch=4, remat="dots") \
        < gb(batch=4, remat="dots+")
    # per-layer mix lands between its uniform endpoints
    mix = gb(batch=4, remat="dots:8,attn:8")
    assert gb(batch=4, remat="attn") < mix < gb(batch=4, remat="dots")
    # grad accumulation shrinks activation HBM at fixed batch
    assert gb(batch=16, remat="attn", grad_accum=4) \
        < gb(batch=16, remat="attn", grad_accum=2) \
        < gb(batch=16, remat="attn")
    # zero1 divides optimizer state across data shards
    c = Candidate(batch=8, remat="attn", zero1=True)
    p1 = predict_hbm(cfg, 2048, c, data_shards=1)
    p4 = predict_hbm(cfg, 2048, c, data_shards=4)
    assert p4.components["opt_state"] < p1.components["opt_state"]


def test_remat_flops_factor():
    assert remat_flops_factor("attn", 16) == POLICY_FLOPS_FACTOR["attn"]
    mixed = remat_flops_factor("attn:8,dots:8", 16)
    assert POLICY_FLOPS_FACTOR["dots"] < mixed < POLICY_FLOPS_FACTOR["attn"]


def test_device_hbm_budget_env_override(monkeypatch):
    monkeypatch.setenv("RTPU_HBM_BUDGET_GB", "15.75")
    assert device_hbm_budget_bytes() == int(15.75 * (1 << 30))
    monkeypatch.delenv("RTPU_HBM_BUDGET_GB")
    # CPU host, no override: unknown budget
    assert device_hbm_budget_bytes() is None


# ---------------------------------------------------------------------------
# Candidate space + search driver
# ---------------------------------------------------------------------------

def test_candidate_space_dimensions():
    space = candidate_space(16)
    labels = [c.label for c in space]
    assert len(labels) == len(set(labels))
    assert any(c.zero1 for c in space)
    assert any(c.grad_accum > 1 for c in space)
    assert any("," in c.remat for c in space)          # per-layer specs
    assert any(c.flash_block_q for c in space)
    assert any(c.ce_chunk for c in space)


def test_candidate_env_roundtrip(monkeypatch):
    monkeypatch.setenv("RTPU_FLASH_BLOCK_Q", "64")
    c = Candidate(batch=4, remat="attn", flash_block_q=256, ce_chunk=128)
    with c.applied_env():
        assert os.environ["RTPU_FLASH_BLOCK_Q"] == "256"
        assert os.environ["RTPU_CE_CHUNK"] == "128"
    assert os.environ["RTPU_FLASH_BLOCK_Q"] == "64"
    assert "RTPU_CE_CHUNK" not in os.environ


def test_search_prunes_without_measuring():
    """Candidates predicted over budget are pruned at analysis time: the
    measure callback must NEVER see them (the acceptance criterion: zero
    failed compile-and-run attempts for pruned configs)."""
    cfg = _bench_cfg()
    space = candidate_space(cfg.num_layers)
    budget = int(15.75 * (1 << 30))
    measured = []

    def spy(cand):
        measured.append(cand.label)
        from ray_tpu.autotune.model import predict_hbm as p

        assert p(cfg, 2048, cand).total_bytes <= budget * 1.05
        return {"tokens_per_sec": 100.0}

    res = autotune_train_configs(cfg, 2048, space, hbm_budget_bytes=budget,
                                 measure_fn=spy, max_measure=4)
    assert res.pruned > 0
    assert len(measured) == 4 == res.measured
    pruned_labels = {r["config"] for r in res.trace if r.get("pruned")}
    assert not pruned_labels & set(measured)
    # sweep covers the PR-4 machinery: zero1 / grad-accum / per-layer
    # candidates survive pruning and are in the ranked pool
    kept = {r["config"] for r in res.trace if not r.get("pruned")}
    assert any("/z1" in c for c in kept)
    assert any("/ga" in c for c in kept)
    assert any("|" in c for c in kept)


def test_search_cached_champion_measures_first(tmp_path):
    cfg = _bench_cfg()
    cache = AutotuneCache(path=str(tmp_path / "cache.json"))
    geo = geometry_sig(cfg, 2048, 1)
    champion = "b4/attn+/flash/lowmem"
    cache.put("v5e", geo, champion, {"tokens_per_sec": 16601.4})
    order = []

    def spy(cand):
        order.append(cand.label)
        return {"tokens_per_sec": 10.0}

    res = autotune_train_configs(
        cfg, 2048, candidate_space(cfg.num_layers),
        hbm_budget_bytes=int(15.75 * (1 << 30)), measure_fn=spy,
        max_measure=3, cache=cache, device_kind="v5e")
    assert order[0] == champion
    assert res.measured == 3
    # fresh measurements landed in the cache
    assert cache.get("v5e", geo, order[1])["tokens_per_sec"] == 10.0


def test_search_analysis_only_mode():
    """measure_fn=None: everything priced and ranked, nothing executed —
    the CI smoke path."""
    cfg = _bench_cfg()
    res = autotune_train_configs(
        cfg, 2048, candidate_space(cfg.num_layers),
        hbm_budget_bytes=int(15.75 * (1 << 30)), measure_fn=None)
    assert res.measured == 0
    assert res.winner is not None
    assert res.pruned > 0
    assert all("predicted_hbm_gb" in r for r in res.trace)


def test_search_has_no_winner_when_every_measurement_fails(tmp_path):
    """A cached number orders the candidates; it is never the result."""
    cfg = _bench_cfg()
    cache = AutotuneCache(path=str(tmp_path / "cache.json"))
    geo = geometry_sig(cfg, 2048, 1)
    cache.put("v5e", geo, "b4/attn/flash/lowmem",
              {"tokens_per_sec": 16573.5})

    def broken(cand):
        raise RuntimeError("RESOURCE_EXHAUSTED")

    res = autotune_train_configs(
        cfg, 2048, [Candidate(batch=4, remat="attn")],
        hbm_budget_bytes=None, measure_fn=broken, max_measure=2,
        cache=cache, device_kind="v5e")
    assert res.winner is None and res.tokens_per_sec == 0.0
    assert any("error" in r for r in res.trace)
    # failed attempts must not be reported as successful measurements
    assert res.measured == 0 and res.failed == 1
    # ... and analysis-only mode ranks without reporting a throughput
    res = autotune_train_configs(
        cfg, 2048, [Candidate(batch=4, remat="attn")],
        hbm_budget_bytes=None, measure_fn=None, cache=cache,
        device_kind="v5e")
    assert res.winner == "b4/attn/flash/lowmem"
    assert res.tokens_per_sec == 0.0


# ---------------------------------------------------------------------------
# Per-layer remat (models/llama.py)
# ---------------------------------------------------------------------------

def test_per_layer_remat_matches_uniform():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import init_params, loss_fn

    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)

    def loss(p, remat):
        return loss_fn(cfg, p, tokens, targets, attn_impl="blockwise",
                       remat=remat)

    base = loss(params, "attn")
    for spec in [("attn", "dots"), "attn:1,dots:1", ("attn", "attn")]:
        np.testing.assert_allclose(float(loss(params, spec)), float(base),
                                   rtol=1e-6, atol=1e-6)
    g1 = jax.grad(lambda p: loss(p, "attn"))(params)
    g2 = jax.grad(lambda p: loss(p, ("dots", "attn")))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_per_layer_remat_validation():
    from ray_tpu.models.llama import normalize_remat

    with pytest.raises(ValueError, match="per-layer remat"):
        normalize_remat(("attn",), 2)
    assert normalize_remat("attn:2", 2) == "attn"      # uniform collapses
    assert normalize_remat(("attn", "dots"), 2) == ("attn", "dots")
    assert normalize_remat("dots", 2) == "dots"
    assert normalize_remat(True, 2) is True


# ---------------------------------------------------------------------------
# A candidate is a train step (tiny geometry, CPU, one AOT compile)
# ---------------------------------------------------------------------------

def test_a_candidate_builds_compiles_and_steps():
    """What a search's ``measure_fn`` does with a candidate: its kernel
    environment and ``step_options()`` build the Llama train step, the AOT
    compile's memory has a named source, and the step runs."""
    import jax
    import numpy as np

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train.optim import adamw_lowmem
    from ray_tpu.train.spmd import make_llama_train_step

    cfg, seq = LlamaConfig.tiny(), 32
    cand = Candidate(batch=2, remat="attn", attn="blockwise", grad_accum=2,
                     zero1=True)
    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    with cand.applied_env():
        step_fn, init_state, shard = make_llama_train_step(
            cfg, mesh, optimizer=adamw_lowmem(3e-4, weight_decay=0.1),
            attn_impl=cand.attn, remat=cand.remat, **cand.step_options())
        state = init_state()
        tokens = shard(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (cand.batch, seq), dtype=np.int32))
        targets = shard(np.roll(np.asarray(tokens), -1, axis=1))
        compiled = step_fn.lower(state, tokens, targets).compile()
    hbm, source = compiled_hbm_bytes(compiled)
    assert hbm > 0 and source in ("memory_analysis", "hlo_liveness")
    state, metrics = compiled(state, tokens, targets)
    assert np.isfinite(float(metrics["loss"]))
