"""The DeepSeek-V2 cell's files: its configuration against the published
one, its adapter's arithmetic against hand-worked values, its plan pinned,
its own entries in the manifest (never the number of cells), each roofline
reader at 100 on a made-up trace that takes exactly the roofline's time,
the four metrics this cell brings, and the control at a small size."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, REPO
from rtbench import gen, manifest, trace_reduce as tr, xplane_meta as xm
from rtbench.adapters import deepseek
from rtbench.readers import (
    counter_ratio,
    grouped_matmul_roofline,
    latent_attention_roofline,
    phases,
    scope_ms_per,
    scope_ms_per_count,
    scope_share,
)

CELL = "deepseek-v2-serve-longdoc-16k"

# The catalog row's ``config`` (huggingface.co/deepseek-ai/DeepSeek-V2/blob/
# main/config.json), as published.
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 128,
    "vocab_size": 102400}
REDUCED = {"num_hidden_layers": 8, "n_routed_experts": 20,
           "vocab_size": 12800}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "deepseek-v2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "serve-longdoc-16k.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- the files

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_key_is_kept_or_listed_as_reduced(config, key):
    entry = manifest.config_entry(manifest.load(REPO), "deepseek-v2")
    if key in REDUCED:
        assert key in entry["reduced"] and key in config["reduced"]
        assert config[key] == REDUCED[key]
        assert config["published"][key] == PUBLISHED[key]
    else:
        assert key not in entry["reduced"]
        assert config[key] == PUBLISHED[key]
        assert type(config[key]) is type(PUBLISHED[key])


def test_the_cut_is_depth_experts_held_and_vocabulary_inside_the_floors(
        config):
    entry = manifest.config_entry(manifest.load(REPO), "deepseek-v2")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/"
        "config.json")
    # the dense layer and seven routed ones (floor: four after the dense
    # one), one of the rule's eight groups (floor: 8 experts), an eighth of
    # the vocabulary (the floor)
    assert deepseek.routed_layers(config) == 7 >= 4
    assert config["n_routed_experts"] == 160 // config["n_group"] == 20 >= 8
    assert (config["expert_shard"], config["expert_shards"]) == (0, 8)
    assert config["n_routed_experts"] * config["expert_shards"] == \
        config["published"]["n_routed_experts"]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    for item in ("equations", "layer", "attention", "rotary",
                 "softmax_scale", "router", "experts", "norm", "embeddings",
                 "init"):
        assert config["assumed"][item]
    assert "modeling_deepseek.py" in config["assumed"]["equations"]
    assert "(10, 23)" in config["assumed"]["rotary"]
    assert "1.5896" in config["assumed"]["softmax_scale"]
    assert set(config["departures"]) == {"latent_row_width", "router_dtype",
                                         "kv_b_order"}
    assert "8 accelerators" in config["deployment"]
    assert config["guarantees"].startswith(
        "every pick that falls on a held expert is computed")


def test_the_cell_s_own_entries_are_what_the_issue_names(traffic):
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    cell = manifest.load_cell(CELL, REPO)
    assert cell["workload"] == {**cell["workload"], "config": "deepseek-v2",
                                "traffic": "serve-longdoc-16k", "chips": 1}
    why = cell["workload"]["why"]
    for said in ("19 rows a chunk (154 deployed)", "0.6 a step (4.8)",
                 "8 of 60 layers"):
        assert said in why
    assert [x["name"] for x in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    mine = {"part_share_moe_shared.tok_s", "part_share_latent_prefill.tok_s",
            "latent_prefill_ms_per_ktok", "moe_local_token_share"}
    longcat = {x["name"] for x in manifest.load_cell(
        "longcat-flash-serve-agent-8k", REPO)["per_layer"]}
    names = {x["name"] for x in cell["per_layer"]}
    # every metric the other latent-attention cell reports but the share of
    # zero-compute experts, which this model has none of, and
    # ``decode_bw_share.tok_s``, whose count of steps (tokens received over
    # requests decoding, by the clients' clocks) is half again too high where
    # prefill takes three quarters of the device's time: it read 123.5% in
    # the builder's traced run (PERF.md section 7), and the reader is a
    # ``benchmark`` PR's to repair; and this cell's own four
    assert names == (longcat - {"moe_zero_pick_share",
                                "decode_bw_share.tok_s"}) | mine
    assert {"latent_decode_attention_roofline",
            "moe_grouped_matmul_roofline"} <= names
    for x in cell["per_layer"]:
        if x["name"] in mine:
            assert x["workloads"] == [CELL] and x["moves"] == "serve_tok_s"
    readers = {x["name"]: (x["reader"], x["params"])
               for x in cell["per_layer"] if x["name"] in mine}
    assert readers["part_share_moe_shared.tok_s"] == (
        "scope_share", {"scopes": ["moe_shared"]})
    assert readers["part_share_latent_prefill.tok_s"] == (
        "scope_share", {"scopes": ["latent_prefill"]})
    assert readers["latent_prefill_ms_per_ktok"] == ("scope_ms_per", {
        "scopes": ["latent_prefill"], "programs": ["jit_prefill_chunk"],
        "phase": "engine.prefill_dispatch", "count": "tokens", "per": 1000})
    assert readers["moe_local_token_share"] == ("counter_ratio", {
        "num": "moe_tokens_local", "den": "moe_picks", "scale": 600.0})
    assert traffic["kind"] == "closed_loop"
    assert traffic["engine"] == {
        "max_num_seqs": 16, "max_seq_len": 16384, "dtype": "bfloat16",
        "kv_block_size": 0, "max_ongoing_requests": 48}
    assert traffic["clients"] == 24 and traffic["cycle_requests"] == 24
    assert traffic["prompt_tokens"] == {"kind": "lognormal", "median": 8192,
                                        "sigma": 0.4, "min": 4096,
                                        "max": 15360}
    assert traffic["max_tokens"] == {"kind": "uniform", "min": 256,
                                     "max": 768}
    assert traffic["trace"] == {"after_s": 10, "for_s": 4}
    assert traffic["check"]["requests"] == 4
    assert "control" in traffic["check"]["margin_why"]
    assert traffic["use"] == "serve_longdoc"


PINNED = {   # sha256 of json.dumps(plan, sort_keys=True) at 51 s
    1: "07e6eac4d5c4105eda68722a9d935c1ddb4c09e5a9ffc9d36caf6c88e7e44f26",
    2147483700: "e63751b44adb45bba510ee2733988e8f29cbec95acb663b99b675f15ac35352b",
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_the_plan_is_what_it_was_and_fits_the_line(traffic, seed):
    plan = gen.closed_loop_plan(traffic, seed, 51)
    assert hashlib.sha256(json.dumps(plan, sort_keys=True).encode()
                          ).hexdigest() == PINNED[seed]
    cycle = plan["requests"][:24]
    assert min(r["prompt_tokens"] for r in cycle) == 4096
    assert 15000 < max(r["prompt_tokens"] for r in cycle) <= 15360
    assert all(256 <= r["max_tokens"] <= 768 for r in cycle)
    longest = max(r["prompt_tokens"] + r["max_tokens"] for r in cycle)
    assert 15360 < longest <= 16128 <= traffic["engine"]["max_seq_len"]
    # every seed sends the same 24 requests, in an order of its own
    other = gen.closed_loop_plan(traffic, seed + 1, 51)["requests"][:24]
    key = lambda r: (r["prompt_tokens"], r["max_tokens"])  # noqa: E731
    assert sorted(map(key, cycle)) == sorted(map(key, other))
    assert [key(r) for r in cycle] != [key(r) for r in other]
    # a backlog of 8: no slot waits for a client
    assert plan["clients"] == traffic["engine"]["max_num_seqs"] + 8 == 24
    assert {w["prompt_tokens"] for w in traffic["warmup"]} >= {
        16, 32, 64, 128, 256, 512}
    # ids come from the held slice of the vocabulary
    ids = gen.prompt_ids(seed, 1000, 4096, 12800)
    assert 259 <= min(ids) and max(ids) < 12800


# ----------------------------------------------------------- the arithmetic

def test_the_cut_is_5153m_parameters_of_which_3303m_are_routed_experts(
        config):
    c = config
    assert deepseek.mla_params(c) == (5120 * 1536 + 1536 * 128 * 192
                                      + 5120 * 576 + 512 * 128 * 256
                                      + 16384 * 5120) == 149225472
    assert deepseek.dense_ffn_params(c) == 3 * 5120 * 12288 == 188743680
    assert deepseek.shared_params(c) == 3 * 5120 * 3072 == 47185920
    assert deepseek.expert_params(c) == 3 * 5120 * 1536 == 23592960
    assert deepseek.router_params(c) == 5120 * 160
    # a routed layer outside its routed experts: the catalog's "about 197M"
    assert 149225472 + 47185920 + 819200 == 197230592
    assert deepseek.params_held(c) == (
        8 * 149225472 + 188743680
        + 7 * (819200 + 47185920 + 20 * 23592960) + 2 * 5120 * 12800
    ) == 5152669696
    assert 7 * 20 * 23592960 == 3303014400
    assert deepseek.params_held(c) * 2 / 2 ** 30 == pytest.approx(9.598,
                                                                  abs=1e-3)
    # all 160 experts of one layer would not fit a chip
    assert 160 * deepseek.expert_params(c) * 2 / 2 ** 30 == pytest.approx(
        7.03, abs=0.01)


def test_depth_is_layers_and_the_program_s_configuration_follows(config):
    assert deepseek.depth(config, "serve_longdoc") == 8
    cfg = deepseek.model_config(config, "serve_longdoc", 16384)
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.num_routed_layers,
            cfg.experts_held, cfg.num_heads) == (8, 1, 7, 20, 128)
    rule = cfg.router_rule
    assert (rule.score, rule.use_bias, rule.renormalize, rule.scaling_factor,
            rule.zero_experts, rule.topk, rule.held, rule.groups,
            rule.topk_groups, rule.outputs) == \
        ("softmax", False, False, 16.0, 0, 6, 20, 8, 3, 160)
    assert cfg.max_seq_len == 16384 and cfg.latent_row == 640
    assert cfg.rope_scaling["factor"] == 40
    assert cfg.sm_scale == pytest.approx(1.5896 / 192 ** 0.5, rel=1e-4)
    # the norms' weights are what the adapter's count of matrices leaves out
    assert cfg.num_params() - deepseek.params_held(config) == \
        8 * (1536 + 512 + 2 * 5120) + 5120 == 103424


def test_a_cached_position_is_9216_bytes_and_the_kernel_sits_at_the_ridge(
        config):
    c = config
    assert deepseek.kv_bytes_per_token(c, 8) == 576 * 2 * 8 == 9216
    assert deepseek.attention_calls_per_step(c, 8) == 8
    flops = deepseek.decode_attention_flops(c, 8, 1000)
    bytes_ = deepseek.decode_attention_bytes(c, 8, 1000)
    assert flops == 1000 * 8 * 2 * 128 * (576 + 512)
    assert bytes_ == 1000 * 9216
    # 241.8 FLOPs a byte where the v5e's ridge is 240.5: the two sides of
    # the roofline within a hundredth (64 heads: 120.9, bound by bytes)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    ridge = peaks["bf16_flops_per_s"] / peaks["hbm_bytes_per_s"]
    assert flops / bytes_ == pytest.approx(241.8, abs=0.1)
    assert flops / bytes_ / ridge == pytest.approx(1.005, abs=0.005)


def test_a_chunk_s_attention_is_10_gflop_a_token_at_8k_rows(config):
    """``latent_prefill_attention`` for one chunk of 512 against 8,192 live
    rows in one layer: the up-projection of every live row to 128 heads of
    256 and the scores and mix at the heads' own widths."""
    one = deepseek.prefill_attention_flops(config, 512, 8192)
    assert one == (2 * 8192 * 512 * 128 * 256
                   + 2 * 512 * 8192 * 128 * (128 + 64 + 128))
    assert 8 * one / 512 / 1e9 == pytest.approx(9.66, abs=0.01)


def test_a_decode_step_counts_the_experts_16_lines_touch(config):
    c = config
    assert deepseek.experts_touched_grouped(c, 16) == pytest.approx(
        20 * (1 - (1 - 6 / 160) ** 16)) == pytest.approx(9.15, abs=0.01)
    assert deepseek.experts_touched_grouped(c, 512) > 19.99
    dense = (8 * 149225472 + 188743680 + 7 * 47185920 + 5120 * 12800)
    base = deepseek.decode_step_bytes(c, 8, 0)
    assert base == pytest.approx(
        2 * (dense + 7 * 9.149698 * 23592960) + 4 * 7 * 819200, rel=1e-6)
    assert deepseek.decode_step_bytes(c, 8, 1000) - base == 1000 * 9216
    # 6.6 GB a step of weights, 8.1 ms at 819 GB/s; 16 lines of 9,000 live
    # positions add 1.3 GB, 1.6 ms
    assert base / 1e9 == pytest.approx(6.60, abs=0.01)
    assert deepseek.decode_step_bytes(c, 8, 16 * 9000) / 819e9 == \
        pytest.approx(0.00968, abs=1e-4)


# -------------------------------------------------------------- the readers

def _trace(modules, ops):
    dev = tr.DeviceTrace(0, [tr.Event(n, a, b) for n, a, b in ops], [],
                         [tr.Event(n, a, b) for n, a, b in modules])
    tr._self_times(dev.ops)
    return tr.Trace([dev], {})


def _obs(config, trace, polls, **more):
    cell = {"config": config, "traffic": {"use": "serve_longdoc"}}
    return {"trace": trace, "trace_span": (1.0, 2.0), "polls": polls,
            "cell": cell, "peaks": {"hbm_bytes_per_s": 819e9,
                                    "bf16_flops_per_s": 197e12}, **more}


def test_latent_attention_roofline_is_100_at_the_larger_side_s_time(config):
    """16 lines of 9,216 fetched positions a step: at 128 heads the FLOPs'
    side is the larger by half a hundredth, and a kernel that takes that
    time a call reads 100."""
    positions = 16 * 9216
    flops = deepseek.decode_attention_flops(config, 8, positions)
    bytes_ = deepseek.decode_attention_bytes(config, 8, positions)
    assert flops / 197e12 > bytes_ / 819e9
    call_s = flops / 197e12 / 8
    polls = [(0.9, {"kv_positions_read": 0, "decode_steps": 0}),
             (2.1, {"kv_positions_read": 10 * positions, "decode_steps": 10})]
    ops = [(f"%latent_decode_attention.{i} = bf16[16,128,512] custom-call()",
            1.0 + i * 1e-2, 1.0 + i * 1e-2 + call_s) for i in range(16)]
    params = manifest.load_json(
        REPO, "layer_metrics", "latent_decode_attention_roofline.json")[
            "params"]
    obs = _obs(config, _trace([], ops), polls)
    assert latent_attention_roofline.read(obs, params) == pytest.approx(100.0)
    slow = [(n, a, a + 2 * call_s) for n, a, _ in ops]
    assert latent_attention_roofline.read(
        _obs(config, _trace([], slow), polls), params) == pytest.approx(50.0)


def test_grouped_matmul_roofline_is_100_at_the_touched_experts_bytes(config):
    """A prefill chunk: 19 rows an expert on all 20; the two calls of a
    layer-step take, at the roofline, the touched experts' weights and the
    rows over 819 GB/s (bytes bind: the ridge is at 240 rows an expert)."""
    work = deepseek.grouped_matmul_work(config, 20, 384)
    assert work["bytes"] == 2 * (20 * 23592960 + 384 * (10240 + 3072))
    assert work["flops"] == 2 * 384 * 23592960
    assert work["bytes"] / 819e9 > work["flops"] / 197e12
    call_s = work["bytes"] / 819e9 / 2
    polls = [(0.9, {"moe_experts_touched": 0, "moe_picks_local": 0,
                    "moe_layer_steps": 0}),
             (2.1, {"moe_experts_touched": 2000, "moe_picks_local": 38400,
                    "moe_layer_steps": 100})]
    ops = [(f"%moe_grouped_matmul.{i} = bf16[4352,5120] custom-call()",
            1.0 + i * 1e-2, 1.0 + i * 1e-2 + call_s) for i in range(20)]
    params = manifest.load_json(REPO, "layer_metrics",
                                "moe_grouped_matmul_roofline.json")["params"]
    obs = _obs(config, _trace([], ops), polls)
    assert grouped_matmul_roofline.read(obs, params) == pytest.approx(100.0)


def _scoped(paths, modules=()):
    """A device's operations, 10 ms each, one after the other from 1.0 s
    on, each with a name-stack path."""
    ops = []
    for i, path in enumerate(paths):
        op = xm.Op(f"%fusion.{i} = bf16[1] fusion()", 1.0 + i * 0.01,
                   1.01 + i * 0.01, tf_op=path, part=xm.part_of(path))
        op.self_s = 0.01
        ops.append(op)
    return xm.DeviceOps(0, ops, [tr.Event(*m) for m in modules])


CHUNK = "jit(prefill_chunk)/stack/while/body/closed_call/"
PATHS = [CHUNK + "attn/dot_general", CHUNK + "attn/cache/dynamic_update_slice",
         CHUNK + "attn/latent_prefill/while/body/dot_general",
         CHUNK + "attn/latent_prefill/while/body/exp",
         CHUNK + "attn/latent_prefill/transpose",
         CHUNK + "mlp/moe_shared/dot_general", CHUNK + "mlp/mul",
         CHUNK + "moe_experts/pallas_call", CHUNK + "moe_combine/add",
         "jit(prefill_chunk)/head/dot_general"]


def test_the_two_scopes_shares_lie_inside_the_parts_around_them():
    """The partition knows ``attn`` and ``mlp`` and books the chunk's
    attention and the shared experts there; ``scope_share`` finds
    ``latent_prefill`` and ``moe_shared`` on the same paths."""
    dev = _scoped(PATHS)
    assert [op.part for op in dev.ops[:7]] == [
        "attn", "cache", "attn", "attn", "attn", "mlp", "mlp"]
    obs = {"trace": object(), "device_ops": dev}
    prefill = manifest.load_json(
        REPO, "layer_metrics", "part_share_latent_prefill.tok_s.json")
    shared = manifest.load_json(
        REPO, "layer_metrics", "part_share_moe_shared.tok_s.json")
    assert scope_share.read(obs, prefill["params"]) == pytest.approx(30.0)
    assert scope_share.read(obs, shared["params"]) == pytest.approx(10.0)
    # a program without the scopes (the parent commit) gives nothing
    bare = _scoped([p.replace("latent_prefill/", "").replace(
        "moe_shared/", "") for p in PATHS])
    for spec in (prefill, shared):
        assert scope_share.read({"trace": object(), "device_ops": bare},
                                spec["params"]) is None
        assert scope_share.read({"trace": None}, spec["params"]) is None


def test_latent_prefill_ms_per_ktok_is_the_scope_s_time_a_thousand_tokens():
    spec = manifest.load_json(REPO, "layer_metrics",
                              "latent_prefill_ms_per_ktok.json")
    modules = [("jit_prefill_chunk(1)", 0.999, 1.02),   # touches the edge
               ("jit_prefill_chunk(1)", 1.02, 1.06),
               ("jit_decode_burst(2)", 1.06, 1.08),
               ("jit_prefill_chunk(1)", 1.08, 1.1)]     # touches the edge
    dev = _scoped(PATHS, modules)
    trace = _trace(modules, [(op.name, op.start, op.end) for op in dev.ops])
    disp = [phases.Phase("engine.prefill_dispatch", t, t + 0.001,
                         {"tokens": 512, "bucket": 512})
            for t in (0.95, 1.0, 1.07)]
    obs = {"trace": trace, "device_ops": dev, "phases": disp}
    # the one whole chunk away from the edges holds three operations of the
    # scope (those that start at 1.02, 1.03, 1.04): 30 ms over 512 tokens
    assert scope_ms_per_count.read(obs, spec["params"]) == pytest.approx(
        30.0 / 512)
    assert scope_ms_per.read(obs, spec["params"]) == pytest.approx(
        30.0 / 512 * 1000)
    obs["device_ops"] = _scoped([CHUNK + "attn/dot_general"] * 10, modules)
    assert scope_ms_per.read(obs, spec["params"]) is None
    names = manifest.module_names(os.path.join(
        BENCH, "rtbench", "readers", "scope_ms_per.py"))
    assert "read" in names and "ADAPTER_NEEDS" not in names


def test_moe_local_token_share_is_tokens_with_a_pick_here_over_tokens():
    spec = manifest.load_json(REPO, "layer_metrics",
                              "moe_local_token_share.json")
    polls = [(1.0, {"moe_tokens_local": 100, "moe_picks": 6000}),
             (2.0, {"moe_tokens_local": 460, "moe_picks": 12000})]
    obs = {"polls": polls, "t_open": 0.5, "t_close": 2.5}
    # 1,000 (token, layer) pairs, 360 of them with a pick on a held expert
    assert counter_ratio.read(obs, spec["params"]) == pytest.approx(36.0)
    # the parent commit's stats() lack the counter
    old = [(t, {"moe_picks": s["moe_picks"]}) for t, s in polls]
    assert counter_ratio.read({**obs, "polls": old}, spec["params"]) is None


# -------------------------------------------------------------- the control

def tiny(config):
    c = dict(config)
    c.update(hidden_size=512, intermediate_size=1024,
             moe_intermediate_size=128, num_attention_heads=8,
             q_lora_rank=192, kv_lora_rank=64, qk_nope_head_dim=32,
             qk_rope_head_dim=16, v_head_dim=32, vocab_size=2048,
             torch_dtype="float32")     # the cell's own depth, 8 layers
    return c


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_the_fp8_control_comes_out_as_not_correct(config, traffic, seed):
    """benchmark/control.py at a size a test run can hold: the reference on
    weights rounded through fp8 chooses tokens that lie further under the
    float32 reference's top logit than the cell allows, and further than
    the stated precision (bfloat16 weights) does. The readings at the
    cell's own size are PERF.md's (section 4)."""
    from reference import deepseek as reference

    from ray_tpu.llm import engine

    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(BENCH, "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    limit = traffic["check"]["margin"]

    c = tiny(config)
    cfg = deepseek.model_config(c, "serve_longdoc", 256)
    weights = deepseek.reference_weights(
        engine.init_params(cfg, jax.random.PRNGKey(seed)))
    assert weights["layers"]["kv_b"].shape == (8, 64, 8 * 64)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (128,), 0, 2048)
    want = reference.logits(c, weights, tokens)
    fp8 = control.margin(
        want, reference.logits(c, control.to_fp8(weights), tokens), 32)
    bf16 = control.margin(want, reference.logits(c, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), weights), tokens), 32)
    assert fp8 > limit
    assert bf16 < fp8
