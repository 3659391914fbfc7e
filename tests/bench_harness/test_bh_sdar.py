"""The SDAR-30B-A3B-Chat cell's files: its configuration against the
published one, its adapter's arithmetic against hand-worked values, its
plan, its own entries in the manifest (sets held with ``>=``: a later PR
that gives the cell a metric, or adds a cell after it, turns nothing here),
the two roofline readers at 100 on a made-up trace of this cell's shapes,
the two metrics this cell brings, and the control at a small size."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, REPO
from rtbench import gen, manifest, trace_reduce as tr, xplane_meta as xm
from rtbench.adapters import sdar
from rtbench.readers import (
    counter_ratio,
    decode_attention_roofline,
    grouped_matmul_roofline,
)

CELL = "sdar-30b-serve-generate-512"

# The catalog row's ``config`` (huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/
# main/config.json), as published.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 6}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "serve-generate-512.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- the files

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_key_is_kept_or_listed_as_reduced(config, key):
    entry = manifest.config_entry(manifest.load(REPO), "sdar-30b-a3b-chat")
    if key in REDUCED:
        assert key in entry["reduced"] and key in config["reduced"]
        assert config[key] == REDUCED[key]
        assert config["published"][key] == PUBLISHED[key]
    else:
        assert key not in entry["reduced"]
        assert config[key] == PUBLISHED[key]
        assert type(config[key]) is type(PUBLISHED[key])


def test_the_cut_is_depth_alone_and_says_what_it_assumed(config):
    entry = manifest.config_entry(manifest.load(REPO), "sdar-30b-a3b-chat")
    assert entry["reduced"] == ["num_hidden_layers"] == list(config["reduced"])
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")
    # every layer is alike, so the floor is four; six divide the 48
    assert 4 <= config["num_hidden_layers"] == 6 and 48 % 6 == 0
    assert config["num_experts"] == 128 and config["vocab_size"] == 151936
    assert (config["block_length"], config["denoising_steps"],
            config["remasking_strategy"], config["mask_token_id"]) == \
        (4, 4, "sequential", 151669)
    for item in ("equations", "layer", "attention", "mask", "block_length",
                 "denoising_steps", "remasking_strategy", "mask_token_id",
                 "logits", "commit", "router", "init"):
        assert config["assumed"][item], item
    assert "modeling_sdar_moe.py" in config["assumed"]["equations"]
    assert "generate.py" in config["assumed"]["equations"]
    assert "no shift" in config["assumed"]["logits"]
    assert "_reference_check" in config["assumed"]["remasking_strategy"]
    assert "5 forwards" in config["departures"]["forwards"]
    assert "eight pipeline stages" in config["deployment"]


def test_the_manifest_is_clean_and_the_cell_is_what_the_issue_names(traffic):
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    cell = manifest.load_cell(CELL, REPO)
    assert cell["workload"] == {**cell["workload"],
                                "config": "sdar-30b-a3b-chat",
                                "traffic": "serve-generate-512", "chips": 1}
    assert "6 of 48 layers" in cell["workload"]["why"]
    assert "32 rows" in cell["workload"]["why"]
    assert {x["name"] for x in cell["end_to_end"]} >= {"serve_tok_s",
                                                        "setup_s"}
    shares = {f"part_share_{g}.tok_s" for g in (
        "attn", "mlp", "head", "lowering", "unnamed", "moe_experts",
        "moe_glue")}
    names = {x["name"] for x in cell["per_layer"]}
    assert names >= shares | {
        "slots_active_share", "device_idle_share.tok_s",
        "idle_in_scheduler_share.tok_s", "admit_to_first_token_mean_ms.tok_s",
        "decode_slot_use_share.tok_s", "decode_ahead_share.tok_s",
        "prefill_ms_per_ktok.counted", "decode_ms_per_step.tok_s",
        "decode_kv_read_share.tok_s", "prefill_kv_read_share.tok_s",
        "tpot_p90_ms.tok_s", "decode_attention_roofline.tok_s",
        "moe_grouped_matmul_roofline", "moe_experts_touched_share",
        "moe_ms_per_step", "moe_glue_ms_per_step", "moe_tiles_per_expert",
        "diffusion_forwards_per_token", "diffusion_commit_share"}
    # its reader estimates steps from the tokens clients received, which
    # here are 0.8 a line-forward
    assert "decode_bw_share.tok_s" not in names
    # the seven shares name every part this cell's programs can have once
    listed = [p for x in cell["per_layer"] if x["name"] in shares
              for p in x["params"]["parts"]]
    assert sorted(listed) == sorted({*xm.PARTS, xm.UNNAMED, xm.LOWERED}
                                    - {"optim"})
    # the two this cell brings: data files over a reader that was there
    for name, num, den in (
            ("diffusion_forwards_per_token", "diffusion_forwards",
             "decode_tokens"),
            ("diffusion_commit_share", "diffusion_commits",
             "diffusion_forwards")):
        spec = next(x for x in cell["per_layer"] if x["name"] == name)
        assert spec["reader"] == "counter_ratio" and CELL in spec["workloads"]
        assert (spec["params"]["num"], spec["params"]["den"]) == (num, den)
        assert spec["moves"] == "serve_tok_s" and spec["better"] == "lower"
        assert spec["source"] == "program_counter"


def test_the_traffic_is_what_the_issue_names(traffic):
    assert traffic["kind"] == "closed_loop"
    assert traffic["clients"] in (128, 64)     # the one stated fallback
    assert traffic["engine"] == {
        "max_num_seqs": traffic["clients"], "max_seq_len": 1536,
        "dtype": "bfloat16", "kv_block_size": 0,
        "decode_burst": traffic["engine"]["decode_burst"],
        "max_ongoing_requests": 2 * traffic["clients"]}
    assert traffic["engine"]["decode_burst"] in (1, 2, 4) \
        and traffic["engine_why"]
    assert traffic["prompt_tokens"] == {"kind": "lognormal", "median": 256,
                                        "sigma": 0.7, "min": 32, "max": 1024}
    assert traffic["max_tokens"] == {"kind": "constant", "value": 512}
    assert traffic["cycle_requests"] == traffic["clients"]
    assert traffic["trace"] == {"after_s": 10, "for_s": 4}
    assert traffic["check"]["requests"] == 4
    assert traffic["check"]["min_readable"] == 256
    assert "0.64" in traffic["check"]["min_readable_why"]
    assert "control" in traffic["check"]["margin_why"]
    for why in ("why", "max_requests_per_s_why", "warmup_why", "cycle_why",
                "stagger_why"):
        assert traffic[why] and "TO BE SET" not in traffic[why], why


@pytest.mark.parametrize("seed", [1, 2147483700])
def test_the_plan_fills_the_line_and_walks_every_shape(traffic, seed):
    plan = gen.closed_loop_plan(traffic, seed, 51)
    n = traffic["cycle_requests"]
    cycle = plan["requests"][:n]
    # the lowest of 128 quantiles is 40; the three highest are clipped
    assert min(r["prompt_tokens"] for r in cycle) == 40 >= 32
    assert max(r["prompt_tokens"] for r in cycle) == 1024
    assert sum(r["prompt_tokens"] == 1024 for r in cycle) == 3
    assert {r["max_tokens"] for r in cycle} == {512}
    # the longest request is the line's whole length, its last block the
    # line's last
    assert max(r["prompt_tokens"] + r["max_tokens"] for r in cycle) \
        == traffic["engine"]["max_seq_len"]
    # prompts of every length mod 4: first blocks of 1 to 4 tokens
    assert {r["prompt_tokens"] % 4 for r in cycle} == {0, 1, 2, 3}
    # every seed sends the same requests, in an order of its own
    other = gen.closed_loop_plan(traffic, seed + 1, 51)["requests"][:n]
    key = lambda r: (r["prompt_tokens"], r["max_tokens"])  # noqa: E731
    assert sorted(map(key, cycle)) == sorted(map(key, other))
    assert [key(r) for r in cycle] != [key(r) for r in other]
    assert plan["clients"] == traffic["engine"]["max_num_seqs"]
    # the plan has room: a run at 6,378 tokens/s takes 632 to 644 requests
    # (my chip runs, PR 41), and half as many again still fit
    assert len(plan["requests"]) >= 1.5 * 644
    # a cycle is its seed's and its number's alone: a smaller rate gives
    # the same plan cut short (the first twelve runs were made at 12)
    fewer = gen.closed_loop_plan({**traffic, "max_requests_per_s": 12},
                                 seed, 51)["requests"]
    assert fewer == plan["requests"][:len(fewer)] and len(fewer) == 6 * n
    # the warm-up: a prompt whose whole blocks fall in every prefill bucket,
    # every length mod 4, and answers of 1, 3 and 5 or more blocks (bursts
    # of 1 and 2, each also behind a running burst)
    whole = {w["prompt_tokens"] - w["prompt_tokens"] % 4
             for w in traffic["warmup"]}
    assert whole >= {16, 32, 64, 128, 256, 512}
    assert {w["prompt_tokens"] % 4 for w in traffic["warmup"]} == {0, 1, 2, 3}
    blocks = {-(-(w["max_tokens"] + w["prompt_tokens"] % 4) // 4)
              for w in traffic["warmup"]}
    assert 1 in blocks and 3 in blocks and max(blocks) >= 5


# ----------------------------------------------------------- the arithmetic

def test_the_cut_is_4361m_parameters_8_12_gib(config):
    c = config
    assert sdar.expert_params(c) == 3 * 2048 * 768 == 4718592
    assert 128 * sdar.expert_params(c) * 2 / 2 ** 30 == 1.125
    assert sdar.attention_params(c) == (2 * 2048 * 4096 + 2 * 2048 * 512
                                        + 256) == 18874624
    assert sdar.router_params(c) == 2048 * 128 == 262144
    assert sdar.layer_params(c) == 623120640
    assert sdar.params_held(c) == 6 * 623120640 + 2 * 311164928 + 2048 \
        == 4361055744
    assert sdar.params_held(c) * 2 / 2 ** 30 == pytest.approx(8.123, abs=1e-3)
    # the published 48 layers would not fit
    assert 48 * sdar.layer_params(c) * 2 / 2 ** 30 == pytest.approx(55.7,
                                                                    abs=0.05)


def test_depth_is_layers_and_the_program_s_configuration_follows(config):
    assert sdar.depth(config, "serve_generate") == 6
    assert sdar.forwards_per_block(config) == 5
    cfg = sdar.model_config(config, "serve_generate", 1536)
    assert (cfg.num_layers, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.head_dim, cfg.num_heads, cfg.num_kv_heads) == \
        (6, 128, 8, 128, 32, 4)
    assert (cfg.block_length, cfg.denoising_steps, cfg.remasking_strategy,
            cfg.mask_token_id, cfg.max_seq_len) == \
        (4, 4, "sequential", 151669, 1536)
    rule = cfg.router_rule
    assert (rule.score, rule.use_bias, rule.renormalize, rule.renorm_eps,
            rule.zero_experts, rule.topk, rule.held) == \
        ("softmax", False, True, 0.0, 0, 8, 128)
    assert cfg.num_params() == sdar.params_held(config)
    with pytest.raises(ValueError, match="attention_bias"):
        sdar.model_config({**config, "attention_bias": True},
                          "serve_generate", 1536)


def test_a_cached_position_is_12_kib_and_128_lines_2_25_gib(config):
    assert sdar.kv_bytes_per_token(config, 6) == 6 * 2 * 4 * 128 * 2 \
        == 12 * 1024
    assert 128 * 1536 * sdar.kv_bytes_per_token(config, 6) == 2.25 * 2 ** 30


def test_a_forward_reads_every_expert_and_7_6_gib(config):
    c = config
    # 512 rows x 8 picks over 128 experts: every expert, 32 rows each
    assert sdar.experts_touched_uniform(c, 512) == pytest.approx(
        128 * (1 - (127 / 128) ** 4096)) == pytest.approx(128.0, abs=1e-9)
    assert 512 * 8 / 128 == 32
    base = sdar.decode_step_bytes(c, 6, 0, slots=128)
    assert base == pytest.approx(
        2 * (6 * 18874624 + 311164928 + 6 * 128 * 4718592)
        + 4 * 6 * 262144, rel=1e-9)
    assert sdar.decode_step_bytes(c, 6, 1000, slots=128) - base == (
        1000 * 12 * 1024)
    # 7.54 GiB of weights a forward, 9.9 ms at 819 GB/s; with 128 lines of
    # 600 live positions 10.8
    assert base / 2 ** 30 == pytest.approx(7.54, abs=0.01)
    assert base / 819e9 == pytest.approx(0.00989, abs=1e-4)
    assert sdar.decode_step_bytes(c, 6, 128 * 600, slots=128) / 819e9 == (
        pytest.approx(0.01104, abs=1e-4))
    # fewer lines touch fewer experts; no cell's lines are a default
    assert sdar.decode_step_bytes(c, 6, 0, slots=4) < 0.7 * base
    with pytest.raises(TypeError):
        sdar.decode_step_bytes(c, 6, 0)


# -------------------------------------------------------------- the readers

def _trace(ops):
    dev = tr.DeviceTrace(0, [tr.Event(n, a, b) for n, a, b in ops], [], [])
    tr._self_times(dev.ops)
    return tr.Trace([dev], {})


def _obs(config, trace, polls):
    cell = {"config": config, "traffic": {"use": "serve_generate"}}
    return {"trace": trace, "trace_span": (1.0, 2.0), "polls": polls,
            "cell": cell, "t_open": 0.0, "t_close": 3.0,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}


def test_decode_attention_roofline_is_100_at_a_forward_s_bytes(config):
    """``decode_steps`` and ``kv_positions_read`` both count forwards (5 a
    block): 128 lines of 1,024 fetched positions are 128 x 1,024 x 2 KiB a
    call, whatever the 4 query rows a line."""
    positions = 128 * 1024
    call_s = positions * 2048 / 819e9
    assert sdar.decode_attention_bytes(config, 6, positions) \
        == 6 * positions * 2048
    polls = [(0.9, {"kv_positions_read": 0, "decode_steps": 0}),
             (2.1, {"kv_positions_read": 10 * positions, "decode_steps": 10})]
    ops = [(f"%decode_attention.{i} = bf16[128,4,32,128] custom-call()",
            1.0 + i * 1e-2, 1.0 + i * 1e-2 + call_s) for i in range(20)]
    obs = _obs(config, _trace(ops), polls)
    assert decode_attention_roofline.read(
        obs, {"kernel": "decode_attention"}) == pytest.approx(100.0)


def test_grouped_matmul_roofline_is_100_at_every_expert_once(config):
    """A forward of 128 lines: 4,096 rows on 128 experts a layer; the two
    calls of a layer-step take, at the roofline, every expert's weights
    once and the rows over 819 GB/s (bytes bind at 32 rows an expert)."""
    work = sdar.grouped_matmul_work(config, 128, 4096)
    assert work["bytes"] == 2 * (128 * 4718592 + 4096 * (4096 + 1536))
    assert work["flops"] == 2 * 4096 * 4718592
    assert work["bytes"] / 819e9 > work["flops"] / 197e12
    call_s = work["bytes"] / 819e9 / 2
    polls = [(0.9, {"moe_experts_touched": 0, "moe_picks_local": 0,
                    "moe_layer_steps": 0}),
             (2.1, {"moe_experts_touched": 12800, "moe_picks_local": 409600,
                    "moe_layer_steps": 100})]
    ops = [(f"%moe_grouped_matmul.{i} = bf16[12288,2048] custom-call()",
            1.0 + i * 1e-2, 1.0 + i * 1e-2 + call_s) for i in range(20)]
    params = manifest.load_json(REPO, "layer_metrics",
                                "moe_grouped_matmul_roofline.json")["params"]
    assert grouped_matmul_roofline.read(_obs(config, _trace(ops), polls),
                                        params) == pytest.approx(100.0)


def test_the_two_diffusion_metrics_read_the_engine_s_counters(config):
    """A window in which 100 line-blocks ran and gave 380 tokens (a few
    first and last blocks gave fewer than 4): 500 forwards, 100 of them
    commits."""
    polls = [(0.5, {"diffusion_forwards": 50, "diffusion_commits": 10,
                    "decode_tokens": 38}),
             (2.5, {"diffusion_forwards": 550, "diffusion_commits": 110,
                    "decode_tokens": 418})]
    obs = _obs(config, None, polls)
    spec = {name: manifest.load_json(REPO, "layer_metrics", name + ".json")
            for name in ("diffusion_forwards_per_token",
                         "diffusion_commit_share")}
    assert counter_ratio.read(
        obs, spec["diffusion_forwards_per_token"]["params"]) \
        == pytest.approx(500 / 380)
    assert counter_ratio.read(
        obs, spec["diffusion_commit_share"]["params"]) == pytest.approx(20.0)
    # a program without the counters (a parent commit) gives nothing
    bare = _obs(config, None, [(t, {"decode_tokens": s["decode_tokens"]})
                               for t, s in polls])
    for s in spec.values():
        assert counter_ratio.read(bare, s["params"]) is None
        assert s["layer"] == spec["diffusion_commit_share"]["layer"]


def test_the_adapter_has_what_the_cell_s_kind_and_readers_call():
    names = manifest.module_names(os.path.join(
        BENCH, "rtbench", "adapters", "sdar.py"))
    assert names["REFERENCE"] == "reference.sdar"
    assert set(names) >= {"depth", "model_config", "reference_weights",
                          "decode_attention_bytes", "grouped_matmul_work",
                          "decode_step_bytes", "kv_bytes_per_token"}
    # the shape arithmetic imports nothing of the program at module level
    assert "ray_tpu" not in names and "jax" not in names


# -------------------------------------------------------------- the control

def tiny(config):
    c = dict(config)
    c.update(hidden_size=512, moe_intermediate_size=128,
             num_attention_heads=8, num_key_value_heads=2, head_dim=64,
             num_experts=16, num_experts_per_tok=4, vocab_size=2048,
             mask_token_id=2000, num_hidden_layers=4, torch_dtype="float32")
    return c


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_fp8_control_comes_out_as_not_correct(config, traffic, seed):
    """benchmark/control.py at a size a test run can hold: the reference on
    weights rounded through fp8 chooses tokens that lie further under the
    float32 reference's top logit than the cell allows, and further than
    the stated precision (bfloat16 weights) does. The readings at the
    cell's own size are PERF.md's (section 4)."""
    from reference import sdar as reference

    from ray_tpu.llm import engine

    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(BENCH, "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    limit = traffic["check"]["margin"]

    c = tiny(config)
    cfg = sdar.model_config(c, "serve_generate", 128)
    weights = sdar.reference_weights(
        engine.init_params(cfg, jax.random.PRNGKey(seed)))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (96,), 0, 2000)
    want = reference.logits(c, weights, tokens)
    fp8 = control.margin(
        want, reference.logits(c, control.to_fp8(weights), tokens), 24)
    bf16 = control.margin(want, reference.logits(c, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), weights), tokens), 24)
    assert fp8 > limit
    assert bf16 < fp8
