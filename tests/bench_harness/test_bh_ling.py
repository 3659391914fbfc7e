"""The Ling-3.0-flash-VL cell's files: its configuration against the
published one (every key kept but the cuts the file lists), its adapter's
arithmetic against hand-worked values at the published widths, its plan, its
own entries in the manifest (never the number of cells, never which cell is
last, and the cell's metric set held with ``<=``), each new metric file on a
made-up trace, the latent kernel's roofline at this cell's two lines, the
reference at a small size against ``models/ling.py``, the run without a TPU,
and the control at a small size."""

import importlib
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, REPO
from rtbench import gen, manifest, trace_reduce as tr
from rtbench.adapters import ling
from rtbench.readers import (
    counter_ratio,
    delta_rule_roofline,
    latent_attention_roofline,
    phases,
    scope_ms_per,
    scope_ms_per_count,
    scope_share,
)
from test_bh_qwen3_next import _scoped, _trace  # noqa: E402

CELL = "ling3-flash-serve-rollout-8k"
CONFIG = "ling-3.0-flash-vl"
SOURCE = ("https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/"
          "config.json")
LIMITS = [0] * 35 + [4] * 7
SHARED_LIMITS = [0] * 34 + [5] * 6 + [7] * 2

# The catalog row's ``config`` (the URL above), as published.
PUBLISHED = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": LIMITS,
    "share_expert_swiglu_limit_list": SHARED_LIMITS}
CUT = {"num_hidden_layers": 12, "num_experts": 64, "vocab_size": 19648,
       "expert_swiglu_limit_list": [0] * 12,
       "share_expert_swiglu_limit_list": [0] * 12}
LAYER = ("Linear attention (models/qwen3_next.py Gated DeltaNet, "
         "ops/gated_delta.py gated_delta_chunk, gated_delta_step)")
MINE = ("kda_chunk_roofline", "kda_step_roofline", "kda_ms_per_ktok",
        "kda_step_ms_per_step", "part_share_kda.tok_s",
        "kda_state_update_share")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
STEP_PROGRAMS = ["jit_decode_burst", "jit_decode_step"]
# Requests a 51 s window finishes (my chip runs, PR 58: serve_tok_s over the
# cycle's mean request of 2,534 tokens), rounded up.
WINDOW_REQUESTS = 160


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "serve-rollout-8k.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- the files

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_key_is_kept_or_its_cut_is_listed(config, key):
    want = CUT.get(key, PUBLISHED[key])
    assert config[key] == want and type(config[key]) is type(want)
    if key in CUT:
        assert config["published"][key] == PUBLISHED[key]
        assert config["reduced"][key]


def test_the_file_lists_its_cuts_and_what_it_assumed(config):
    entry = manifest.config_entry(manifest.load(REPO), CONFIG)
    # the three cuts ISSUE 58 names and the two lists that are a value a
    # layer and are cut with the layers
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size", "expert_swiglu_limit_list",
                                "share_expert_swiglu_limit_list"]
    assert sorted(config["reduced"]) == sorted(CUT)
    # no width among them
    assert not [k for k in entry["reduced"] if k.endswith(("_dim", "_rank",
                                                           "_size"))
                and k != "vocab_size"]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["adapter"] == "ling"
    assert (config["expert_shard"], config["expert_shards"]) == (0, 8)
    for key in ("equations", "parameter_count", "norms", "layer", "kda",
                "attention", "router", "experts", "embeddings", "init",
                "sizes"):
        assert config["assumed"][key], key
    for key in ("vision_tower", "mtp", "swiglu_clamp", "state_dtype",
                "router_dtype", "projection_order", "chunked_rule"):
        assert config["departures"][key], key
    assert "no capacity" in config["guarantees"]
    assert "the state is float32" in config["guarantees"]
    assert "32 chips" in config["deployment"]
    assert "share 0" in config["deployment"]
    # the arithmetic of the cut, and the compiler's figures beside it
    for said in ("124.41B", "4,736.4M", "8.85 GiB", "1.875 GiB",
                 "memory_analysis"):
        assert said in config["reduced"]["num_hidden_layers"], said


def test_the_cell_s_own_entries_are_what_the_issue_names(traffic):
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    assert manifest.check_modules(m, REPO) == []
    cell = manifest.load_cell(CELL, REPO)
    assert cell["workload"] == {**cell["workload"], "config": CONFIG,
                                "traffic": "serve-rollout-8k", "chips": 1}
    why = cell["workload"]["why"]
    for said in ("128 clients", "96 slots x 8,192", "960 states of 2 MiB",
                 "12 of 42 layers", "1.5 rows an expert", "bunched"):
        assert said in why
    assert [x["name"] for x in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    names = {x["name"] for x in cell["per_layer"]}
    assert set(MINE) <= names
    # the serve set, what Qwen3-Next's cell reads of a linear layer, what
    # DeepSeek's reads of the latent kernel and of the routed layer
    assert {"decode_ms_per_step.tok_s", "decode_kv_read_share.tok_s",
            "prefill_kv_read_share.tok_s", "prefill_ms_per_ktok.counted",
            "tpot_p90_ms.tok_s", "part_share_attn.tok_s",
            "part_share_mlp.tok_s", "part_share_head.tok_s",
            "part_share_lowering.tok_s", "part_share_unnamed.tok_s",
            "device_idle_share.tok_s", "slots_active_share",
            "decode_slot_use_share.tok_s", "decode_ahead_share.tok_s",
            "idle_in_scheduler_share.tok_s",
            "admit_to_first_token_mean_ms.tok_s", "ingress_mean_ms.tok_s",
            "egress_chunk_lag_mean_ms.tok_s", "egress_write_mean_ms.tok_s",
            "stream_close_lag_mean_ms.tok_s", "last_frame_lag_mean_ms.tok_s",
            "slot_vacant_mean_ms.tok_s", "part_share_linear_attn.tok_s",
            "part_share_conv.tok_s", "latent_decode_attention_roofline",
            "moe_local_pick_share", "moe_experts_touched_share",
            "moe_ms_per_step", "moe_glue_ms_per_step",
            "moe_tiles_per_expert", "part_share_moe_experts.tok_s",
            "part_share_moe_glue.tok_s"} <= names
    # left out and why: tests that pass today hold these four with
    # ``== [CELL]`` (test_bh_deepseek.py:146), and the fourth's scale is
    # DeepSeek's 6 picks a token; this model has no line of per-head keys
    assert not names & {"part_share_moe_shared.tok_s",
                        "part_share_latent_prefill.tok_s",
                        "latent_prefill_ms_per_ktok",
                        "moe_local_token_share", "decode_bw_share.tok_s",
                        "decode_attention_roofline.tok_s",
                        "part_share_delta_rule.tok_s"}
    for x in cell["per_layer"]:
        if x["name"] in MINE:
            # (``in``, not ``==``: a later cell may be appended)
            assert CELL in x["workloads"] and x["moves"] == "serve_tok_s"
            assert x["layer"] == LAYER
    readers = {x["name"]: (x["reader"], x["params"])
               for x in cell["per_layer"] if x["name"] in MINE}
    assert readers["kda_chunk_roofline"] == ("delta_rule_roofline", {
        "form": "chunk", "scopes": ["kda_rule"],
        "programs": ["jit_prefill_chunk"],
        "phase": "engine.prefill_dispatch", "count": "tokens"})
    assert readers["kda_step_roofline"] == ("delta_rule_roofline", {
        "form": "step", "scopes": ["kda_rule", "linear_state"],
        "programs": STEP_PROGRAMS, "phase": "engine.decode_dispatch",
        "count": "steps"})
    assert readers["kda_ms_per_ktok"] == ("scope_ms_per", {
        "scopes": ["kda_rule"], "programs": ["jit_prefill_chunk"],
        "phase": "engine.prefill_dispatch", "count": "tokens", "per": 1000})
    assert readers["kda_step_ms_per_step"] == ("scope_ms_per_count", {
        "scopes": ["kda_rule", "linear_state"], "programs": STEP_PROGRAMS,
        "phase": "engine.decode_dispatch", "count": "steps"})
    assert readers["part_share_kda.tok_s"] == ("scope_share", {
        "scopes": ["kda_rule"]})
    assert readers["kda_state_update_share"] == ("counter_ratio", {
        "num": "linear_state_updates", "den": "decode_steps",
        "den_times": "slots", "scale": 10.0})
    assert traffic["kind"] == "closed_loop"
    assert traffic["engine"] == {
        "max_num_seqs": 96, "max_seq_len": 8192, "dtype": "bfloat16",
        "kv_block_size": 0, "max_ongoing_requests": 256}
    assert traffic["clients"] == 128 and traffic["cycle_requests"] == 128
    assert traffic["prompt_tokens"] == {"kind": "lognormal", "median": 1024,
                                        "sigma": 0.8, "min": 128,
                                        "max": 6144}
    assert traffic["max_tokens"] == {"kind": "uniform", "min": 768,
                                     "max": 1536}
    assert traffic["trace"] == {"after_s": 10, "for_s": 4}
    assert traffic["check"]["requests"] == 4
    assert "control" in traffic["check"]["margin_why"]
    assert traffic["use"] == "serve_rollout"
    longctx = manifest.load_json(REPO, "traffic", "serve-longctx-32k.json")
    assert traffic["warmup"] == longctx["warmup"]
    for key in ("why", "warmup_why", "cycle_why", "stagger_why",
                "max_requests_per_s_why"):
        assert len(traffic[key]) > 100 and "TBD" not in traffic[key], key


@pytest.mark.parametrize("seed", [1, 2147483700])
def test_the_plan_outlasts_its_window_and_fits_the_line(traffic, seed):
    """ROADMAP R0 (r): docqa's list ends with its window; this cell's holds
    at least twice what a window can finish. A line ends after 896 to 7,680
    positions, inside the slot's 8,192."""
    plan = gen.closed_loop_plan(traffic, seed, 51)
    assert plan == gen.closed_loop_plan(traffic, seed, 51)
    cycle = plan["requests"][:128]
    prompts = sorted(r["prompt_tokens"] for r in cycle)
    assert (prompts[0], prompts[-1]) == (128, 6144)
    assert 1000 <= prompts[64] <= 1050                  # median 1,024
    assert round(sum(prompts) / 128) == 1382
    assert all(768 <= r["max_tokens"] <= 1536 for r in cycle)
    assert round(sum(r["max_tokens"] for r in cycle) / 128) == 1152
    longest = max(r["prompt_tokens"] + r["max_tokens"] for r in cycle)
    assert longest <= 6144 + 1536 <= traffic["engine"]["max_seq_len"]
    # every seed sends the same 128 requests, in an order of its own
    other = gen.closed_loop_plan(traffic, seed + 1, 51)["requests"][:128]
    key = lambda r: (r["prompt_tokens"], r["max_tokens"])  # noqa: E731
    assert sorted(map(key, cycle)) == sorted(map(key, other))
    assert [key(r) for r in cycle] != [key(r) for r in other]
    # 32 clients wait for a slot
    assert plan["clients"] == 128 == traffic["engine"]["max_num_seqs"] + 32
    # the list: the warm-up aside, the ramp's two generations (256) and
    # twice what a window finishes at the rate the builder measured
    # (``max_requests_per_s_why``); no client waits on the rate, which
    # only sizes the list
    per_window = WINDOW_REQUESTS
    assert len(plan["requests"]) >= 256 + 2 * per_window
    # ids come from the slice of the vocabulary that is held, and no image
    # or video token is among them
    ids = gen.prompt_ids(seed, 1000, 4096, 19648)
    assert 259 <= min(ids) and 15000 < max(ids) < 19648 < 156909


# ----------------------------------------------------------- the arithmetic

def test_this_chip_s_share_is_4736m_parameters(config):
    """ISSUE 58's count at the published widths: a KDA layer 63,049,888, a
    latent layer 31,965,696, a dense SwiGLU 47,185,920, an expert
    5,898,240, a router 1,311,232 with its bias; 8.82 GiB with the routers'
    float32."""
    assert ling.kda_params(config) == 63_049_888 == (
        6 * 2560 * 4096 + 2560 * 32 + 12288 * 4 + 4096 + 32 + 128)
    assert ling.latent_params(config) == 31_965_696 == (
        2560 * 32 * 192 + 2560 * 576 + 512 + 512 * 32 * 256 + 2560 * 32
        + 4096 * 2560)
    assert ling.dense_ffn_params(config) == 47_185_920
    assert ling.expert_params(config) == ling.shared_params(config) \
        == 5_898_240
    assert ling.router_params(config) == 1_311_232
    assert (ling.linear_lines(config), ling.latent_lines(config),
            ling.routed_layers(config), ling.router_outputs(config),
            ling.conv_dim(config), ling.latent_dim(config)) == (
                10, 2, 10, 512, 12288, 576)
    held = ling.params_held(config)
    assert held == (10 * 63_049_888 + 2 * 31_965_696 + 2 * 47_185_920
                    + 10 * (1_311_232 + 65 * 5_898_240) + 25 * 2560
                    + 2 * 2560 * 19648) == 4_736_432_192
    gib = (2 * (held - 10 * 1_311_232) + 4 * 10 * 1_311_232) / 2 ** 30
    assert round(gib, 2) == 8.85
    # the whole model, by the same functions on the published counts
    whole = {**config, **config["published"],
             "published": {"num_experts": 512}}
    assert round(ling.params_held(whole) / 1e9, 2) == 124.41
    # a token's parameters: 8 picks and the shared expert in 40 layers
    active = (ling.params_held(whole)
              - 40 * (512 - 8) * ling.expert_params(config))
    assert round(active / 1e9, 2) == 5.51
    assert round(ling.experts_touched_grouped(config, 96), 1) == 49.9


def test_depth_is_the_layers_and_the_program_follows(config):
    assert ling.depth(config, "serve_rollout") == 12
    assert ling.attention_calls_per_step(config, 12) == 2
    cfg = ling.model_config(config, "serve_rollout", 8192)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.linear_num_heads, cfg.linear_head_dim, cfg.kv_lora_rank,
            cfg.qk_head_dim, cfg.v_head_dim, cfg.vocab_size,
            cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.n_group, cfg.topk_group, cfg.max_seq_len, cfg.dtype) == (
                12, 2560, 32, 32, 128, 512, 192, 128, 19648, 512, 64, 8, 8,
                4, 8192, "bfloat16")
    assert [cfg.kind(l) for l in range(12)] == (["kda"] * 5 + ["latent"]) * 2
    assert (cfg.num_dense_layers, cfg.num_routed_layers, cfg.linear_lines,
            cfg.latent_lines) == (2, 10, 10, 2)
    assert (cfg.rope_theta, cfg.kda_lower_bound, cfg.norm_eps,
            cfg.routed_scaling_factor, cfg.short_conv_kernel_size,
            cfg.q_lora_rank, cfg.mla_rope_interleaved) == (
                6e6, -5.0, 1e-6, 2.5, 4, None, False)
    assert cfg.num_params() == ling.params_held(config)
    assert cfg.linear_state_bytes == ling.linear_state_bytes(config) \
        == 2 * 2 ** 20
    for key, bad in (("q_lora_rank", 1536), ("score_function", "softmax"),
                     ("moe_router_enable_expert_bias", False),
                     ("num_kv_heads_for_linear_attn", 8),
                     ("no_kda_lora", False), ("kda_safe_gate", False),
                     ("gated_attention_proj_granularity_type",
                      "elementwise"), ("rotary_dim", 128)):
        with pytest.raises(ValueError, match="LingConfig runs"):
            ling.model_config({**config, key: bad}, "serve_rollout", 64)
    # the clamp of the last layers is refused for a layer that is held
    with pytest.raises(ValueError, match="clamped SwiGLU"):
        ling.model_config({**config, "expert_swiglu_limit_list":
                           [0] * 11 + [4]}, "serve_rollout", 64)


def test_a_slot_is_20_mib_of_states_and_2304_bytes_a_position(config):
    assert ling.linear_state_bytes(config) == 32 * 128 * 128 * 4
    assert ling.conv_window_bytes(config) == 3 * 12288 * 2
    assert ling.kv_bytes_per_token(config, 12) == 2 * 576 * 2 == 2304
    gib = 2 ** 30
    assert 96 * 10 * ling.linear_state_bytes(config) / gib == 1.875
    assert round(96 * 8192 * 2304 / gib, 2) == 1.69
    assert round(96 * 10 * ling.conv_window_bytes(config) / 2 ** 20) == 68


def test_the_rule_s_yardstick_counts_a_decay_a_channel(config):
    """A token's ``g`` is 4,096 float32 numbers a layer and not 32:
    ``q``, ``k``, ``v``, ``g`` and ``o`` a head's 128 each and ``beta`` one;
    the FLOPs are the recurrence's; a step moves a state twice."""
    work = ling.delta_rule_token_work(config)
    assert work == {"flops": 7 * 128 * 128 * 32,
                    "bytes": 32 * (5 * 128 + 1) * 4}
    from rtbench.adapters import qwen3_next

    q3 = manifest.load_json(REPO, "configs", "qwen3-next-80b-a3b.json")
    assert work["flops"] == qwen3_next.delta_rule_token_work(q3)["flops"]
    assert work["bytes"] - qwen3_next.delta_rule_token_work(q3)["bytes"] \
        == 32 * 127 * 4
    # bytes bound it: 100.2 ns a token and layer against 18.6 of FLOPs
    assert work["bytes"] / 819e9 > 5 * work["flops"] / 197e12
    assert ling.linear_step_bytes(config, 960) == 960 * 4 * 2 ** 20
    assert round(ling.linear_step_bytes(config, 960) / 819e9 * 1e3, 2) \
        == 4.92


def test_a_decode_step_reads_its_weights_the_states_and_the_rows(config):
    live = 96 * 2048
    step = ling.decode_step_bytes(config, 12, live)
    touched = ling.experts_touched_grouped(config, 96)
    weights = 2 * (10 * 63_049_888 + 2 * 31_965_696 + 2 * 47_185_920
                   + 10 * (1 + touched) * 5_898_240 + 2560 * 19648) \
        + 4 * 10 * 1_311_232
    state = 2 * 96 * 10 * (2 * 2 ** 20 + 3 * 12288 * 2)
    assert step == pytest.approx(weights + live * 2304 + state)
    assert state / 2 ** 30 == pytest.approx(3.75 + 0.132, abs=0.01)
    assert 11.5e9 < step < 13e9
    assert ling.decode_attention_bytes(config, 12, live) == live * 2304
    assert ling.decode_attention_flops(config, 12, live) \
        == live * 2 * 32 * (576 + 512) * 2
    work = ling.grouped_matmul_work(config, 50, 144)
    assert work["bytes"] == (50 * 5_898_240 + 144 * (2 * 2560 + 2 * 768)) * 2
    assert work["flops"] == 2 * 144 * 3 * 2560 * 768


# -------------------------------------------------------------- the readers

CHUNK = "jit(prefill_chunk)/stack/while/body/closed_call/"
STEP = "jit(decode_burst)/stack/while/body/closed_call/stack/while/body/" \
       "closed_call/"
PATHS = [CHUNK + "attn/linear_attn/dot_general",
         CHUNK + "attn/linear_state/dynamic_slice",
         CHUNK + "attn/linear_attn/kda_gate/dot_general",
         CHUNK + "attn/linear_attn/conv/mul",
         CHUNK + "attn/linear_attn/kda_rule/dot_general",
         CHUNK + "attn/linear_attn/kda_rule/while/body/dot_general",
         CHUNK + "attn/linear_state/dynamic_update_slice",
         CHUNK + "attn/latent_prefill/dot_general",
         CHUNK + "moe_experts/pallas_call",
         "jit(prefill_chunk)/head/dot_general"]


def _spec(name):
    return manifest.load_json(REPO, "layer_metrics", name + ".json")


def _obs(config, **more):
    return {"cell": {"config": config, "traffic": {"use": "serve_rollout"}},
            "peaks": PEAKS, **more}


def test_the_rule_s_share_lies_inside_the_linear_layer_s():
    """The partition knows ``attn`` and books the whole mixer there;
    ``scope_share`` finds ``kda_rule`` on the same paths, the rule alone;
    Qwen3-Next's ``part_share_linear_attn.tok_s`` reads the whole KDA layer
    (its gate, its convolution and the rule are inside ``linear_attn``, the
    innermost name that reader knows), LFM2's ``part_share_conv.tok_s`` the
    convolution."""
    dev = _scoped(PATHS)
    assert [op.part for op in dev.ops[:8]] == ["attn"] * 8
    obs = {"trace": object(), "device_ops": dev}
    assert scope_share.read(obs, _spec("part_share_kda.tok_s")["params"]) \
        == pytest.approx(20.0)
    assert scope_share.read(
        obs, _spec("part_share_linear_attn.tok_s")["params"]) \
        == pytest.approx(70.0)
    assert scope_share.read(obs, _spec("part_share_conv.tok_s")["params"]) \
        == pytest.approx(10.0)
    # Qwen3-Next's rule is another scope: its metrics stay silent here
    assert scope_share.read(
        obs, _spec("part_share_delta_rule.tok_s")["params"]) is None
    # a program without the scopes (the parent commit) gives nothing
    bare = _scoped([CHUNK + "attn/dot_general", CHUNK + "mlp/dot_general"])
    for name in MINE[:5]:
        obs = {"trace": _trace([], []), "device_ops": bare, "phases": []}
        assert importlib.import_module(
            "rtbench.readers." + _spec(name)["reader"]).read(
                obs, _spec(name)["params"]) is None


def test_kda_ms_per_ktok_and_the_chunk_s_roofline(config):
    """The one whole chunk away from the edges holds two operations of the
    rule (10 ms each): 20 ms over 512 tokens, where the yardstick wants 10
    layers x 100.2 ns a token."""
    modules = [("jit_prefill_chunk(1)", 0.999, 1.02),   # touches the edge
               ("jit_prefill_chunk(1)", 1.02, 1.09),
               ("jit_decode_burst(2)", 1.09, 1.095),
               ("jit_prefill_chunk(1)", 1.095, 1.1)]    # touches the edge
    dev = _scoped(PATHS, modules)
    trace = _trace(modules, [(op.name, op.start, op.end) for op in dev.ops])
    disp = [phases.Phase("engine.prefill_dispatch", t, t + 0.001,
                         {"tokens": 512, "bucket": 512})
            for t in (0.95, 1.0, 1.07)]
    obs = _obs(config, trace=trace, device_ops=dev, phases=disp)
    assert scope_ms_per.read(obs, _spec("kda_ms_per_ktok")["params"]) \
        == pytest.approx(20.0 / 512 * 1000)
    roof = _spec("kda_chunk_roofline")
    least_ms = 10 * 32 * 641 * 4 / 819e9 * 1e3
    assert delta_rule_roofline.read(obs, roof["params"]) == pytest.approx(
        100 * least_ms / (20.0 / 512))
    names = manifest.module_names(os.path.join(
        BENCH, "rtbench", "readers", "delta_rule_roofline.py"))
    assert set(names["ADAPTER_NEEDS"]) <= set(manifest.module_names(
        os.path.join(BENCH, "rtbench", "adapters", "ling.py")))


def test_kda_step_ms_per_step_and_the_step_s_roofline(config):
    """Two bursts of 4 steps inside the trace; in each, two operations of
    10 ms under ``kda_rule`` or ``linear_state``: 5 ms a step. Over the
    measured window 1,000 steps updated 900 states each (90 of 96 slots
    decode in 10 lines): 4 MiB a pair at 819 GB/s is 4.61 ms: 92.2%."""
    paths = [STEP + "attn/linear_state/dynamic_slice",
             STEP + "attn/linear_attn/kda_rule/reduce",
             STEP + "attn/linear_attn/kda_gate/dot_general",
             STEP + "moe_experts/pallas_call",
             STEP + "attn/linear_attn/kda_rule/reduce",
             STEP + "attn/linear_state/dynamic_update_slice",
             STEP + "mlp/moe_shared/dot_general",
             "jit(decode_burst)/head/dot_general"]
    modules = [("jit_decode_burst(3)", 0.9995, 1.0395),
               ("jit_decode_burst(3)", 1.0396, 1.0795)]
    dev = _scoped(paths, modules)
    trace = _trace([("jit_decode_burst(3)", 0.5, 0.6)] + modules
                   + [("jit_decode_burst(3)", 1.5, 1.6)],
                   [(op.name, op.start, op.end) for op in dev.ops])
    disp = [phases.Phase("engine.decode_dispatch", t, t + 0.001,
                         {"steps": 4, "slots": 96})
            for t in (0.49, 0.99, 1.03, 1.49)]
    polls = [(0.1, {"linear_state_updates": 9000, "decode_steps": 10,
                    "slots": 96}),
             (49.9, {"linear_state_updates": 909000, "decode_steps": 1010,
                     "slots": 96})]
    obs = _obs(config, trace=trace, device_ops=dev, phases=disp,
               trace_span=(1.0, 2.0), polls=polls, t_open=0.0, t_close=50.0)
    assert scope_ms_per_count.read(
        obs, _spec("kda_step_ms_per_step")["params"]) == pytest.approx(5.0)
    least_ms = 900 * 4 * 2 ** 20 / 819e9 * 1e3
    assert delta_rule_roofline.read(
        obs, _spec("kda_step_roofline")["params"]) == pytest.approx(
            100 * least_ms / 5.0)
    # 900 of 960 (slot, layer) pairs a step
    assert counter_ratio.read(
        obs, _spec("kda_state_update_share")["params"]) == pytest.approx(
            100 * 900 / 960)
    # the parent commit's stats() lack the counter
    bare = {**obs, "polls": [(t, {"decode_steps": s["decode_steps"],
                                  "slots": 96}) for t, s in polls]}
    assert counter_ratio.read(
        bare, _spec("kda_state_update_share")["params"]) is None
    assert delta_rule_roofline.read(
        bare, _spec("kda_step_roofline")["params"]) is None


def test_the_latent_kernel_s_roofline_counts_two_lines_of_twelve_layers(
        config):
    """A step calls the latent decode kernel twice (two latent lines), each
    call the bytes of a line's live rows: events that take exactly the
    bytes' time read 100, whatever ``depth`` says of the layers."""
    live = 96 * 2048.0                          # positions read a step
    call_s = live * 576 * 2 / 819e9             # one line's call
    events, t = [], 1.0
    for _ in range(3):                          # three steps
        for _line in range(2):
            events.append(tr.Event("latent_decode_attention", t, t + call_s))
            t += call_s + 1e-5

    class Trace:
        def kernel_events(self, name):
            return events if name == "latent_decode_attention" else []

    polls = [(0.5, {"kv_positions_read": 0, "decode_steps": 0}),
             (3.0, {"kv_positions_read": int(live) * 3, "decode_steps": 3})]
    obs = _obs(config, trace=Trace(), trace_span=(1.0, 2.0), polls=polls)
    share = latent_attention_roofline.read(
        obs, _spec("latent_decode_attention_roofline")["params"])
    assert share == pytest.approx(100.0)
    # at 32 heads the bytes bound the kernel, four times over the FLOPs
    assert ling.decode_attention_bytes(config, 12, live) / 819e9 > \
        3.9 * ling.decode_attention_flops(config, 12, live) / 197e12


def test_without_a_tpu_the_cell_s_run_exits_2_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert "needs a TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


# ------------------------------------------ the reference and the control

def tiny(config):
    c = dict(config)
    c.update(hidden_size=128, intermediate_size=256, moe_intermediate_size=32,
             moe_shared_expert_intermediate_size=32, num_attention_heads=4,
             head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, rotary_dim=8, v_head_dim=16,
             vocab_size=2048, torch_dtype="float32")
    return c


def test_the_reference_at_a_small_size_is_the_program_s_forward(config):
    """``benchmark/reference/ling.py`` imports nothing of the program and
    sets ``highest``; on the program's own seeded weights, through the
    adapter's names, it gives ``models/ling.forward``'s logits: the
    depth, the share of the experts (one group of 64 of 512) and the routing
    as the cell has them, the widths small. float32 against float32: what
    is left is the order of the sums."""
    from reference import ling as reference

    from ray_tpu.models import ling as model

    with open(os.path.join(BENCH, "reference", "ling.py")) as f:
        text = f.read()
    assert "ray_tpu" not in text.replace("``ray_tpu", "")
    assert 'default_matmul_precision("highest")' in text
    c = tiny(config)
    cfg = ling.model_config(c, "serve_rollout", 256)
    params = model.init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (96,), 259, 2048)
    got, counts = jax.jit(model.forward, static_argnums=0)(
        cfg, params, tokens[None])
    want = reference.logits(c, ling.reference_weights(params), tokens)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-4)
    # a share: about an eighth of the picks fall on the 64 held experts
    assert 0.02 < int(counts[1]) / int(counts[0]) < 0.3


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_fp8_control_comes_out_as_not_correct(config, traffic, seed):
    """benchmark/control.py at a size a test run can hold: the reference on
    weights rounded through fp8 chooses tokens that lie further under the
    float32 reference's top logit than the cell allows, and further than
    the stated precision (bfloat16 weights) does. The readings at the
    cell's own size are records/control-ling.jsonl's."""
    from reference import ling as reference

    from ray_tpu.llm import engine

    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(BENCH, "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    limit = traffic["check"]["margin"]

    c = tiny(config)
    cfg = ling.model_config(c, "serve_rollout", 256)
    weights = ling.reference_weights(
        engine.init_params(cfg, jax.random.PRNGKey(seed)))
    assert weights["layers"]["kda_q"].shape == (10, 128, 64)
    assert weights["layers"]["kv_b"].shape == (2, 32, 4 * 32)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (256,), 0, 2048)
    want = reference.logits(c, weights, tokens)
    fp8 = control.margin(
        want, reference.logits(c, control.to_fp8(weights), tokens), 32)
    bf16 = control.margin(want, reference.logits(c, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), weights), tokens), 32)
    assert fp8 > limit
    assert bf16 < fp8


def test_the_control_s_rows_set_the_limit(traffic):
    """records/control-ling.jsonl: the fp8 control at the cell's own depth
    and widths on the chip, every seed over the limit the traffic file
    carries, and the limit under the smallest with room."""
    with open(os.path.join(BENCH, "records", "control-ling.jsonl")) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    assert len(rows) >= 3 and len({r["seed"] for r in rows}) == len(rows)
    limit = traffic["check"]["margin"]
    for r in rows:
        assert r["workload"] == CELL and r["layers"] == 12
        assert r["device"] == "TPU v5 lite"
        assert r["limit"] == limit and not r["control_correct"]
        assert r["control_fp8_margin"] > 2 * limit
