"""The granite-4.0-h-small cell's files: its configuration against the
published one (every key kept but the cuts the file lists), its adapter's
arithmetic against hand-worked values at the published widths, its plan, its
own entries in the manifest (never the number of cells, never which cell is
last, and the cell's metric set held with ``<=``), each new metric file on a
made-up trace, the decode kernel's roofline at this cell's one line, the
reference at a small size against ``models/granite.py``, the run without a
TPU, and the control at a small size."""

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
from rtbench.adapters import granite
from rtbench.readers import (
    counter_ratio,
    decode_attention_roofline,
    delta_rule_roofline,
    phases,
    scope_ms_per,
    scope_ms_per_count,
    scope_share,
)
from test_bh_qwen3_next import _scoped, _trace  # noqa: E402

CELL = "granite4-h-small-serve-support-2k"
CONFIG = "granite-4.0-h-small"
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
          "config.json")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4

# The catalog row's ``config`` (the URL above), as published.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}
CUT = {"num_hidden_layers": 10, "layer_types": PERIOD,
       "num_local_experts": 36, "vocab_size": 50176}
LAYER = ("Linear attention (models/qwen3_next.py Gated DeltaNet, "
         "ops/gated_delta.py gated_delta_chunk, gated_delta_step)")
MINE = ("ssd_chunk_roofline", "ssd_step_roofline", "ssd_ms_per_ktok",
        "ssd_step_ms_per_step", "part_share_ssd.tok_s",
        "ssd_state_update_share")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
STEP_PROGRAMS = ["jit_decode_burst", "jit_decode_step"]
# Requests a 51 s window finishes (my chip runs, PR 62: serve_tok_s over the
# cycle's mean request of 859 tokens), rounded up.
WINDOW_REQUESTS = 290


@pytest.fixture(scope="module", autouse=True)
def release_the_compiled_programs():
    """After the module: this file's programs are its own (their
    configuration is a static argument), and a compiled program keeps its
    memory mappings as long as JAX's caches hold it: 17,000 of them after
    tests/test_granite.py alone, where a process may have 65,530
    (``vm.max_map_count``) and a worker of the suite runs some seventy
    files. Past the limit XLA's CPU compile dies of a segmentation fault
    under whichever test comes next (PERF.md section 7, PR 62)."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "serve-support-2k.json")) as f:
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
    # the four cuts ISSUE 62 names: the depth, the kinds that go with it,
    # the experts held, the vocabulary
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "num_local_experts", "vocab_size"]
    assert sorted(config["reduced"]) == sorted(CUT)
    # no width among them
    assert not [k for k in entry["reduced"] if k.endswith(("_dim", "_rank",
                                                           "_size"))
                and k != "vocab_size"]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["adapter"] == "granite"
    assert (config["expert_shard"], config["expert_shards"]) == (0, 2)
    for key in ("equations", "parameter_count", "norms", "layer", "mamba",
                "time_step_limit", "gated_norm", "projection_order",
                "attention", "router", "experts", "embeddings", "init",
                "sizes"):
        assert config["assumed"][key], key
    for key in ("state_dtype", "state_layout", "float32_scalars",
                "router_dtype", "sub_chunk", "stored_apart"):
        assert config["departures"][key], key
    assert "no capacity" in config["guarantees"]
    assert "the state is float32" in config["guarantees"]
    assert "8 chips" in config["deployment"]
    assert "share 0" in config["deployment"]
    # the arithmetic of the cut, and the compiler's figures beside it
    for said in ("32,207,337,984", "4,757,211,776", "8.86 GiB", "3.375 GiB",
                 "memory_analysis"):
        assert said in config["reduced"]["num_hidden_layers"], said


def test_the_cell_s_own_entries_are_what_the_issue_names(traffic):
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    assert manifest.check_modules(m, REPO) == []
    cell = manifest.load_cell(CELL, REPO)
    assert cell["workload"] == {**cell["workload"], "config": CONFIG,
                                "traffic": "serve-support-2k", "chips": 1}
    why = cell["workload"]["why"]
    for said in ("128 clients", "96 slots x 2,048", "864 states of 4 MiB",
                 "10 of 40 layers", "13 rows an expert", "27 deployed"):
        assert said in why
    assert [x["name"] for x in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    names = {x["name"] for x in cell["per_layer"]}
    assert set(MINE) <= names
    # the serve set, what Qwen3-Next's and Ling's cells read of a linear
    # layer, of the decode kernel and of the routed layer
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
            "part_share_conv.tok_s", "decode_attention_roofline.tok_s",
            "moe_grouped_matmul_roofline", "moe_local_pick_share",
            "moe_experts_touched_share", "moe_ms_per_step",
            "moe_glue_ms_per_step", "moe_tiles_per_expert",
            "part_share_moe_experts.tok_s",
            "part_share_moe_glue.tok_s"} <= names
    # left out and why: a test that passes today holds the first with
    # ``== [CELL]`` (test_bh_deepseek.py:146), so ISSUE 62's wish for it
    # waits for a ``benchmark`` PR; the others read scopes, kernels or
    # counters this model has not
    assert not names & {"part_share_moe_shared.tok_s",
                        "part_share_latent_prefill.tok_s",
                        "latent_decode_attention_roofline",
                        "moe_local_token_share", "decode_bw_share.tok_s",
                        "part_share_delta_rule.tok_s",
                        "part_share_kda.tok_s", "part_share_ssm.tok_s"}
    for x in cell["per_layer"]:
        if x["name"] in MINE:
            # (``in``, not ``==``: a later cell may be appended)
            assert CELL in x["workloads"] and x["moves"] == "serve_tok_s"
            assert x["layer"] == LAYER
    readers = {x["name"]: (x["reader"], x["params"])
               for x in cell["per_layer"] if x["name"] in MINE}
    assert readers["ssd_chunk_roofline"] == ("delta_rule_roofline", {
        "form": "chunk", "scopes": ["ssd"],
        "programs": ["jit_prefill_chunk"],
        "phase": "engine.prefill_dispatch", "count": "tokens"})
    assert readers["ssd_step_roofline"] == ("delta_rule_roofline", {
        "form": "step", "scopes": ["ssd", "linear_state"],
        "programs": STEP_PROGRAMS, "phase": "engine.decode_dispatch",
        "count": "steps"})
    assert readers["ssd_ms_per_ktok"] == ("scope_ms_per", {
        "scopes": ["ssd"], "programs": ["jit_prefill_chunk"],
        "phase": "engine.prefill_dispatch", "count": "tokens", "per": 1000})
    assert readers["ssd_step_ms_per_step"] == ("scope_ms_per_count", {
        "scopes": ["ssd", "linear_state"], "programs": STEP_PROGRAMS,
        "phase": "engine.decode_dispatch", "count": "steps"})
    assert readers["part_share_ssd.tok_s"] == ("scope_share", {
        "scopes": ["ssd"]})
    kind, params = readers["ssd_state_update_share"]
    assert kind == "counter_ratio" and params == {
        "num": "linear_state_updates", "den": "decode_steps",
        "den_times": "slots", "scale": pytest.approx(100 / 9, abs=1e-5)}
    assert traffic["kind"] == "closed_loop"
    assert traffic["engine"] == {
        "max_num_seqs": 96, "max_seq_len": 2048, "dtype": "bfloat16",
        "kv_block_size": 0, "max_ongoing_requests": 256}
    assert traffic["clients"] == 128 and traffic["cycle_requests"] == 128
    assert traffic["prompt_tokens"] == {"kind": "lognormal", "median": 256,
                                        "sigma": 0.9, "min": 32, "max": 1024}
    assert traffic["max_tokens"] == {"kind": "uniform", "min": 256,
                                     "max": 768}
    assert traffic["trace"] == {"after_s": 10, "for_s": 4}
    assert traffic["check"]["requests"] == 4
    assert "control" in traffic["check"]["margin_why"]
    assert traffic["use"] == "serve_support"
    rollout = manifest.load_json(REPO, "traffic", "serve-rollout-8k.json")
    assert traffic["warmup"] == rollout["warmup"]
    for key in ("why", "warmup_why", "cycle_why", "stagger_why",
                "max_requests_per_s_why"):
        assert len(traffic[key]) > 100 and "TBD" not in traffic[key], key
    for key in ("margin_why", "min_readable_why"):
        assert len(traffic["check"][key]) > 100 \
            and "TBD" not in traffic["check"][key], key


@pytest.mark.parametrize("seed", [1, 2147483700])
def test_the_plan_outlasts_its_window_and_fits_the_line(traffic, seed):
    """ROADMAP R0 (r): the list holds at least twice what a window can
    finish beside the ramp. A line ends after 288 to 1,790 positions, inside
    the slot's 2,048."""
    plan = gen.closed_loop_plan(traffic, seed, 51)
    assert plan == gen.closed_loop_plan(traffic, seed, 51)
    cycle = plan["requests"][:128]
    prompts = sorted(r["prompt_tokens"] for r in cycle)
    assert (prompts[0], prompts[-1]) == (32, 1024)
    assert 250 <= prompts[64] <= 262                    # median 256
    assert round(sum(prompts) / 128) == 347
    # three prompts in four are one chunk of the engine's 512
    assert sum(p <= 512 for p in prompts) == 100
    assert all(256 <= r["max_tokens"] <= 768 for r in cycle)
    assert round(sum(r["max_tokens"] for r in cycle) / 128) == 512
    longest = max(r["prompt_tokens"] + r["max_tokens"] for r in cycle)
    assert longest <= 1024 + 768 <= traffic["engine"]["max_seq_len"]
    # every seed sends the same 128 requests, in an order of its own
    other = gen.closed_loop_plan(traffic, seed + 1, 51)["requests"][:128]
    key = lambda r: (r["prompt_tokens"], r["max_tokens"])  # noqa: E731
    assert sorted(map(key, cycle)) == sorted(map(key, other))
    assert [key(r) for r in cycle] != [key(r) for r in other]
    # 32 clients wait for a slot
    assert plan["clients"] == 128 == traffic["engine"]["max_num_seqs"] + 32
    # the list: the ramp's two generations (256) and twice what a window
    # finishes at the rate the builder measured
    # (``max_requests_per_s_why``); no client waits on the rate, which only
    # sizes the list
    assert len(plan["requests"]) >= 256 + 2 * WINDOW_REQUESTS
    # ids come from the slice of the vocabulary that is held
    ids = gen.prompt_ids(seed, 1000, 4096, 50176)
    assert 259 <= min(ids) and 40000 < max(ids) < 50176


# ----------------------------------------------------------- the arithmetic

def test_this_chip_s_share_is_4757m_parameters_of_32_2b(config):
    """ISSUE 62's count at the published widths: a Mamba-2 mixer
    102,286,976, an attention 41,943,040, the shared SwiGLU 18,874,368, a
    router 294,912, an expert 9,437,184, two norms 8,192: a Mamba layer
    800,941,696 whole and 461,203,072 with 36 experts. (The issue's
    4,757,207,680 for the share leaves the final norm's 4,096 out; the
    whole model's 32,207,337,984 has it in.)"""
    assert granite.mamba_params(config) == 102_286_976 == (
        4096 * 16768 + 8448 * 4 + 8448 + 3 * 128 + 8192 + 8192 * 4096)
    assert granite.attention_params(config) == 41_943_040
    assert granite.shared_params(config) == 18_874_368
    assert granite.router_params(config) == 294_912
    assert granite.expert_params(config) == 9_437_184
    assert (granite.linear_lines(config), granite.attention_lines(config),
            granite.router_outputs(config), granite.conv_dim(config),
            granite.d_inner(config), granite.head_dim(config)) == (
                9, 1, 72, 8448, 8192, 128)
    assert granite.layer_params(config, "mamba", 72) == 800_941_696
    assert granite.layer_params(config, "attention", 72) == 740_597_760
    assert granite.layer_params(config, "mamba", 36) == 461_203_072
    assert granite.layer_params(config, "attention", 36) == 400_859_136
    held = granite.params_held(config)
    assert held == (9 * 461_203_072 + 400_859_136 + 50176 * 4096 + 4096) \
        == 4_757_207_680 + 4096 == 4_757_211_776
    gib = (2 * (held - 10 * 294_912) + 4 * 10 * 294_912) / 2 ** 30
    assert round(gib, 2) == 8.87
    # the whole model, by the same functions on the published counts
    whole = {**config, **config["published"],
             "published": {"num_local_experts": 72}}
    assert granite.params_held(whole) == (
        36 * 800_941_696 + 4 * 740_597_760 + 411_045_888) \
        == 32_207_337_984
    # a token's parameters: 10 picks and the shared SwiGLU in 40 layers
    active = granite.params_held(whole) \
        - 40 * (72 - 10) * granite.expert_params(config)
    assert round(active / 1e9, 2) == 8.80
    # 96 tokens a step touch every held expert
    assert round(granite.experts_touched_uniform(config, 96), 2) == 36.0


def test_depth_is_the_layers_and_the_program_follows(config):
    assert granite.depth(config, "serve_support") == 10
    cfg = granite.model_config(config, "serve_support", 2048)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.mamba_n_heads, cfg.mamba_d_head,
            cfg.mamba_d_state, cfg.mamba_d_conv, cfg.vocab_size,
            cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.intermediate_size, cfg.shared_intermediate_size,
            cfg.max_seq_len, cfg.dtype) == (
                10, 4096, 32, 8, 128, 128, 64, 128, 4, 50176, 72, 36, 10,
                768, 1536, 2048, "bfloat16")
    assert list(cfg.layer_types) == PERIOD and cfg.period == 10
    assert (cfg.linear_lines, cfg.attention_lines) == (9, 1)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling, cfg.norm_eps) == (
                12.0, 0.22, 0.0078125, 16.0, 1e-5)
    assert cfg.num_params() == granite.params_held(config)
    assert cfg.linear_state_bytes == granite.linear_state_bytes(config) \
        == 4 * 2 ** 20
    assert cfg.state_shape == (64, 128, 128)
    for key, bad in (("position_embedding_type", "rope"),
                     ("hidden_act", "gelu"), ("mamba_conv_bias", False),
                     ("mamba_proj_bias", True), ("attention_bias", True),
                     ("tie_word_embeddings", False),
                     ("normalization_function", "layernorm")):
        with pytest.raises(ValueError, match="GraniteConfig runs"):
            granite.model_config({**config, key: bad}, "serve_support", 64)
    with pytest.raises(ValueError, match="mamba_n_groups"):
        granite.model_config({**config, "mamba_n_groups": 8},
                             "serve_support", 64)


def test_a_slot_is_36_mib_of_states_and_4096_bytes_a_position(config):
    assert granite.linear_state_bytes(config) == 128 * 128 * 64 * 4
    assert granite.conv_window_bytes(config) == 3 * 8448 * 2
    assert granite.kv_bytes_per_token(config, 10) == 2 * 8 * 128 * 2 == 4096
    gib = 2 ** 30
    assert 96 * 9 * granite.linear_state_bytes(config) / gib == 3.375
    assert 96 * 2048 * 4096 / gib == 0.75
    assert round(96 * 9 * granite.conv_window_bytes(config) / 2 ** 20) == 42
    # a line of 2,048 positions: 36 MiB of state against 8 of keys and values
    assert 9 * granite.linear_state_bytes(config) == 36 * 2 ** 20
    assert 2048 * 4096 == 8 * 2 ** 20


def test_the_rule_s_yardstick_counts_b_and_c_once_for_all_heads(config):
    """A token and layer: a head's within-chunk row at a sub-chunk of 64
    (2 x 64 x 64), the state's read and update (4 x 128 x 64), 128 heads;
    ``C B^T`` once (2 x 128 x 64); ``x`` and ``y`` 8,192 float32 each, ``B``
    and ``C`` 128 each and not 128 a head, ``dt`` one a head. A step moves a
    state of 4 MiB twice."""
    work = granite.delta_rule_token_work(config)
    assert work == {"flops": 128 * (2 * 64 * 64 + 4 * 128 * 64)
                    + 2 * 128 * 64,
                    "bytes": (2 * 8192 + 2 * 128 + 128) * 4}
    assert work["bytes"] == 67_072
    # B and C a head would be 128 times their bytes
    assert work["bytes"] < (2 * 8192 + 2 * 128 * 128 + 128) * 4 / 2
    # bytes bound it: 81.9 ns a token and layer against 26.7 of FLOPs
    assert work["bytes"] / 819e9 > 3 * work["flops"] / 197e12
    assert granite.linear_step_bytes(config, 864) == 864 * 8 * 2 ** 20
    assert round(granite.linear_step_bytes(config, 864) / 819e9 * 1e3, 2) \
        == 8.85


def test_a_decode_step_reads_its_weights_the_states_and_the_rows(config):
    live = 96 * 600
    step = granite.decode_step_bytes(config, 10, live)
    touched = granite.experts_touched_uniform(config, 96)
    weights = 2 * (9 * 102_286_976 + 41_943_040
                   + 10 * (18_874_368 + touched * 9_437_184)
                   + 4096 * 50176) + 4 * 10 * 294_912
    state = 2 * 96 * 9 * (4 * 2 ** 20 + 3 * 8448 * 2)
    assert step == pytest.approx(weights + live * 4096 + state)
    assert state / 2 ** 30 == pytest.approx(6.75 + 0.082, abs=0.01)
    assert 16e9 < step < 17.5e9
    # one call's bytes, ``layers`` times: the reader's convention
    assert granite.decode_attention_bytes(config, 10, live) \
        == live * 4096 * 10
    work = granite.grouped_matmul_work(config, 36, 480)
    assert work["bytes"] == (36 * 9_437_184 + 480 * (2 * 4096 + 2 * 768)) * 2
    assert work["flops"] == 2 * 480 * 3 * 4096 * 768


# -------------------------------------------------------------- the readers

CHUNK = "jit(prefill_chunk)/stack/while/body/closed_call/"
STEP = "jit(decode_burst)/stack/while/body/closed_call/stack/while/body/" \
       "closed_call/"
PATHS = [CHUNK + "attn/linear_attn/dot_general",
         CHUNK + "attn/linear_state/dynamic_slice",
         CHUNK + "attn/linear_attn/softplus",
         CHUNK + "attn/linear_attn/conv/mul",
         CHUNK + "attn/linear_attn/ssd/dot_general",
         CHUNK + "attn/linear_attn/ssd/while/body/dot_general",
         CHUNK + "attn/linear_state/dynamic_update_slice",
         CHUNK + "attn/pallas_call",
         CHUNK + "moe_experts/pallas_call",
         "jit(prefill_chunk)/head/dot_general"]


def _spec(name):
    return manifest.load_json(REPO, "layer_metrics", name + ".json")


def _obs(config, **more):
    return {"cell": {"config": config, "traffic": {"use": "serve_support"}},
            "peaks": PEAKS, **more}


def test_the_rule_s_share_lies_inside_the_linear_layer_s():
    """The partition knows ``attn`` and books the whole mixer there;
    ``scope_share`` finds ``ssd`` on the same paths, the rule alone;
    Qwen3-Next's ``part_share_linear_attn.tok_s`` reads the whole Mamba-2
    mixer (its step, its convolution and the rule are inside
    ``linear_attn``; the state's and the window's reads and writes beside
    it, under ``linear_state``), LFM2's ``part_share_conv.tok_s`` the
    convolution."""
    dev = _scoped(PATHS)
    assert [op.part for op in dev.ops[:8]] == ["attn"] * 8
    obs = {"trace": object(), "device_ops": dev}
    assert scope_share.read(obs, _spec("part_share_ssd.tok_s")["params"]) \
        == pytest.approx(20.0)
    assert scope_share.read(
        obs, _spec("part_share_linear_attn.tok_s")["params"]) \
        == pytest.approx(70.0)
    assert scope_share.read(obs, _spec("part_share_conv.tok_s")["params"]) \
        == pytest.approx(10.0)
    # the delta rule's and the scan's are other scopes: their metrics stay
    # silent here
    for other in ("part_share_delta_rule.tok_s", "part_share_kda.tok_s",
                  "part_share_ssm.tok_s"):
        assert scope_share.read(obs, _spec(other)["params"]) is None
    # a program without the scopes (the parent commit) gives nothing
    bare = _scoped([CHUNK + "attn/dot_general", CHUNK + "mlp/dot_general"])
    for name in MINE[:5]:
        obs = {"trace": _trace([], []), "device_ops": bare, "phases": []}
        assert importlib.import_module(
            "rtbench.readers." + _spec(name)["reader"]).read(
                obs, _spec(name)["params"]) is None


def test_ssd_ms_per_ktok_and_the_chunk_s_roofline(config):
    """The one whole chunk away from the edges holds two operations of the
    rule (10 ms each): 20 ms over 512 tokens, where the yardstick wants 9
    layers x 81.9 ns a token."""
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
    assert scope_ms_per.read(obs, _spec("ssd_ms_per_ktok")["params"]) \
        == pytest.approx(20.0 / 512 * 1000)
    roof = _spec("ssd_chunk_roofline")
    least_ms = 9 * 67_072 / 819e9 * 1e3
    assert delta_rule_roofline.read(obs, roof["params"]) == pytest.approx(
        100 * least_ms / (20.0 / 512))
    names = manifest.module_names(os.path.join(
        BENCH, "rtbench", "readers", "delta_rule_roofline.py"))
    assert set(names["ADAPTER_NEEDS"]) <= set(manifest.module_names(
        os.path.join(BENCH, "rtbench", "adapters", "granite.py")))


def test_ssd_step_ms_per_step_and_the_step_s_roofline(config):
    """Two bursts of 4 steps inside the trace; in each, two operations of
    10 ms under ``ssd`` or ``linear_state``: 5 ms a step. Over the measured
    window 1,000 steps updated 810 states each (90 of 96 slots decode in 9
    lines): 8 MiB a pair at 819 GB/s is 8.30 ms, which is over the made-up
    5 ms (the reader does not cap: 166%; the driver refuses a real run
    over 105)."""
    paths = [STEP + "attn/linear_state/dynamic_slice",
             STEP + "attn/linear_attn/ssd/pallas_call",
             STEP + "attn/linear_attn/dot_general",
             STEP + "moe_experts/pallas_call",
             STEP + "attn/linear_attn/ssd/mul",
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
    polls = [(0.1, {"linear_state_updates": 8100, "decode_steps": 10,
                    "slots": 96}),
             (49.9, {"linear_state_updates": 818100, "decode_steps": 1010,
                     "slots": 96})]
    obs = _obs(config, trace=trace, device_ops=dev, phases=disp,
               trace_span=(1.0, 2.0), polls=polls, t_open=0.0, t_close=50.0)
    assert scope_ms_per_count.read(
        obs, _spec("ssd_step_ms_per_step")["params"]) == pytest.approx(5.0)
    least_ms = 810 * 8 * 2 ** 20 / 819e9 * 1e3
    assert delta_rule_roofline.read(
        obs, _spec("ssd_step_roofline")["params"]) == pytest.approx(
            100 * least_ms / 5.0)
    # 810 of 864 (slot, layer) pairs a step
    assert counter_ratio.read(
        obs, _spec("ssd_state_update_share")["params"]) == pytest.approx(
            100 * 810 / 864, rel=1e-6)
    # the parent commit's stats() lack the counter
    bare = {**obs, "polls": [(t, {"decode_steps": s["decode_steps"],
                                  "slots": 96}) for t, s in polls]}
    assert counter_ratio.read(
        bare, _spec("ssd_state_update_share")["params"]) is None
    assert delta_rule_roofline.read(
        bare, _spec("ssd_step_roofline")["params"]) is None


def test_the_decode_kernel_s_roofline_counts_one_line_of_ten_layers(config):
    """A step calls the decode kernel once (one attention line): events that
    take exactly a call's bytes' time read 100, ``depth`` standing on both
    sides (the adapter's docstring)."""
    live = 96 * 640.0                           # positions read a step
    call_s = live * 4096 / 819e9
    events, t = [], 1.0
    for _ in range(3):                          # three steps, a call each
        events.append(tr.Event("decode_attention", t, t + call_s))
        t += call_s + 1e-5

    class Trace:
        def kernel_events(self, name):
            return events if name == "decode_attention" else []

    polls = [(0.5, {"kv_positions_read": 0, "decode_steps": 0}),
             (3.0, {"kv_positions_read": int(live) * 3, "decode_steps": 3})]
    obs = _obs(config, trace=Trace(), trace_span=(1.0, 2.0), polls=polls)
    share = decode_attention_roofline.read(
        obs, _spec("decode_attention_roofline.tok_s")["params"])
    assert share == pytest.approx(100.0)


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
    c.update(hidden_size=128, intermediate_size=32,
             shared_intermediate_size=48, num_attention_heads=4,
             num_key_value_heads=2, mamba_n_heads=16, mamba_d_head=16,
             mamba_d_state=16, vocab_size=2048, torch_dtype="float32")
    return c


def test_the_reference_at_a_small_size_is_the_program_s_forward(config):
    """``benchmark/reference/granite.py`` imports nothing of the program and
    sets ``highest``; on the program's own seeded weights, through the
    adapter's names, it gives ``models/granite.forward``'s logits: the
    depth, the kinds, the share of the experts (36 of 72), the routing and
    the four multipliers as the cell has them, the widths small. float32
    against float32: what is left is the order of the sums."""
    from reference import granite as reference

    from ray_tpu.models import granite as model

    with open(os.path.join(BENCH, "reference", "granite.py")) as f:
        text = f.read()
    assert "ray_tpu" not in text.replace("``ray_tpu", "")
    assert 'default_matmul_precision("highest")' in text
    c = tiny(config)
    cfg = granite.model_config(c, "serve_support", 256)
    params = model.init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (96,), 259, 2048)
    got, counts = jax.jit(model.forward, static_argnums=0)(
        cfg, params, tokens[None])
    want = reference.logits(c, granite.reference_weights(params), tokens)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-4)
    # a share: about half of the picks fall on the 36 held experts
    assert 0.3 < int(counts[1]) / int(counts[0]) < 0.7
    # the published matrix is z | xBC | dt in one
    w = granite.reference_weights(params)["layers"]
    assert w["in_proj"].shape == (9, 128, 256 + 288 + 16)
    assert w["conv"].shape == (9, 288, 4)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_fp8_control_comes_out_as_not_correct(config, traffic, seed):
    """benchmark/control.py at a size a test run can hold: the reference on
    weights rounded through fp8 chooses tokens that lie further under the
    float32 reference's top logit than the cell allows, and further than
    the stated precision (bfloat16 weights) does. The readings at the
    cell's own size are records/control-granite.jsonl's."""
    from reference import granite as reference

    from ray_tpu.llm import engine

    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(BENCH, "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    limit = traffic["check"]["margin"]

    c = tiny(config)
    cfg = granite.model_config(c, "serve_support", 256)
    weights = granite.reference_weights(
        engine.init_params(cfg, jax.random.PRNGKey(seed)))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (256,), 0, 2048)
    want = reference.logits(c, weights, tokens)
    fp8 = control.margin(
        want, reference.logits(c, control.to_fp8(weights), tokens), 32)
    bf16 = control.margin(want, reference.logits(c, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), weights), tokens), 32)
    assert fp8 > limit
    assert bf16 < fp8


def test_the_control_s_rows_set_the_limit(traffic):
    """records/control-granite.jsonl: the fp8 control at the cell's own
    depth and widths on the chip, every seed over the limit the traffic
    file carries, and the limit under the smallest with room."""
    with open(os.path.join(BENCH, "records", "control-granite.jsonl")) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    assert len(rows) >= 3 and len({r["seed"] for r in rows}) == len(rows)
    limit = traffic["check"]["margin"]
    for r in rows:
        assert r["workload"] == CELL and r["layers"] == 10
        assert r["device"] == "TPU v5 lite"
        assert r["limit"] == limit and not r["control_correct"]
        assert r["control_fp8_margin"] > 2 * limit
