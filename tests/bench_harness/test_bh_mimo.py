"""The MiMo-V2.5 cell's files: its configuration against the published one
(every key kept but the cuts the file lists), its adapter's arithmetic for
both cache geometries against hand-worked values, its plan pinned, its own
entries in the manifest (never the number of cells, never which cell is
last, and the cell's metric set held with ``<=``), each new metric file on a
made-up trace, the decode kernel's roofline under 100 at two kinds of call,
the run without a TPU, and the control at a small size."""

import hashlib
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, REPO
from rtbench import gen, manifest, trace_reduce as tr
from rtbench.adapters import mimo
from rtbench.readers import (
    counter_ratio,
    decode_attention_roofline,
    phases,
    scope_ms_per,
    scope_ms_per_count,
    scope_share,
)

CELL = "mimo-v2.5-serve-mixed-32k"
CONFIG = "mimo-v2.5"
SOURCE = "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
PATTERN = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]

# The catalog row's ``config`` (the URL above), as published.
PUBLISHED = {
    "attention_bias": False, "attention_chunk_size": 128,
    "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192,
    "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
    "hidden_size": 4096, "hybrid_block_size": None,
    "hybrid_layer_pattern": PATTERN, "intermediate_size": 16384,
    "layernorm_epsilon": 1e-05, "max_position_embeddings": 1048576,
    "model_type": "mimo_v2", "moe_intermediate_size": 2048,
    "moe_layer_freq": [0] + [1] * 47, "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": None, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_size": 128, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152576}
CUT = {"num_hidden_layers": 7, "n_routed_experts": 16, "vocab_size": 19072,
       "hybrid_layer_pattern": PATTERN[:7], "moe_layer_freq": [0] + [1] * 6}
LAYER = ("Window and full attention mixed (models/mimo.py window layers "
         "with a sink, llm/mimo_serving.py rings and full lines, "
         "ops/decode_attention.py sink)")
MINE = ("window_attn_ms_per_step", "window_attn_ms_per_ktok",
        "window_kv_read_share")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
from test_bh_qwen3_next import _scoped, _trace  # noqa: E402


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "serve-mixed-32k.json")) as f:
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
    # the three cuts ISSUE 54 names, and the two lists that are a value a
    # layer and are cut with the layers (as lfm2-24b-a2b lists layer_types)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "hybrid_layer_pattern",
                                "moe_layer_freq"]
    assert sorted(config["reduced"]) == sorted(CUT)
    # no width among them
    assert not [k for k in entry["reduced"] if k.endswith(("_dim", "_rank",
                                                           "_size"))
                and k != "vocab_size"]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["adapter"] == "mimo"
    assert (config["expert_shard"], config["expert_shards"]) == (0, 16)
    for key in ("equations", "layer", "kinds", "attention", "window", "sink",
                "rotary", "router", "experts", "norm", "left_out", "init",
                "sizes"):
        assert config["assumed"][key], key
    for key in ("kv_row_width", "router_dtype", "sink_dtype"):
        assert config["departures"][key], key
    assert "no capacity" in config["guarantees"]
    assert "16 accelerators" in config["deployment"]
    assert "share 0" in config["deployment"]
    # the arithmetic of the cut, and the compiler's figures beside it
    for said in ("308.8B", "15.3B", "6.40 GiB", "4.50 GiB"):
        assert said in config["reduced"]["num_hidden_layers"], said
    assert "memory_analysis" in config["memory"]
    assert "10.988 GiB" in config["memory"]


def test_the_cell_s_own_entries_are_what_the_issue_names(traffic):
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    assert manifest.check_modules(m, REPO) == []
    cell = manifest.load_cell(CELL, REPO)
    assert cell["workload"] == {**cell["workload"], "config": CONFIG,
                                "traffic": "serve-mixed-32k", "chips": 1}
    why = cell["workload"]["why"]
    for said in ("24 slots x 32,768", "5 rings of 128", "7 of 48 layers",
                 "0.75 rows"):
        assert said in why
    assert [x["name"] for x in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    names = {x["name"] for x in cell["per_layer"]}
    assert set(MINE) <= names
    # what phi4-mini-flash-serve-reason-12k reads of the service, the
    # scheduler and the parts, and the routed layer's metrics
    assert {"decode_ms_per_step.tok_s", "decode_attention_roofline.tok_s",
            "decode_kv_read_share.tok_s", "prefill_kv_read_share.tok_s",
            "prefill_ms_per_ktok.counted", "tpot_p90_ms.tok_s",
            "part_share_attn.tok_s", "part_share_mlp.tok_s",
            "part_share_head.tok_s", "part_share_lowering.tok_s",
            "part_share_unnamed.tok_s", "part_share_window_attn.tok_s",
            "device_idle_share.tok_s", "slots_active_share",
            "decode_slot_use_share.tok_s", "decode_ahead_share.tok_s",
            "idle_in_scheduler_share.tok_s",
            "admit_to_first_token_mean_ms.tok_s", "ingress_mean_ms.tok_s",
            "egress_chunk_lag_mean_ms.tok_s", "egress_write_mean_ms.tok_s",
            "stream_close_lag_mean_ms.tok_s", "last_frame_lag_mean_ms.tok_s",
            "slot_vacant_mean_ms.tok_s", "moe_local_pick_share",
            "moe_experts_touched_share", "moe_ms_per_step",
            "moe_glue_ms_per_step", "moe_tiles_per_expert",
            "part_share_moe_experts.tok_s",
            "part_share_moe_glue.tok_s"} <= names
    # what ISSUE 54 leaves out and why: its reader lumps a prompt's chunk
    # counts (ROADMAP R0 (o)); it reads over 100 where prefill is large;
    # tests that pass today hold these two with ``== [CELL]``; the model
    # has no shared expert
    assert not names & {"moe_grouped_matmul_roofline",
                        "decode_bw_share.tok_s", "moe_local_token_share",
                        "part_share_moe_shared.tok_s"}
    for x in cell["per_layer"]:
        if x["name"] in MINE:
            # (``in``, not ``==``: a later cell may be appended)
            assert CELL in x["workloads"] and x["moves"] == "serve_tok_s"
            assert x["layer"] == LAYER
    readers = {x["name"]: (x["reader"], x["params"])
               for x in cell["per_layer"] if x["name"] in MINE}
    assert readers["window_attn_ms_per_step"] == ("scope_ms_per_count", {
        "scopes": ["window_attn"],
        "programs": ["jit_decode_burst", "jit_decode_step"],
        "phase": "engine.decode_dispatch", "count": "steps"})
    assert readers["window_attn_ms_per_ktok"] == ("scope_ms_per", {
        "scopes": ["window_attn"], "programs": ["jit_prefill_chunk"],
        "phase": "engine.prefill_dispatch", "count": "tokens", "per": 1000})
    assert readers["window_kv_read_share"] == ("counter_ratio", {
        "num": "window_positions_read", "den": "attn_positions_read",
        "scale": 100.0})
    assert traffic["kind"] == "closed_loop"
    assert traffic["engine"] == {
        "max_num_seqs": 24, "max_seq_len": 32768, "dtype": "bfloat16",
        "kv_block_size": 0, "max_ongoing_requests": 64}
    assert traffic["clients"] == 32 and traffic["cycle_requests"] == 32
    assert traffic["prompt_tokens"] == {"kind": "lognormal", "median": 4096,
                                        "sigma": 1.0, "min": 256,
                                        "max": 30720}
    assert traffic["max_tokens"] == {"kind": "uniform", "min": 512,
                                     "max": 1536}
    assert traffic["trace"] == {"after_s": 10, "for_s": 4}
    assert traffic["check"]["requests"] == 4
    assert "control" in traffic["check"]["margin_why"]
    assert traffic["use"] == "serve_mixed"
    longctx = manifest.load_json(REPO, "traffic", "serve-longctx-32k.json")
    assert traffic["warmup"] == longctx["warmup"]


PINNED = {   # sha256 of json.dumps(plan, sort_keys=True) at 51 s
    1: "fa57fc4e2e15c1e6b7737889632e9f6dd4a99b618d59a2227c6d979224937cd7",
    2147483700: "d0cd3cc29ea019a6b86a3c64ec9f3baab463b25bcc7d33f08cf2c027b15d7a36",
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_the_plan_is_what_it_was_and_fits_the_line(traffic, seed):
    plan = gen.closed_loop_plan(traffic, seed, 51)
    assert hashlib.sha256(json.dumps(plan, sort_keys=True).encode()
                          ).hexdigest() == PINNED[seed]
    cycle = plan["requests"][:32]
    prompts = sorted(r["prompt_tokens"] for r in cycle)
    # short and long in one queue: 475 to 30,720, 10 of 32 under 2,600 and
    # 10 over 6,400, mean 6,389
    assert (prompts[0], prompts[-1]) == (475, 30720)
    assert sum(p < 2600 for p in prompts) == 10
    assert sum(p > 6400 for p in prompts) == 10
    assert round(sum(prompts) / 32) == 6389
    assert all(512 <= r["max_tokens"] <= 1536 for r in cycle)
    longest = max(r["prompt_tokens"] + r["max_tokens"] for r in cycle)
    assert longest <= 30720 + 1536 <= traffic["engine"]["max_seq_len"]
    # every seed sends the same 32 requests, in an order of its own
    other = gen.closed_loop_plan(traffic, seed + 1, 51)["requests"][:32]
    key = lambda r: (r["prompt_tokens"], r["max_tokens"])  # noqa: E731
    assert sorted(map(key, cycle)) == sorted(map(key, other))
    assert [key(r) for r in cycle] != [key(r) for r in other]
    # 8 clients wait for a slot
    assert plan["clients"] == 32 == traffic["engine"]["max_num_seqs"] + 8
    assert {w["prompt_tokens"] for w in traffic["warmup"]} >= {
        16, 32, 64, 128, 256, 512}
    # ids come from the slice of the vocabulary that is held
    ids = gen.prompt_ids(seed, 1000, 4096, 19072)
    assert 259 <= min(ids) and 15000 < max(ids) < 19072


# ----------------------------------------------------------- the arithmetic

def test_this_chip_s_share_is_3430m_parameters(config):
    """ISSUE 54's count: a full layer's attention 89.13M, a window layer's
    94.37M, an expert 25.17M; 6.40 GiB with the routers' float32."""
    assert mimo.attention_params(config, False) == 89_128_960
    assert mimo.attention_params(config, True) == 94_371_840
    assert mimo.dense_ffn_params(config) == 201_326_592
    assert mimo.expert_params(config) == 25_165_824
    assert (mimo.full_lines(config), mimo.window_lines(config),
            mimo.dense_layers(config), mimo.routed_layers(config),
            mimo.router_outputs(config)) == (2, 5, 1, 6, 256)
    assert mimo.params_held(config) == (
        2 * 89_128_960 + 5 * 94_371_840 + 201_326_592
        + 6 * (4096 * 256 + 16 * 25_165_824) + 2 * 4096 * 19072)
    gib = (2 * (mimo.params_held(config) - 6 * 4096 * 256)
           + 4 * 6 * 4096 * 256) / 2 ** 30
    assert round(gib, 2) == 6.40
    # the whole model, by the same functions on the published counts
    whole = {**config, **config["published"],
             "published": {"n_routed_experts": 256}}
    assert round(mimo.params_held(whole) / 1e9, 1) == 308.8
    assert round(mimo.experts_touched(config, 24), 1) == 8.5


def test_depth_is_the_decode_kernel_s_calls_and_the_program_follows(config):
    assert mimo.depth(config, "serve_mixed") == 7
    cfg = mimo.model_config(config, "serve_mixed", 32768)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.swa_num_kv_heads, cfg.head_dim, cfg.v_head_dim,
            cfg.sliding_window, cfg.vocab_size, cfg.n_routed_experts,
            cfg.experts_held, cfg.num_experts_per_tok, cfg.max_seq_len,
            cfg.dtype) == (7, 4096, 64, 4, 8, 192, 128, 128, 19072, 256, 16,
                           8, 32768, "bfloat16")
    assert (cfg.kinds, cfg.routed) == ((0, 1, 1, 1, 1, 0, 1),
                                       (0, 1, 1, 1, 1, 1, 1))
    assert (cfg.rope_theta, cfg.swa_rope_theta, cfg.rotary_dim,
            cfg.attention_value_scale, cfg.norm_eps,
            cfg.routed_scaling_factor, cfg.window_sink) == (
                1e7, 1e4, 64, 0.707, 1e-5, 1.0, True)
    # the program's count: the adapter's matrices, the norms and the sinks
    assert cfg.num_params() == mimo.params_held(config) + 15 * 4096 \
        + 5 * 64 + 6 * 256
    for key, bad in (("hidden_act", "gelu"), ("tie_word_embeddings", True),
                     ("scoring_func", "softmax"), ("n_group", 8),
                     ("n_shared_experts", 1),
                     ("add_full_attention_sink_bias", True),
                     ("swa_head_dim", 128)):
        with pytest.raises(ValueError, match="untied head"):
            mimo.model_config({**config, key: bad}, "serve_mixed", 64)


def test_a_cached_position_is_6144_bytes_and_a_ring_768_kib(config):
    """Both geometries: a row is 384 lanes (a key of 192, a value of 128
    and 64 zeros), 768 bytes a KV head; a position of the two full lines of
    4 KV heads is 6,144 bytes; a window layer's ring of 128 positions of 8
    KV heads is 786,432, whatever the line's length."""
    assert mimo.kv_row_bytes(config) == 768
    assert mimo.kv_bytes_per_token(config, 7) == 2 * 4 * 768 == 6144
    assert mimo.ring_bytes(config) == 128 * 8 * 768 == 786_432
    gib = 2 ** 30
    assert 24 * 32768 * 6144 / gib == 4.5
    assert round(5 * 24 * mimo.ring_bytes(config) / gib, 3) == 0.088
    # what the model owns of a row: 320 of 384 lanes
    assert (config["head_dim"] + config["v_head_dim"]) / (
        2 * config["head_dim"]) == pytest.approx(1 / 1.2)


def test_a_decode_step_reads_its_weights_the_live_rows_and_the_rings(config):
    live = 24 * 8000
    step = mimo.decode_step_bytes(config, 7, live)
    touched = mimo.experts_touched(config, 24)
    weights = 2 * (2 * 89_128_960 + 5 * 94_371_840 + 201_326_592
                   + 6 * touched * 25_165_824 + 4096 * 19072) \
        + 4 * 6 * 4096 * 256
    assert step == pytest.approx(weights + live * 6144 + 5 * 24 * 786_432)
    assert 5.0e9 < step < 6.0e9        # ISSUE 54's "about 5.5 GB"
    assert mimo.decode_attention_bytes(config, 7, live) == live * 6144
    # ``layers`` is the kernel's calls and not a factor of the bytes
    assert mimo.decode_attention_bytes(config, 2, live) == live * 6144


# -------------------------------------------------------------- the readers

CHUNK = "jit(prefill_chunk)/stack/while/body/closed_call/"
STEP = "jit(decode_burst)/stack/while/body/closed_call/stack/while/body/" \
       "closed_call/"


def _spec(name):
    return manifest.load_json(REPO, "layer_metrics", name + ".json")


def _obs(config, **more):
    return {"cell": {"config": config, "traffic": {"use": "serve_mixed"}},
            "peaks": PEAKS, **more}


def test_the_window_layers_share_lies_inside_attn():
    """The partition knows ``attn`` and ``cache``; ``scope_share`` finds
    ``window_attn`` on the same paths: the ring's read with the sink and
    the chunk's banded product, not the projections, not the full layers'
    attention, not a row write."""
    paths = [CHUNK + "attn/dot_general",
             CHUNK + "attn/cache/dynamic_slice",
             CHUNK + "attn/window_attn/dot_general",
             CHUNK + "attn/window_attn/exp",
             CHUNK + "attn/cache/dynamic_update_slice",
             CHUNK + "attn/pallas_call", CHUNK + "moe_experts/pallas_call",
             "jit(prefill_chunk)/attn/window_attn/and",
             "jit(prefill_chunk)/head/dot_general",
             "jit(prefill_chunk)/attn/cache/dynamic_update_slice"]
    dev = _scoped(paths)
    assert [op.part for op in dev.ops[:6]] == ["attn", "cache", "attn",
                                               "attn", "cache", "attn"]
    spec = _spec("part_share_window_attn.tok_s")
    assert scope_share.read({"trace": object(), "device_ops": dev},
                            spec["params"]) == pytest.approx(30.0)
    # a program without the scope (the parent commit) gives nothing
    bare = _scoped([CHUNK + "attn/dot_general", CHUNK + "mlp/dot_general"])
    for name in MINE[:2]:
        obs = {"trace": _trace([], []), "device_ops": bare, "phases": []}
        assert importlib.import_module(
            "rtbench.readers." + _spec(name)["reader"]).read(
                obs, _spec(name)["params"]) is None


def test_window_attn_ms_per_ktok_on_a_made_up_chunk(config):
    """The one whole chunk away from the trace's edges holds two operations
    under ``window_attn`` (10 ms each): 20 ms over 512 tokens."""
    paths = [CHUNK + "attn/dot_general",
             CHUNK + "attn/cache/dynamic_slice",
             CHUNK + "attn/window_attn/dot_general",
             CHUNK + "attn/window_attn/exp",
             CHUNK + "attn/cache/dynamic_update_slice",
             CHUNK + "attn/pallas_call",
             CHUNK + "attn/dot_general", CHUNK + "mlp/dot_general",
             CHUNK + "moe_experts/pallas_call",
             CHUNK + "attn/window_attn/dot_general",
             CHUNK + "attn/dot_general",
             "jit(prefill_chunk)/head/dot_general"]
    modules = [("jit_prefill_chunk(1)", 0.999, 1.02),   # touches the edge
               ("jit_prefill_chunk(1)", 1.02, 1.06),
               ("jit_decode_burst(2)", 1.06, 1.08),
               ("jit_prefill_chunk(1)", 1.08, 1.12)]    # touches the edge
    dev = _scoped(paths, modules)
    trace = _trace(modules, [(op.name, op.start, op.end) for op in dev.ops])
    disp = [phases.Phase("engine.prefill_dispatch", t, t + 0.001,
                         {"tokens": 512, "bucket": 512})
            for t in (0.95, 1.0, 1.07)]
    obs = _obs(config, trace=trace, device_ops=dev, phases=disp)
    spec = _spec("window_attn_ms_per_ktok")
    assert scope_ms_per.read(obs, spec["params"]) == pytest.approx(
        20.0 / 512 * 1000)


def test_window_attn_ms_per_step_on_two_made_up_bursts(config):
    """Two bursts of 4 steps inside the trace; in each, two operations of
    10 ms under ``window_attn`` (a ring's read in two window layers): 5 ms
    a step. The ring's row write is ``cache``'s and not counted."""
    paths = [STEP + "attn/dot_general",
             STEP + "attn/window_attn/pallas_call",
             STEP + "attn/cache/pallas_call",
             STEP + "attn/pallas_call",
             STEP + "attn/window_attn/pallas_call",
             STEP + "attn/cache/pallas_call",
             STEP + "moe_experts/pallas_call",
             "jit(decode_burst)/head/dot_general"]
    modules = [("jit_decode_burst(3)", 0.9995, 1.0395),
               ("jit_decode_burst(3)", 1.0396, 1.0795)]
    dev = _scoped(paths, modules)
    trace = _trace([("jit_decode_burst(3)", 0.5, 0.6)] + modules
                   + [("jit_decode_burst(3)", 1.5, 1.6)],
                   [(op.name, op.start, op.end) for op in dev.ops])
    disp = [phases.Phase("engine.decode_dispatch", t, t + 0.001,
                         {"steps": 4, "slots": 24})
            for t in (0.49, 0.99, 1.03, 1.49)]
    obs = _obs(config, trace=trace, device_ops=dev, phases=disp)
    spec = _spec("window_attn_ms_per_step")
    assert scope_ms_per_count.read(obs, spec["params"]) == pytest.approx(2.5)


def test_the_rings_share_of_the_positions_fetched():
    """24 lines of 8,192 live rows: a step fetches 24 x 5 x 8 x 128 ring
    positions and 24 x 2 x 4 x 8,192 of the full lines: 7.2%."""
    spec = _spec("window_kv_read_share")
    ring, line = 24 * 5 * 8 * 128, 24 * 2 * 4 * 8192
    polls = [(1.0, {"window_positions_read": ring, "full_positions_read":
                    line, "attn_positions_read": ring + line}),
             (2.0, {"window_positions_read": 101 * ring,
                    "full_positions_read": 101 * line,
                    "attn_positions_read": 101 * (ring + line)})]
    obs = {"polls": polls, "t_open": 0.5, "t_close": 2.5}
    assert counter_ratio.read(obs, spec["params"]) == pytest.approx(
        100 * ring / (ring + line))
    assert 7.0 < 100 * ring / (ring + line) < 7.5
    # the parent commit's stats() lack the counters
    assert counter_ratio.read({**obs, "polls": [(t, {}) for t, _ in polls]},
                              spec["params"]) is None


def test_the_decode_kernel_s_roofline_stays_under_100_at_two_kinds_of_call(
        config):
    """A step calls the decode kernel 7 times: twice on the full lines,
    which take their bytes' time here, and five times on a ring, short. The
    reader takes the mean event times ``depth`` 7, all the kernel's time of
    a step, and the full lines' bytes over it stay under 100."""
    live = 24 * 8192.0                          # positions read a step
    full_s = live * 4 * 768 / 819e9             # one full layer's call
    ring_s = 24 * mimo.ring_bytes(config) / 819e9
    events, t = [], 1.0
    for _ in range(3):                          # three steps
        for kind in (0, 1, 1, 1, 1, 0, 1):
            d = ring_s if kind else full_s
            events.append(tr.Event("decode_attention", t, t + d))
            t += d + 1e-5

    class Trace:
        def kernel_events(self, name):
            return events if name == "decode_attention" else []

    polls = [(0.5, {"kv_positions_read": 0, "decode_steps": 0}),
             (3.0, {"kv_positions_read": int(live) * 3, "decode_steps": 3})]
    obs = _obs(config, trace=Trace(), trace_span=(1.0, 2.0), polls=polls)
    share = decode_attention_roofline.read(
        obs, _spec("decode_attention_roofline.tok_s")["params"])
    assert share == pytest.approx(100 * 2 * full_s / (2 * full_s
                                                       + 5 * ring_s))
    assert 85 < share < 100


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


# -------------------------------------------------------------- the control

def tiny(config):
    c = dict(config)
    c.update(hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
             num_attention_heads=8, swa_num_attention_heads=8,
             num_key_value_heads=2, swa_num_key_value_heads=4, head_dim=24,
             swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16,
             sliding_window=16, vocab_size=2048, torch_dtype="float32")
    return c


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_the_fp8_control_comes_out_as_not_correct(config, traffic, seed):
    """benchmark/control.py at a size a test run can hold: the reference on
    weights rounded through fp8 chooses tokens that lie further under the
    float32 reference's top logit than the cell allows, and further than
    the stated precision (bfloat16 weights) does. The readings at the
    cell's own size are PERF.md's (section 4)."""
    from reference import mimo as reference

    from ray_tpu.llm import engine

    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(BENCH, "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    limit = traffic["check"]["margin"]

    c = tiny(config)
    cfg = mimo.model_config(c, "serve_mixed", 256)
    weights = mimo.reference_weights(
        engine.init_params(cfg, jax.random.PRNGKey(seed)))
    assert weights["layers"]["qkv_window"].shape == (5, 128, 12 * 24 + 64)
    assert weights["layers"]["sink"].shape == (5, 8)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (256,), 0, 2048)
    want = reference.logits(c, weights, tokens)
    fp8 = control.margin(
        want, reference.logits(c, control.to_fp8(weights), tokens), 32)
    bf16 = control.margin(want, reference.logits(c, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), weights), tokens), 32)
    assert fp8 > limit
    assert bf16 < fp8
