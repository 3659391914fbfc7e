"""The Keye-VL-2.0 cell's files: its configuration against the published one
(every key kept but the cuts the file lists), its adapter's arithmetic
against hand-worked values at the published widths, its plan, its own
entries in the manifest (never the number of cells, never which cell is
last, and the cell's metric set held with ``<=``), the new roofline reader
on a made-up trace with the kernels' work counted by hand, and the run
without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO
from rtbench import gen, manifest, trace_reduce as tr
from rtbench.adapters import keye
from rtbench.readers import (
    counter_ratio,
    phases,
    scope_ms_per,
    scope_share,
    sparse_attention_roofline,
)
from test_bh_qwen3_next import _scoped, _trace  # noqa: E402

CELL = "keye-vl2-serve-longctx-48k"
CONFIG = "keye-vl-2.0-30b-a3b"
SOURCE = ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
          "config.json")
# The catalog row's ``config`` (the URL above), as published.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CUT = {"num_hidden_layers": 12, "num_experts": 16, "vocab_size": 18992}
LAYER = ("Learned sparse attention (models/keye.py indexer, "
         "ops/sparse_attention.py index_scores, topk_threshold, "
         "sparse_attention)")
MINE = ("part_share_indexer.tok_s", "part_share_index_select.tok_s",
        "part_share_sparse_attn.tok_s", "index_select_ms_per_step",
        "indexer_ms_per_ktok", "sparse_selected_share",
        "index_scores_roofline", "sparse_decode_attention_roofline")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
STEP = {"programs": ["jit_decode_burst", "jit_decode_step"],
        "phase": "engine.decode_dispatch", "count": "steps"}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "serve-longctx-48k.json")) as f:
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
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert sorted(config["reduced"]) == sorted(CUT)
    assert entry["source"] == config["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["adapter"] == "keye"
    assert (config["expert_shard"], config["expert_shards"]) == (0, 8)
    for key in ("equations", "layer", "attention", "mrope", "indexer",
                "selection", "router", "experts", "embeddings", "init",
                "sizes"):
        assert config["assumed"][key], key
    for key in ("vision_tower", "indexer_fp8", "mrope_on_text",
                "router_dtype", "index_key_leaf"):
        assert config["departures"][key], key
    assert "exactly the topk positions" in config["guarantees"]
    assert "ties to the lower position" in config["guarantees"]
    assert "no capacity" in config["guarantees"]
    assert "32 chips" in config["deployment"]
    assert "share 0" in config["deployment"]
    # the arithmetic of the cut, and the compiler's figures beside it
    for said in ("30,640,656,384", "1,240,586,752", "2.31 GiB", "9.56 GiB",
                 "memory_analysis"):
        assert said in config["reduced"]["num_hidden_layers"], said
    assert "TBD" not in json.dumps(config)
    assert "_NUMBERS" not in json.dumps(config)


def test_the_cell_s_own_entries_are_what_the_issue_names(traffic):
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    assert manifest.check_modules(m, REPO) == []
    cell = manifest.load_cell(CELL, REPO)
    assert cell["workload"] == {**cell["workload"], "config": CONFIG,
                                "traffic": "serve-longctx-48k", "chips": 1}
    why = cell["workload"]["why"]
    for said in ("12 clients", "8 slots x 49,152", "2,048",
                 "0.5 rows an expert", "4 deployed"):
        assert said in why
    assert [x["name"] for x in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    names = {x["name"] for x in cell["per_layer"]}
    assert set(MINE) <= names
    # the lists ISSUE 64 names: those Qwen3-Next's long-context cell is in
    # that are not the delta rule's
    assert {"slots_active_share", "device_idle_share.tok_s",
            "admit_to_first_token_mean_ms.tok_s",
            "decode_slot_use_share.tok_s", "prefill_ms_per_ktok.counted",
            "idle_in_scheduler_share.tok_s", "decode_ms_per_step.tok_s",
            "tpot_p90_ms.tok_s", "decode_ahead_share.tok_s",
            "moe_local_pick_share", "moe_experts_touched_share",
            "moe_ms_per_step", "moe_glue_ms_per_step",
            "moe_tiles_per_expert", "part_share_attn.tok_s",
            "part_share_mlp.tok_s", "part_share_head.tok_s",
            "part_share_moe_experts.tok_s", "part_share_moe_glue.tok_s",
            "part_share_lowering.tok_s", "part_share_unnamed.tok_s"} <= names
    # left out and why: neither ``decode_attention`` nor
    # ``prefill_attention`` runs in this cell's programs (a line is read
    # under a mask by ops/sparse_attention.py's own pass), so the shares
    # that read those kernels' walks would be of nothing; the others read
    # scopes, kernels or counters this model has not
    assert not names & {"decode_attention_roofline.tok_s",
                        "decode_kv_read_share.tok_s",
                        "prefill_kv_read_share.tok_s",
                        "part_share_linear_attn.tok_s",
                        "part_share_delta_rule.tok_s",
                        "part_share_moe_shared.tok_s",
                        "moe_local_token_share", "decode_bw_share.tok_s"}
    for x in cell["per_layer"]:
        if x["name"] in MINE:
            # (``in``, not ``==``: a later cell may be appended)
            assert CELL in x["workloads"] and x["moves"] == "serve_tok_s"
            assert x["layer"] == LAYER
    readers = {x["name"]: (x["reader"], x["params"])
               for x in cell["per_layer"] if x["name"] in MINE}
    for part in ("indexer", "index_select", "sparse_attn"):
        assert readers[f"part_share_{part}.tok_s"] == (
            "scope_share", {"scopes": [part]})
    assert readers["index_select_ms_per_step"] == (
        "scope_ms_per_count", {"scopes": ["index_select"], **STEP})
    assert readers["indexer_ms_per_ktok"] == ("scope_ms_per", {
        "scopes": ["indexer"], "programs": ["jit_prefill_chunk"],
        "phase": "engine.prefill_dispatch", "count": "tokens", "per": 1000})
    assert readers["sparse_selected_share"] == ("counter_ratio", {
        "num": "index_positions_selected", "den": "index_positions_scored",
        "scale": 100.0})
    assert readers["index_scores_roofline"] == (
        "sparse_attention_roofline", {
            "kernel": "index_scores",
            "counter": "index_step_positions_scored",
            "work": "index_scores_work", **STEP})
    assert readers["sparse_decode_attention_roofline"] == (
        "sparse_attention_roofline", {
            "kernel": "sparse_decode_attention",
            "counter": "index_step_positions_selected",
            "work": "sparse_attention_work", **STEP})
    assert traffic["kind"] == "closed_loop"
    assert traffic["engine"] == {
        "max_num_seqs": 8, "max_seq_len": 49152, "dtype": "bfloat16",
        "kv_block_size": 0, "max_ongoing_requests": 24}
    assert traffic["clients"] == 12 and traffic["cycle_requests"] == 12
    assert traffic["prompt_tokens"] == {
        "kind": "lognormal", "median": 20480, "sigma": 0.5, "min": 8192,
        "max": 45056}
    assert traffic["max_tokens"] == {"kind": "uniform", "min": 256,
                                     "max": 768}
    assert traffic["trace"] == {"after_s": 10, "for_s": 4}
    assert traffic["check"]["requests"] == 4
    for said in ("fp8", "selection left out", "most recent 2,048"):
        assert said in traffic["check"]["margin_why"], said
    assert traffic["use"] == "serve_longctx"
    longctx = manifest.load_json(REPO, "traffic", "serve-longctx-32k.json")
    assert traffic["warmup"] == longctx["warmup"]
    for key in ("why", "warmup_why", "cycle_why", "stagger_why",
                "max_requests_per_s_why"):
        assert len(traffic[key]) > 100 and "TBD" not in traffic[key], key
    for key in ("margin_why", "min_readable_why"):
        assert len(traffic["check"][key]) > 100 \
            and "TBD" not in traffic["check"][key], key


def test_the_controls_are_recorded_and_the_margin_lies_between():
    """benchmark/records/control-keye.jsonl: control.py's fp8 rows and
    devbench/keye_bench.py margins' rows, sound runs among them. The limit
    is over every sound reading and under every control's."""
    limit = manifest.load_json(REPO, "traffic",
                               "serve-longctx-48k.json")["check"]["margin"]
    with open(os.path.join(BENCH, "records", "control-keye.jsonl")) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    fp8 = [r["control_fp8_margin"] for r in rows if "control_fp8_margin" in r]
    by_case = {}
    for r in rows:
        if "worst" in r:          # (the "sets" rows count swaps, no margin)
            by_case.setdefault(r["case"], []).append(r["worst"])
    assert len(fp8) >= 3 and min(fp8) > limit
    for case in ("no_selection", "recent_window"):
        assert len(by_case[case]) >= 2 and min(by_case[case]) > limit, case
    assert len(by_case["sound"]) >= 2 and max(by_case["sound"]) < limit
    assert all(r.get("device", "TPU v5 lite") == "TPU v5 lite" for r in rows)


@pytest.mark.parametrize("seed", [1, 2147483700])
def test_the_plan_outlasts_its_window_and_fits_the_line(traffic, seed):
    plan = gen.closed_loop_plan(traffic, seed, 51)
    assert plan == gen.closed_loop_plan(traffic, seed, 51)
    cycle = plan["requests"][:12]
    prompts = sorted(r["prompt_tokens"] for r in cycle)
    assert (prompts[0], prompts[-1]) == (8616, 45056)
    assert round(sum(prompts) / 12) == 22582
    # every prompt is past the 2,048 positions a query keeps: the selection
    # decides from a prompt's 2,049th token on
    assert prompts[0] > 4 * 2048
    assert all(256 <= r["max_tokens"] <= 768 for r in cycle)
    longest = max(r["prompt_tokens"] + r["max_tokens"] for r in cycle)
    assert longest == 45461 <= traffic["engine"]["max_seq_len"]
    other = gen.closed_loop_plan(traffic, seed + 1, 51)["requests"][:12]
    key = lambda r: (r["prompt_tokens"], r["max_tokens"])  # noqa: E731
    assert sorted(map(key, cycle)) == sorted(map(key, other))
    assert [key(r) for r in cycle] != [key(r) for r in other]
    # 4 clients wait for a slot
    assert plan["clients"] == 12 == traffic["engine"]["max_num_seqs"] + 4
    # the ramp's two generations (24) and twice the 23 a window finishes at
    # the rate the builder measured (``max_requests_per_s_why``)
    assert len(plan["requests"]) >= 24 + 2 * 23
    ids = gen.prompt_ids(seed, 1000, 4096, 18992)
    assert 259 <= min(ids) and 15000 < max(ids) < 18992


# ----------------------------------------------------------- the arithmetic

def test_this_chip_s_share_is_1241m_parameters_of_30_6b(config):
    """ISSUE 64's count at the published widths."""
    assert keye.attention_params(config) == 18_874_624
    assert keye.indexer_params(config) == 2_261_120 == (
        2048 * (16 * 64 + 64 + 16) + 2 * 64)
    assert keye.router_params(config) == 262_144
    assert keye.expert_params(config) == 4_718_592
    assert keye.layer_params(config, 128) == 625_381_760
    assert keye.layer_params(config, 0) == 21_401_984
    assert keye.params_published(config) == (
        48 * 625_381_760 + 622_331_904) == 30_640_656_384
    assert keye.params_held(config) == (
        12 * (21_401_984 + 16 * 4_718_592) + 2 * 18992 * 2048 + 2048) \
        == 1_240_586_752
    assert round(keye.params_held(config) * 2 / 2 ** 30, 2) == 2.31
    # a token's parameters: 8 picks of 128 in 48 layers
    active = keye.params_published(config) \
        - 48 * (128 - 8) * keye.expert_params(config)
    # (3.46B with both vocabulary matrices, 3.15B with the head alone: the
    # catalog's "A3B")
    assert round(active / 1e9, 2) == 3.46
    assert round((active - 151936 * 2048) / 1e9, 2) == 3.15
    # a cached position: 2,048 bytes of keys and values and 128 of index
    # key a layer; 8 lines of 49,152 are 9.56 GiB
    assert keye.selected_bytes_per_position(config) == 2048
    assert keye.index_bytes_per_position(config) == 128
    assert keye.kv_bytes_per_token(config, 12) == 26_112
    assert round(8 * 49152 * 26_112 / 2 ** 30, 2) == 9.56


def test_depth_is_the_layers_and_the_program_follows(config):
    assert keye.depth(config, "serve_longctx") == 12
    cfg = keye.model_config(config, "serve_longctx", 49152)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.num_experts, cfg.experts_held,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.index_heads, cfg.index_head_dim, cfg.index_rope_dim,
            cfg.index_topk, cfg.max_seq_len, cfg.dtype, cfg.rope_theta) == (
                12, 2048, 32, 4, 128, 18992, 128, 16, 8, 768, 16, 64, 32,
                2048, 49152, "bfloat16", 1e7)
    assert cfg.num_params() == keye.params_held(config)
    with pytest.raises(ValueError, match="one key a position"):
        keye.model_config({**config, "sa_config": {
            **config["sa_config"], "indexer_num_kv_heads": 2}},
            "serve_longctx", 49152)


def test_the_kernels_work_is_the_mathematics_counted_by_hand(config):
    """At one shape, a decode step of 8 lines of 24,576 positions in 12
    layers: 2,359,296 scored positions, each one index key of 128 bytes and
    2 x 16 x 64 multiply-adds; 196,608 selected, each 2,048 bytes of keys
    and values and 4 x 32 x 128 multiply-adds. Both are bound by their
    bytes on this chip."""
    scored, selected = 8 * 24576 * 12, 8 * 2048 * 12
    work = keye.index_scores_work(config, scored)
    assert work == {"flops": 2 * 16 * 64 * scored, "bytes": 128 * scored}
    assert work["bytes"] / 819e9 > work["flops"] / 197e12
    assert round(work["bytes"] / 819e9 * 1e6) == 369       # microseconds
    work = keye.sparse_attention_work(config, selected)
    assert work == {"flops": 4 * 32 * 128 * selected,
                    "bytes": 2048 * selected}
    assert work["bytes"] / 819e9 > work["flops"] / 197e12
    assert round(work["bytes"] / 819e9 * 1e6) == 492
    # a chunk's 512 rows share their line: its keys once, the scores written
    chunk = keye.index_chunk_work(config, 512 * 20000.0, 512)
    assert chunk["flops"] == 2 * 16 * 64 * 512 * 20000
    assert chunk["bytes"] == 128 * 20000 + 4 * 512 * 20000


def _obs(config, trace, polls, **more):
    cell = {"config": config, "traffic": {"use": "serve_longctx"}}
    return {"trace": trace, "trace_span": (1.0, 2.0), "polls": polls,
            "cell": cell, "peaks": PEAKS, "t_open": 0.5, "t_close": 2.5,
            **more}


@pytest.mark.parametrize("name, kernel, per_call_s, counter, a_step", [
    ("index_scores_roofline", "index_scores", 165e-6,
     "index_step_positions_scored", 8 * 24576 * 12),
    ("sparse_decode_attention_roofline", "sparse_decode_attention", 1.58e-3,
     "index_step_positions_selected", 8 * 2048 * 12)])
def test_a_roofline_is_the_step_s_bytes_over_the_kernel_s_time(
        config, name, kernel, per_call_s, counter, a_step):
    """Two whole bursts of 8 steps, 12 calls of the kernel a step; the
    counters grow by a step's positions a step over the window."""
    spec = manifest.load_json(REPO, "layer_metrics", name + ".json")
    modules = [("jit_decode_burst(1)", 1.1, 1.1 + 0.3),
               ("jit_prefill_chunk(2)", 1.42, 1.45),
               ("jit_decode_burst(1)", 1.5, 1.5 + 0.3)]
    ops = [("%fusion.1 = bf16[1] fusion()", 1.0, 1.0001)]
    for start in (1.1, 1.5):
        ops += [(f"%{kernel}.{i} = bf16[8,4,8,16,128] custom-call()",
                 start + i * 3e-3, start + i * 3e-3 + per_call_s)
                for i in range(8 * 12)]
    # a chunk's call of the same kernel is no step's
    ops.append((f"%{kernel}.99 = bf16[1] custom-call()", 1.43, 1.44))
    ops.append(("%fusion.2 = bf16[1] fusion()", 1.9999, 2.0))
    disp = [phases.Phase("engine.decode_dispatch", t, t + 0.001,
                         {"steps": 8, "slots": 8}) for t in (1.05, 1.48)]
    polls = [(0.6, {counter: 0, "decode_steps": 0}),
             (2.4, {counter: 100 * a_step, "decode_steps": 100})]
    obs = _obs(config, _trace(modules, sorted(ops, key=lambda o: o[1])),
               polls, phases=disp)
    got = sparse_attention_roofline.read(obs, spec["params"])
    per_position = 128 if kernel == "index_scores" else 2048
    least = a_step * per_position / 819e9
    assert got == pytest.approx(100 * least / (12 * per_call_s), rel=1e-6)
    assert 15 < got < 30 if kernel == "index_scores" else 2 < got < 4
    # a program without the counter, or a trace without the kernel (the
    # parent commit), gives nothing and does not raise
    bare = {**obs, "polls": [(t, {"decode_steps": s["decode_steps"]})
                             for t, s in polls]}
    assert sparse_attention_roofline.read(bare, spec["params"]) is None
    none = {**obs, "trace": _trace(modules, ops[:1] + ops[-1:])}
    assert sparse_attention_roofline.read(none, spec["params"]) is None
    assert sparse_attention_roofline.read({**obs, "trace": None},
                                          spec["params"]) is None


def test_the_scopes_shares_lie_inside_attn_and_the_counters_share():
    chunk = "jit(prefill_chunk)/stack/while/body/closed_call/"
    paths = [chunk + "attn/dot_general", chunk + "attn/indexer/dot_general",
             chunk + "attn/indexer/pallas_call",
             chunk + "attn/index_select/pallas_call",
             chunk + "attn/sparse_attn/pallas_call",
             chunk + "attn/sparse_attn/transpose",
             chunk + "attn/cache/dynamic_update_slice",
             chunk + "moe_experts/pallas_call", chunk + "moe_combine/add",
             "jit(prefill_chunk)/head/dot_general"]
    dev = _scoped(paths)
    assert [op.part for op in dev.ops[:6]] == ["attn"] * 6
    obs = {"trace": object(), "device_ops": dev}
    for part, share in (("indexer", 20.0), ("index_select", 10.0),
                        ("sparse_attn", 20.0)):
        spec = manifest.load_json(REPO, "layer_metrics",
                                  f"part_share_{part}.tok_s.json")
        assert scope_share.read(obs, spec["params"]) == pytest.approx(share)
        bare = _scoped([p.replace(part + "/", "") for p in paths])
        assert scope_share.read({"trace": object(), "device_ops": bare},
                                spec["params"]) is None
    spec = manifest.load_json(REPO, "layer_metrics",
                              "sparse_selected_share.json")
    polls = [(1.0, {"index_positions_scored": 10,
                    "index_positions_selected": 10}),
             (2.0, {"index_positions_scored": 20010,
                    "index_positions_selected": 2058})]
    obs = {"polls": polls, "t_open": 0.5, "t_close": 2.5}
    assert counter_ratio.read(obs, spec["params"]) == pytest.approx(10.24)
    assert counter_ratio.read({**obs, "polls": [(t, {}) for t, _ in polls]},
                              spec["params"]) is None
    assert scope_ms_per.read({"trace": None}, manifest.load_json(
        REPO, "layer_metrics", "indexer_ms_per_ktok.json")["params"]) is None


def test_without_a_tpu_the_cell_exits_non_zero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
