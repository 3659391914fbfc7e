"""The Qwen3-Next cell's files: its configuration against the published
one, its adapter's arithmetic against hand-worked values, its plan pinned,
its own entries in the manifest (never the number of cells, and the cell's
metric set held with ``<=``), each new reader on a made-up trace that takes
exactly the yardstick's time, and the control at a small size."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, REPO
from rtbench import gen, manifest, trace_reduce as tr, xplane_meta as xm
from rtbench.adapters import qwen3_next
from rtbench.readers import (
    counter_ratio,
    delta_rule_roofline,
    phases,
    scope_ms_per,
    scope_ms_per_count,
    scope_share,
)

CELL = "qwen3-next-serve-longctx-32k"
CONFIG = "qwen3-next-80b-a3b"
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/"
          "main/config.json")

# The catalog row's ``config`` (the URL above), as published.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 16, "num_experts": 64, "vocab_size": 18992}
MINE = {"part_share_linear_attn.tok_s", "part_share_delta_rule.tok_s",
        "delta_rule_ms_per_ktok", "linear_step_ms_per_step",
        "delta_rule_chunk_roofline", "delta_rule_step_roofline",
        "linear_state_update_share"}
LAYER = ("Linear attention (models/qwen3_next.py Gated DeltaNet, "
         "ops/gated_delta.py gated_delta_chunk, gated_delta_step)")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "serve-longctx-32k.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- the files

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_key_is_kept_or_listed_as_reduced(config, key):
    entry = manifest.config_entry(manifest.load(REPO), CONFIG)
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
    entry = manifest.config_entry(manifest.load(REPO), CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert entry["source"] == config["source"] == SOURCE
    assert config["adapter"] == "qwen3_next"
    # four whole periods of (3 linear, 1 full): floor, at least 4 layers and
    # a whole period; an eighth of the experts (floor: 8), an eighth of the
    # vocabulary (the floor)
    assert config["num_hidden_layers"] % config["full_attention_interval"] \
        == 0 and config["num_hidden_layers"] >= 4
    assert (qwen3_next.linear_lines(config),
            qwen3_next.attention_lines(config)) == (12, 4)
    assert config["num_experts"] == 64 >= 8
    assert (config["expert_shard"], config["expert_shards"]) == (0, 8)
    assert config["num_experts"] * config["expert_shards"] == \
        config["published"]["num_experts"] == \
        qwen3_next.router_outputs(config)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    for item in ("equations", "norms", "layer", "attention", "linear_attention",
                 "router", "experts", "embeddings", "init", "sizes"):
        assert config["assumed"][item], item
    assert "modeling_qwen3_next.py" in config["assumed"]["equations"]
    assert "Qwen3NextGatedDeltaNet" in config["assumed"]["linear_attention"]
    assert "1e-6" in config["assumed"]["linear_attention"]
    assert "1 + w" in config["assumed"]["norms"]
    assert {"mtp", "state_dtype", "router_dtype", "projection_order"} <= \
        set(config["departures"])
    assert "three pipeline stages" in config["deployment"]
    assert config["guarantees"].startswith(
        "every pick that falls on a held expert is computed")
    assert all("PR 48" in config["reduced"][key] for key in REDUCED)
    assert "memory_analysis" in config["reduced"]["num_hidden_layers"]


def test_the_cell_s_own_entries_are_what_the_issue_names(traffic):
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    cell = manifest.load_cell(CELL, REPO)
    assert cell["workload"] == {**cell["workload"], "config": CONFIG,
                                "traffic": "serve-longctx-32k", "chips": 1}
    why = cell["workload"]["why"]
    for said in ("10 rows a chunk (80 deployed)", "0.3 a step (2.5)",
                 "16 of 48 layers: host work"):
        assert said in why
    assert [x["name"] for x in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    names = {x["name"] for x in cell["per_layer"]}
    lfm2 = {x["name"] for x in manifest.load_cell(
        "lfm2-24b-serve-extract-8k", REPO)["per_layer"]}
    # this cell's own, and of what the LFM2 cell reads all but the
    # convolution's two and ``decode_bw_share.tok_s`` (it reads wrongly
    # where prefill takes most of the time: test_bh_deepseek.py); held with
    # ``<=``: a later PR may append the cell to a metric of its own.
    # ``part_share_moe_shared.tok_s`` reads this cell's ``moe_shared`` scope
    # too, but test_bh_deepseek.py:146 holds that metric's ``workloads`` to
    # its own cell alone with ``==``, so the cell is not on its list
    # (PERF.md section 7)
    assert MINE <= names
    # (PERF.md section 7). ``moe_grouped_matmul_roofline`` is left off too:
    # its work a layer-step comes from counters the engine adds a whole
    # prompt at a time (up to 60 chunks at a prompt's last one), so between
    # two polls the mix of chunks and steps is not the traced span's, and
    # three traced runs read 58.3, 69.8 and 91.1 at a kernel that did not
    # change: a fourth could pass 105 (my chip runs, PR 48)
    assert lfm2 - {"part_share_conv.tok_s", "conv_ms_per_step",
                   "decode_bw_share.tok_s",
                   "moe_grouped_matmul_roofline"} <= names
    assert {"moe_local_pick_share",
            "decode_attention_roofline.tok_s"} <= names
    assert not names & {"part_share_conv.tok_s", "conv_ms_per_step",
                        "decode_bw_share.tok_s", "moe_zero_pick_share",
                        "moe_grouped_matmul_roofline"}
    for x in cell["per_layer"]:
        if x["name"] in MINE:
            # (``in``, not ``==``: a later cell may be appended)
            assert CELL in x["workloads"] and x["moves"] == "serve_tok_s"
            assert x["layer"] == LAYER
    readers = {x["name"]: (x["reader"], x["params"])
               for x in cell["per_layer"] if x["name"] in MINE}
    decode = ["jit_decode_burst", "jit_decode_step"]
    assert readers["part_share_linear_attn.tok_s"] == (
        "scope_share", {"scopes": ["linear_attn", "delta_rule",
                                   "linear_state"]})
    assert readers["part_share_delta_rule.tok_s"] == (
        "scope_share", {"scopes": ["delta_rule"]})
    chunk = {"scopes": ["delta_rule"], "programs": ["jit_prefill_chunk"],
             "phase": "engine.prefill_dispatch", "count": "tokens"}
    step = {"scopes": ["delta_rule", "linear_state"], "programs": decode,
            "phase": "engine.decode_dispatch", "count": "steps"}
    assert readers["delta_rule_ms_per_ktok"] == (
        "scope_ms_per", {**chunk, "per": 1000})
    assert readers["linear_step_ms_per_step"] == ("scope_ms_per_count", step)
    assert readers["delta_rule_chunk_roofline"] == (
        "delta_rule_roofline", {"form": "chunk", **chunk})
    assert readers["delta_rule_step_roofline"] == (
        "delta_rule_roofline", {"form": "step", **step})
    assert readers["linear_state_update_share"] == ("counter_ratio", {
        "num": "linear_state_updates", "den": "decode_steps",
        "den_times": "slots", "scale": pytest.approx(100.0 / 12)})
    assert traffic["kind"] == "closed_loop"
    assert traffic["engine"] == {
        "max_num_seqs": 16, "max_seq_len": 32768, "dtype": "bfloat16",
        "kv_block_size": 0, "max_ongoing_requests": 48}
    assert traffic["clients"] == 24 and traffic["cycle_requests"] == 24
    assert traffic["prompt_tokens"] == {"kind": "lognormal", "median": 12288,
                                        "sigma": 0.5, "min": 4096,
                                        "max": 30720}
    assert traffic["max_tokens"] == {"kind": "uniform", "min": 256,
                                     "max": 768}
    assert traffic["stagger_s"] == 16
    assert traffic["trace"] == {"after_s": 10, "for_s": 4}
    assert traffic["check"]["requests"] == 4
    assert "control" in traffic["check"]["margin_why"]
    assert traffic["use"] == "serve_longctx"


PINNED = {   # sha256 of json.dumps(plan, sort_keys=True) at 51 s
    1: "8cf09484538f5b419c43cd2ff07c48e194488d308f80c5f1dd7b929f9ea87cbe",
    2147483700: "334fd212941aa63d62cfb18eeca15a4629f99be4be7232619ed9563da8f270a7",
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_the_plan_is_what_it_was_and_fits_the_line(traffic, seed):
    plan = gen.closed_loop_plan(traffic, seed, 51)
    assert hashlib.sha256(json.dumps(plan, sort_keys=True).encode()
                          ).hexdigest() == PINNED[seed]
    cycle = plan["requests"][:24]
    assert 4096 <= min(r["prompt_tokens"] for r in cycle) < 4500
    assert max(r["prompt_tokens"] for r in cycle) == 30720
    assert all(256 <= r["max_tokens"] <= 768 for r in cycle)
    longest = max(r["prompt_tokens"] + r["max_tokens"] for r in cycle)
    assert 30720 < longest <= 31488 <= traffic["engine"]["max_seq_len"]
    # a prompt is 8 to 60 chunks of 512: the rule's state crosses 7 to 59
    # chunk boundaries
    assert {-(-r["prompt_tokens"] // 512) for r in cycle} <= set(range(8, 61))
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
    ids = gen.prompt_ids(seed, 1000, 4096, 18992)
    assert 259 <= min(ids) and max(ids) < 18992


# ----------------------------------------------------------- the arithmetic

def test_the_cut_is_3880m_parameters_of_which_3221m_are_routed_experts(
        config):
    c = config
    assert qwen3_next.linear_params(c) == (
        2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * 2048
    ) == 33718464
    assert qwen3_next.attention_params(c) == (
        2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256) == 27263488
    assert qwen3_next.expert_params(c) == 3 * 2048 * 512 == 3145728
    assert qwen3_next.shared_params(c) == 3145728 + 2048
    assert qwen3_next.router_params(c) == 2048 * 512
    assert qwen3_next.params_held(c) == (
        12 * 33718464 + 4 * 27263488
        + 16 * (1048576 + 3147776 + 64 * 3145728 + 4096)
        + 2 * 18992 * 2048 + 2048) == 3_879_901_440
    assert 16 * 64 * 3145728 == 3_221_225_472
    assert qwen3_next.params_held(c) * 2 / 2 ** 30 == pytest.approx(
        7.227, abs=1e-3)
    # all 512 experts of one layer are 3.0 GiB: no chip holds five layers
    assert 512 * qwen3_next.expert_params(c) * 2 / 2 ** 30 == 3.0


def test_depth_is_layers_and_the_program_s_configuration_follows(config):
    assert qwen3_next.depth(config, "serve_longctx") == 16
    cfg = qwen3_next.model_config(config, "serve_longctx", 32768)
    assert (cfg.num_layers, cfg.linear_lines, cfg.attention_lines,
            cfg.experts_held, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.rotary_dim, cfg.vocab_size) == \
        (16, 12, 4, 64, 16, 2, 256, 64, 18992)
    rule = cfg.router_rule
    assert (rule.score, rule.use_bias, rule.renormalize, rule.renorm_eps,
            rule.scaling_factor, rule.zero_experts, rule.topk, rule.held,
            rule.outputs, rule.groups) == \
        ("softmax", False, True, 0.0, 1.0, 0, 10, 64, 512, 1)
    assert cfg.max_seq_len == 32768 and cfg.rope_theta == 1e7
    assert cfg.linear_state_bytes == qwen3_next.linear_state_bytes(config)
    # the adapter counts every parameter, the norms among them
    assert cfg.num_params() == qwen3_next.params_held(config)
    with pytest.raises(ValueError, match="unscaled rotary"):
        qwen3_next.model_config({**config, "rope_scaling": {"factor": 2}},
                                "serve_longctx", 32768)


def test_a_cached_position_is_8_kib_and_a_state_2_mib(config):
    c = config
    assert qwen3_next.kv_bytes_per_token(c, 16) == 2 * 256 * 2 * 2 * 4 \
        == 8192
    assert qwen3_next.linear_state_bytes(c) == 32 * 128 * 128 * 4 \
        == 2 * 2 ** 20
    assert qwen3_next.conv_window_bytes(c) == 3 * 8192 * 2
    # 16 slots x 32,768 positions: 4.00 GiB of lines, 0.375 of states,
    # 9 MiB of windows
    assert 16 * 32768 * 8192 == 4 * 2 ** 30
    assert 12 * 16 * qwen3_next.linear_state_bytes(c) == 0.375 * 2 ** 30
    assert 12 * 16 * qwen3_next.conv_window_bytes(c) == 9 * 2 ** 20
    # one kernel call's bytes ``depth`` times (the module's docstring)
    assert qwen3_next.decode_attention_bytes(c, 16, 1000) == \
        1000 * 2048 * 16


def test_a_decode_step_counts_the_experts_16_lines_touch(config):
    c = config
    assert qwen3_next.experts_touched_uniform(c, 16) == pytest.approx(
        64 * (1 - (1 - 10 / 512) ** 16)) == pytest.approx(17.33, abs=0.01)
    assert qwen3_next.experts_touched_uniform(c, 512) > 63.99
    dense = (12 * 33718464 + 4 * 27263488 + 16 * 3147776 + 2048 * 18992)
    base = qwen3_next.decode_step_bytes(c, 16, 0)
    state = 12 * 16 * (2 * 2 ** 20 + 49152)
    assert base == pytest.approx(
        2 * (dense + 16 * qwen3_next.experts_touched_uniform(c, 16) * 3145728)
        + 4 * 16 * 1048576 + 2 * state, rel=1e-9)
    assert qwen3_next.decode_step_bytes(c, 16, 1000) - base == 1000 * 8192
    # 3.8 GB a step before the lines: 1.2 GB of operators, shared experts
    # and head, 1.7 GB of touched experts, 0.8 GB of states in and out
    assert base / 1e9 == pytest.approx(3.84, abs=0.01)
    work = qwen3_next.grouped_matmul_work(c, 64, 5120)
    assert work["bytes"] == 2 * (64 * 3145728 + 5120 * (4096 + 1024))
    assert work["flops"] == 2 * 5120 * 3145728


def test_the_rule_s_yardstick_is_the_recurrence_s_work_and_the_states_bytes(
        config):
    work = qwen3_next.delta_rule_token_work(config)
    assert work == {"flops": 7 * 128 * 128 * 32,
                    "bytes": 32 * (2 * 128 + 2 * 128 + 2) * 4}
    # bytes bind: 80 ns a token and layer against 19 ns of FLOPs
    assert work["bytes"] / 819e9 > 4 * work["flops"] / 197e12
    assert qwen3_next.linear_step_bytes(config, 192) == 192 * 4 * 2 ** 20


# -------------------------------------------------------------- the readers

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


def _trace(modules, ops):
    dev = tr.DeviceTrace(0, [tr.Event(n, a, b) for n, a, b in ops], [],
                         [tr.Event(n, a, b) for n, a, b in modules])
    tr._self_times(dev.ops)
    return tr.Trace([dev], {})


CHUNK = "jit(prefill_chunk)/stack/while/body/closed_call/"
STEP = "jit(decode_burst)/stack/while/body/closed_call/stack/while/body/" \
       "closed_call/"
PATHS = [CHUNK + "attn/linear_attn/dot_general",
         CHUNK + "attn/linear_state/dynamic_slice",
         CHUNK + "attn/linear_attn/delta_rule/dot_general",
         CHUNK + "attn/linear_attn/delta_rule/while/body/dot_general",
         CHUNK + "attn/linear_state/dynamic_update_slice",
         CHUNK + "attn/dot_general", CHUNK + "mlp/moe_shared/dot_general",
         CHUNK + "moe_experts/pallas_call", CHUNK + "moe_combine/add",
         "jit(prefill_chunk)/head/dot_general"]


def _spec(name):
    return manifest.load_json(REPO, "layer_metrics", name + ".json")


def test_the_scopes_shares_lie_inside_attn():
    """The partition knows ``attn`` and books the whole operator there;
    ``scope_share`` finds ``linear_attn``, ``delta_rule`` and
    ``linear_state`` on the same paths, the rule alone in the narrower
    metric."""
    dev = _scoped(PATHS)
    assert [op.part for op in dev.ops[:6]] == ["attn"] * 6
    obs = {"trace": object(), "device_ops": dev}
    whole, rule = (_spec("part_share_linear_attn.tok_s"),
                   _spec("part_share_delta_rule.tok_s"))
    assert scope_share.read(obs, whole["params"]) == pytest.approx(50.0)
    assert scope_share.read(obs, rule["params"]) == pytest.approx(20.0)
    # a program without the scopes (the parent commit) gives nothing
    bare = _scoped([p.replace("linear_attn/", "").replace("delta_rule/", "")
                    .replace("linear_state/", "") for p in PATHS])
    for spec in (whole, rule):
        assert scope_share.read({"trace": object(), "device_ops": bare},
                                spec["params"]) is None
        assert scope_share.read({"trace": None}, spec["params"]) is None


def _chunk_obs(config, paths=PATHS):
    modules = [("jit_prefill_chunk(1)", 0.999, 1.02),   # touches the edge
               ("jit_prefill_chunk(1)", 1.02, 1.06),
               ("jit_decode_burst(2)", 1.06, 1.08),
               ("jit_prefill_chunk(1)", 1.08, 1.1)]     # touches the edge
    dev = _scoped(paths, modules)
    trace = _trace(modules, [(op.name, op.start, op.end) for op in dev.ops])
    disp = [phases.Phase("engine.prefill_dispatch", t, t + 0.001,
                         {"tokens": 512, "bucket": 512})
            for t in (0.95, 1.0, 1.07)]
    return {"trace": trace, "device_ops": dev, "phases": disp,
            "cell": {"config": config, "traffic": {"use": "serve_longctx"}},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}


def test_delta_rule_ms_per_ktok_and_the_chunk_s_roofline(config):
    """The one whole chunk away from the edges holds two operations of the
    rule (those that start at 1.02 and 1.03): 20 ms over 512 tokens, where
    the yardstick wants 12 layers x 80.4 ns a token."""
    obs = _chunk_obs(config)
    spec = _spec("delta_rule_ms_per_ktok")
    assert scope_ms_per.read(obs, spec["params"]) == pytest.approx(
        20.0 / 512 * 1000)
    roof = _spec("delta_rule_chunk_roofline")
    least_ms = 12 * 32 * 514 * 4 / 819e9 * 1e3
    assert delta_rule_roofline.read(obs, roof["params"]) == pytest.approx(
        100 * least_ms / (20.0 / 512))
    # a trace whose rule takes exactly the yardstick's time reads 100
    exact = _chunk_obs(config)
    for op in exact["device_ops"].ops:
        op.self_s = least_ms * 512 / 2 / 1e3
    assert delta_rule_roofline.read(exact, roof["params"]) == \
        pytest.approx(100.0)
    # the parent commit: no scope, no metric
    bare = _chunk_obs(config, [CHUNK + "attn/dot_general"] * 10)
    assert delta_rule_roofline.read(bare, roof["params"]) is None
    assert scope_ms_per.read(bare, spec["params"]) is None
    names = manifest.module_names(os.path.join(
        BENCH, "rtbench", "readers", "delta_rule_roofline.py"))
    assert set(names["ADAPTER_NEEDS"]) <= set(manifest.module_names(
        os.path.join(BENCH, "rtbench", "adapters", "qwen3_next.py")))


def test_linear_step_ms_per_step_and_the_step_s_roofline(config):
    """Two bursts of 4 steps inside the trace; in each, two operations of
    10 ms under ``delta_rule`` or ``linear_state``: 5 ms a step. Over the
    measured window 1,000 steps updated 96 states each (8 of 16 slots decode
    in 12 lines): 4 MiB a pair at 819 GB/s is 0.49 ms."""
    paths = [STEP + "attn/linear_state/dynamic_slice",
             STEP + "attn/linear_attn/delta_rule/reduce",
             STEP + "attn/linear_attn/dot_general",
             STEP + "moe_experts/pallas_call",
             STEP + "attn/linear_attn/delta_rule/reduce",
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
                         {"steps": 4, "slots": 8})
            for t in (0.49, 0.99, 1.03, 1.49)]
    # The polls round the traced span say 192 pairs a step (a burst's counts
    # land a burst after its steps): the window's first and last are read.
    polls = [(-4.0, {"linear_state_updates": 0, "decode_steps": 0}),
             (0.1, {"linear_state_updates": 1000, "decode_steps": 10}),
             (0.9, {"linear_state_updates": 9000, "decode_steps": 100}),
             (2.1, {"linear_state_updates": 28200, "decode_steps": 200}),
             (49.9, {"linear_state_updates": 97000, "decode_steps": 1010}),
             (51.5, {"linear_state_updates": 99000, "decode_steps": 1020})]
    obs = {"trace": trace, "device_ops": dev, "phases": disp,
           "trace_span": (1.0, 2.0), "polls": polls, "t_open": 0.0,
           "t_close": 50.0,
           "cell": {"config": config, "traffic": {"use": "serve_longctx"}},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    spec = _spec("linear_step_ms_per_step")
    assert scope_ms_per_count.read(obs, spec["params"]) == pytest.approx(5.0)
    roof = _spec("delta_rule_step_roofline")
    least_ms = 96 * 4 * 2 ** 20 / 819e9 * 1e3
    assert least_ms == pytest.approx(0.4916, abs=1e-3)
    assert delta_rule_roofline.read(obs, roof["params"]) == pytest.approx(
        100 * least_ms / 5.0)
    # every slot decoding in every step and the rule at the bandwidth
    # (a burst's two operations are its four steps' time): 100
    full = [(t, {**s, "linear_state_updates": 192 * s["decode_steps"]})
            for t, s in polls]
    for op in dev.ops:
        op.self_s = 2 * 192 * 4 * 2 ** 20 / 819e9
    assert delta_rule_roofline.read({**obs, "polls": full},
                                    roof["params"]) == pytest.approx(100.0)
    # the parent commit's stats() lack the counter
    old = [(t, {"decode_steps": s["decode_steps"]}) for t, s in polls]
    assert delta_rule_roofline.read({**obs, "polls": old},
                                    roof["params"]) is None


def test_linear_state_update_share_is_decoding_slots_over_slots():
    spec = _spec("linear_state_update_share")
    polls = [(1.0, {"linear_state_updates": 0, "decode_steps": 0,
                    "slots": 16}),
             (2.0, {"linear_state_updates": 12 * 12 * 50, "decode_steps": 50,
                    "slots": 16})]
    obs = {"polls": polls, "t_open": 0.5, "t_close": 2.5}
    # 12 of 16 slots decode in every one of 50 steps, 12 linear lines each
    assert counter_ratio.read(obs, spec["params"]) == pytest.approx(75.0)
    old = [(t, {k: v for k, v in s.items() if k != "linear_state_updates"})
           for t, s in polls]
    assert counter_ratio.read({**obs, "polls": old}, spec["params"]) is None


# -------------------------------------------------------------- the control

def tiny(config):
    c = dict(config)
    c.update(hidden_size=256, moe_intermediate_size=64,
             shared_expert_intermediate_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, linear_num_key_heads=2,
             linear_num_value_heads=4, linear_key_head_dim=32,
             linear_value_head_dim=32, num_hidden_layers=8,
             vocab_size=2048, torch_dtype="float32")
    return c


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_the_fp8_control_comes_out_as_not_correct(config, traffic, seed):
    """benchmark/control.py at a size a test run can hold: the reference on
    weights rounded through fp8 chooses tokens that lie further under the
    float32 reference's top logit than the cell allows, and further than
    the stated precision (bfloat16 weights) does. The readings at the
    cell's own size are PERF.md's (section 4)."""
    from reference import qwen3_next as reference

    from ray_tpu.llm import engine

    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(BENCH, "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    limit = traffic["check"]["margin"]

    c = tiny(config)
    cfg = qwen3_next.model_config(c, "serve_longctx", 256)
    weights = qwen3_next.reference_weights(
        engine.init_params(cfg, jax.random.PRNGKey(seed)))
    # the published order: a key head's q | k | v | z side by side
    assert weights["layers"]["qkvz"].shape == (6, 256, 2 * (32 + 32 + 128))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (256,), 0, 2048)
    want = reference.logits(c, weights, tokens)
    fp8 = control.margin(
        want, reference.logits(c, control.to_fp8(weights), tokens), 32)
    bf16 = control.margin(want, reference.logits(c, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), weights), tokens), 32)
    assert fp8 > limit
    assert bf16 < fp8
