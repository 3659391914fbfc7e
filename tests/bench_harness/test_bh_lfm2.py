"""The LFM2-24B-A2B cell's files: its configuration against the published
one, its adapter's arithmetic against hand-worked values, its plan pinned,
its own entries in the manifest (never the number of cells), each roofline
reader at 100 on a made-up trace that takes exactly the roofline's time,
the two readers this cell brings, and the control at a small size."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, REPO
from rtbench import gen, manifest, trace_reduce as tr, xplane_meta as xm
from rtbench.adapters import lfm2
from rtbench.readers import (
    decode_attention_roofline,
    grouped_matmul_roofline,
    phases,
    scope_ms_per_count,
    scope_share,
)

CELL = "lfm2-24b-serve-extract-8k"
PERIOD = ["full_attention", "conv", "conv", "conv"]

# The catalog row's ``config`` (huggingface.co/LiquidAI/LFM2-24B-A2B/blob/
# main/config.json), as published.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + PERIOD * 9 + ["full_attention", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
REDUCED = {"num_hidden_layers": 10, "layer_types": PUBLISHED["layer_types"][:10]}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "serve-extract-8k.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- the files

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_key_is_kept_or_listed_as_reduced(config, key):
    entry = manifest.config_entry(manifest.load(REPO), "lfm2-24b-a2b")
    if key in REDUCED:
        assert key in entry["reduced"] and key in config["reduced"]
        assert config[key] == REDUCED[key]
        assert config["published"][key] == PUBLISHED[key]
    else:
        assert key not in entry["reduced"]
        assert config[key] == PUBLISHED[key]
        assert type(config[key]) is type(PUBLISHED[key])


def test_the_cut_is_depth_alone_and_keeps_the_floors(config):
    entry = manifest.config_entry(manifest.load(REPO), "lfm2-24b-a2b")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert sorted(config["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    # both leading dense layers, then two whole periods: eight routed
    # layers (floor: one period and four), every expert, the whole
    # vocabulary, every width
    assert config["layer_types"] == ["conv", "conv"] + PERIOD * 2
    assert lfm2.routed_layers(config) == 8 >= 4
    assert (lfm2.conv_lines(config), lfm2.attention_lines(config)) == (8, 2)
    assert config["num_experts"] == 64 and config["vocab_size"] == 65536
    assert "expert_shards" not in config     # nothing divided
    for item in ("layer", "short_convolution", "attention", "router",
                 "tie_word_embeddings", "init", "equations"):
        assert config["assumed"][item]
    assert "modeling_lfm2_moe.py" in config["assumed"]["equations"]
    assert config["tie_word_embeddings"] is True
    assert "nothing of it is approximated" in config["departures"]["none"]
    assert "four pipeline stages" in config["deployment"]


def test_the_cell_s_own_entries_are_what_the_issue_names(traffic):
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    cell = manifest.load_cell(CELL, REPO)
    assert cell["workload"] == {**cell["workload"], "config": "lfm2-24b-a2b",
                                "traffic": "serve-extract-8k", "chips": 1}
    assert "10 of 40 layers" in cell["workload"]["why"]
    assert [x["name"] for x in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    shares = {f"part_share_{g}.tok_s" for g in (
        "attn", "mlp", "head", "lowering", "unnamed", "moe_experts",
        "moe_glue")}
    assert {x["name"] for x in cell["per_layer"]} == shares | {
        "slots_active_share", "device_idle_share.tok_s",
        "idle_in_scheduler_share.tok_s", "admit_to_first_token_mean_ms.tok_s",
        "decode_slot_use_share.tok_s", "decode_ahead_share.tok_s",
        "prefill_ms_per_ktok.counted", "decode_ms_per_step.tok_s",
        "decode_bw_share.tok_s", "decode_kv_read_share.tok_s",
        "prefill_kv_read_share.tok_s", "tpot_p90_ms.tok_s",
        "decode_attention_roofline.tok_s", "moe_grouped_matmul_roofline",
        "moe_experts_touched_share", "moe_ms_per_step",
        "moe_glue_ms_per_step", "part_share_conv.tok_s", "conv_ms_per_step"}
    # the seven shares name every part this cell's programs can have once
    listed = [p for x in cell["per_layer"] if x["name"] in shares
              for p in x["params"]["parts"]]
    assert sorted(listed) == sorted({*xm.PARTS, xm.UNNAMED, xm.LOWERED}
                                    - {"optim"})
    # the two this cell brings: new files over new readers, this cell alone
    for name, reader in (("part_share_conv.tok_s", "scope_share"),
                         ("conv_ms_per_step", "scope_ms_per_count")):
        spec = next(x for x in cell["per_layer"] if x["name"] == name)
        assert spec["reader"] == reader and spec["workloads"] == [CELL]
        assert spec["params"]["scopes"] == ["conv", "conv_state"]
        assert spec["moves"] == "serve_tok_s"
    assert traffic["kind"] == "closed_loop"
    assert traffic["engine"] == {
        "max_num_seqs": traffic["clients"], "max_seq_len": 8192,
        "dtype": "bfloat16", "kv_block_size": 0,
        "max_ongoing_requests": 2 * traffic["clients"]}
    assert traffic["clients"] in (64, 48)      # the one stated fallback
    assert traffic["prompt_tokens"] == {"kind": "lognormal", "median": 3072,
                                        "sigma": 0.5, "min": 1024,
                                        "max": 7168}
    assert traffic["max_tokens"] == {"kind": "uniform", "min": 256,
                                     "max": 768}
    assert traffic["cycle_requests"] == 64 and traffic["stagger_s"] == 15
    assert traffic["trace"] == {"after_s": 10, "for_s": 4}
    assert traffic["check"]["requests"] == 4
    assert traffic["check"]["min_readable"] == 256
    assert "control" in traffic["check"]["margin_why"]


def test_it_is_the_newest_cell_which_the_run_without_a_tpu_tries():
    """``test_bh_manifest.py`` runs the last cell of the manifest with JAX
    held to the CPU and wants a non-zero exit and no result line."""
    assert manifest.load(REPO)["workloads"][-1]["name"] == CELL


PINNED = {   # sha256 of json.dumps(plan, sort_keys=True) at 51 s
    1: "bc41a5a0937f1f61c78c8d7e19e3c0aea9bafd52f068cca89856efdad1d15303",
    2147483700: "357569003aa2418512e1466de403ec2223e7cea3c2c937214c1fe07691f38354",
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_the_plan_is_what_it_was_and_fits_the_line(traffic, seed):
    plan = gen.closed_loop_plan(traffic, seed, 51)
    assert hashlib.sha256(json.dumps(plan, sort_keys=True).encode()
                          ).hexdigest() == PINNED[seed]
    cycle = plan["requests"][:traffic["cycle_requests"]]
    assert min(r["prompt_tokens"] for r in cycle) == 1024
    assert max(r["prompt_tokens"] for r in cycle) == 7168
    assert all(256 <= r["max_tokens"] <= 768 for r in cycle)
    assert max(r["prompt_tokens"] + r["max_tokens"] for r in cycle) \
        <= traffic["engine"]["max_seq_len"]
    # every seed sends the same 64 requests, in an order of its own
    other = gen.closed_loop_plan(traffic, seed + 1, 51)["requests"][:64]
    key = lambda r: (r["prompt_tokens"], r["max_tokens"])  # noqa: E731
    assert sorted(map(key, cycle)) == sorted(map(key, other))
    assert [key(r) for r in cycle] != [key(r) for r in other]
    assert plan["clients"] == traffic["engine"]["max_num_seqs"]
    # a warm-up prompt for every prefill bucket
    assert {w["prompt_tokens"] for w in traffic["warmup"]} >= {
        16, 32, 64, 128, 256, 512}


# ----------------------------------------------------------- the arithmetic

def test_the_cut_is_5267m_parameters_nine_tenths_of_them_experts(config):
    c = config
    assert lfm2.head_dim(c) == 64
    assert lfm2.expert_params(c) == 3 * 2048 * 1536 == 9437184
    assert 64 * lfm2.expert_params(c) * 2 / 2 ** 30 == 1.125
    assert lfm2.conv_params(c) == 2048 * 6144 + 3 * 2048 + 2048 * 2048 \
        == 16783360
    assert lfm2.attention_params(c) == (2 * 2048 * 2048 + 2 * 2048 * 512
                                        + 128) == 10485888
    assert lfm2.dense_ffn_params(c) == 3 * 2048 * 11776 == 72351744
    assert lfm2.router_params(c) == 2048 * 64 + 64
    assert lfm2.params_held(c) == (
        8 * 16783360 + 2 * 10485888 + 2 * 72351744
        + 8 * (131136 + 64 * 9437184) + 21 * 2048 + 134217728) == 5267090176
    assert lfm2.params_held(c) * 2 / 2 ** 30 == pytest.approx(9.811, abs=1e-3)
    # the published 38 routed layers alone would not fit
    assert 38 * 64 * lfm2.expert_params(c) * 2 / 2 ** 30 == 42.75


def test_depth_is_layers_and_the_program_s_configuration_follows(config):
    assert lfm2.depth(config, "serve_extract") == 10
    cfg = lfm2.model_config(config, "serve_extract", 8192)
    assert (cfg.num_layers, cfg.conv_lines, cfg.attention_lines,
            cfg.num_dense_layers, cfg.experts_held) == (10, 8, 2, 2, 64)
    assert (cfg.head_dim, cfg.num_heads, cfg.num_kv_heads) == (64, 32, 8)
    rule = cfg.router_rule
    assert (rule.score, rule.use_bias, rule.renormalize, rule.scaling_factor,
            rule.zero_experts, rule.topk, rule.held) == \
        ("sigmoid", True, True, 1.0, 0, 4, 64)
    assert cfg.tie_embeddings and cfg.max_seq_len == 8192
    assert cfg.num_params() == lfm2.params_held(config)


def test_a_cached_position_is_4_kib_and_the_state_4_mib(config):
    c = config
    assert lfm2.kv_bytes_per_token(c, 10) == 2 * 2 * 8 * 64 * 2 == 4096
    assert 64 * 8192 * lfm2.kv_bytes_per_token(c, 10) == 2 * 2 ** 30
    assert lfm2.conv_state_bytes(c, 64) == 8 * 64 * 2 * 2048 * 2 == 4 * 2 ** 20


def test_a_decode_step_counts_the_experts_64_lines_touch(config):
    c = config
    # 256 picks over 64 experts: 62.9 expected, 98% of them
    assert lfm2.experts_touched_uniform(c, 64) == pytest.approx(
        64 * (1 - (63 / 64) ** 256)) == pytest.approx(62.86, abs=0.01)
    assert lfm2.experts_touched_uniform(c, 1) == pytest.approx(3.907,
                                                               abs=1e-3)
    dense = (8 * 16783360 + 2 * 10485888 + 2 * 72351744 + 134217728)
    base = lfm2.decode_step_bytes(c, 10, 0)
    assert base == pytest.approx(
        2 * (dense + 8 * 62.864238 * 9437184) + 4 * 8 * 131136
        + 2 * 4 * 2 ** 20, rel=1e-6)
    assert lfm2.decode_step_bytes(c, 10, 1000) - base == 1000 * 4096
    # nine tenths of a step's 9.66 GiB are expert weights; 12.7 ms at
    # 819 GB/s, 13.8 with 64 lines of 3,500 live positions
    assert base / 2 ** 30 == pytest.approx(9.661, abs=1e-3)
    assert 2 * 8 * 62.864 * 9437184 / base == pytest.approx(0.915, abs=2e-3)
    assert base / 819e9 == pytest.approx(0.01267, abs=1e-4)
    assert lfm2.decode_step_bytes(c, 10, 64 * 3500) / 819e9 == pytest.approx(
        0.01379, abs=1e-4)


# -------------------------------------------------------------- the readers

def _trace(modules, ops):
    dev = tr.DeviceTrace(0, [tr.Event(n, a, b) for n, a, b in ops], [],
                         [tr.Event(n, a, b) for n, a, b in modules])
    tr._self_times(dev.ops)
    return tr.Trace([dev], {})


def _obs(config, trace, polls, **more):
    cell = {"config": config, "traffic": {"use": "serve_extract"}}
    return {"trace": trace, "trace_span": (1.0, 2.0), "polls": polls,
            "cell": cell, "peaks": {"hbm_bytes_per_s": 819e9,
                                    "bf16_flops_per_s": 197e12}, **more}


def test_decode_attention_roofline_is_100_at_a_call_s_bytes_over_a_call_s_time(
        config):
    """The reader takes ``depth`` (10) for the kernel's calls a step though
    two layers call it; ``decode_attention_bytes`` counts the same factor.
    64 lines of 4,096 fetched positions: a call fetches 64 x 4,096 x 2 KiB
    and, at the roofline, takes that over 819 GB/s."""
    positions = 64 * 4096
    call_s = positions * 2048 / 819e9
    assert lfm2.decode_attention_bytes(config, 10, positions) \
        == 10 * positions * 2048
    polls = [(0.9, {"kv_positions_read": 0, "decode_steps": 0}),
             (2.1, {"kv_positions_read": 10 * positions, "decode_steps": 10})]
    ops = [(f"%decode_attention.{i} = bf16[64,8,16,128] custom-call()",
            1.0 + i * 1e-2, 1.0 + i * 1e-2 + call_s) for i in range(20)]
    obs = _obs(config, _trace([], ops), polls)
    got = decode_attention_roofline.read(obs, {"kernel": "decode_attention"})
    assert got == pytest.approx(100.0)
    # a kernel half as fast reads 50, whatever the depth
    slow = [(n, a, a + 2 * call_s) for n, a, _ in ops]
    assert decode_attention_roofline.read(
        _obs(config, _trace([], slow), polls),
        {"kernel": "decode_attention"}) == pytest.approx(50.0)


def test_grouped_matmul_roofline_is_100_at_the_touched_experts_bytes(config):
    """A decode step of 64 lines: 256 rows on 62.9 experts a routed layer;
    the two calls of a layer-step take, at the roofline, the touched
    experts' weights and the rows over 819 GB/s (bytes bind: 4 rows an
    expert)."""
    work = lfm2.grouped_matmul_work(config, 62.9, 256)
    assert work["bytes"] == 2 * (62.9 * 9437184 + 256 * (4096 + 3072))
    assert work["flops"] == 2 * 256 * 9437184
    assert work["bytes"] / 819e9 > work["flops"] / 197e12
    call_s = work["bytes"] / 819e9 / 2
    polls = [(0.9, {"moe_experts_touched": 0, "moe_picks_local": 0,
                    "moe_layer_steps": 0}),
             (2.1, {"moe_experts_touched": 6290, "moe_picks_local": 25600,
                    "moe_layer_steps": 100})]
    ops = [(f"%moe_grouped_matmul.{i} = bf16[1280,2048] custom-call()",
            1.0 + i * 1e-2, 1.0 + i * 1e-2 + call_s) for i in range(20)]
    obs = _obs(config, _trace([], ops), polls)
    params = manifest.load_json(REPO, "layer_metrics",
                                "moe_grouped_matmul_roofline.json")["params"]
    assert grouped_matmul_roofline.read(obs, params) == pytest.approx(100.0)
    # a prefill chunk's 2,048 rows on all 64 experts: bytes still bind
    chunk = lfm2.grouped_matmul_work(config, 64, 2048)
    assert chunk["bytes"] / 819e9 > chunk["flops"] / 197e12


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


STEP = "jit(decode_burst)/stack/while/body/closed_call/stack/while/body/" \
       "closed_call/"
PATHS = [STEP + "attn/conv/dot_general", STEP + "attn/conv_state/select_n",
         STEP + "attn/conv/mul", STEP + "attn/dot_general",
         STEP + "attn/cache/pallas_call", STEP + "mlp/dot_general",
         STEP + "moe_experts/pallas_call", "jit(decode_burst)/head/dot_general",
         None, "jit(decode_burst)/stack/while/body/closed_call/mul"]


def test_the_convolution_s_share_lies_inside_the_operator_s(config):
    """The partition knows ``attn`` and books the convolution there; the
    new reader finds ``conv`` and ``conv_state`` on the same paths."""
    dev = _scoped(PATHS)
    assert [op.part for op in dev.ops[:5]] == ["attn"] * 4 + ["cache"]
    obs = {"trace": object(), "device_ops": dev}
    spec = manifest.load_json(REPO, "layer_metrics",
                              "part_share_conv.tok_s.json")
    assert scope_share.read(obs, spec["params"]) == pytest.approx(30.0)
    from rtbench.readers import part_share

    attn = part_share.read(obs, {"parts": ["attn", "cache"]})
    assert attn == pytest.approx(50.0)
    # a program without the scopes (a parent commit) gives nothing
    bare = _scoped([p.replace("conv_state/", "").replace("conv/", "")
                    if p else p for p in PATHS])
    assert scope_share.read({"trace": object(), "device_ops": bare},
                            spec["params"]) is None
    assert scope_share.read({"trace": None}, spec["params"]) is None
    # an inner part wins over the scope around it, as in the partition
    assert scope_share.innermost("jit(f)/attn/conv/cache/mul",
                                 {*xm.PARTS, "conv"}) == "cache"
    assert scope_share.innermost("jit(f)/transpose(jvp(conv))/mul",
                                 {"conv"}) == "conv"
    assert scope_share.innermost(None, {"conv"}) is None


def test_conv_ms_per_step_pairs_programs_with_their_dispatches(config):
    spec = manifest.load_json(REPO, "layer_metrics", "conv_ms_per_step.json")
    assert spec["reader"] == "scope_ms_per_count"
    modules = [("jit_decode_burst(1)", 0.999, 1.02),    # touches the edge
               ("jit_decode_burst(1)", 1.02, 1.06),
               ("jit_prefill_chunk(2)", 1.06, 1.08),
               ("jit_decode_burst(1)", 1.08, 1.1)]      # touches the edge
    dev = _scoped(PATHS, modules)
    trace = _trace(modules, [(op.name, op.start, op.end) for op in dev.ops])
    disp = [phases.Phase("engine.decode_dispatch", t, t + 0.001,
                         {"steps": 8, "slots": 64})
            for t in (0.95, 1.0, 1.07)]
    obs = {"trace": trace, "device_ops": dev, "phases": disp}
    # the one whole burst away from the edges holds the operation that
    # starts at 1.02 (attn/conv/mul), 10 ms over its 8 steps
    assert scope_ms_per_count.read(obs, spec["params"]) == pytest.approx(
        10.0 / 8)
    obs["device_ops"] = _scoped([STEP + "attn/dot_general"] * 10, modules)
    assert scope_ms_per_count.read(obs, spec["params"]) is None


def test_the_new_readers_ask_nothing_of_an_adapter():
    for reader in ("scope_share", "scope_ms_per_count"):
        names = manifest.module_names(os.path.join(
            BENCH, "rtbench", "readers", f"{reader}.py"))
        assert "read" in names and "ADAPTER_NEEDS" not in names


# -------------------------------------------------------------- the control

def tiny(config):
    c = dict(config)
    c.update(hidden_size=512, intermediate_size=1024,
             moe_intermediate_size=256, num_attention_heads=8,
             num_key_value_heads=2, num_experts=16, vocab_size=2048,
             torch_dtype="float32")
    return c


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_the_fp8_control_comes_out_as_not_correct(config, traffic, seed):
    """benchmark/control.py at a size a test run can hold: the reference on
    weights rounded through fp8 chooses tokens that lie further under the
    float32 reference's top logit than the cell allows, and further than
    the stated precision (bfloat16 weights) does. The readings at the
    cell's own size are PERF.md's (section 4)."""
    from reference import lfm2 as reference

    from ray_tpu.llm import engine

    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(BENCH, "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    limit = traffic["check"]["margin"]

    c = tiny(config)
    cfg = lfm2.model_config(c, "serve_extract", 128)
    weights = lfm2.reference_weights(
        engine.init_params(cfg, jax.random.PRNGKey(seed)))
    assert "head" not in weights        # tied: rounded once, as the embedding
    tokens = jax.random.randint(jax.random.PRNGKey(9), (96,), 0, 2048)
    want = reference.logits(c, weights, tokens)
    fp8 = control.margin(
        want, reference.logits(c, control.to_fp8(weights), tokens), 24)
    bf16 = control.margin(want, reference.logits(c, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), weights), tokens), 24)
    assert fp8 > limit
    assert bf16 < fp8
