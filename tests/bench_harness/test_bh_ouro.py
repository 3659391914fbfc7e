"""The Ouro-2.6B cell's files: its configuration against the published one,
its adapter's arithmetic against hand-worked values, its plan pinned, the
manifest clean with the new entries, the readers that are there on made-up
observations of a model with more cache lines than layers, and the control
at a small size."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, REPO
from rtbench import gen, manifest, trace_reduce as tr
from rtbench.adapters import ouro
from rtbench.readers import counter_ratio, decode_attention_roofline

CELL = "ouro2.6b-serve-solve"

# The catalog row's ``config`` (huggingface.co/ByteDance/Ouro-2.6B/blob/main/
# config.json), as published.
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "serve-solve.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- the files

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_key_is_kept_unchanged(config, key):
    assert config[key] == PUBLISHED[key]
    assert type(config[key]) is type(PUBLISHED[key])


def test_nothing_is_reduced_and_what_is_assumed_is_said(config):
    entry = manifest.config_entry(manifest.load(REPO), "ouro-2.6b")
    assert entry["reduced"] == [] and config["reduced"] == {}
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    for item in ("norm_layout", "loop_norm", "exit_gate", "no_biases",
                 "cache_index"):
        assert "modeling_ouro.py" in config["assumed"][item]
    assert "NOT taken" in config["departures"]["none"]
    assert "One v5e chip holding the whole model" in config["deployment"]


def test_the_manifest_is_clean_and_the_cell_is_what_the_issue_names(traffic):
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    cell = manifest.load_cell(CELL, REPO)
    assert cell["workload"] == {**cell["workload"], "config": "ouro-2.6b",
                                "traffic": "serve-solve", "chips": 1}
    assert [x["name"] for x in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    assert {x["name"] for x in cell["per_layer"]} == {
        "slots_active_share", "device_idle_share.tok_s",
        "admit_to_first_token_mean_ms.tok_s", "decode_slot_use_share.tok_s",
        "prefill_ms_per_ktok.counted", "idle_in_scheduler_share.tok_s",
        "decode_kv_read_share.tok_s", "decode_ms_per_step.tok_s",
        "decode_bw_share.tok_s", "decode_attention_roofline.tok_s",
        "tpot_p90_ms.tok_s", "prefill_kv_read_share.tok_s",
        "decode_ahead_share.tok_s", "loop_steps_per_token"}
    assert traffic["kind"] == "closed_loop"
    assert traffic["engine"] == {
        "max_num_seqs": traffic["clients"], "max_seq_len": 768,
        "dtype": "bfloat16", "kv_block_size": 0,
        "max_ongoing_requests": 16}
    assert traffic["clients"] in (8, 7, 6)     # the one stated fallback
    assert traffic["prompt_tokens"] == {"kind": "lognormal", "median": 160,
                                        "sigma": 0.5, "min": 64, "max": 384}
    assert traffic["max_tokens"] == {"kind": "uniform", "min": 128,
                                     "max": 384}
    assert traffic["cycle_requests"] == 16 and traffic["stagger_s"] == 10
    assert traffic["check"]["requests"] == 4
    assert traffic["check"]["min_readable"] == 128


PINNED = {   # sha256 of json.dumps(plan, sort_keys=True) at 51 s
    1: "d650ef9c8149034ed9630251c1f181af52c47e785c5af9685e749760df2420f2",
    2147483700:
        "95b2b32dfa9b1869efd853c9c10dfbfa99aec6cc32e1fd5878963724dcd3d0e7",
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_the_plan_is_what_it_was_and_fits_the_line(traffic, seed):
    plan = gen.closed_loop_plan(traffic, seed, 51)
    assert hashlib.sha256(json.dumps(plan, sort_keys=True).encode()
                          ).hexdigest() == PINNED[seed]
    cycle = plan["requests"][:traffic["cycle_requests"]]
    assert min(r["prompt_tokens"] for r in cycle) == 64
    assert max(r["prompt_tokens"] for r in cycle) == 384
    assert all(128 <= r["max_tokens"] <= 384 for r in cycle)
    assert max(r["prompt_tokens"] + r["max_tokens"] for r in cycle) \
        <= traffic["engine"]["max_seq_len"]
    assert plan["clients"] == traffic["engine"]["max_num_seqs"]
    # a warm-up prompt for every bucket a prompt of 64 to 384 reaches
    assert {w["prompt_tokens"] for w in traffic["warmup"]} >= {64, 128, 256,
                                                               384}


# ----------------------------------------------------------- the arithmetic

def test_the_whole_model_is_2668m_parameters_and_nothing_is_cut(config):
    c = config
    assert ouro.attn_params_per_layer(c) == 4 * 2048 * 2048 == 16777216
    assert ouro.mlp_params_per_layer(c) == 3 * 2048 * 5632 == 34603008
    assert ouro.matmul_params_per_layer(c) == 51380224
    # 48 x (51.38M + four norms) + embedding and head + final norm + gate
    assert ouro.params_held(c) == (48 * (51380224 + 8192) + 2 * 100663296
                                   + 2048 + 2049) == 2667974657
    assert ouro.params_held(c) * 2 / 2 ** 30 == pytest.approx(4.969,
                                                              abs=1e-3)


def test_depth_is_layer_applications_a_token(config):
    assert ouro.depth(config, "serve_solve") == 4 * 48 == 192
    cfg = ouro.model_config(config, "serve_solve", 768)
    assert (cfg.num_layers, cfg.total_ut_steps, cfg.cache_lines) == (48, 4,
                                                                     192)
    assert cfg.early_exit_threshold == 1.0 and cfg.max_seq_len == 768


def test_a_cached_position_is_a_mebibyte_and_a_half(config):
    c = config
    assert ouro.kv_bytes_per_token(c, 1) == 2 * 16 * 128 * 2 == 8192
    assert ouro.kv_bytes_per_token(c, 192) == 1.5 * 2 ** 20
    assert 8 * 768 * ouro.kv_bytes_per_token(c, 192) == 9 * 2 ** 30
    assert ouro.decode_attention_bytes(c, 192, 1000) == 1000 * 1572864


def test_a_decode_step_reads_the_layers_once_a_pass_and_the_head_once(
        config):
    c = config
    assert ouro.decode_step_bytes(c, 192, 0) \
        == 2 * (192 * 51380224 + 100663296) == 19931332608
    assert ouro.decode_step_bytes(c, 192, 3600) \
        - ouro.decode_step_bytes(c, 192, 0) == 3600 * 1572864
    # 24.3 ms at 819 GB/s before any cached position
    assert ouro.decode_step_bytes(c, 192, 0) / 819e9 == pytest.approx(
        0.02434, abs=1e-5)


# -------------------------------------------------------------- the readers

def _trace(ops):
    dev = tr.DeviceTrace(0, [tr.Event(n, a, b) for n, a, b in ops], [], [])
    tr._self_times(dev.ops)
    return tr.Trace([dev], {})


def test_the_roofline_reader_multiplies_by_calls_a_step_not_by_layers(
        config):
    """8 lines of 384 fetched positions a step; the kernel runs once a
    (pass, layer), 192 calls a step of 100 us each."""
    positions = 8 * 384
    polls = [(0.9, {"kv_positions_read": 0, "decode_steps": 0}),
             (2.1, {"kv_positions_read": 10 * positions, "decode_steps": 10})]
    ops = [(f"%decode_attention.{i} = bf16[8,16,16,128] custom-call()",
            1.0 + i * 1e-3, 1.0 + i * 1e-3 + 100e-6) for i in range(40)]
    obs = {"trace": _trace(ops), "trace_span": (1.0, 2.0), "polls": polls,
           "cell": {"config": config, "traffic": {"use": "serve_solve"}},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    got = decode_attention_roofline.read(obs, {"kernel": "decode_attention"})
    least = positions * 1572864 / 819e9
    assert got == pytest.approx(100 * least / (192 * 100e-6))
    assert 30 < got < 31


def test_loop_steps_per_token_reads_the_programs_counters():
    spec = manifest.load_json(REPO, "layer_metrics",
                              "loop_steps_per_token.json")
    assert spec["reader"] == "counter_ratio"
    obs = {"t_open": 1.0, "t_close": 3.0, "polls": [
        (1.1, {"loop_tokens": 100, "loop_exit_steps": 400}),
        (2.9, {"loop_tokens": 600, "loop_exit_steps": 2400})]}
    assert counter_ratio.read(obs, spec["params"]) == 4.0
    # a program without the counters (a parent commit) gives nothing
    obs["polls"] = [(1.1, {"decode_steps": 1}), (2.9, {"decode_steps": 9})]
    assert counter_ratio.read(obs, spec["params"]) is None


# -------------------------------------------------------------- the control

def tiny(config):
    c = dict(config)
    c.update(hidden_size=64, intermediate_size=176, num_attention_heads=4,
             num_key_value_heads=4, head_dim=16, vocab_size=512,
             num_hidden_layers=16, torch_dtype="float32")
    return c


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_the_fp8_control_comes_out_as_not_correct(config, traffic, seed):
    """benchmark/control.py at a size a test run can hold, as
    test_bh_reference.py keeps it for the dense cells: the reference on
    weights rounded through fp8 chooses tokens that lie further under the
    float32 reference's top logit than the cell allows, and further than
    the stated precision (bfloat16 weights) does: 0.61 to 2.08 against
    0.00 to 0.06 at 16 layers, 64 applications (at 3 layers one seed reads
    0.23). The readings at the cell's own size are PERF.md's (section 4)."""
    from reference import ouro as reference

    from ray_tpu.llm import engine

    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(BENCH, "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    limit = traffic["check"]["margin"]

    c = tiny(config)
    cfg = ouro.model_config(c, "serve_solve", 128)
    weights = ouro.reference_weights(
        engine.init_params(cfg, jax.random.PRNGKey(seed)))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (96,), 0, 512)
    want = reference.logits(c, weights, tokens)
    fp8 = control.margin(
        want, reference.logits(c, control.to_fp8(weights), tokens), 24)
    bf16 = control.margin(want, reference.logits(c, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), weights), tokens), 24)
    assert fp8 > limit
    assert bf16 < fp8
