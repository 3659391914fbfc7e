"""The Olmo-Hybrid cell's files: its configuration against the published one
(every key kept but the cuts the file lists), its adapter's arithmetic
against hand-worked values at the published widths (the parameter counts to
the unit), its own entries in the manifest (never the number of cells, never
which cell is last), the new roofline reader on a made-up trace with the
rule's work counted by hand, and the run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO
from rtbench import manifest
from rtbench.adapters import olmo_hybrid as adapter
from rtbench.readers import delta_rule_train_roofline, scope_share
from test_bh_qwen3_next import _scoped, _trace  # noqa: E402

CELL = "olmo-hybrid-train-8k"
CONFIG = "olmo-hybrid-7b"
SOURCE = "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# The catalog row's ``config`` (the URL above), as published.
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
CUT = {"num_hidden_layers": {"published": 32, "train": 4},
       "vocab_size": 12544}
LAYER = ("Linear attention, training (models/olmo_hybrid.py Gated DeltaNet, "
         "ops/gated_delta.py gated_delta_chunk_batch and its backward)")
MINE = ("delta_rule_train_roofline", "part_share_delta_rule.train")
LISTS = ("input_wait_share", "train_mfu", "flash_roofline",
         "device_idle_share.train", "part_share_attn.train",
         "part_share_mlp.train", "part_share_head.train",
         "part_share_remat.train", "part_share_optim.train",
         "part_share_lowering.train", "part_share_unnamed.train")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "train-8k.json")) as f:
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
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert sorted(config["reduced"]) == sorted(CUT)
    assert entry["source"] == config["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["adapter"] == "olmo_hybrid"
    assert config["head_dim"] == 128 and config["assumed"]["head_dim"]
    for key in ("equations", "norms", "layer", "attention",
                "linear_attention", "rope", "init"):
        assert config["assumed"][key], key
    for key in ("chunked_rule", "rule_precision", "projections",
                "reference_scan"):
        assert config["departures"][key], key
    for said in ("8 pipeline stages", "stage 0", "eighth",
                 "arXiv:2411.05288"):
        assert said in config["deployment"], said
    # the arithmetic of the cut, and the compiler's figures beside it
    for said in ("88,750,332", "58,990,080", "126,812,160", "215,570,172",
                 "185,809,920", "832,520,436", "7,430,870,688",
                 "928,862,196", "5.19 GiB", "memory_analysis"):
        assert said in config["reduced"]["num_hidden_layers"], said
    for said in ("100,352", "12,544", "48,168,960"):
        assert said in config["reduced"]["vocab_size"], said
    assert "TODO" not in json.dumps(config)


def test_the_cell_s_own_entries_are_what_the_issue_names(traffic):
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    assert manifest.check_modules(m, REPO) == []
    assert len(m["per_layer"]) <= 128
    cell = manifest.load_cell(CELL, REPO)
    assert cell["workload"] == {**cell["workload"], "config": CONFIG,
                                "traffic": "train-8k", "chips": 1}
    assert [x["name"] for x in cell["end_to_end"]] == ["train_tok_s_chip",
                                                       "setup_s"]
    names = {x["name"] for x in cell["per_layer"]}
    assert names == set(MINE) | set(LISTS)
    for x in cell["per_layer"]:
        # (``in``, not ``==``: a later cell may be appended)
        assert CELL in x["workloads"] and x["moves"] == "train_tok_s_chip"
        if x["name"] in MINE:
            assert x["layer"] == LAYER and x["source"] == "device_trace"
    readers = {x["name"]: (x["reader"], x["params"])
               for x in cell["per_layer"] if x["name"] in MINE}
    assert readers["part_share_delta_rule.train"] == (
        "scope_share", {"scopes": ["delta_rule"]})
    assert readers["delta_rule_train_roofline"] == (
        "delta_rule_train_roofline",
        {"scopes": ["delta_rule"], "programs": ["jit__step"]})
    assert traffic == {**traffic, "kind": "train_steps", "seq_len": 8192,
                       "global_batch": 2, "mesh": {"dp": 1},
                       "attn_impl": "flash", "remat": "dots",
                       "untimed_steps": 2, "trace_steps": 4, "use": "train",
                       "optimizer": {"name": "adamw_lowmem", "lr": 0.0003,
                                     "weight_decay": 0.1}}
    dense = manifest.load_json(REPO, "traffic", "train-4k.json")
    assert traffic["seq_len"] * traffic["global_batch"] \
        == dense["seq_len"] * dense["global_batch"] == 16384
    assert traffic["loss_tolerance"] == dense["loss_tolerance"]
    for key in ("why", "loss_tolerance_why"):
        assert len(traffic[key]) > 100 and "TODO" not in traffic[key], key


# ------------------------------------------------------------ the arithmetic

def test_the_parameters_are_the_issue_s_to_the_unit(config):
    c = config
    assert adapter.linear_params(c) == 88_750_332 == (
        2 * 3840 * 2880 + 2 * 3840 * 5760 + 2 * 3840 * 30 + 4 * 11520
        + 30 + 30 + 192 + 5760 * 3840)
    assert adapter.attention_params(c) == 58_990_080 == (
        4 * 3840 * 3840 + 2 * 3840)
    assert adapter.mlp_params(c) == 126_812_160 == 3 * 3840 * 11008
    linear = adapter.linear_params(c) + adapter.mlp_params(c) + 2 * 3840
    full = adapter.attention_params(c) + adapter.mlp_params(c) + 2 * 3840
    assert (linear, full) == (215_570_172, 185_809_920)
    assert 3 * linear + full == 832_520_436
    # whole: 8 periods, the published vocabulary, the final norm
    assert adapter.params_held(c, 32, 100352) == 7_430_870_688 \
        == 8 * 832_520_436 + 2 * 100352 * 3840 + 3840
    assert 2 * 100352 * 3840 == 770_703_360
    # held: one period and an eighth of the vocabulary
    assert adapter.depth(c, "train") == 4
    assert adapter.linear_layers(c, 4) == 3
    assert adapter.linear_layers(c, 32) == 24
    assert adapter.params_held(c, 4) == 928_862_196 \
        == 832_520_436 + 2 * 12544 * 3840 + 3840
    assert 12544 * 8 == 100352
    # at 6 bytes a parameter of state under adamw_lowmem
    assert round(928_862_196 * 6 / 2 ** 30, 2) == 5.19


def test_the_flops_a_token_are_the_multiplied_parameters_and_the_rule(config):
    c = config
    head = 3840 * 12544
    assert head == 48_168_960
    multiplied = (3 * (2 * 3840 * 2880 + 2 * 3840 * 5760 + 2 * 3840 * 30
                       + 5760 * 3840) + 4 * 3840 * 3840
                  + 4 * 126_812_160 + head)
    assert adapter.active_matmul_params(c, 4) == multiplied == 880_512_000
    # the head's share of what a token multiplies with: as in the whole
    # model (385M of 7,045M)
    assert round(100 * head / multiplied, 1) == 5.5
    whole = adapter.active_matmul_params({**c, "vocab_size": 100352}, 32)
    assert round(100 * 3840 * 100352 / whole, 1) == 5.5
    cell = 30 * 96 * 192
    attention = 3 * 2 * 2 * (8192 + 1) / 2 * 30 * 128
    assert adapter.train_flops_per_token(c, 4, 8192) == pytest.approx(
        6 * multiplied + attention + 3 * 22 * cell)
    assert adapter.delta_rule_cell(c) == cell
    # the dense adapter's kernel work at 30 heads of 128
    work = adapter.flash_kernel_work(c, 2, 8192)
    fwd = 2 * 2 * 2 * 30 * 8192 * 8193 / 2 * 128
    assert work["flash_fwd"]["flops"] == pytest.approx(fwd)
    assert work["flash_bwd"]["flops"] == pytest.approx(2.5 * fwd)


def test_the_rule_s_work_is_counted_on_the_work_at_the_published_widths(
        config):
    """Forward 7, backward 15 and the state made again 7: ``29 Dk Dv`` a
    head; bytes: q, k, v, g, beta in and o out forward, those and do in and
    the five gradients out backward, in bfloat16; never a padded width."""
    work = adapter.delta_rule_train_token_work(config)
    assert work["flops"] == 29 * 30 * 96 * 192 == 16_035_840
    operands = 2 * 2880 + 5760 + 2 * 30
    assert work["bytes"] == 2 * ((operands + 5760)
                                 + (2 * operands + 2 * 5760)) == 104_040
    # the bytes bound it: 0.127 us a token and layer against 0.081
    assert work["bytes"] / PEAKS["hbm_bytes_per_s"] \
        > work["flops"] / PEAKS["bf16_flops_per_s"]
    assert adapter.delta_rule_train_token_work(config, 4)["bytes"] \
        == 2 * work["bytes"]


# ------------------------------------------------------------- the readers

STEP = "jit(_step)/jit(main)/stack/while/body/closed_call/"
BWD = "jit(_step)/jit(main)/transpose(jvp(stack))/while/body/"
PATHS = [STEP + "attn/linear_attn/dot_general",
         STEP + "attn/linear_attn/conv/mul",
         STEP + "attn/linear_attn/delta_rule/while/body/dot_general",
         BWD + "checkpoint/rematted_computation/attn/linear_attn/delta_rule/"
               "while/body/dot_general",
         BWD + "transpose(jvp(attn))/linear_attn/delta_rule/while/body/"
               "transpose(jvp(while))/body/dot_general",
         BWD + "transpose(jvp(attn))/linear_attn/delta_rule/while/body/"
               "jvp(while)/body/dot_general",
         BWD + "transpose(jvp(attn))/dot_general",
         STEP + "mlp/dot_general", "jit(_step)/jit(main)/loss/reduce_sum",
         "jit(_step)/jit(main)/optim/mul"]


def _obs(config, traffic, dev, modules):
    # the operations as trace events for the window, the scoped ones from
    # ``device_ops``
    trace = _trace(modules, [(f"%fusion.{i}", op.start, op.end)
                             for i, op in enumerate(dev.ops)])
    return {"kind": "train", "trace": trace, "device_ops": dev,
            "peaks": PEAKS,
            "cell": {"config": config, "traffic": traffic}}


def test_the_rule_s_share_of_the_busy_time_lies_inside_attn(config):
    dev = _scoped(PATHS)
    assert [op.part for op in dev.ops[:7]] == ["attn"] * 7
    spec = manifest.load_json(REPO, "layer_metrics",
                              "part_share_delta_rule.train.json")
    obs = {"trace": object(), "device_ops": dev}
    assert scope_share.read(obs, spec["params"]) == pytest.approx(40.0)
    bare = _scoped([p.replace("delta_rule/", "") for p in PATHS])
    assert scope_share.read({"trace": object(), "device_ops": bare},
                            spec["params"]) is None


def test_the_roofline_reads_whole_steps_and_all_three_passes(config,
                                                             traffic):
    """Two steps of ten operations of 10 ms each, four of them the rule's
    (forward, the recomputed forward, two of the backward): 80 ms spent;
    least: 2 steps x 16,384 tokens x 3 linear layers x 0.127 us. A step cut
    by the trace's edge counts on neither side."""
    spec = manifest.load_json(REPO, "layer_metrics",
                              "delta_rule_train_roofline.json")
    tail = [PATHS[-1]]              # the end of a step the trace cut
    dev = _scoped(tail + PATHS * 2 + PATHS[:5])
    whole = [("jit__step(1)", 1.0099, 1.1101),
             ("jit__step(1)", 1.1102, 1.2101)]
    cut = [("jit__step(1)", 1.2102, 1.26)]
    obs = _obs(config, traffic, dev, whole + cut)
    per_token = 104_040 / 819e9
    want = 100 * 2 * 16384 * 3 * per_token / 0.08
    got = delta_rule_train_roofline.read(obs, spec["params"])
    assert got == pytest.approx(want) and 0 < got < 105
    # the steps are counted from the trace: one whole step, half the tokens
    # and half the time
    obs = _obs(config, traffic, _scoped(tail + PATHS + PATHS[:5]),
               whole[:1] + [("jit__step(1)", 1.1102, 1.16)])
    assert delta_rule_train_roofline.read(obs, spec["params"]) \
        == pytest.approx(want)
    # a program without the scope (the parent commit), a serving trace, no
    # whole step: nothing, and no error
    bare = _scoped(tail + [p.replace("delta_rule/", "") for p in PATHS * 2]
                   + tail)
    assert delta_rule_train_roofline.read(
        _obs(config, traffic, bare, whole), spec["params"]) is None
    assert delta_rule_train_roofline.read(
        {**_obs(config, traffic, dev, whole), "kind": "serve"},
        spec["params"]) is None
    assert delta_rule_train_roofline.read(
        _obs(config, traffic, dev, cut), spec["params"]) is None
    assert delta_rule_train_roofline.read({"kind": "train"},
                                          spec["params"]) is None


def test_the_adapter_names_what_the_kind_and_the_readers_call():
    have = manifest.module_names(os.path.join(
        BENCH, "rtbench", "adapters", "olmo_hybrid.py"))
    for name in ("REFERENCE", "depth", "model_config", "reference_weights",
                 "train_step", "train_flops_per_token", "flash_kernel_work",
                 "delta_rule_train_token_work", "linear_layers"):
        assert name in have, name
    assert have["REFERENCE"] == "reference.olmo_hybrid"
    ref = manifest.module_names(os.path.join(BENCH, "reference",
                                             "olmo_hybrid.py"))
    assert {"loss", "logits", "loss_array", "delta_rule"} <= set(ref)


def test_without_a_tpu_the_cell_exits_non_zero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
