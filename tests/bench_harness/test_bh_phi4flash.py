"""The Phi-4-mini-flash cell's files: its configuration against the
published one (every key kept, nothing reduced), its adapter's arithmetic
against hand-worked values, its plan pinned, its own entries in the manifest
(never the number of cells, never which cell is last, and the cell's metric
set held with ``<=``), each new reader on a made-up trace that takes exactly
the yardstick's time, the decode kernel's roofline under 100 on one, and the
control at a small size."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, REPO
from rtbench import gen, manifest, trace_reduce as tr
from rtbench.adapters import phi4flash
from rtbench.readers import (
    counter_ratio,
    decode_attention_roofline,
    phases,
    scope_ms_per,
    scope_ms_per_count,
    scope_share,
    ssm_scan_roofline,
)

CELL = "phi4-mini-flash-serve-reason-12k"
CONFIG = "phi-4-mini-flash-reasoning"
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
          "main/config.json")

# The catalog row's ``config`` (the URL above), as published.
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
SSM = ("State-space layers (models/phi4flash.py scan operator, "
       "ops/selective_scan.py selective_scan_chunk, selective_scan_step)")
DHD = ("Decoder-hybrid-decoder (models/phi4flash.py window and cross "
       "attention, gated memory unit; llm/phi4flash_serving.py)")
MINE = {"part_share_ssm.tok_s": SSM, "part_share_window_attn.tok_s": DHD,
        "part_share_cross_attn.tok_s": DHD, "part_share_gmu.tok_s": DHD,
        "ssm_scan_ms_per_ktok": SSM, "ssm_step_ms_per_step": SSM,
        "ssm_scan_chunk_roofline": SSM, "ssm_scan_step_roofline": SSM,
        "cross_decoder_skip_share": DHD}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# A made-up device's operations (10 ms each, one after the other from 1.0 s
# on, each with a name-stack path) and a made-up trace, as the Qwen3-Next
# cell's tests build them.
from test_bh_qwen3_next import _scoped, _trace  # noqa: E402


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "serve-reason-12k.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- the files

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_key_is_kept(config, key):
    assert config[key] == PUBLISHED[key]
    assert type(config[key]) is type(PUBLISHED[key])


def test_nothing_is_reduced_and_the_file_says_what_it_assumed(config):
    entry = manifest.config_entry(manifest.load(REPO), CONFIG)
    assert entry["reduced"] == [] and config["reduced"] == {}
    assert entry["source"] == config["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["adapter"] == "phi4flash"
    for key in ("equations", "sizes", "norms", "layer", "scan", "memory_unit",
                "attention", "window", "positions", "init"):
        assert config["assumed"][key], key
    for key in ("state_dtype", "state_layout", "packed_pairs",
                "fused_projections", "layer_skip", "dropout"):
        assert config["departures"][key], key
    assert "bit for bit" in config["guarantees"]
    assert "whole model" in config["deployment"]
    # the scan's sizes are the family's defaults, written into the file
    assert {k: config[k] for k in phi4flash.SSM_DEFAULTS} == \
        phi4flash.SSM_DEFAULTS
    assert config["mamba_dt_rank"] == "auto"
    # the compiler's figures beside the arithmetic
    assert "memory_analysis" in config["memory"]


def test_the_cell_s_own_entries_are_what_the_issue_names(traffic):
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    cell = manifest.load_cell(CELL, REPO)
    assert cell["workload"] == {**cell["workload"], "config": CONFIG,
                                "traffic": "serve-reason-12k", "chips": 1}
    why = cell["workload"]["why"]
    for said in ("64 slots x 12,288", "8 times", "skips 14 of 32 layers",
                 "nothing cut"):
        assert said in why
    assert [x["name"] for x in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    names = {x["name"] for x in cell["per_layer"]}
    # this cell's own, and what the issue names of ``mistral7b-serve-
    # reason``'s; held with ``<=``: a later PR may append the cell to a
    # metric of its own
    assert set(MINE) <= names
    assert {"decode_ms_per_step.tok_s", "decode_attention_roofline.tok_s",
            "decode_kv_read_share.tok_s", "prefill_kv_read_share.tok_s",
            "prefill_ms_per_ktok.counted", "tpot_p90_ms.tok_s",
            "part_share_attn.tok_s", "part_share_mlp.tok_s",
            "part_share_head.tok_s", "part_share_lowering.tok_s",
            "part_share_unnamed.tok_s", "device_idle_share.tok_s",
            "slots_active_share", "decode_slot_use_share.tok_s",
            "decode_ahead_share.tok_s", "idle_in_scheduler_share.tok_s",
            "admit_to_first_token_mean_ms.tok_s"} <= names
    # it reads wrongly where prefill takes a large share
    # (test_bh_deepseek.py's own comment)
    assert "decode_bw_share.tok_s" not in names
    for x in cell["per_layer"]:
        if x["name"] in MINE:
            # (``in``, not ``==``: a later cell may be appended)
            assert CELL in x["workloads"] and x["moves"] == "serve_tok_s"
            assert x["layer"] == MINE[x["name"]]
    readers = {x["name"]: (x["reader"], x["params"])
               for x in cell["per_layer"] if x["name"] in MINE}
    decode = ["jit_decode_burst", "jit_decode_step"]
    assert readers["part_share_ssm.tok_s"] == (
        "scope_share", {"scopes": ["ssm", "ssm_scan", "ssm_state"]})
    for part in ("window_attn", "cross_attn", "gmu"):
        assert readers[f"part_share_{part}.tok_s"] == (
            "scope_share", {"scopes": [part]})
    chunk = {"scopes": ["ssm_scan"], "programs": ["jit_prefill_chunk"],
             "phase": "engine.prefill_dispatch", "count": "tokens"}
    step = {"scopes": ["ssm_scan", "ssm_state"], "programs": decode,
            "phase": "engine.decode_dispatch", "count": "steps"}
    assert readers["ssm_scan_ms_per_ktok"] == (
        "scope_ms_per", {**chunk, "per": 1000})
    assert readers["ssm_step_ms_per_step"] == ("scope_ms_per_count", step)
    assert readers["ssm_scan_chunk_roofline"] == (
        "ssm_scan_roofline", {"form": "chunk", **chunk})
    assert readers["ssm_scan_step_roofline"] == (
        "ssm_scan_roofline", {"form": "step", **step})
    assert readers["cross_decoder_skip_share"] == ("counter_ratio", {
        "num": "cross_decoder_chunks_skipped", "den": "prefill_chunks",
        "scale": 100.0})
    assert traffic["kind"] == "closed_loop"
    assert traffic["engine"] == {
        "max_num_seqs": 64, "max_seq_len": 12288, "dtype": "bfloat16",
        "kv_block_size": 0, "max_ongoing_requests": 128}
    assert traffic["clients"] == 64 and traffic["cycle_requests"] == 64
    assert traffic["prompt_tokens"] == {"kind": "lognormal", "median": 4096,
                                        "sigma": 0.6, "min": 1024,
                                        "max": 10240}
    assert traffic["max_tokens"] == {"kind": "uniform", "min": 512,
                                     "max": 1536}
    assert traffic["stagger_s"] == 24
    assert traffic["trace"] == {"after_s": 10, "for_s": 4}
    assert traffic["timeout_s"] == 300
    assert traffic["check"]["requests"] == 4
    assert "control" in traffic["check"]["margin_why"]
    assert traffic["use"] == "serve_reason"
    longctx = manifest.load_json(REPO, "traffic", "serve-longctx-32k.json")
    assert traffic["warmup"] == longctx["warmup"]


PINNED = {   # sha256 of json.dumps(plan, sort_keys=True) at 51 s
    1: "8b98487015e094e00c7d00aa539e71aac29292df62034d64f47eba4cb509ebe2",
    2147483700: "c4e0529a027c92eb94e582179598ac502c963b62228baf4334aadcb96bc897d5",
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_the_plan_is_what_it_was_and_fits_the_line(traffic, seed):
    plan = gen.closed_loop_plan(traffic, seed, 51)
    assert hashlib.sha256(json.dumps(plan, sort_keys=True).encode()
                          ).hexdigest() == PINNED[seed]
    cycle = plan["requests"][:64]
    assert 1024 <= min(r["prompt_tokens"] for r in cycle) < 1200
    assert max(r["prompt_tokens"] for r in cycle) == 10240
    assert all(512 <= r["max_tokens"] <= 1536 for r in cycle)
    longest = max(r["prompt_tokens"] + r["max_tokens"] for r in cycle)
    assert 10240 < longest <= 11776 <= traffic["engine"]["max_seq_len"]
    # a prompt is 2 to 20 chunks of 512, all but the last of which skip the
    # cross-decoder: over a cycle about nine chunks in ten
    chunks = [-(-r["prompt_tokens"] // 512) for r in cycle]
    assert set(chunks) <= set(range(2, 21))
    assert 0.85 < 1 - len(chunks) / sum(chunks) < 0.92
    # every seed sends the same 64 requests, in an order of its own
    other = gen.closed_loop_plan(traffic, seed + 1, 51)["requests"][:64]
    key = lambda r: (r["prompt_tokens"], r["max_tokens"])  # noqa: E731
    assert sorted(map(key, cycle)) == sorted(map(key, other))
    assert [key(r) for r in cycle] != [key(r) for r in other]
    # a client a slot
    assert plan["clients"] == traffic["engine"]["max_num_seqs"] == 64
    assert {w["prompt_tokens"] for w in traffic["warmup"]} >= {
        16, 32, 64, 128, 256, 512}
    # ids come from the whole vocabulary
    ids = gen.prompt_ids(seed, 1000, 4096, 200064)
    assert 259 <= min(ids) and 150000 < max(ids) < 200064


# ----------------------------------------------------------- the arithmetic

def test_the_whole_model_is_3853m_parameters(config):
    """ISSUE 52's count by kind of layer, the catalog row's "3.8B"."""
    assert phi4flash.ssm_params(config) == 41_241_600
    assert phi4flash.attention_params(config) == 19_668_864
    assert phi4flash.gmu_params(config) == 26_214_400
    assert phi4flash.cross_params(config) == 13_112_704
    assert phi4flash.mlp_params(config) == 78_643_200 + 10_240
    assert (phi4flash.ssm_lines(config), phi4flash.window_lines(config),
            phi4flash.cross_lines(config), phi4flash.line_readers(config),
            phi4flash.d_inner(config), phi4flash.dt_rank(config)) == \
        (9, 8, 7, 8, 5120, 160)
    assert phi4flash.params_held(config) == 3_852_562_944 == (
        9 * 119_895_040 + 9 * 98_322_304 + 7 * 104_867_840
        + 7 * 91_766_144 + 512_168_960)


def test_depth_is_the_decode_kernel_s_calls_and_the_program_follows(config):
    assert phi4flash.depth(config, "serve_reason") == 16
    cfg = phi4flash.model_config(config, "serve_reason", 12288)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.sliding_window, cfg.vocab_size, cfg.d_inner, cfg.dt_rank,
            cfg.mamba_d_state, cfg.mamba_d_conv, cfg.max_seq_len,
            cfg.dtype) == (32, 2560, 40, 20, 512, 200064, 5120, 160, 16, 4,
                           12288, "bfloat16")
    assert cfg.num_params() == phi4flash.params_held(config)
    assert cfg.ssm_state_bytes == phi4flash.ssm_state_bytes(config)
    for key, bad in (("hidden_act", "gelu"), ("tie_word_embeddings", False),
                     ("mlp_bias", True), ("mamba_conv_bias", False)):
        with pytest.raises(ValueError, match="tied head"):
            phi4flash.model_config({**config, key: bad}, "serve_reason", 64)


def test_a_cached_position_is_5120_bytes_and_a_state_320_kib(config):
    assert phi4flash.kv_bytes_per_token(config, 16) == 5120
    assert phi4flash.ssm_state_bytes(config) == 327_680
    assert phi4flash.ring_bytes(config) == 512 * 5120
    assert phi4flash.conv_window_bytes(config) == 3 * 5120 * 2
    # 64 slots x 12,288: the line 3.75 GiB, the rings 1.25, the states 0.18
    gib = 2 ** 30
    assert 64 * 12288 * 5120 / gib == 3.75
    assert 8 * 64 * phi4flash.ring_bytes(config) / gib == 1.25
    assert 9 * 64 * 327_680 / gib == pytest.approx(0.176, abs=1e-3)


def test_a_decode_step_reads_the_line_eight_times(config):
    """What a step reads is not what a position occupies: 64 lines of 5,200
    live rows are 1.7 GB of line, read by 8 layers."""
    live = 64 * 5200
    step = phi4flash.decode_step_bytes(config, 16, live)
    weights = 2 * 3_852_562_944
    line = live * 5120 * 8
    rings = 8 * 64 * 512 * 5120
    states = 2 * 9 * 64 * (327_680 + 30_720)
    assert step == weights + line + rings + states
    assert line > weights          # more than its weights
    assert phi4flash.decode_attention_bytes(config, 16, live) == line
    # ``layers`` is the kernel's calls and not a factor of the bytes
    assert phi4flash.decode_attention_bytes(config, 8, live) == line


def test_the_scan_s_yardstick_is_the_recurrence_s_work_and_the_states_bytes(
        config):
    work = phi4flash.ssm_token_work(config)
    assert work == {"flops": 6 * 5120 * 16, "bytes": (4 * 5120 + 32) * 4}
    # bytes bind: 100 ns a token and layer against 2.5 ns of FLOPs
    assert work["bytes"] / 819e9 > 30 * work["flops"] / 197e12
    assert phi4flash.ssm_step_bytes(config, 576) == 576 * 2 * 327_680


# -------------------------------------------------------------- the readers

CHUNK = "jit(prefill_chunk)/stack/while/body/closed_call/"
STEP = "jit(decode_burst)/stack/while/body/closed_call/stack/while/body/" \
       "closed_call/"
PATHS = [CHUNK + "attn/ssm/dot_general",
         CHUNK + "attn/ssm_state/dynamic_slice",
         CHUNK + "attn/ssm/ssm_scan/pallas_call",
         CHUNK + "attn/ssm/ssm_scan/select_n",
         CHUNK + "attn/ssm_state/dynamic_update_slice",
         CHUNK + "attn/window_attn/dot_general",
         CHUNK + "attn/window_attn/cache/dynamic_update_slice",
         CHUNK + "attn/dot_general", CHUNK + "mlp/dot_general",
         "jit(prefill_chunk)/cond/branch_1_fun/stack/while/body/"
         "closed_call/attn/cross_attn/dot_general",
         "jit(prefill_chunk)/cond/branch_1_fun/stack/while/body/"
         "closed_call/attn/gmu/dot_general",
         "jit(prefill_chunk)/cond/branch_1_fun/head/dot_general"]


def _spec(name):
    return manifest.load_json(REPO, "layer_metrics", name + ".json")


def _obs(config, **more):
    return {"cell": {"config": config, "traffic": {"use": "serve_reason"}},
            "peaks": PEAKS, **more}


def test_the_scopes_shares_lie_inside_attn():
    """The partition knows ``attn`` and ``cache`` and books the operators
    there; ``scope_share`` finds the finer names on the same paths. A ring's
    write inside ``window_attn`` is ``cache``'s (a part of the vocabulary is
    innermost)."""
    dev = _scoped(PATHS)
    assert [op.part for op in dev.ops[:6]] == ["attn"] * 6
    assert dev.ops[6].part == "cache"
    obs = {"trace": object(), "device_ops": dev}
    share = lambda name: scope_share.read(  # noqa: E731
        obs, _spec(f"part_share_{name}.tok_s")["params"])
    assert share("ssm") == pytest.approx(100 * 5 / 12)
    assert share("window_attn") == pytest.approx(100 / 12)
    assert share("cross_attn") == pytest.approx(100 / 12)
    assert share("gmu") == pytest.approx(100 / 12)
    # a program without the scopes (the parent commit) gives nothing
    bare = _scoped([CHUNK + "attn/dot_general", CHUNK + "mlp/dot_general"])
    for name in ("ssm", "window_attn", "cross_attn", "gmu"):
        spec = _spec(f"part_share_{name}.tok_s")
        assert scope_share.read({"trace": object(), "device_ops": bare},
                                spec["params"]) is None
        assert scope_share.read({"trace": None}, spec["params"]) is None


def _chunk_obs(config, paths=PATHS):
    modules = [("jit_prefill_chunk(1)", 0.999, 1.02),   # touches the edge
               ("jit_prefill_chunk(1)", 1.02, 1.06),
               ("jit_decode_burst(2)", 1.06, 1.08),
               ("jit_prefill_chunk(1)", 1.08, 1.12)]    # touches the edge
    dev = _scoped(paths, modules)
    trace = _trace(modules, [(op.name, op.start, op.end) for op in dev.ops])
    disp = [phases.Phase("engine.prefill_dispatch", t, t + 0.001,
                         {"tokens": 512, "bucket": 512})
            for t in (0.95, 1.0, 1.07)]
    return _obs(config, trace=trace, device_ops=dev, phases=disp)


def test_ssm_scan_ms_per_ktok_and_the_chunk_s_roofline(config):
    """The one whole chunk away from the edges holds two operations of the
    scan (those that start at 1.02 and 1.03): 20 ms over 512 tokens, where
    the yardstick wants 9 layers x 100.2 ns a token."""
    obs = _chunk_obs(config)
    spec = _spec("ssm_scan_ms_per_ktok")
    assert scope_ms_per.read(obs, spec["params"]) == pytest.approx(
        20.0 / 512 * 1000)
    roof = _spec("ssm_scan_chunk_roofline")
    least_ms = 9 * (4 * 5120 + 32) * 4 / 819e9 * 1e3
    assert least_ms * 1e6 / 9 == pytest.approx(100.2, abs=0.1)
    assert ssm_scan_roofline.read(obs, roof["params"]) == pytest.approx(
        100 * least_ms / (20.0 / 512))
    # a trace whose scan takes exactly the yardstick's time reads 100
    exact = _chunk_obs(config)
    for op in exact["device_ops"].ops:
        op.self_s = least_ms * 512 / 2 / 1e3
    assert ssm_scan_roofline.read(exact, roof["params"]) == \
        pytest.approx(100.0)
    # the parent commit: no scope, no metric
    bare = _chunk_obs(config, [CHUNK + "attn/dot_general"] * 12)
    assert ssm_scan_roofline.read(bare, roof["params"]) is None
    assert scope_ms_per.read(bare, spec["params"]) is None
    names = manifest.module_names(os.path.join(
        BENCH, "rtbench", "readers", "ssm_scan_roofline.py"))
    assert set(names["ADAPTER_NEEDS"]) <= set(manifest.module_names(
        os.path.join(BENCH, "rtbench", "adapters", "phi4flash.py")))


def _step_obs(config):
    paths = [STEP + "attn/ssm_state/dynamic_slice",
             STEP + "attn/ssm/ssm_scan/reduce",
             STEP + "attn/ssm/dot_general",
             STEP + "attn/window_attn/pallas_call",
             STEP + "attn/ssm/ssm_scan/reduce",
             STEP + "attn/ssm_state/dynamic_update_slice",
             STEP + "attn/cross_attn/pallas_call",
             "jit(decode_burst)/head/dot_general"]
    modules = [("jit_decode_burst(3)", 0.9995, 1.0395),
               ("jit_decode_burst(3)", 1.0396, 1.0795)]
    dev = _scoped(paths, modules)
    trace = _trace([("jit_decode_burst(3)", 0.5, 0.6)] + modules
                   + [("jit_decode_burst(3)", 1.5, 1.6)],
                   [(op.name, op.start, op.end) for op in dev.ops])
    disp = [phases.Phase("engine.decode_dispatch", t, t + 0.001,
                         {"steps": 4, "slots": 32})
            for t in (0.49, 0.99, 1.03, 1.49)]
    # The polls round the traced span say 576 pairs a step (a burst's counts
    # land a burst after its steps): the window's first and last are read.
    polls = [(-4.0, {"ssm_state_updates": 0, "decode_steps": 0}),
             (0.1, {"ssm_state_updates": 2880, "decode_steps": 10}),
             (0.9, {"ssm_state_updates": 28800, "decode_steps": 100}),
             (2.1, {"ssm_state_updates": 86400, "decode_steps": 200}),
             (49.9, {"ssm_state_updates": 290880, "decode_steps": 1010}),
             (51.5, {"ssm_state_updates": 299000, "decode_steps": 1020})]
    return _obs(config, trace=trace, device_ops=dev, phases=disp,
                trace_span=(1.0, 2.0), polls=polls, t_open=0.0, t_close=50.0)


def test_ssm_step_ms_per_step_and_the_step_s_roofline(config):
    """Two bursts of 4 steps inside the trace; in each, two operations of
    10 ms under ``ssm_scan`` or ``ssm_state``: 5 ms a step. Over the
    measured window 1,000 steps updated 288 states each (32 of 64 slots
    decode in 9 lines): 640 KiB a pair at 819 GB/s is 0.23 ms."""
    obs = _step_obs(config)
    spec = _spec("ssm_step_ms_per_step")
    assert scope_ms_per_count.read(obs, spec["params"]) == pytest.approx(5.0)
    roof = _spec("ssm_scan_step_roofline")
    least_ms = 288 * 2 * 327_680 / 819e9 * 1e3
    assert least_ms == pytest.approx(0.2305, abs=1e-3)
    assert ssm_scan_roofline.read(obs, roof["params"]) == pytest.approx(
        100 * least_ms / 5.0)
    # every slot decoding in every step and the step at the bandwidth (a
    # burst's two operations are its four steps' time): 100
    full = [(t, {**s, "ssm_state_updates": 576 * s["decode_steps"]})
            for t, s in obs["polls"]]
    for op in obs["device_ops"].ops:
        op.self_s = 2 * 576 * 2 * 327_680 / 819e9
    assert ssm_scan_roofline.read({**obs, "polls": full},
                                  roof["params"]) == pytest.approx(100.0)
    # the parent commit's stats() lack the counter
    old = [(t, {"decode_steps": s["decode_steps"]}) for t, s in obs["polls"]]
    assert ssm_scan_roofline.read({**obs, "polls": old},
                                  roof["params"]) is None


def test_the_decode_kernel_s_roofline_stays_under_100_at_two_kinds_of_call(
        config):
    """A step calls the decode kernel 16 times: 8 on the full line, which
    take their bytes' time here, and 8 on a ring, short. The reader takes
    the mean event times ``depth``: with ``depth`` 16 that is all the
    kernel's time of a step, and the full line's bytes over it stay under
    100 (with ``depth`` 8 it would be half the time and read 178)."""
    live = 64 * 5200.0                          # positions read a step
    full_s = live * 5120 / 819e9                # one call on the line
    ring_s = 64 * 512 * 5120 / 819e9 / 4        # a ring, partly full
    events, t = [], 1.0
    for _ in range(3):                          # three steps
        for call in range(16):
            d = full_s if call >= 8 else ring_s
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
    assert share == pytest.approx(100 * 8 * full_s / (8 * full_s
                                                       + 8 * ring_s))
    assert 85 < share < 100


def test_the_skip_share_is_chunks_that_ran_the_self_decoder_alone():
    spec = _spec("cross_decoder_skip_share")
    polls = [(1.0, {"cross_decoder_chunks_skipped": 10, "prefill_chunks": 12}),
             (2.0, {"cross_decoder_chunks_skipped": 100,
                    "prefill_chunks": 112})]
    obs = {"polls": polls, "t_open": 0.5, "t_close": 2.5}
    # ten prompts of ten chunks each: nine in ten skip
    assert counter_ratio.read(obs, spec["params"]) == pytest.approx(90.0)
    old = [(t, {"prefill_chunks": s["prefill_chunks"]}) for t, s in polls]
    assert counter_ratio.read({**obs, "polls": old}, spec["params"]) is None


# -------------------------------------------------------------- the control

def tiny(config):
    c = dict(config)
    c.update(hidden_size=256, intermediate_size=512, num_attention_heads=8,
             num_key_value_heads=4, num_hidden_layers=8, sliding_window=32,
             vocab_size=2048, torch_dtype="float32")
    return c


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_the_fp8_control_comes_out_as_not_correct(config, traffic, seed):
    """benchmark/control.py at a size a test run can hold: the reference on
    weights rounded through fp8 chooses tokens that lie further under the
    float32 reference's top logit than the cell allows, and further than
    the stated precision (bfloat16 weights) does. The readings at the
    cell's own size are PERF.md's (section 4)."""
    from reference import phi4flash as reference

    from ray_tpu.llm import engine

    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(BENCH, "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    limit = traffic["check"]["margin"]

    c = tiny(config)
    cfg = phi4flash.model_config(c, "serve_reason", 256)
    weights = phi4flash.reference_weights(
        engine.init_params(cfg, jax.random.PRNGKey(seed)))
    # the published order: A_log [channels, states], the taps [channels, 4]
    assert weights["layers"]["a_log"].shape == (3, 512, 16)
    assert weights["layers"]["conv"].shape == (3, 512, 4)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (256,), 0, 2048)
    want = reference.logits(c, weights, tokens)
    fp8 = control.margin(
        want, reference.logits(c, control.to_fp8(weights), tokens), 32)
    bf16 = control.margin(want, reference.logits(c, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), weights), tokens), 32)
    assert fp8 > limit
    assert bf16 < fp8
