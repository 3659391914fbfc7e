"""FLOPs per token and decode bytes against hand-worked values."""

import json
import os

import pytest

from conftest import BENCH
from rtbench.adapters import llama, mixtral


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_layer_is_218_1m_parameters():
    c = config("mistral-7b-v0.3")
    # q 4096x4096, k and v 4096x1024, o 4096x4096; SwiGLU 3 x 4096x14336
    assert llama.attn_params_per_layer(c) == 16777216 + 2 * 4194304 + 16777216
    assert llama.mlp_params_per_layer(c) == 3 * 58720256
    per_layer = llama.attn_params_per_layer(c) + llama.mlp_params_per_layer(c)
    assert per_layer == 218103808
    assert llama.active_matmul_params(c, 4) == 4 * 218103808 + 4096 * 32768


def test_mistral_train_flops_per_token_at_4_layers_4k():
    c = config("mistral-7b-v0.3")
    matmul = 6 * (4 * 218103808 + 134217728)             # 6.04 GFLOP
    attn = 3 * 2 * 2 * (4097 / 2) * 32 * 128 * 4          # 0.40 GFLOP
    assert llama.train_flops_per_token(c, 4, 4096) == pytest.approx(
        matmul + attn)
    # 14.3k tokens/s/chip would be 46.8% of 197 TFLOP/s
    assert 14300 * (matmul + attn) / 197e12 == pytest.approx(0.468, abs=2e-3)


def test_mistral_decode_bytes():
    c = config("mistral-7b-v0.3")
    assert llama.kv_bytes_per_token(c, 1) == 4096          # 4 KB a layer
    weights = (12 * 218103808 + 134217728) * 2
    assert llama.decode_step_bytes(c, 12, 0) == weights
    assert llama.decode_step_bytes(c, 12, 10000) == \
        weights + 10000 * 12 * 4096


def test_mixtral_counts_two_of_eight_experts():
    c = config("mixtral-8x7b")
    assert mixtral.expert_params(c) == 176160768
    per_layer = 41943040 + 4096 * 8 + 2 * 176160768
    assert mixtral.active_matmul_params(c, 2) == \
        2 * per_layer + 4096 * 32000
    attn = 3 * 2 * 2 * (4097 / 2) * 32 * 128 * 2
    assert mixtral.train_flops_per_token(c, 2, 4096) == pytest.approx(
        6 * (2 * per_layer + 131072000) + attn)


def test_flash_kernel_work_is_compute_bound_at_4k():
    c = config("mistral-7b-v0.3")
    w = llama.flash_kernel_work(c, 4, 4096)
    fwd = 2 * 2 * 4 * 32 * 4096 * (4097 / 2) * 128
    assert w["flash_fwd"]["flops"] == pytest.approx(fwd)
    assert w["flash_bwd"]["flops"] == pytest.approx(2.5 * fwd)
    q = 4 * 32 * 4096 * 128 * 2
    kv = 2 * 4 * 8 * 4096 * 128 * 2
    assert w["flash_fwd"]["bytes"] == 2 * q + kv
    assert fwd / 197e12 > w["flash_fwd"]["bytes"] / 819e9


@pytest.mark.parametrize("name,adapter", [("mistral-7b-v0.3", llama),
                                          ("mixtral-8x7b", mixtral)])
def test_config_files_state_source_cut_and_published_widths(name, adapter):
    c = config(name)
    assert c["source"].startswith("https://huggingface.co/mistralai/")
    assert (c["hidden_size"], c["intermediate_size"], c["head_dim"],
            c["num_attention_heads"], c["num_key_value_heads"]) == \
        (4096, 14336, 128, 32, 8)
    assert c["num_hidden_layers"]["published"] == 32
    assert set(c["reduced"]) == {"num_hidden_layers"}
    for key in ("assumed", "departures", "deployment"):
        assert key in c
    assert adapter.depth(c, "train") >= 2
