"""Adapter for Ling-3.0 configurations (the language model of
``inclusionAI/Ling-3.0-flash-VL``), which run through the program's
``LingConfig``, ``models/ling.py``, ``models/mla.py``, ``models/routed.py``,
``ops/gated_delta.py``, ``llm/ling_serving.py`` and the one
``llm/engine.py``.

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the two functions at the bottom are the only ones that touch
it. All of it is of **this chip's share**: the configuration file's
``num_experts`` is the number of experts held (one of the router's
``n_group`` groups; the router keeps its ``published.num_experts``
outputs), its ``vocab_size`` the rows of the vocabulary held, its
``num_hidden_layers`` the layers run here. Layer ``l`` is a gated latent
attention where ``(l + 1) % layer_group_size == 0`` and Kimi Delta Attention
(KDA) otherwise; the first ``first_k_dense_replace`` layers' feed-forward is
a dense SwiGLU, every later one routed beside one shared expert.

**The delta rule's yardstick is defined on the work.** A token's decay is a
number a head *and key channel* (``heads x head_dim`` float32 numbers a
layer, 4,096, where Qwen3-Next's is a number a head, 32): it is counted
among the bytes a token brings (``delta_rule_token_work``). The state is the
same ``heads x D x D`` float32 (2 MiB), read once and written once a step
(``linear_step_bytes``).

**``depth`` and the latent kernel's roofline.** ``depth`` is layers, 12.
Only ``latent_lines`` of them (2) have a cache line and call the latent
decode kernel: ``attention_calls_per_step`` says so, and
``decode_attention_bytes`` and ``decode_attention_flops`` count those calls
(``latent_attention_roofline`` takes the calls from the adapter on both
sides, so no layer is counted that has no line).

What the four points of ``adapters/__init__.py`` needed: nothing new. The
cache is a dict of three leaves (``latent``, ``state``, ``conv``) and
dropping the name frees them all; ``stats()`` carries the router's counters
(``moe_*``), this model's own (``linear_state_updates``,
``linear_chunk_tokens``) and the constants ``moe_experts_held``,
``latent_lines``, ``linear_lines``, ``linear_state_bytes``.
"""

from __future__ import annotations

REFERENCE = "reference.ling"


def depth(config: dict, use: str) -> int:
    """Layers run here. Nothing depends on the use."""
    return int(config["num_hidden_layers"])


def latent_lines(c: dict) -> int:
    return c["num_hidden_layers"] // c["layer_group_size"]


def linear_lines(c: dict) -> int:
    return c["num_hidden_layers"] - latent_lines(c)


def routed_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def linear_dim(c: dict) -> int:
    """All KDA heads' keys (or values) side by side: 32 x 128."""
    return c["num_attention_heads"] * c["head_dim"]


def conv_dim(c: dict) -> int:
    """Channels of the convolutions: all heads' q, k and v."""
    return 3 * linear_dim(c)


def latent_dim(c: dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def attention_calls_per_step(c: dict, layers: int) -> int:
    """One latent attention a group of ``layer_group_size`` layers."""
    return latent_lines(c)


def kda_params(c: dict) -> int:
    """One KDA layer: q, k, v, the decay's f (one full matrix: no_kda_lora),
    the gate's z and o (hidden x 4,096 each way), beta (hidden x heads),
    the three convolutions' taps, dt_bias a channel, A_log a head, the
    output norm a head's width: 63,049,888 at the published widths."""
    h, ld, heads = c["hidden_size"], linear_dim(c), c["num_attention_heads"]
    return (6 * h * ld + h * heads + conv_dim(c) * c["short_conv_kernel_size"]
            + ld + heads + c["head_dim"])


def latent_params(c: dict) -> int:
    """One gated latent attention: q (no low-rank pair), kv_a and its norm,
    kv_b, the head-wise gate, o: 31,965,696 at the published widths."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    return (h * nh * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
            + h * latent_dim(c) + c["kv_lora_rank"]
            + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"]
                                        + c["v_head_dim"])
            + h * nh + nh * c["v_head_dim"] * h)


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_shared_expert_intermediate_size"]


def router_outputs(c: dict) -> int:
    return c["published"]["num_experts"]


def router_params(c: dict) -> int:
    """The router's matrix and its selection bias."""
    return (c["hidden_size"] + 1) * router_outputs(c)


def params_held(c: dict) -> int:
    """Every parameter this chip holds: the mixers, the dense SwiGLUs, of
    each routed layer the router, the shared expert and the held experts,
    two norms a layer, the final norm, the embedding and the untied head
    over the held vocabulary."""
    h = c["hidden_size"]
    return (linear_lines(c) * kda_params(c)
            + latent_lines(c) * latent_params(c)
            + c["first_k_dense_replace"] * dense_ffn_params(c)
            + routed_layers(c) * (router_params(c) + shared_params(c)
                                  + c["num_experts"] * expert_params(c))
            + c["num_hidden_layers"] * 2 * h + 2 * h * c["vocab_size"] + h)


def kv_bytes_per_token(c: dict, layers: int, dtype_bytes: int = 2) -> int:
    """One cached position: a latent row (512 + 64 values) in each latent
    line (2 of the 12 layers: 2,304 bytes). ``layers`` is not used: the
    lines are counted from ``layer_group_size``."""
    return latent_dim(c) * dtype_bytes * latent_lines(c)


def linear_state_bytes(c: dict) -> int:
    """One slot's state in one KDA layer: a float32 matrix of D x D a head
    (32 x 128 x 128 x 4 = 2 MiB)."""
    return c["num_attention_heads"] * c["head_dim"] ** 2 * 4


def conv_window_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """One slot's convolution window in one KDA layer."""
    return (c["short_conv_kernel_size"] - 1) * conv_dim(c) * dtype_bytes


def experts_touched_grouped(c: dict, tokens: float) -> float:
    """Of the held experts (one group), how many a layer-step of ``tokens``
    tokens is expected to touch if groups were kept and picks fell
    uniformly: an expert here is picked by a token with probability per_tok
    / outputs, as under an ungrouped rule; held x (1 - (1 - per_tok /
    outputs)^tokens)."""
    p = c["num_experts_per_tok"] / router_outputs(c)
    return c["num_experts"] * (1 - (1 - p) ** tokens)


def decode_step_bytes(c: dict, layers: int, live_kv_tokens: float,
                      dtype_bytes: int = 2, slots: int = 96) -> float:
    """Bytes one decode step must read: every mixer's, dense SwiGLU's and
    shared expert's weights once for the whole batch, the head, of each
    routed layer the held experts a step is expected to touch
    (``experts_touched_grouped`` at ``slots`` tokens a step: 49.9 of 64 at
    96), the latent rows live in the batch in the two latent lines, and
    every slot's state and window in the KDA lines, read and written. The
    router's float32 weights count at their 4 bytes; norms are left out."""
    experts = experts_touched_grouped(c, slots) * expert_params(c)
    dense = (linear_lines(c) * kda_params(c)
             + latent_lines(c) * latent_params(c)
             + c["first_k_dense_replace"] * dense_ffn_params(c)
             + routed_layers(c) * (shared_params(c) + experts)
             + c["hidden_size"] * c["vocab_size"])
    state = linear_lines(c) * slots * (
        linear_state_bytes(c) + conv_window_bytes(c, dtype_bytes))
    return (dense * dtype_bytes + routed_layers(c) * router_params(c) * 4
            + live_kv_tokens * kv_bytes_per_token(c, layers, dtype_bytes)
            + 2 * state)


def decode_attention_bytes(c: dict, layers: int, positions: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes ``ops/latent_attention.py``'s decode kernel must fetch for
    ``positions`` cached positions (the engine's ``kv_positions_read``: per
    decode step, each decoding slot's length rounded up to the kernel's
    block), summed over its call a latent line: one row of ``rank + Dr``
    values a position and line. Left out, so the count is a floor: the
    queries and outputs, the lengths, and the padding of a 576-wide row to
    the device's 128-lane tiles (640)."""
    return positions * kv_bytes_per_token(c, layers, dtype_bytes)


def decode_attention_flops(c: dict, layers: int, positions: float) -> float:
    """FLOPs of the same calls in the absorbed form: every head scores a
    position over the whole row (rank + Dr) and mixes its first ``rank``
    values, 2 FLOPs a multiply-add: 32 x (576 + 512) x 2 = 69,632 a
    position and line against 1,152 bytes, 60 a byte, a quarter of the
    v5e's ridge (240.5): the bytes bound the kernel at 32 heads."""
    per_position = 2 * c["num_attention_heads"] * (latent_dim(c)
                                                   + c["kv_lora_rank"])
    return positions * per_position * attention_calls_per_step(c, layers)


def grouped_matmul_work(c: dict, experts_touched: float, rows: float,
                        dtype_bytes: int = 2) -> dict:
    """FLOPs and bytes of one routed layer's two grouped matmuls
    (``ops/grouped_matmul.py``: gate and up fused, then down) when
    ``experts_touched`` held experts got ``rows`` picks in all: the touched
    experts' weights once, the rows in and out (a floor: whole tiles and a
    fetch a tile are the kernel's own affair)."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    return {"flops": 2 * rows * 3 * h * f,
            "bytes": (experts_touched * expert_params(c)
                      + rows * (2 * h + 2 * f)) * dtype_bytes}


def delta_rule_token_work(c: dict, dtype_bytes: int = 4) -> dict:
    """What the delta rule with a decay a key channel needs for ONE token in
    ONE KDA layer, whichever form computes it: the recurrence's FLOPs a
    head (the decay of the state's rows, the read at ``k``, the rank-one
    update and the read at ``q``: 7 x D x D), and the bytes of ``q``, ``k``,
    ``v`` and ``g`` (a head's D numbers each: the decay is a number a
    channel), ``beta`` in and ``o`` out once (float32, as the rule takes
    them). The state is not counted: the chunked form keeps it on the chip
    from token to token."""
    heads, d = c["num_attention_heads"], c["head_dim"]
    return {"flops": 7 * d * d * heads,
            "bytes": heads * (5 * d + 1) * dtype_bytes}


def linear_step_bytes(c: dict, updates: float) -> float:
    """What ``updates`` (slot, KDA layer) pairs of a decode step must move:
    each state read once and written once."""
    return 2 * linear_state_bytes(c) * updates


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.ling import LingConfig

    if config["q_lora_rank"] is not None or config.get("rope_scaling") \
            or config["score_function"] != "sigmoid" \
            or not config["moe_router_enable_expert_bias"] \
            or config["num_kv_heads_for_linear_attn"] \
            or config["group_norm_size"] != 1 or not config["linear_silu"] \
            or not config["no_kda_lora"] or not config["kda_safe_gate"] \
            or config["gated_attention_proj_granularity_type"] \
            != "head_wise" or config["rotary_dim"] \
            != config["qk_rope_head_dim"]:
        raise ValueError(
            "LingConfig runs one query matrix, unscaled rotary over the "
            "rope part, sigmoid scores under a selection bias, KDA with a "
            "key head a value head, a head a norm group, silu after the "
            "convolutions, a full-rank bounded gate, and a head-wise "
            "attention gate")
    return LingConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        layer_group_size=config["layer_group_size"],
        num_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        linear_num_heads=config["num_attention_heads"],
        linear_head_dim=config["head_dim"],
        short_conv_kernel_size=config["short_conv_kernel_size"],
        kda_lower_bound=float(config["kda_lower_bound"]),
        num_experts=config["published"]["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        expert_swiglu_limits=tuple(config["expert_swiglu_limit_list"]),
        shared_swiglu_limits=tuple(config["share_expert_swiglu_limit_list"]),
        expert_shard=int(config["expert_shard"]),
        expert_shards=int(config["expert_shards"]),
        max_seq_len=max_seq_len, rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=config.get("torch_dtype", "bfloat16"))


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses and in the published order. Matrices are [in, out]; a leaf of
    ``layers`` is stacked over the layers that have it, in layer order.

    The program keeps ``in_qkvz`` as ``W_q | W_k | W_v | W_z`` side by side
    (four equal parts; published: four matrices) and ``wkv_b`` a head at a
    time, [layers, heads, rank, Dn + Dv] (published: [rank, heads x (Dn +
    Dv)]). The taps are the published ``conv1d`` weights, [channels, taps],
    q's channels, then k's, then v's."""
    import jax.numpy as jnp

    lay = params["layers"]
    q, k, v, z = jnp.split(lay["in_qkvz"], 4, axis=-1)
    kv_b = lay["wkv_b"].transpose(0, 2, 1, 3)
    kv_b = kv_b.reshape(*kv_b.shape[:2], -1)
    return {"embed": params["embed_tokens"], "head": params["lm_head"],
            "final_norm": params["final_norm"],
            "layers": {"input_norm": lay["input_norm"],
                       "post_norm": lay["post_norm"],
                       "kda_q": q, "kda_k": k, "kda_v": v, "kda_z": z,
                       "kda_f": lay["in_f"], "kda_b": lay["in_b"],
                       "kda_conv": lay["conv_w"].transpose(0, 2, 1),
                       "kda_a_log": lay["a_log"],
                       "kda_dt_bias": lay["dt_bias"],
                       "kda_norm": lay["kda_norm"],
                       "kda_out": lay["out_proj"],
                       "q": lay["wq"], "kv_a": lay["wkv_a"],
                       "kv_a_norm": lay["kv_a_norm"], "kv_b": kv_b,
                       "attn_gate": lay["wg"], "o": lay["wo"],
                       "gate": lay["w_gate"], "up": lay["w_up"],
                       "down": lay["w_down"],
                       "router": lay["router"],
                       "router_bias": lay["router_bias"],
                       "s_gate": lay["ws_gate"], "s_up": lay["ws_up"],
                       "s_down": lay["ws_down"],
                       "e_gate": lay["we_gate"], "e_up": lay["we_up"],
                       "e_down": lay["we_down"]}}
