"""Adapter for DeepSeek-V2 configurations (``model_type: "deepseek_v2"``),
which run through the program's ``DeepseekV2Config``, ``models/deepseek.py``,
``models/routed.py``, ``llm/deepseek_serving.py`` and the one
``llm/engine.py``.

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the two functions at the bottom are the only ones that touch
it. All of it is of **this chip's share**: the configuration file's
``n_routed_experts`` is the number of experts held (one of the router's
``n_group`` groups), its ``vocab_size`` the rows of the vocabulary held, its
``num_hidden_layers`` the layers run here, the ``first_k_dense_replace``
dense ones first. Every layer has one latent attention and one cache line.

What the four points of ``adapters/__init__.py`` needed: nothing new. The
cache is a dict with one leaf, ``latent``; ``stats()`` carries the router's
counters (``moe_*``, among them this model's own ``moe_tokens_local``) and
the constant ``moe_experts_held``.
"""

from __future__ import annotations

REFERENCE = "reference.deepseek"


def depth(config: dict, use: str) -> int:
    """Layers run here. Nothing depends on the use."""
    return int(config["num_hidden_layers"])


def routed_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def latent_dim(c: dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def attention_calls_per_step(c: dict, layers: int) -> int:
    """One attention a layer."""
    return layers


def mla_params(c: dict) -> int:
    """One attention: q_a, q_b, kv_a, kv_b, o (norm weights left out)."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (h * c["q_lora_rank"] + c["q_lora_rank"] * nh * qk
            + h * latent_dim(c)
            + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"]
                                        + c["v_head_dim"])
            + nh * c["v_head_dim"] * h)


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def shared_params(c: dict) -> int:
    """The shared experts: one SwiGLU of n_shared_experts x the expert
    width."""
    return (3 * c["hidden_size"] * c["n_shared_experts"]
            * c["moe_intermediate_size"])


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_outputs(c: dict) -> int:
    return c["published"]["n_routed_experts"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * router_outputs(c)


def params_held(c: dict) -> int:
    """Matrices this chip holds: an attention a layer, the dense SwiGLUs,
    of each routed layer the router, the shared experts and the held
    experts, the embedding and the untied head over the held vocabulary."""
    return (c["num_hidden_layers"] * mla_params(c)
            + c["first_k_dense_replace"] * dense_ffn_params(c)
            + routed_layers(c) * (router_params(c) + shared_params(c)
                                  + c["n_routed_experts"] * expert_params(c))
            + 2 * c["hidden_size"] * c["vocab_size"])


def experts_touched_grouped(c: dict, tokens: float) -> float:
    """Of the held experts (one group), how many a layer-step of ``tokens``
    tokens is expected to touch if groups were kept and picks fell
    uniformly: a token reaches this group with topk_group / n_group and
    then spreads its ``num_experts_per_tok`` picks over the kept groups'
    experts, so an expert here is picked by a token with probability
    per_tok / outputs, as under an ungrouped rule; held x (1 - (1 -
    per_tok / outputs)^tokens)."""
    p = c["num_experts_per_tok"] / router_outputs(c)
    return c["n_routed_experts"] * (1 - (1 - p) ** tokens)


def kv_bytes_per_token(c: dict, layers: int, dtype_bytes: int = 2) -> int:
    """One cached position: a latent row in each layer."""
    return latent_dim(c) * dtype_bytes * attention_calls_per_step(c, layers)


def decode_step_bytes(c: dict, layers: int, live_kv_tokens: float,
                      dtype_bytes: int = 2, slots: int = 16) -> float:
    """Bytes one decode step must read: every attention's, dense SwiGLU's
    and shared expert's weights once for the whole batch, the head, the
    latent rows live in the batch, and of the held experts only those a
    step is expected to touch (``experts_touched_grouped`` at ``slots``
    tokens a step: 9.1 of 20 at 16), an expectation and not a floor by
    itself: a step whose picks spread wider reads more, one whose lines
    are fewer than ``slots`` reads less. The router's float32 weights count
    at their 4 bytes; norms are left out."""
    experts = experts_touched_grouped(c, slots) * expert_params(c)
    dense = (layers * mla_params(c)
             + c["first_k_dense_replace"] * dense_ffn_params(c)
             + routed_layers(c) * (shared_params(c) + experts)
             + c["hidden_size"] * c["vocab_size"])
    return (dense * dtype_bytes + routed_layers(c) * router_params(c) * 4
            + live_kv_tokens * kv_bytes_per_token(c, layers, dtype_bytes))


def decode_attention_bytes(c: dict, layers: int, positions: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes ``ops/latent_attention.py``'s decode kernel must fetch for
    ``positions`` cached positions (the engine's ``kv_positions_read``: per
    decode step, each decoding slot's length rounded up to the kernel's
    block), summed over its call a layer: one row of ``rank + Dr`` values a
    position and layer. Left out, so the count is a floor: the queries and
    outputs (slots x 128 heads x (576 + 512) x 2 bytes a call), the
    lengths, and the padding of a 576-wide row to the device's 128-lane
    tiles (640)."""
    return positions * kv_bytes_per_token(c, layers, dtype_bytes)


def decode_attention_flops(c: dict, layers: int, positions: float) -> float:
    """FLOPs of the same calls in the absorbed form: every head scores a
    position over the whole row (rank + Dr) and mixes its first ``rank``
    values, 2 FLOPs a multiply-add: 128 x (576 + 512) x 2 = 278,528 a
    position and layer against 1,152 bytes, 242 a byte, the v5e's ridge
    (``peaks.json``: 197e12 / 819e9 = 240.5), so the two sides of the
    roofline are within a hundredth of each other here."""
    per_position = 2 * c["num_attention_heads"] * (latent_dim(c)
                                                   + c["kv_lora_rank"])
    return positions * per_position * attention_calls_per_step(c, layers)


def prefill_attention_flops(c: dict, chunk: int, visible: float) -> float:
    """FLOPs of ``ops/latent_attention.latent_prefill_attention`` for one
    chunk of ``chunk`` queries in one layer whose line holds ``visible``
    live positions (rounded up to its block of 512 by the caller): the
    up-projection of every live row to all heads' keys and values, the
    scores over Dn + Dr and the mix over Dv."""
    nh = c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    up = 2 * visible * c["kv_lora_rank"] * nh * (dn + dv)
    return up + 2 * chunk * visible * nh * (dn + dr + dv)


def grouped_matmul_work(c: dict, experts_touched: float, rows: float,
                        dtype_bytes: int = 2) -> dict:
    """FLOPs and bytes of one routed layer's two grouped matmuls
    (``ops/grouped_matmul.py``: gate and up fused, then down) when
    ``experts_touched`` held experts got ``rows`` picks in all: the
    touched experts' weights once, the rows in and out. That the kernel
    multiplies whole tiles (``models/routed.row_tile``), and reads an
    expert's weights once a tile, is its own affair and not counted (a
    floor)."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    return {"flops": 2 * rows * 3 * h * f,
            "bytes": (experts_touched * expert_params(c)
                      + rows * (2 * h + 2 * f)) * dtype_bytes}


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.deepseek import DeepseekV2Config

    rs = config["rope_scaling"]
    if rs["type"] != "yarn" or config["scoring_func"] != "softmax" \
            or config["topk_method"] != "group_limited_greedy":
        raise ValueError("DeepseekV2Config runs YaRN rotary, softmax scores "
                         "and the group-limited rule")
    return DeepseekV2Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_routed_experts=config["published"]["n_routed_experts"],
        n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        max_seq_len=max_seq_len, rope_theta=float(config["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_original_max_position=int(
            rs["original_max_position_embeddings"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=config.get("torch_dtype", "bfloat16"),
        expert_shard=int(config["expert_shard"]),
        expert_shards=int(config["expert_shards"]))


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses. Matrices are [in, out]; a leaf of ``layers`` is stacked over the
    layers that have it, in layer order."""
    lay = params["layers"]
    # The program keeps ``wkv_b`` a head at a time, [layers, heads, rank,
    # Dn + Dv]; the published matrix is [rank, heads * (Dn + Dv)].
    kv_b = lay["wkv_b"].transpose(0, 2, 1, 3)
    kv_b = kv_b.reshape(*kv_b.shape[:2], -1)
    return {"embed": params["embed_tokens"], "head": params["lm_head"],
            "final_norm": params["final_norm"],
            "layers": {"attn_norm": lay["attn_norm"],
                       "post_norm": lay["post_norm"],
                       "q_a": lay["wq_a"], "q_a_norm": lay["q_a_norm"],
                       "q_b": lay["wq_b"], "kv_a": lay["wkv_a"],
                       "kv_a_norm": lay["kv_a_norm"], "kv_b": kv_b,
                       "o": lay["wo"], "gate": lay["w_gate"],
                       "up": lay["w_up"], "down": lay["w_down"],
                       "router": lay["router"],
                       "s_gate": lay["ws_gate"], "s_up": lay["ws_up"],
                       "s_down": lay["ws_down"],
                       "e_gate": lay["we_gate"], "e_up": lay["we_up"],
                       "e_down": lay["we_down"]}}
