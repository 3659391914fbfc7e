"""Adapter for LongCat-Flash configurations, which run through the program's
``LongcatConfig``, ``models/longcat.py``, ``llm/longcat_serving.py`` and the
one ``llm/engine.py`` (the first model served here that is not a Llama).

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the functions at the bottom are the only ones that touch it.
All of it is of **this chip's share**: the configuration file's
``n_routed_experts`` is the number of experts held, its ``vocab_size`` the
rows of the vocabulary held.

What a model that is not a Llama had to keep of the four points of
``adapters/__init__.py``, and what was learned about them (PR 27):

1. One ``LLMEngine`` class serves it: the model comes in through
   ``engine.ServedModel`` (its programs, cache and initialiser), so
   ``_take_engine`` finds the engine as before.
2. ``ray_tpu.llm.engine.init_params(cfg, key)`` is still the name the
   engine calls; it now dispatches on the configuration's type, and a
   jitted copy swapped in for it dispatches the same way (the type is part
   of the static argument).
3. ``engine.params`` and ``engine.cache`` keep their names; the cache is a
   dict with one leaf, ``latent``, and dropping the name frees it.
4. ``stats()`` carries the router's counters (``moe_*``) beside the
   engine's own; ``kv_positions_read`` counts the latent kernel's blocks.
"""

from __future__ import annotations

REFERENCE = "reference.longcat"


def depth(config: dict, use: str) -> int:
    """Double layers under the use a traffic file names."""
    return int(config["num_layers"][use])


def latent_dim(c: dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def attention_calls_per_step(c: dict, layers: int) -> int:
    """Two attentions a double layer."""
    return 2 * layers


def mla_params(c: dict) -> int:
    """One attention: q_a, q_b, kv_a, kv_b, o (norm weights left out)."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (h * c["q_lora_rank"] + c["q_lora_rank"] * nh * qk
            + h * latent_dim(c)
            + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"]
                                        + c["v_head_dim"])
            + nh * c["v_head_dim"] * h)


def ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["ffn_hidden_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def router_outputs(c: dict) -> int:
    return c["published"]["n_routed_experts"] + c["zero_expert_num"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * router_outputs(c)


def dense_params_per_layer(c: dict) -> int:
    """A double layer outside its experts: two attentions, two FFNs, the
    router."""
    return 2 * (mla_params(c) + ffn_params(c)) + router_params(c)


def params_held(c: dict, layers: int) -> int:
    """Matrices this chip holds: the double layers with the held experts,
    the embedding and the untied head over the held vocabulary."""
    return (layers * (dense_params_per_layer(c)
                      + c["n_routed_experts"] * expert_params(c))
            + 2 * c["hidden_size"] * c["vocab_size"])


def experts_touched_uniform(c: dict, tokens: float) -> float:
    """Of the held experts, how many a layer-step of ``tokens`` tokens is
    expected to touch if every pick fell uniformly over the router's
    outputs: held x (1 - (1 - 1/outputs)^(tokens x topk))."""
    picks = tokens * c["moe_topk"]
    return c["n_routed_experts"] * (1 - (1 - 1 / router_outputs(c)) ** picks)


def kv_bytes_per_token(c: dict, layers: int, dtype_bytes: int = 2) -> int:
    """One cached position: a latent row in each attention."""
    return latent_dim(c) * dtype_bytes * attention_calls_per_step(c, layers)


def decode_step_bytes(c: dict, layers: int, live_kv_tokens: float,
                      dtype_bytes: int = 2, slots: int = 32) -> float:
    """Bytes one decode step must read: every dense weight once for the
    whole batch, the head, the latent rows live in the batch, and of the
    held experts only those a step is expected to touch under uniform
    routing (``experts_touched_uniform`` at ``slots`` tokens a step: 6.3 of
    16 at 32), so that the count is a floor: a step whose picks spread
    wider reads more, and the share this feeds cannot pass 100% for that
    reason. The router's float32 weights count at their 4 bytes."""
    experts = experts_touched_uniform(c, slots) * expert_params(c)
    dense = 2 * (mla_params(c) + ffn_params(c))
    return (layers * ((dense + experts) * dtype_bytes + router_params(c) * 4)
            + c["hidden_size"] * c["vocab_size"] * dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(c, layers, dtype_bytes))


def decode_attention_bytes(c: dict, layers: int, positions: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes ``ops/latent_attention.py``'s decode kernel must fetch for
    ``positions`` cached positions (the engine's ``kv_positions_read``: per
    decode step, each decoding slot's length rounded up to the kernel's
    block), summed over its two calls a double layer: one row of ``rank +
    Dr`` values a position and attention. Left out, so the count is a floor:
    the queries and outputs (slots x 64 heads x (576 + 512) x 2 bytes a
    call), the lengths, and the padding of a 576-wide row to the device's
    128-lane tiles (640)."""
    return positions * kv_bytes_per_token(c, layers, dtype_bytes)


def decode_attention_flops(c: dict, layers: int, positions: float) -> float:
    """FLOPs of the same calls in the absorbed form: every head scores a
    position over the whole row (rank + Dr) and mixes its first ``rank``
    values, 2 FLOPs a multiply-add."""
    per_position = 2 * c["num_attention_heads"] * (latent_dim(c)
                                                   + c["kv_lora_rank"])
    return positions * per_position * attention_calls_per_step(c, layers)


def grouped_matmul_work(c: dict, experts_touched: float, rows: float,
                        dtype_bytes: int = 2) -> dict:
    """FLOPs and bytes of one routed layer's two grouped matmuls
    (``ops/grouped_matmul.py``: gate and up fused, then down) when
    ``experts_touched`` held experts got ``rows`` picks in all: the
    touched experts' weights once, the rows in and out. That the kernel
    multiplies whole tiles of 16 rows (``models/longcat.MOE_TILE``) is its
    own affair and not counted (a floor)."""
    h, f = c["hidden_size"], c["expert_ffn_hidden_size"]
    return {"flops": 2 * rows * 3 * h * f,
            "bytes": (experts_touched * expert_params(c)
                      + rows * (2 * h + 2 * f)) * dtype_bytes}


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.longcat import LongcatConfig

    return LongcatConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        ffn_hidden_size=config["ffn_hidden_size"],
        expert_ffn_hidden_size=config["expert_ffn_hidden_size"],
        num_layers=depth(config, use),
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_routed_experts=config["published"]["n_routed_experts"],
        zero_expert_num=config["zero_expert_num"],
        moe_topk=config["moe_topk"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        mla_scale_q_lora=bool(config["mla_scale_q_lora"]),
        mla_scale_kv_lora=bool(config["mla_scale_kv_lora"]),
        max_seq_len=max_seq_len, rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=config.get("torch_dtype", "bfloat16"),
        expert_shard=int(config["expert_shard"]),
        expert_shards=int(config["expert_shards"]))


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses. Matrices are [in, out]; leaves of ``layers`` carry the sub-layer
    (attentions, dense FFNs) or the double layer (router, experts) on their
    leading axis."""
    lay = params["layers"]
    return {"embed": params["embed_tokens"], "head": params["lm_head"],
            "final_norm": params["final_norm"],
            "layers": {"attn_norm": lay["attn_norm"],
                       "post_norm": lay["post_norm"],
                       "q_a": lay["wq_a"], "q_a_norm": lay["q_a_norm"],
                       "q_b": lay["wq_b"], "kv_a": lay["wkv_a"],
                       "kv_a_norm": lay["kv_a_norm"], "kv_b": lay["wkv_b"],
                       "o": lay["wo"], "gate": lay["w_gate"],
                       "up": lay["w_up"], "down": lay["w_down"],
                       "router": lay["router"],
                       "router_bias": lay["router_bias"],
                       "e_gate": lay["we_gate"], "e_up": lay["we_up"],
                       "e_down": lay["we_down"]}}
