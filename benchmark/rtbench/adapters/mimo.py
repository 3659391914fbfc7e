"""Adapter for MiMo-V2 configurations (``model_type: "mimo_v2"``), which run
through the program's ``MimoConfig``, ``models/mimo.py``,
``models/routed.py``, ``llm/mimo_serving.py`` and the one ``llm/engine.py``.

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the two functions at the bottom are the only ones that touch
it. All of it is of **this chip's share**: the configuration file's
``n_routed_experts`` is the number of experts held, its ``vocab_size`` the
rows of the vocabulary held, its ``num_hidden_layers`` the layers run here,
``hybrid_layer_pattern`` and ``moe_layer_freq`` a value for each of them.

**Two geometries.** A full layer (``hybrid_layer_pattern`` 0) keeps a line
that grows: ``num_key_value_heads`` KV heads a position. A window layer (1)
keeps a ring of ``sliding_window`` positions of ``swa_num_key_value_heads``
KV heads, which does not. A cached row of either is a key of ``head_dim``
and a value of ``v_head_dim`` stored as ``2 x head_dim`` lanes (the value
padded to a key's width: 384 lanes at 192 + 128, three whole lane rows), so
a position of a full line is ``kv_row_bytes`` a KV head and layer.

**What a position occupies and what the roofline counts.**
``kv_bytes_per_token`` is one cached position of the full lines, all full
layers; the rings are ``ring_bytes`` a slot and layer, whatever the length.
``depth`` is the decode kernel's calls a step: one a layer, on a full line
or on a ring. ``decode_attention_roofline`` takes ``depth`` for the kernel's
calls on the time's side (the mean event times ``depth`` is all the
kernel's time of a step) and hands it to ``decode_attention_bytes``, which
does not use it: the bytes are the full lines' live positions, and the
rings' reads are left out. The share is a floor of bytes over all the
kernel's time, so it stays under 100 (adapters/phi4flash.py argues the
same).

What the four points of ``adapters/__init__.py`` needed: nothing new. The
cache is a dict of two leaves (``kv``, ``ring``) and dropping the name frees
both; ``stats()`` carries the router's counters (``moe_*``), this model's
``window_positions_read`` and ``full_positions_read`` and the constants
``window_lines``, ``full_lines``, ``window``, ``window_kv_heads``,
``full_kv_heads``, ``kv_row_lanes``, ``moe_experts_held``.
"""

from __future__ import annotations

REFERENCE = "reference.mimo"


def depth(config: dict, use: str) -> int:
    """The decode kernel's calls a step: one a layer run here. Nothing
    depends on the use."""
    return int(config["num_hidden_layers"])


def full_lines(c: dict) -> int:
    return c["hybrid_layer_pattern"].count(0)


def window_lines(c: dict) -> int:
    return c["hybrid_layer_pattern"].count(1)


def routed_layers(c: dict) -> int:
    return sum(c["moe_layer_freq"])


def dense_layers(c: dict) -> int:
    return c["num_hidden_layers"] - routed_layers(c)


def attention_params(c: dict, window: bool) -> int:
    """One attention: q, k, v (the fused qkv's three blocks) and o; norm
    weights and the sinks left out."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    nkv = c["swa_num_key_value_heads" if window else "num_key_value_heads"]
    return (h * (nh + nkv) * c["head_dim"] + h * nkv * c["v_head_dim"]
            + nh * c["v_head_dim"] * h)


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_outputs(c: dict) -> int:
    return c["published"]["n_routed_experts"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * router_outputs(c)


def params_held(c: dict) -> int:
    """Matrices this chip holds: an attention a layer, the dense SwiGLUs,
    of each routed layer the router and the held experts, the embedding
    and the untied head over the held vocabulary."""
    return (full_lines(c) * attention_params(c, False)
            + window_lines(c) * attention_params(c, True)
            + dense_layers(c) * dense_ffn_params(c)
            + routed_layers(c) * (router_params(c)
                                  + c["n_routed_experts"] * expert_params(c))
            + 2 * c["hidden_size"] * c["vocab_size"])


def experts_touched(c: dict, tokens: float) -> float:
    """Of the held experts, how many a layer-step of ``tokens`` tokens is
    expected to touch if picks fell uniformly: held x (1 - (1 - per_tok /
    outputs)^tokens), 8.5 of 16 at 24 tokens."""
    p = c["num_experts_per_tok"] / router_outputs(c)
    return c["n_routed_experts"] * (1 - (1 - p) ** tokens)


def kv_row_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """One cached row: a key and a value of one KV head, the value stored
    in a key's width (2 x 192 x 2 bytes = 768)."""
    return 2 * c["head_dim"] * dtype_bytes


def kv_bytes_per_token(c: dict, layers: int, dtype_bytes: int = 2) -> int:
    """One cached position of the full lines: a row of every KV head in
    every full layer (2 x 4 x 768 = 6,144 bytes). ``layers`` is not used:
    the window layers' rings do not grow with the positions."""
    return (full_lines(c) * c["num_key_value_heads"]
            * kv_row_bytes(c, dtype_bytes))


def ring_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """One slot's ring in one window layer (128 x 8 x 768 = 786,432)."""
    return (c["sliding_window"] * c["swa_num_key_value_heads"]
            * kv_row_bytes(c, dtype_bytes))


def decode_step_bytes(c: dict, layers: int, live_kv_tokens: float,
                      dtype_bytes: int = 2, slots: int = 24) -> float:
    """Bytes one decode step must read: every attention's and the dense
    SwiGLU's weights once for the whole batch, the head, the full lines'
    live positions, every slot's rings (a floor: a ring read before it is
    full is shorter), and of the held experts only those a step is
    expected to touch (``experts_touched`` at ``slots`` tokens a step), an
    expectation and not a floor by itself. The router's float32 weights
    count at their 4 bytes; norms and sinks are left out."""
    dense = (full_lines(c) * attention_params(c, False)
             + window_lines(c) * attention_params(c, True)
             + dense_layers(c) * dense_ffn_params(c)
             + routed_layers(c) * experts_touched(c, slots) * expert_params(c)
             + c["hidden_size"] * c["vocab_size"])
    return (dense * dtype_bytes + routed_layers(c) * router_params(c) * 4
            + live_kv_tokens * kv_bytes_per_token(c, layers, dtype_bytes)
            + window_lines(c) * slots * ring_bytes(c, dtype_bytes))


def decode_attention_bytes(c: dict, layers: int, positions: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes ``ops/decode_attention.py``'s kernel fetches from the full
    lines for ``positions`` cached positions a step (the engine's
    ``kv_positions_read``: each decoding slot's length rounded up to the
    full lines' block): 6,144 bytes a position. ``layers`` (``depth``: all
    the kernel's calls) is not used, and the rings' reads are left out, so
    the count is a floor (the module's docstring)."""
    return positions * kv_bytes_per_token(c, layers, dtype_bytes)


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.mimo import MimoConfig

    if config["hidden_act"] != "silu" or config["tie_word_embeddings"] \
            or config["attention_bias"] or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" or config["n_group"] != 1 \
            or config["n_shared_experts"] \
            or config["add_full_attention_sink_bias"] \
            or config["rope_scaling"]["rope_type"] != "default" \
            or (config["swa_head_dim"], config["swa_v_head_dim"],
                config["swa_num_attention_heads"]) != (
                    config["head_dim"], config["v_head_dim"],
                    config["num_attention_heads"]):
        raise ValueError(
            "MimoConfig runs silu, an untied head, projections without "
            "bias, sigmoid scores under noaux_tc in one group, no shared "
            "expert, a sink in the window layers alone, an unscaled rotary "
            "and one head geometry for both kinds of layer")
    factor = config["routed_scaling_factor"]
    return MimoConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        swa_num_kv_heads=config["swa_num_key_value_heads"],
        head_dim=config["head_dim"], v_head_dim=config["v_head_dim"],
        layer_kinds=tuple(config["hybrid_layer_pattern"]),
        layer_routed=tuple(config["moe_layer_freq"]),
        sliding_window=config["sliding_window"],
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        swa_rope_theta=float(config["swa_rope_theta"]),
        attention_value_scale=float(config["attention_value_scale"]),
        window_sink=bool(config["add_swa_attention_sink_bias"]),
        n_routed_experts=config["published"]["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=1.0 if factor is None else float(factor),
        max_seq_len=max_seq_len, norm_eps=float(config["layernorm_epsilon"]),
        dtype=config.get("torch_dtype", "bfloat16"),
        expert_shard=int(config["expert_shard"]),
        expert_shards=int(config["expert_shards"]))


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses. Matrices are [in, out]; a leaf of ``layers`` is stacked over the
    layers that have it, in layer order. No leaf is copied: the fused
    ``qkv`` goes as the column blocks the program keeps (the reference
    takes them so)."""
    lay = params["layers"]
    return {"embed": params["embed_tokens"], "head": params["lm_head"],
            "final_norm": params["final_norm"],
            "layers": {"attn_norm": lay["attn_norm"],
                       "post_norm": lay["post_norm"],
                       "qkv_full": lay["wqkv_full"],
                       "qkv_window": lay["wqkv_window"],
                       "o": lay["wo"],
                       **({"sink": lay["sink"]} if "sink" in lay else {}),
                       "gate": lay["w_gate"], "up": lay["w_up"],
                       "down": lay["w_down"], "router": lay["router"],
                       "router_bias": lay["router_bias"],
                       "e_gate": lay["we_gate"], "e_up": lay["we_up"],
                       "e_down": lay["we_down"]}}
