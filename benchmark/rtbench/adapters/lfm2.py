"""Adapter for LFM2-MoE configurations (``model_type: "lfm2_moe"``), which
run through the program's ``Lfm2Config``, ``models/lfm2.py``,
``models/routed.py``, ``llm/lfm2_serving.py`` and the one ``llm/engine.py``.

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the two functions at the bottom are the only ones that touch
it. The configuration file's ``layer_types`` and ``num_hidden_layers`` are
the layers run here; every other key is the published one, and every
expert is held.

**What ``depth`` means here, and the one trap.** ``depth`` is layers, 10.
Only ``attention_lines`` of them (2) have a cache line and call the decode
kernel, and ``decode_attention_roofline`` takes ``depth`` for the kernel's
calls a step: it multiplies the kernel's mean time by it and hands it to
``decode_attention_bytes``. So that function counts a call's bytes
``layers`` times, the same calls the reader multiplied by, and the share
is a call's bytes over a call's time. ``kv_bytes_per_token`` and
``decode_step_bytes`` count what is there: two lines.

What the four points of ``adapters/__init__.py`` needed: nothing new. The
cache is a dict of two leaves (``kv``, ``conv``) and dropping the name
frees both; ``stats()`` carries the router's counters (``moe_*``) and the
constants ``moe_experts_held``, ``attention_lines``, ``conv_lines``.
"""

from __future__ import annotations

REFERENCE = "reference.lfm2"


def depth(config: dict, use: str) -> int:
    """Layers run here. Nothing depends on the use."""
    return int(config["num_hidden_layers"])


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def conv_lines(c: dict) -> int:
    return c["layer_types"].count("conv")


def attention_lines(c: dict) -> int:
    return c["layer_types"].count("full_attention")


def routed_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


def conv_params(c: dict) -> int:
    """One short convolution: in_proj (hidden -> 3 hidden), the taps,
    out_proj."""
    h = c["hidden_size"]
    return 3 * h * h + c["conv_L_cache"] * h + h * h


def attention_params(c: dict) -> int:
    """One attention: q, k, v, o and the two head norms."""
    h, d = c["hidden_size"], head_dim(c)
    return (2 * h * c["num_attention_heads"] * d
            + 2 * h * c["num_key_value_heads"] * d + 2 * d)


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    """The gate and the selection bias (both float32 in the program)."""
    return c["hidden_size"] * c["num_experts"] + c["num_experts"]


def params_held(c: dict) -> int:
    """Every parameter this chip holds: the operators, the leading dense
    SwiGLUs, the routed layers with all their experts, two norms a layer,
    the final norm, and the embedding, which is the head too (tied)."""
    h = c["hidden_size"]
    return (conv_lines(c) * conv_params(c)
            + attention_lines(c) * attention_params(c)
            + c["num_dense_layers"] * dense_ffn_params(c)
            + routed_layers(c) * (router_params(c)
                                  + c["num_experts"] * expert_params(c))
            + 2 * c["num_hidden_layers"] * h + h
            + h * c["vocab_size"])


def kv_bytes_per_token(c: dict, layers: int, dtype_bytes: int = 2) -> int:
    """One cached position: a key and a value of ``head_dim`` in each KV
    head, side by side in one row, in each attention line (two of the ten
    layers: 2 x 8 x 128 x 2 bytes = 4 KiB). ``layers`` is not used: the
    lines are counted from ``layer_types``."""
    return (2 * head_dim(c) * c["num_key_value_heads"] * dtype_bytes
            * attention_lines(c))


def conv_state_bytes(c: dict, slots: int, dtype_bytes: int = 2) -> int:
    """The convolutions' state, as the program keeps it: the last
    ``conv_L_cache - 1`` rows of the gated input a slot and conv layer."""
    return (conv_lines(c) * slots * (c["conv_L_cache"] - 1)
            * c["hidden_size"] * dtype_bytes)


def experts_touched_uniform(c: dict, tokens: float) -> float:
    """How many of a layer's experts a step of ``tokens`` tokens is
    expected to touch if every pick fell uniformly over them: experts x
    (1 - (1 - 1/experts)^(tokens x per token))."""
    picks = tokens * c["num_experts_per_tok"]
    return c["num_experts"] * (1 - (1 - 1 / c["num_experts"]) ** picks)


def decode_step_bytes(c: dict, layers: int, live_kv_tokens: float,
                      dtype_bytes: int = 2, slots: int = 64) -> float:
    """Bytes one decode step must read: every operator's and dense
    SwiGLU's weights once for the whole batch, the head (the embedding),
    of each routed layer the experts a step is expected to touch under
    uniform routing (``experts_touched_uniform`` at ``slots`` tokens a
    step: 62.9 of 64 at 64), the cached positions live in the batch in the
    two attention lines, and the convolutions' state of every slot, read
    and written. The count of experts is an expectation and not a floor
    by itself: a step whose picks spread wider reads more, one whose lines
    are fewer than ``slots`` reads less. The router's float32 weights count
    at their 4 bytes; norms are left out."""
    experts = experts_touched_uniform(c, slots) * expert_params(c)
    dense = (conv_lines(c) * conv_params(c)
             + attention_lines(c) * attention_params(c)
             + c["num_dense_layers"] * dense_ffn_params(c)
             + c["hidden_size"] * c["vocab_size"])
    return ((dense + routed_layers(c) * experts) * dtype_bytes
            + routed_layers(c) * router_params(c) * 4
            + live_kv_tokens * kv_bytes_per_token(c, layers, dtype_bytes)
            + 2 * conv_state_bytes(c, slots, dtype_bytes))


def decode_attention_bytes(c: dict, layers: int, positions: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes ``ops/decode_attention.py``'s kernel fetches from HBM for
    ``positions`` cached positions in ONE call (a packed row of 2 x 64
    values in each of the 8 KV heads: 2 KiB a position), times ``layers``:
    the reader that calls this takes ``depth`` (10) for the kernel's calls
    a step and multiplies the kernel's mean time by it, though only the
    two attention layers call it, so the same factor stands on both sides
    and the share is one call's bytes over one call's time (the module's
    docstring). ``positions`` is the engine's ``kv_positions_read``: per
    decode step, each decoding slot's length rounded up to the kernel's
    block. Left out, so the count is a floor: the query rows and the
    output (padded to the row's 128 lanes) and the lengths."""
    per_call = 2 * head_dim(c) * c["num_key_value_heads"] * dtype_bytes
    return positions * per_call * layers


def grouped_matmul_work(c: dict, experts_touched: float, rows: float,
                        dtype_bytes: int = 2) -> dict:
    """FLOPs and bytes of one routed layer's two grouped matmuls
    (``ops/grouped_matmul.py``: gate and up fused, then down) when
    ``experts_touched`` experts got ``rows`` picks in all: the touched
    experts' weights once, the rows in and out. That the kernel multiplies
    whole tiles of 16 rows (``models/routed.MOE_TILE``), and reads an
    expert's weights once a tile, is its own affair and not counted (a
    floor)."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    return {"flops": 2 * rows * 3 * h * f,
            "bytes": (experts_touched * expert_params(c)
                      + rows * (2 * h + 2 * f)) * dtype_bytes}


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.lfm2 import Lfm2Config

    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers "
                         "layers")
    return Lfm2Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["num_dense_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=head_dim(config),
        conv_L_cache=config["conv_L_cache"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        router_score="sigmoid",
        use_expert_bias=bool(config["use_expert_bias"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        max_seq_len=max_seq_len,
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        norm_eps=float(config["norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config.get("torch_dtype", "bfloat16"))


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses. Matrices are [in, out]; a leaf of ``layers`` is stacked over the
    layers that have it, in layer order. Tied, the head is the embedding
    and is not named twice: the reference transposes it."""
    lay = params["layers"]
    head = {"head": params["lm_head"]} if "lm_head" in params else {}
    return {"embed": params["embed_tokens"], **head,
            "final_norm": params["final_norm"],
            "layers": {"operator_norm": lay["operator_norm"],
                       "ffn_norm": lay["ffn_norm"],
                       "conv_in": lay["conv_in"], "conv_w": lay["conv_w"],
                       "conv_out": lay["conv_out"],
                       "q": lay["wq"], "k": lay["wk"], "v": lay["wv"],
                       "o": lay["wo"], "q_norm": lay["q_norm"],
                       "k_norm": lay["k_norm"],
                       "gate": lay["w_gate"], "up": lay["w_up"],
                       "down": lay["w_down"],
                       "router": lay["router"],
                       "expert_bias": lay["router_bias"],
                       "e_gate": lay["we_gate"], "e_up": lay["we_up"],
                       "e_down": lay["we_down"]}}
