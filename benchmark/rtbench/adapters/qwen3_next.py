"""Adapter for Qwen3-Next configurations (``model_type: "qwen3_next"``), which
run through the program's ``Qwen3NextConfig``, ``models/qwen3_next.py``,
``models/routed.py``, ``ops/gated_delta.py``, ``llm/qwen3_next_serving.py``
and the one ``llm/engine.py``.

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the two functions at the bottom are the only ones that touch
it. All of it is of **this chip's share**: the configuration file's
``num_experts`` is the number of experts held (the router keeps its
``published.num_experts`` outputs), its ``vocab_size`` the rows of the
vocabulary held, its ``num_hidden_layers`` the layers run here. Layer ``l``
is a gated attention where ``(l + 1) % full_attention_interval == 0`` and a
Gated DeltaNet otherwise; every layer's feed-forward is routed.

**``depth`` and the decode kernel's roofline.** ``depth`` is layers, 16.
Only ``attention_lines`` of them (4) have a cache line and call the decode
kernel, and ``decode_attention_roofline`` takes ``depth`` for the kernel's
calls a step on both sides (``adapters/lfm2.py``, "the one trap"): so
``decode_attention_bytes`` counts one call's bytes ``layers`` times, and the
share is a call's bytes over a call's time. ``kv_bytes_per_token`` and
``decode_step_bytes`` count what is there: four lines.

What the four points of ``adapters/__init__.py`` needed: nothing new. The
cache is a dict of four leaves (``k``, ``v``, ``state``, ``conv``) and
dropping the name frees them all; ``stats()`` carries the router's counters
(``moe_*``), this model's own (``linear_state_updates``,
``linear_chunk_tokens``) and the constants ``moe_experts_held``,
``attention_lines``, ``linear_lines``, ``linear_state_bytes``.
"""

from __future__ import annotations

REFERENCE = "reference.qwen3_next"


def depth(config: dict, use: str) -> int:
    """Layers run here. Nothing depends on the use."""
    return int(config["num_hidden_layers"])


def attention_lines(c: dict) -> int:
    return c["num_hidden_layers"] // c["full_attention_interval"]


def linear_lines(c: dict) -> int:
    return c["num_hidden_layers"] - attention_lines(c)


def key_dim(c: dict) -> int:
    return c["linear_num_key_heads"] * c["linear_key_head_dim"]


def value_dim(c: dict) -> int:
    return c["linear_num_value_heads"] * c["linear_value_head_dim"]


def conv_dim(c: dict) -> int:
    """Channels of the convolution: all heads' q, k and v."""
    return 2 * key_dim(c) + value_dim(c)


def linear_params(c: dict) -> int:
    """One Gated DeltaNet: in_proj_qkvz, in_proj_ba, the taps, dt_bias,
    A_log, the output norm, out_proj."""
    h, nv = c["hidden_size"], c["linear_num_value_heads"]
    return (h * (conv_dim(c) + value_dim(c)) + h * 2 * nv
            + conv_dim(c) * c["linear_conv_kernel_dim"] + 2 * nv
            + c["linear_value_head_dim"] + value_dim(c) * h)


def attention_params(c: dict) -> int:
    """One gated attention: q (a query and a gate a head), k, v, o and the
    two head norms."""
    h, d = c["hidden_size"], c["head_dim"]
    return (3 * h * c["num_attention_heads"] * d
            + 2 * h * c["num_key_value_heads"] * d + 2 * d)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    """The shared expert and its gate."""
    return (3 * c["hidden_size"] * c["shared_expert_intermediate_size"]
            + c["hidden_size"])


def router_outputs(c: dict) -> int:
    return c["published"]["num_experts"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * router_outputs(c)


def params_held(c: dict) -> int:
    """Every parameter this chip holds: the operators, of every layer the
    router, the shared expert and the held experts, two norms a layer, the
    final norm, the embedding and the untied head over the held
    vocabulary."""
    h = c["hidden_size"]
    return (linear_lines(c) * linear_params(c)
            + attention_lines(c) * attention_params(c)
            + c["num_hidden_layers"] * (
                router_params(c) + shared_params(c)
                + c["num_experts"] * expert_params(c) + 2 * h)
            + 2 * h * c["vocab_size"] + h)


def kv_bytes_per_token(c: dict, layers: int, dtype_bytes: int = 2) -> int:
    """One cached position: a key and a value of ``head_dim`` in each KV
    head in each attention line (4 of the 16 layers: 2 x 2 x 256 x 2 bytes
    x 4 = 8 KiB). ``layers`` is not used: the lines are counted from
    ``full_attention_interval``."""
    return (2 * c["head_dim"] * c["num_key_value_heads"] * dtype_bytes
            * attention_lines(c))


def linear_state_bytes(c: dict) -> int:
    """One slot's state in one linear layer: a float32 matrix of Dk x Dv a
    value head (32 x 128 x 128 x 4 = 2 MiB)."""
    return (c["linear_num_value_heads"] * c["linear_key_head_dim"]
            * c["linear_value_head_dim"] * 4)


def conv_window_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """One slot's convolution window in one linear layer."""
    return (c["linear_conv_kernel_dim"] - 1) * conv_dim(c) * dtype_bytes


def experts_touched_uniform(c: dict, tokens: float) -> float:
    """Of the held experts, how many a layer-step of ``tokens`` tokens is
    expected to touch if every pick fell uniformly over the router's
    outputs: held x (1 - (1 - per_tok / outputs)^tokens)."""
    p = c["num_experts_per_tok"] / router_outputs(c)
    return c["num_experts"] * (1 - (1 - p) ** tokens)


def decode_step_bytes(c: dict, layers: int, live_kv_tokens: float,
                      dtype_bytes: int = 2, slots: int = 16) -> float:
    """Bytes one decode step must read: every operator's and shared
    expert's weights once for the whole batch, the head, of each layer the
    held experts a step is expected to touch under uniform routing
    (``experts_touched_uniform`` at ``slots`` tokens a step: 17.3 of 64 at
    16), the cached positions live in the batch in the four attention
    lines, and every slot's state and window in the linear lines, read and
    written. The router's float32 weights count at their 4 bytes; norms are
    left out."""
    experts = experts_touched_uniform(c, slots) * expert_params(c)
    dense = (linear_lines(c) * linear_params(c)
             + attention_lines(c) * attention_params(c)
             + c["num_hidden_layers"] * (shared_params(c) + experts)
             + c["hidden_size"] * c["vocab_size"])
    state = linear_lines(c) * slots * (
        linear_state_bytes(c) + conv_window_bytes(c, dtype_bytes))
    return (dense * dtype_bytes
            + c["num_hidden_layers"] * router_params(c) * 4
            + live_kv_tokens * kv_bytes_per_token(c, layers, dtype_bytes)
            + 2 * state)


def decode_attention_bytes(c: dict, layers: int, positions: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes ``ops/decode_attention.py``'s kernel fetches from HBM for
    ``positions`` cached positions in ONE call (a key and a value of 256 in
    each of the 2 KV heads: 2 KiB a position), times ``layers``: the reader
    takes ``depth`` for the kernel's calls a step on both sides (the
    module's docstring). Left out, so the count is a floor: the query rows
    and the output, and the lengths."""
    per_call = 2 * c["head_dim"] * c["num_key_value_heads"] * dtype_bytes
    return positions * per_call * layers


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
    """What the gated delta rule needs for ONE token in ONE linear layer,
    whichever form computes it: the recurrence's FLOPs a value head (the
    decay of the state, the read at ``k``, the rank-one update and the read
    at ``q``: 7 x Dk x Dv), and the bytes of ``q``, ``k``, ``v``, ``g``,
    ``beta`` in and ``o`` out once (float32, as the rule takes them). The
    state is not counted: the chunked form keeps it on the chip from token
    to token."""
    nv, dk, dv = (c["linear_num_value_heads"], c["linear_key_head_dim"],
                  c["linear_value_head_dim"])
    return {"flops": 7 * dk * dv * nv,
            "bytes": nv * (2 * dk + 2 * dv + 2) * dtype_bytes}


def linear_step_bytes(c: dict, updates: float) -> float:
    """What ``updates`` (slot, linear layer) pairs of a decode step must
    move: each state read once and written once."""
    return 2 * linear_state_bytes(c) * updates


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.qwen3_next import Qwen3NextConfig

    if config.get("rope_scaling") or config["mlp_only_layers"] \
            or config["decoder_sparse_step"] != 1 \
            or config["hidden_act"] != "silu" \
            or config["tie_word_embeddings"]:
        raise ValueError("Qwen3NextConfig runs unscaled rotary, a routed "
                         "layer in every layer, silu and an untied head")
    return Qwen3NextConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        full_attention_interval=config["full_attention_interval"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        num_experts=config["published"]["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config[
            "shared_expert_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        expert_shard=int(config["expert_shard"]),
        expert_shards=int(config["expert_shards"]),
        max_seq_len=max_seq_len, rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=config.get("torch_dtype", "bfloat16"))


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses and in the published order. Matrices are [in, out]; a leaf of
    ``layers`` is stacked over the layers that have it, in layer order.

    The program keeps ``in_qkvz`` as all heads' q, then k, then v, then z,
    and ``in_ba`` as all heads' b, then a; the published matrices lay a key
    head's ``q | k | v | z`` (and ``b | a``) side by side, and the reference
    splits them so. The sizes are read off the leaves: ``a_log`` is [linear
    layers, key heads, value heads a key head], ``gdn_norm`` has a value of
    a head a column, ``conv_w`` a channel (2 key_dim + value_dim) a column.
    The taps are the published ``conv1d`` weight, [channels, taps]."""
    import jax.numpy as jnp

    lay = params["layers"]
    nl, hidden, _ = lay["in_qkvz"].shape
    nk, r = lay["a_log"].shape[1:]
    vd = nk * r * lay["gdn_norm"].shape[1]
    kd = (lay["conv_w"].shape[2] - vd) // 2
    q, k, v, z = jnp.split(lay["in_qkvz"], (kd, 2 * kd, 2 * kd + vd), axis=-1)
    by_key = lambda a: a.reshape(nl, hidden, nk, -1)  # noqa: E731
    qkvz = jnp.concatenate([by_key(a) for a in (q, k, v, z)],
                           axis=-1).reshape(nl, hidden, -1)
    b, a = jnp.split(lay["in_ba"], 2, axis=-1)
    ba = jnp.concatenate([by_key(b), by_key(a)],
                         axis=-1).reshape(nl, hidden, -1)
    return {"embed": params["embed_tokens"], "head": params["lm_head"],
            "final_norm": params["final_norm"],
            "layers": {"input_norm": lay["input_norm"],
                       "post_norm": lay["post_norm"],
                       "qkvz": qkvz, "ba": ba,
                       "conv": lay["conv_w"].transpose(0, 2, 1),
                       "a_log": lay["a_log"].reshape(nl, -1),
                       "dt_bias": lay["dt_bias"].reshape(nl, -1),
                       "norm": lay["gdn_norm"], "out": lay["out_proj"],
                       "q": lay["wq"], "k": lay["wk"], "v": lay["wv"],
                       "o": lay["wo"], "q_norm": lay["q_norm"],
                       "k_norm": lay["k_norm"],
                       "router": lay["router"],
                       "shared_gate": lay["shared_gate"],
                       "s_gate": lay["ws_gate"], "s_up": lay["ws_up"],
                       "s_down": lay["ws_down"],
                       "e_gate": lay["we_gate"], "e_up": lay["we_up"],
                       "e_down": lay["we_down"]}}
