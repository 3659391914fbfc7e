"""Adapter for Keye-VL-2.0 configurations (``model_type: "KeyeVL2"``), the
language model alone, which run through the program's ``KeyeConfig``,
``models/keye.py`` (the block is ``models/sdar.py``'s), ``models/routed.py``,
``ops/sparse_attention.py``, ``llm/keye_serving.py`` and the one
``llm/engine.py``.

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the two functions at the bottom are the only ones that touch
it. All of it is of **this chip's share**: the configuration file's
``num_experts`` is the number of experts held (the router keeps its
``published.num_experts`` outputs), its ``vocab_size`` the rows of the
vocabulary held, its ``num_hidden_layers`` the layers run here. Every layer
is alike: an attention with an indexer of its own, then a routed layer.

**The learned sparse attention's yardstick** counts the mathematics' bytes
and operations, not the implementation's, so that a later kernel is read by
the same numbers: a scored position is one index key read once
(``index_bytes_per_position``: 128 bytes a layer) and 2 x 16 x 64 multiply-
adds; a selected position is one key and one value in each KV head read
once (``selected_bytes_per_position``: 2,048 bytes a layer) and 4 x 32 x
128 multiply-adds. A program that reads a whole line where 2,048 positions
were chosen reads more than this and its share says so.

What the four points of ``adapters/__init__.py`` needed of a twelfth model
kind: nothing new. The cache is a dict of three leaves (``k``, ``v``,
``index_k``) and dropping the name frees them all; ``stats()`` carries the
router's counters (``moe_*``), the selection's (``index_rows``,
``index_positions_scored``, ``index_positions_selected`` and, of a decode
step's rows alone, ``index_step_positions_scored`` and
``index_step_positions_selected``) and the constants
``moe_experts_held``, ``attention_lines``, ``index_topk``.
"""

from __future__ import annotations

REFERENCE = "reference.keye"


def depth(config: dict, use: str) -> int:
    """Layers run here. Nothing depends on the use."""
    return int(config["num_hidden_layers"])


def attention_params(c: dict) -> int:
    """One attention: q, k, v, o and the two head norms."""
    h, d = c["hidden_size"], c["head_dim"]
    return (2 * h * c["num_attention_heads"] * d
            + 2 * h * c["num_key_value_heads"] * d + 2 * d)


def indexer_params(c: dict) -> int:
    """One indexer: the queries' projection, the key's, the heads'
    weights', and the key's LayerNorm (weight and bias)."""
    sa, h = c["sa_config"], c["hidden_size"]
    di = sa["indexer_head_dim"]
    return (h * (sa["indexer_num_heads"] * di + di
                 + sa["indexer_num_heads"]) + 2 * di)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_outputs(c: dict) -> int:
    return c["published"]["num_experts"]


def router_params(c: dict) -> int:
    """The gate (float32 in the program); no bias."""
    return c["hidden_size"] * router_outputs(c)


def layer_params(c: dict, experts: int) -> int:
    """One layer with ``experts`` experts: attention, indexer, router,
    experts, two norms."""
    return (attention_params(c) + indexer_params(c) + router_params(c)
            + experts * expert_params(c) + 2 * c["hidden_size"])


def params_held(c: dict) -> int:
    """Every parameter this chip holds: the layers with the held experts,
    the embedding, the head (its own matrix) and the final norm."""
    h = c["hidden_size"]
    return (c["num_hidden_layers"] * layer_params(c, c["num_experts"])
            + 2 * h * c["vocab_size"] + h)


def params_published(c: dict) -> int:
    """The published model's parameters, by the same count."""
    p, h = c["published"], c["hidden_size"]
    return (p["num_hidden_layers"] * layer_params(c, p["num_experts"])
            + 2 * h * p["vocab_size"] + h)


def index_bytes_per_position(c: dict, dtype_bytes: int = 2) -> int:
    """One scored position in one layer: the index key (64 x 2 bytes)."""
    sa = c["sa_config"]
    return sa["indexer_head_dim"] * sa["indexer_num_kv_heads"] * dtype_bytes


def selected_bytes_per_position(c: dict, dtype_bytes: int = 2) -> int:
    """One selected position in one layer: a key and a value of
    ``head_dim`` in each KV head (2 x 4 x 128 x 2 bytes = 2 KiB)."""
    return 2 * c["head_dim"] * c["num_key_value_heads"] * dtype_bytes


def kv_bytes_per_token(c: dict, layers: int, dtype_bytes: int = 2) -> int:
    """One cached position: keys, values and the index key in each layer
    (12 x (2,048 + 128) = 26,112 bytes)."""
    return layers * (selected_bytes_per_position(c, dtype_bytes)
                     + index_bytes_per_position(c, dtype_bytes))


def index_scores_work(c: dict, scored: float, dtype_bytes: int = 2) -> dict:
    """FLOPs and bytes of the index scores of ``scored`` (row, position)
    pairs in ONE layer, when each pair's key is read for that row alone (a
    decode row): 2 x heads x head_dim a pair, one index key a pair. The
    queries, the weights and the float32 scores out are left out (a
    floor)."""
    sa = c["sa_config"]
    return {"flops": 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
            * scored,
            "bytes": float(index_bytes_per_position(c, dtype_bytes)) * scored}


def index_chunk_work(c: dict, scored: float, rows: float,
                     dtype_bytes: int = 2) -> dict:
    """The same for the rows of prefill chunks, which share their line: a
    chunk of ``rows`` rows reads a position's key once for all of them, so
    the bytes are those of ``scored / rows`` positions and the float32
    scores written once a pair (what the selection reads)."""
    work = index_scores_work(c, scored, dtype_bytes)
    work["bytes"] = work["bytes"] / max(rows, 1.0) + 4.0 * scored
    return work


def sparse_attention_work(c: dict, selected: float,
                          dtype_bytes: int = 2) -> dict:
    """FLOPs and bytes of the attention over ``selected`` (row, chosen
    position) pairs in ONE layer: QK and PV for every query head, a key
    and a value in each KV head a pair."""
    return {"flops": 4.0 * c["num_attention_heads"] * c["head_dim"]
            * selected,
            "bytes": float(selected_bytes_per_position(c, dtype_bytes))
            * selected}


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.keye import KeyeConfig

    for key, want in (("attention_bias", False), ("use_sliding_window", False),
                      ("mlp_only_layers", []), ("decoder_sparse_step", 1),
                      ("tie_word_embeddings", False), ("hidden_act", "silu")):
        if config[key] != want:
            raise ValueError(f"{key} = {config[key]!r}: the program runs "
                             f"{want!r} alone")
    if config["rope_scaling"].get("rope_type", "default") != "default":
        raise ValueError("the program rotates unscaled: with text alone the "
                         "three components of mrope_section are one position")
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the program's indexer has one key a position")
    return KeyeConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        num_experts=router_outputs(config),
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        expert_shard=int(config["expert_shard"]),
        expert_shards=int(config["expert_shards"]),
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"],
        index_rope_dim=sa["indexer_head_dim"] // 2,
        index_topk=sa["topk"],
        max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=config.get("torch_dtype", "bfloat16"))


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses. Matrices are [in, out]; every leaf of ``layers`` is stacked over
    the layers."""
    lay = params["layers"]
    return {"embed": params["embed_tokens"], "head": params["lm_head"],
            "final_norm": params["final_norm"],
            "layers": {"input_norm": lay["attn_norm"],
                       "post_attention_norm": lay["ffn_norm"],
                       "q": lay["wq"], "k": lay["wk"], "v": lay["wv"],
                       "o": lay["wo"], "q_norm": lay["q_norm"],
                       "k_norm": lay["k_norm"],
                       "index_q": lay["wi_q"], "index_k": lay["wi_k"],
                       "index_w": lay["wi_w"],
                       "index_k_norm": lay["ik_norm"],
                       "index_k_bias": lay["ik_bias"],
                       "router": lay["router"],
                       "e_gate": lay["we_gate"], "e_up": lay["we_up"],
                       "e_down": lay["we_down"]}}
