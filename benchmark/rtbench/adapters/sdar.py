"""Adapter for SDAR-MoE configurations (``model_type: "sdar_moe"``), which
run through the program's ``SdarConfig``, ``models/sdar.py``,
``models/routed.py``, ``llm/sdar_serving.py`` and the one ``llm/engine.py``.

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the two functions at the bottom are the only ones that touch
it. The configuration file's ``num_hidden_layers`` is the layers run here;
every other published key is the published one, and every expert is held.
``block_length``, ``denoising_steps``, ``remasking_strategy``,
``confidence_threshold`` and ``mask_token_id`` are the file's too: none is a
key of the published ``config.json`` (the file's ``assumed`` says where
each comes from).

**A step is a forward.** One decode step of one line decides a block of
``block_length`` positions by ``denoising_steps + 1`` forwards of the
stack. The engine's ``decode_steps``, the ``steps`` of its
``engine.decode_dispatch`` phase, ``kv_positions_read`` (a kernel call a
layer a forward) and the router's ``moe_layer_steps`` all count
*forwards*, so every reader that divides by steps reads a forward's time
and a forward's bytes, comparable with every other cell's step.
``decode_step_bytes`` and ``decode_attention_bytes`` are a forward's.
What the readers cannot see: the commit forward computes no head, so a
fifth of the forwards read 0.58 GiB less than ``decode_step_bytes`` says
(the count is of a denoising forward; a share of the bandwidth read from
it is high by about 1.5%).

What the four points of ``adapters/__init__.py`` needed of a sixth model
kind: nothing new. (1) one ``LLMEngine``; (2) the engine reaches
``init_params`` through the module-level name; (3) the cache is the Llama
dict of ``k`` and ``v`` and dropping the name frees both; (4) ``stats()``
carries the router's counters (``moe_*``), the diffusion's
(``diffusion_blocks``, ``diffusion_forwards``, ``diffusion_commits``,
``diffusion_given``) and the constants ``moe_experts_held``,
``attention_lines``, ``diffusion_block_length``. What the *check* needed:
``kinds/serve_common._reference_check`` hands the reference a finished
sequence and neither the prompt's length nor the order in which a block's
positions took their tokens, so the cell runs the ``sequential`` rule,
under which that order is known (``reference/sdar.logits``).
"""

from __future__ import annotations

REFERENCE = "reference.sdar"


def depth(config: dict, use: str) -> int:
    """Layers run here. Nothing depends on the use."""
    return int(config["num_hidden_layers"])


def forwards_per_block(c: dict) -> int:
    """Forwards of the stack that decide one block: the denoising ones and
    the commit."""
    return c["denoising_steps"] + 1


def attention_params(c: dict) -> int:
    """One attention: q, k, v, o and the two head norms."""
    h, d = c["hidden_size"], c["head_dim"]
    return (2 * h * c["num_attention_heads"] * d
            + 2 * h * c["num_key_value_heads"] * d + 2 * d)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    """The gate (float32 in the program); no bias."""
    return c["hidden_size"] * c["num_experts"]


def layer_params(c: dict) -> int:
    """One layer: attention, router, every expert, two norms."""
    return (attention_params(c) + router_params(c)
            + c["num_experts"] * expert_params(c) + 2 * c["hidden_size"])


def params_held(c: dict) -> int:
    """Every parameter this chip holds: the layers with all their experts,
    the embedding, the head (its own matrix) and the final norm."""
    h = c["hidden_size"]
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * h * c["vocab_size"] + h)


def kv_bytes_per_token(c: dict, layers: int, dtype_bytes: int = 2) -> int:
    """One cached position: a key and a value of ``head_dim`` in each KV
    head of each layer (6 x 2 x 4 x 128 x 2 bytes = 12 KiB)."""
    return (2 * c["head_dim"] * c["num_key_value_heads"] * dtype_bytes
            * layers)


def experts_touched_uniform(c: dict, tokens: float) -> float:
    """How many of a layer's experts a forward over ``tokens`` rows is
    expected to touch if every pick fell uniformly over them."""
    picks = tokens * c["num_experts_per_tok"]
    return c["num_experts"] * (1 - (1 - 1 / c["num_experts"]) ** picks)


def decode_step_bytes(c: dict, layers: int, live_kv_tokens: float,
                      dtype_bytes: int = 2, *, slots: int) -> float:
    """Bytes ONE forward of a block must read (a denoising forward; the
    module's docstring): every attention's weights once for the whole
    batch, the head, of each layer the experts a forward is expected to
    touch under uniform routing (``slots`` lines x ``block_length`` rows:
    128.0 of 128 at 512 rows), and the cached positions live in the batch
    in every layer. The router's float32 weights count at their 4 bytes;
    the embedding's gathered rows and the norms are left out. ``slots`` is
    the caller's to give (the traffic's ``engine.max_num_seqs``), no
    cell's is a default here: the one reader of this function,
    ``decode_bw_share``, gives none and is not on this model's cell
    (PERF.md section 7), so the `benchmark` PR that puts it there passes
    the lines too."""
    rows = slots * c["block_length"]
    experts = experts_touched_uniform(c, rows) * expert_params(c)
    dense = layers * attention_params(c) + c["hidden_size"] * c["vocab_size"]
    return ((dense + layers * experts) * dtype_bytes
            + layers * router_params(c) * 4
            + live_kv_tokens * kv_bytes_per_token(c, layers, dtype_bytes))


def decode_attention_bytes(c: dict, layers: int, positions: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes ``ops/decode_attention.py``'s kernel fetches from HBM for
    ``positions`` cached positions in one call, times ``layers`` (one call
    a layer a forward): a key and a value of 128 in each of the 4 KV heads,
    2 KiB a position. ``positions`` is the engine's ``kv_positions_read``
    over ``decode_steps``: per forward, each decoding line's length through
    its block's end rounded up to the kernel's block. At 4 query rows a
    line (32 rows a KV head) the keys and values are still all that counts:
    left out, so the count is a floor, are the query rows and the output
    (2 x 4 x 32 x 128 x 2 bytes = 64 KiB a line a call against 0.6 to 3 MiB
    of keys and values) and the lengths."""
    per_call = 2 * c["head_dim"] * c["num_key_value_heads"] * dtype_bytes
    return positions * per_call * layers


def grouped_matmul_work(c: dict, experts_touched: float, rows: float,
                        dtype_bytes: int = 2) -> dict:
    """FLOPs and bytes of one routed layer's two grouped matmuls
    (``ops/grouped_matmul.py``: gate and up fused, then down) when
    ``experts_touched`` experts got ``rows`` picks in all: each touched
    expert's three matrices once, the rows in and out. That the kernel
    multiplies whole tiles, and reads an expert's weights once a tile, is
    its own affair and not counted (a floor)."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    return {"flops": 2 * rows * 3 * h * f,
            "bytes": (experts_touched * expert_params(c)
                      + rows * (2 * h + 2 * f)) * dtype_bytes}


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.sdar import SdarConfig

    for key, want in (("attention_bias", False), ("use_sliding_window", False),
                      ("mlp_only_layers", []), ("decoder_sparse_step", 1),
                      ("rope_scaling", None), ("tie_word_embeddings", False),
                      ("hidden_act", "silu")):
        if config[key] != want:
            raise ValueError(f"{key} = {config[key]!r}: the program runs "
                             f"{want!r} alone")
    return SdarConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        block_length=config["block_length"],
        denoising_steps=config["denoising_steps"],
        remasking_strategy=config["remasking_strategy"],
        confidence_threshold=float(config["confidence_threshold"]),
        mask_token_id=config["mask_token_id"],
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
                       "k_norm": lay["k_norm"], "router": lay["router"],
                       "e_gate": lay["we_gate"], "e_up": lay["we_up"],
                       "e_down": lay["we_down"]}}
