"""Adapter for dense decoder configurations that run through the program's
``LlamaConfig``, ``models/llama.py`` and ``llm/engine.py`` (Mistral-7B).

A configuration file names its adapter; a model kind the harness has not
seen brings one such module. The shape arithmetic (parameters, FLOPs per
token, bytes a decode step must read) lives here, with the yardstick, and
imports nothing of the program; the three functions at the bottom are the
only ones that touch it.
"""

from __future__ import annotations

REFERENCE = "reference.dense"


def depth(config: dict, use: str) -> int:
    return int(config["num_hidden_layers"][use])


def attn_params_per_layer(c: dict) -> int:
    h, d = c["hidden_size"], c["head_dim"]
    return (h * c["num_attention_heads"] * d                # q
            + 2 * h * c["num_key_value_heads"] * d          # k, v
            + c["num_attention_heads"] * d * h)             # o


def mlp_params_per_layer(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def active_matmul_params(c: dict, layers: int) -> int:
    """Parameters a token multiplies with: every layer's projections and
    MLP, and the output head (the embedding is a lookup)."""
    return (layers * (attn_params_per_layer(c) + mlp_params_per_layer(c))
            + c["hidden_size"] * c["vocab_size"])


def attention_flops_per_token(c: dict, layers: int, seq_len: int,
                              backward: bool) -> float:
    """Causal attention, scores and values: a token at position p attends
    to p + 1 positions, (seq_len + 1) / 2 on average; 2 FLOPs a
    multiply-add, 2 matmuls (QK^T and PV); the backward pass costs twice
    the forward."""
    fwd = (2 * 2 * (seq_len + 1) / 2
           * c["num_attention_heads"] * c["head_dim"] * layers)
    return fwd * (3 if backward else 1)


def train_flops_per_token(c: dict, layers: int, seq_len: int) -> float:
    """FLOPs one token needs forward and backward; recomputation is not
    counted."""
    return (6 * active_matmul_params(c, layers)
            + attention_flops_per_token(c, layers, seq_len, backward=True))


def kv_bytes_per_token(c: dict, layers: int, dtype_bytes: int = 2) -> int:
    return (2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes
            * layers)


def decode_step_bytes(c: dict, layers: int, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step must read: every weight a token multiplies
    with, once for the whole batch, and the cached keys and values of the
    positions that are live in the batch."""
    return (active_matmul_params(c, layers) * dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(c, layers, dtype_bytes))


def decode_attention_bytes(c: dict, layers: int, positions: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes ``ops/decode_attention.py``'s kernel fetches from HBM for
    ``positions`` cached positions, summed over its one call a layer: a
    position is a key row and a value row of ``head_dim`` elements in each
    of the KV heads, so 2 x 8 x 128 x 2 bytes = 4 KiB a layer for
    Mistral-7B (``kv_bytes_per_token``). ``positions`` is the engine's
    ``kv_positions_read``: per decode step, each decoding slot's length
    rounded up to the kernel's block (the kernel fetches whole blocks and
    skips the blocks past a line's length, and the lines of slots that do
    not decode). Left out, so the count is a floor: the query rows and the
    output (slots x 32 heads x 128 x 2 bytes each, 0.26 MB a call against
    4 KiB x thousands of positions) and the lengths."""
    return positions * kv_bytes_per_token(c, layers, dtype_bytes)


def flash_kernel_work(c: dict, batch: int, seq_len: int) -> dict:
    """FLOPs and bytes of one call of the causal flash kernels on
    [batch, heads, seq, head_dim]: forward 2 matmuls over the causal half,
    backward 5 (the forward's two recomputed, dV, dP, dQ/dK counted as
    the published FlashAttention-2 count of 2.5x forward); bytes are q, k,
    v, o read or written once (and their gradients in the backward)."""
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    fwd_flops = 2 * 2 * batch * hq * seq_len * (seq_len + 1) / 2 * d
    q_bytes = batch * hq * seq_len * d * 2
    kv_bytes = 2 * batch * hkv * seq_len * d * 2
    return {"flash_fwd": {"flops": fwd_flops,
                          "bytes": 2 * q_bytes + kv_bytes},
            "flash_bwd": {"flops": 2.5 * fwd_flops,
                          "bytes": 4 * q_bytes + 2 * kv_bytes}}


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=depth(config, use),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]), rope_scaling=None,
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config.get("torch_dtype", "bfloat16"))


def train_step(model_cfg, mesh, optimizer, traffic: dict, seed: int):
    """(step_fn, init_state, shard, init_params_fn)."""
    from functools import partial

    from ray_tpu.models.llama import init_params
    from ray_tpu.train.spmd import make_llama_train_step

    step, init_state, shard = make_llama_train_step(
        model_cfg, mesh, optimizer=optimizer,
        attn_impl=traffic["attn_impl"], remat=traffic["remat"], seed=seed)
    return step, init_state, shard, partial(init_params, model_cfg)


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses. Matrices are [in, out], layers stacked on the leading axis."""
    lay = params["layers"]
    return {"embed": params["embed_tokens"], "head": params["lm_head"],
            "final_norm": params["final_norm"],
            "layers": {"q": lay["wq"], "k": lay["wk"], "v": lay["wv"],
                       "o": lay["wo"], "gate": lay["w_gate"],
                       "up": lay["w_up"], "down": lay["w_down"],
                       "attn_norm": lay["attn_norm"],
                       "mlp_norm": lay["mlp_norm"]}}
