"""Adapter for looped-decoder configurations (``model_type: "ouro"``), which
run through the program's ``OuroConfig``, ``models/ouro.py``,
``llm/ouro_serving.py`` and the one ``llm/engine.py``.

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the two functions at the bottom are the only ones that touch
it.

**What ``depth`` means here.** The stack of ``num_hidden_layers`` layers is
applied ``total_ut_steps`` times a token over one set of weights, and every
application has a cache line of its own. ``depth`` is *layer applications a
token*, 4 x 48 = 192: that is also the cache's lines and the kernel calls a
step, and it is what the readers that are there multiply by
(``decode_attention_roofline``: a kernel's mean time x calls a step;
``decode_bw_share``: a position's bytes x lines). The weights are a
quarter of that many layers; ``decode_step_bytes`` counts them once a pass
because a step must read them once a pass (5 GB do not stay on the chip
between passes, and pass t + 1 needs all of pass t). ``model_config`` reads
the two keys separately.
"""

from __future__ import annotations

REFERENCE = "reference.ouro"


def depth(config: dict, use: str) -> int:
    """Layer applications a token: passes x layers. Nothing is reduced, so
    no use changes it."""
    return int(config["total_ut_steps"]) * int(config["num_hidden_layers"])


def attn_params_per_layer(c: dict) -> int:
    h, d = c["hidden_size"], c["head_dim"]
    return (h * c["num_attention_heads"] * d                # q
            + 2 * h * c["num_key_value_heads"] * d          # k, v
            + c["num_attention_heads"] * d * h)             # o


def mlp_params_per_layer(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def matmul_params_per_layer(c: dict) -> int:
    """The seven matrices of a layer (its four norms left out)."""
    return attn_params_per_layer(c) + mlp_params_per_layer(c)


def params_held(c: dict) -> int:
    """Every parameter of the model, which one chip holds whole: the
    layers with their four norms, the embedding and the untied head, the
    final norm and the exit gate (a vector and a bias)."""
    h = c["hidden_size"]
    return (c["num_hidden_layers"] * (matmul_params_per_layer(c) + 4 * h)
            + 2 * h * c["vocab_size"] + h + h + 1)


def kv_bytes_per_token(c: dict, lines: int, dtype_bytes: int = 2) -> int:
    """One cached position: a key row and a value row in each KV head, in
    each of ``lines`` cache lines (``depth``: one a (pass, layer))."""
    return (2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes
            * lines)


def decode_step_bytes(c: dict, applications: int, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step must read: every layer's matrices once for
    each of its ``applications`` (``depth``: once a pass), the head once,
    and the cached keys and values of the positions live in the batch, in
    every line. The norms, the gate and the embedding's rows are left out
    (a floor)."""
    return ((applications * matmul_params_per_layer(c)
             + c["hidden_size"] * c["vocab_size"]) * dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(c, applications,
                                                  dtype_bytes))


def decode_attention_bytes(c: dict, applications: int, positions: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes ``ops/decode_attention.py``'s kernel fetches from HBM for
    ``positions`` cached positions, summed over its one call a layer
    application (as ``adapters/llama.decode_attention_bytes``, with the
    cache's lines for layers): 2 x 16 x 128 x 2 bytes = 8 KiB a line.
    ``positions`` is the engine's ``kv_positions_read``: per decode step,
    each decoding slot's length rounded up to the kernel's block. Left out,
    so the count is a floor: the query rows and the output (one row a head,
    slots x 16 x 128 x 2 bytes each a call) and the lengths."""
    return positions * kv_bytes_per_token(c, applications, dtype_bytes)


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.ouro import OuroConfig

    return OuroConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        total_ut_steps=config["total_ut_steps"],
        early_exit_threshold=float(config["early_exit_threshold"]),
        dtype=config.get("torch_dtype", "bfloat16"))


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses. Matrices are [in, out], layers stacked on the leading axis."""
    lay = params["layers"]
    return {"embed": params["embed_tokens"], "head": params["lm_head"],
            "final_norm": params["final_norm"],
            "gate_w": params["exit_gate"]["w"],
            "gate_b": params["exit_gate"]["b"],
            "layers": {"q": lay["wq"], "k": lay["wk"], "v": lay["wv"],
                       "o": lay["wo"], "gate": lay["w_gate"],
                       "up": lay["w_up"], "down": lay["w_down"],
                       "attn_norm": lay["attn_norm"],
                       "attn_post_norm": lay["attn_post_norm"],
                       "mlp_norm": lay["mlp_norm"],
                       "mlp_post_norm": lay["mlp_post_norm"]}}
