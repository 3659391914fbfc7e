"""Adapter for Olmo-Hybrid configurations (``model_type: "olmo_hybrid"``),
which train through the program's ``OlmoHybridConfig``,
``models/olmo_hybrid.py``, ``ops/gated_delta.py`` and
``train/spmd.make_olmo_hybrid_train_step``. Training only.

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the three functions at the bottom are the only ones that touch
it. All of it is of **this chip's share**: the configuration file's
``vocab_size`` is the rows of the vocabulary held, its ``num_hidden_layers``
the layers run here under a use. Layer ``l`` is what ``layer_types[l]``
says: three Gated DeltaNet and one full attention a period.
"""

from __future__ import annotations

from rtbench.adapters import llama as _dense

REFERENCE = "reference.olmo_hybrid"
LINEAR = "linear_attention"


def depth(config: dict, use: str) -> int:
    return int(config["num_hidden_layers"][use])


def linear_layers(c: dict, layers: int) -> int:
    """Gated DeltaNet layers among the first ``layers``."""
    return sum(1 for kind in c["layer_types"][:layers] if kind == LINEAR)


def key_dim(c: dict) -> int:
    return c["linear_num_key_heads"] * c["linear_key_head_dim"]


def value_dim(c: dict) -> int:
    return c["linear_num_value_heads"] * c["linear_value_head_dim"]


def linear_matmul_params(c: dict) -> int:
    """What a token multiplies with in one Gated DeltaNet: q, k, v, the
    output gate, a, b, the output projection."""
    h = c["hidden_size"]
    return (2 * h * key_dim(c) + 2 * h * value_dim(c)
            + 2 * h * c["linear_num_value_heads"] + value_dim(c) * h)


def linear_params(c: dict) -> int:
    """One Gated DeltaNet: its matrices, three convolutions, ``A_log``,
    ``dt_bias``, the output norm."""
    return (linear_matmul_params(c)
            + c["linear_conv_kernel_dim"] * (2 * key_dim(c) + value_dim(c))
            + 2 * c["linear_num_value_heads"] + c["linear_value_head_dim"])


def attention_matmul_params(c: dict) -> int:
    h, d = c["hidden_size"], c["head_dim"]
    return (2 * h * c["num_attention_heads"] * d
            + 2 * h * c["num_key_value_heads"] * d)


def attention_params(c: dict) -> int:
    """One full attention: q, k, v, o and the two norms over all heads."""
    d = c["head_dim"]
    return (attention_matmul_params(c) + c["num_attention_heads"] * d
            + c["num_key_value_heads"] * d)


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def params_held(c: dict, layers: int, vocab: int | None = None) -> int:
    """Every parameter of ``layers`` layers, the final norm, the embedding
    and the untied head over ``vocab`` rows (the held ones by default)."""
    h = c["hidden_size"]
    vocab = c["vocab_size"] if vocab is None else vocab
    lin = linear_layers(c, layers)
    return (lin * linear_params(c) + (layers - lin) * attention_params(c)
            + layers * (mlp_params(c) + 2 * h) + 2 * vocab * h + h)


def active_matmul_params(c: dict, layers: int) -> int:
    """Parameters a token multiplies with: every projection of both
    operators, the SwiGLUs, the head (the embedding is a lookup)."""
    lin = linear_layers(c, layers)
    return (lin * linear_matmul_params(c)
            + (layers - lin) * attention_matmul_params(c)
            + layers * mlp_params(c) + c["hidden_size"] * c["vocab_size"])


def attention_flops_per_token(c: dict, layers: int, seq_len: int) -> float:
    """Causal attention in the full layers, forward and backward
    (``adapters/llama.attention_flops_per_token``'s count)."""
    full = layers - linear_layers(c, layers)
    return (3 * 2 * 2 * (seq_len + 1) / 2
            * c["num_attention_heads"] * c["head_dim"] * full)


def delta_rule_cell(c: dict) -> int:
    """``Dk x Dv`` over all heads: the state's size in one linear layer."""
    return (c["linear_num_value_heads"] * c["linear_key_head_dim"]
            * c["linear_value_head_dim"])


def train_flops_per_token(c: dict, layers: int, seq_len: int) -> float:
    """FLOPs one token needs forward and backward; recomputation is not
    counted. The rule: ``22 Dk Dv`` a head and linear layer, forward 7 (the
    recurrence's four products of ``Dk x Dv`` less the decay's pass:
    ``adapters/qwen3_next.delta_rule_token_work``'s count), backward 15
    (its eight)."""
    return (6 * active_matmul_params(c, layers)
            + attention_flops_per_token(c, layers, seq_len)
            + 22 * delta_rule_cell(c) * linear_layers(c, layers))


def delta_rule_train_token_work(c: dict, dtype_bytes: int = 2) -> dict:
    """The rule's work a token and linear layer over a whole train step,
    defined on the work and not on the implementation. FLOPs: forward ``7
    Dk Dv`` a head, backward ``15 Dk Dv`` and the state made again, ``7 Dk
    Dv``. Bytes: ``q``, ``k``, ``v``, ``g``, ``beta`` in and ``o`` out once
    forward; those and ``do`` in and the five gradients out once backward,
    at the dtype the configuration states. ``Dk`` and ``Dv`` as published,
    never a padded width; the forward that ``jax.checkpoint`` runs again is
    time spent and not work needed."""
    heads = c["linear_num_value_heads"]
    operands = 2 * key_dim(c) + value_dim(c) + 2 * heads       # q k v g beta
    fwd_bytes = (operands + value_dim(c)) * dtype_bytes
    bwd_bytes = (2 * operands + 2 * value_dim(c)) * dtype_bytes
    return {"flops": (7 + 15 + 7) * delta_rule_cell(c),
            "bytes": fwd_bytes + bwd_bytes}


# One call of each flash kernel in each full-attention layer, at this
# model's 30 heads of 128: the dense adapter's count.
flash_kernel_work = _dense.flash_kernel_work


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.olmo_hybrid import OlmoHybridConfig

    kinds = config["layer_types"]
    interval = kinds.index("full_attention") + 1
    return OlmoHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=depth(config, use),
        full_attention_interval=interval,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=bool(config["linear_allow_neg_eigval"]),
        max_seq_len=max_seq_len, norm_eps=float(config["rms_norm_eps"]),
        dtype=config.get("torch_dtype", "bfloat16"))


def train_step(model_cfg, mesh, optimizer, traffic: dict, seed: int):
    """(step_fn, init_state, shard, init_params_fn)."""
    from functools import partial

    from ray_tpu.models.olmo_hybrid import init_params
    from ray_tpu.train.spmd import make_olmo_hybrid_train_step

    step, init_state, shard = make_olmo_hybrid_train_step(
        model_cfg, mesh, optimizer=optimizer,
        attn_impl=traffic["attn_impl"], remat=traffic["remat"], seed=seed)
    return step, init_state, shard, partial(init_params, model_cfg)


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses: matrices [in, out], a leaf stacked over the layers that have it in
    layer order (the program stacks periods, then the layers of a kind
    inside one: period-major is layer order)."""
    lay = params["layers"]

    def flat(name):                    # [periods, n, ...] -> [periods n, ...]
        a = lay[name]
        return a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])

    return {"embed": params["embed_tokens"], "head": params["lm_head"],
            "final_norm": params["final_norm"],
            "layers": {
                "lin_q": flat("lin_wq"), "lin_k": flat("lin_wk"),
                "lin_v": flat("lin_wv"), "lin_g": flat("lin_wz"),
                "lin_a": flat("lin_wa"), "lin_b": flat("lin_wb"),
                "conv_q": flat("conv_q"), "conv_k": flat("conv_k"),
                "conv_v": flat("conv_v"), "a_log": flat("a_log"),
                "dt_bias": flat("dt_bias"), "o_norm": flat("o_norm"),
                "lin_o": flat("lin_wo"),
                "q": lay["wq"], "k": lay["wk"], "v": lay["wv"],
                "o": lay["wo"], "q_norm": lay["q_norm"],
                "k_norm": lay["k_norm"],
                "post_attn_norm": flat("post_attn_norm"),
                "post_ffn_norm": flat("post_ffn_norm"),
                "gate": flat("w_gate"), "up": flat("w_up"),
                "down": flat("w_down")}}
