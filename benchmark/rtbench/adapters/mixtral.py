"""Adapter for sparse-expert configurations that run through the program's
``MixtralConfig`` and ``make_mixtral_train_step`` (Mixtral-8x7B). Training
only: the engine serves the dense family alone today."""

from __future__ import annotations

from rtbench.adapters import llama as _dense

REFERENCE = "reference.sparse"

depth = _dense.depth
attn_params_per_layer = _dense.attn_params_per_layer
attention_flops_per_token = _dense.attention_flops_per_token
flash_kernel_work = _dense.flash_kernel_work


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def active_matmul_params(c: dict, layers: int) -> int:
    """Per token: attention, the router, ``num_experts_per_tok`` experts
    (2 of 8), and the head."""
    per_layer = (attn_params_per_layer(c)
                 + c["hidden_size"] * c["num_local_experts"]
                 + c["num_experts_per_tok"] * expert_params(c))
    return layers * per_layer + c["hidden_size"] * c["vocab_size"]


def train_flops_per_token(c: dict, layers: int, seq_len: int) -> float:
    return (6 * active_matmul_params(c, layers)
            + attention_flops_per_token(c, layers, seq_len, backward=True))


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.mixtral import MixtralConfig

    dep = config["departures"]
    return MixtralConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=depth(config, use),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        num_experts=config["num_local_experts"],
        top_k=config["num_experts_per_tok"],
        capacity_factor=float(dep["capacity_factor"]["value"]),
        router_aux_coef=float(config["router_aux_loss_coef"]),
        dtype=config.get("torch_dtype", "bfloat16"))


def train_step(model_cfg, mesh, optimizer, traffic: dict, seed: int):
    from functools import partial

    from ray_tpu.models import mixtral
    from ray_tpu.train.spmd import make_mixtral_train_step

    step, init_state, shard = make_mixtral_train_step(
        model_cfg, mesh, optimizer=optimizer,
        attn_impl=traffic["attn_impl"], remat=traffic["remat"], seed=seed)
    return step, init_state, shard, partial(mixtral.init_params, model_cfg)


def reference_weights(params: dict) -> dict:
    lay = params["layers"]
    return {"embed": params["embed_tokens"], "head": params["lm_head"],
            "final_norm": params["final_norm"],
            "layers": {"q": lay["wq"], "k": lay["wk"], "v": lay["wv"],
                       "o": lay["wo"], "router": lay["router"],
                       "gate": lay["we_gate"], "up": lay["we_up"],
                       "down": lay["we_down"],
                       "attn_norm": lay["attn_norm"],
                       "mlp_norm": lay["mlp_norm"]}}
