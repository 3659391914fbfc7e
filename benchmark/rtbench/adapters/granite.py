"""Adapter for Granite 4.0-H configurations (``model_type:
"granitemoehybrid"``), which run through the program's ``GraniteConfig``,
``models/granite.py``, ``models/routed.py``, ``ops/ssd.py``,
``llm/granite_serving.py`` and the one ``llm/engine.py``.

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the two functions at the bottom are the only ones that touch
it. All of it is of **this chip's share**: the configuration file's
``num_local_experts`` is the number of experts held (the router keeps its
``published.num_local_experts`` outputs), its ``vocab_size`` the rows of the
vocabulary held, its ``num_hidden_layers`` the layers run here and its
``layer_types`` their kinds. Every layer's feed-forward is routed beside one
shared SwiGLU; the head is the embedding, counted once.

**Mamba-2's yardstick is defined on the work**, under the names the delta
rule's reader calls (``readers/delta_rule_roofline.py``: the rule is the
gated delta rule without its correction). A token in one Mamba layer is the
chunk form's products at ``mamba_n_heads`` heads of ``mamba_d_head`` x
``mamba_d_state`` (``delta_rule_token_work``: the within-chunk product a
head at a sub-chunk of ``YARDSTICK_SUB`` positions, the state's read and its
update, and ``C B^T`` **once for all heads**) against ``x``, ``B``, ``C``,
``dt`` in and ``y`` out; a step's bytes are a state of 4 MiB read once and
written once (``linear_step_bytes``). ``YARDSTICK_SUB`` is the yardstick's
own number, not read from the program: a later kernel at another sub-chunk
is read against the same work.

**``depth`` and the decode kernel's roofline.** ``depth`` is layers, 10.
Only ``attention_lines`` of them (1) have a cache line and call the decode
kernel, and ``decode_attention_roofline`` takes ``depth`` for the kernel's
calls a step on both sides (``adapters/lfm2.py``, "the one trap"): so
``decode_attention_bytes`` counts one call's bytes ``layers`` times, and the
share is a call's bytes over a call's time. ``kv_bytes_per_token`` and
``decode_step_bytes`` count what is there: one line.

What the four points of ``adapters/__init__.py`` needed: nothing new. The
cache is a dict of four leaves (``k``, ``v``, ``state``, ``conv``) and
dropping the name frees them all; ``stats()`` carries the router's counters
(``moe_*``), this model's own (``linear_state_updates``,
``linear_chunk_tokens``) and the constants ``moe_experts_held``,
``attention_lines``, ``linear_lines``, ``linear_state_bytes``.
"""

from __future__ import annotations

REFERENCE = "reference.granite"
# Positions of the sub-chunk the yardstick's within-chunk product is counted
# at: half an MXU's side, the delta rule's kernels' and the published ones'.
YARDSTICK_SUB = 64


def depth(config: dict, use: str) -> int:
    """Layers run here. Nothing depends on the use."""
    return int(config["num_hidden_layers"])


def attention_lines(c: dict) -> int:
    return list(c["layer_types"]).count("attention")


def linear_lines(c: dict) -> int:
    return list(c["layer_types"]).count("mamba")


def d_inner(c: dict) -> int:
    """All Mamba heads' channels side by side: 128 x 64."""
    return c["mamba_n_heads"] * c["mamba_d_head"]


def conv_dim(c: dict) -> int:
    """Channels of the convolution: ``x``, ``B`` and ``C``."""
    return d_inner(c) + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def mamba_params(c: dict) -> int:
    """One Mamba-2 mixer: in_proj (z | xBC | dt), the taps and their bias,
    dt_bias, A_log and D a head, the gated norm over d_inner, out_proj:
    102,286,976 at the published widths."""
    h, di, nh = c["hidden_size"], d_inner(c), c["mamba_n_heads"]
    return (h * (di + conv_dim(c) + nh)
            + conv_dim(c) * (c["mamba_d_conv"] + 1) + 3 * nh + di + di * h)


def attention_params(c: dict) -> int:
    """One attention: q, k, v, o, no bias: 41,943,040."""
    h, d = c["hidden_size"], head_dim(c)
    return (2 * h * c["num_attention_heads"] * d
            + 2 * h * c["num_key_value_heads"] * d)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def shared_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["shared_intermediate_size"]


def router_outputs(c: dict) -> int:
    return c["published"]["num_local_experts"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * router_outputs(c)


def layer_params(c: dict, kind: str, experts: int) -> int:
    """One layer with ``experts`` routed experts: its mixer, the router, the
    shared SwiGLU, the experts and two norms."""
    mixer = mamba_params(c) if kind == "mamba" else attention_params(c)
    return (mixer + router_params(c) + shared_params(c)
            + experts * expert_params(c) + 2 * c["hidden_size"])


def params_held(c: dict) -> int:
    """Every parameter this chip holds: every layer with the held experts,
    the final norm and the tied embedding over the held vocabulary, once."""
    h = c["hidden_size"]
    return (sum(layer_params(c, kind, c["num_local_experts"])
                for kind in c["layer_types"]) + h * c["vocab_size"] + h)


def kv_bytes_per_token(c: dict, layers: int, dtype_bytes: int = 2) -> int:
    """One cached position: a key and a value of ``head_dim`` in each KV
    head in each attention line (1 of the 10 layers: 2 x 8 x 128 x 2 bytes
    = 4 KiB). ``layers`` is not used: the lines are counted from
    ``layer_types``."""
    return (2 * head_dim(c) * c["num_key_value_heads"] * dtype_bytes
            * attention_lines(c))


def linear_state_bytes(c: dict) -> int:
    """One slot's state in one Mamba layer: a float32 matrix of d_state x
    head_dim a head (128 x 128 x 64 x 4 = 4 MiB)."""
    return d_inner(c) * c["mamba_d_state"] * 4


def conv_window_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """One slot's convolution window in one Mamba layer."""
    return (c["mamba_d_conv"] - 1) * conv_dim(c) * dtype_bytes


def experts_touched_uniform(c: dict, tokens: float) -> float:
    """Of the held experts, how many a layer-step of ``tokens`` tokens is
    expected to touch if every pick fell uniformly over the router's
    outputs: held x (1 - (1 - per_tok / outputs)^tokens)."""
    p = c["num_experts_per_tok"] / router_outputs(c)
    return c["num_local_experts"] * (1 - (1 - p) ** tokens)


def decode_step_bytes(c: dict, layers: int, live_kv_tokens: float,
                      dtype_bytes: int = 2, slots: int = 96) -> float:
    """Bytes one decode step must read: every mixer's and shared SwiGLU's
    weights once for the whole batch, the tied head, of each layer the held
    experts a step is expected to touch under uniform routing
    (``experts_touched_uniform`` at ``slots`` tokens a step: all 36 at 96),
    the cached positions live in the batch in the attention line, and every
    slot's state and window in the Mamba lines, read and written. The
    router's float32 weights count at their 4 bytes; norms are left out."""
    experts = experts_touched_uniform(c, slots) * expert_params(c)
    dense = (linear_lines(c) * mamba_params(c)
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
    ``positions`` cached positions in ONE call (a key and a value of 128 in
    each of the 8 KV heads: 4 KiB a position), times ``layers``: the reader
    takes ``depth`` for the kernel's calls a step on both sides (the
    module's docstring). Left out, so the count is a floor: the query rows
    and the output, and the lengths."""
    per_call = 2 * head_dim(c) * c["num_key_value_heads"] * dtype_bytes
    return positions * per_call * layers


def grouped_matmul_work(c: dict, experts_touched: float, rows: float,
                        dtype_bytes: int = 2) -> dict:
    """FLOPs and bytes of one routed layer's two grouped matmuls
    (``ops/grouped_matmul.py``: gate and up fused, then down) when
    ``experts_touched`` held experts got ``rows`` picks in all: the touched
    experts' weights once, the rows in and out (a floor: whole tiles and a
    fetch a tile are the kernel's own affair)."""
    h, f = c["hidden_size"], c["intermediate_size"]
    return {"flops": 2 * rows * 3 * h * f,
            "bytes": (experts_touched * expert_params(c)
                      + rows * (2 * h + 2 * f)) * dtype_bytes}


def delta_rule_token_work(c: dict, dtype_bytes: int = 4) -> dict:
    """What Mamba-2's rule needs for ONE token in ONE Mamba layer, as the
    chunk form's products: a head's within-chunk row against the
    sub-chunk's ``dt x`` (2 x YARDSTICK_SUB x P), the state's read at ``C``
    and its update at ``B`` (2 x N x P each), and one row of ``C B^T`` for
    all heads (2 x N x YARDSTICK_SUB, once: ``B`` and ``C`` are every
    head's); the bytes of ``x``, ``B``, ``C``, ``dt`` in and ``y`` out once
    (float32, as the rule takes them). The state is not counted: the
    chunked form keeps it on the chip from token to token."""
    nh, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    return {"flops": nh * (2 * YARDSTICK_SUB * p + 4 * n * p)
            + 2 * n * YARDSTICK_SUB,
            "bytes": (2 * nh * p + 2 * n + nh) * dtype_bytes}


def linear_step_bytes(c: dict, updates: float) -> float:
    """What ``updates`` (slot, Mamba layer) pairs of a decode step must
    move: each state read once and written once."""
    return 2 * linear_state_bytes(c) * updates


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.granite import GraniteConfig

    if config["position_embedding_type"] != "nope" \
            or config.get("rope_scaling") or config["hidden_act"] != "silu" \
            or config["normalization_function"] != "rmsnorm" \
            or config["attention_bias"] or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"] \
            or not config["tie_word_embeddings"]:
        raise ValueError(
            "GraniteConfig runs an attention without positions or bias, "
            "silu, RMSNorm, a Mamba-2 mixer with a bias on its convolution "
            "alone and a tied head")
    return GraniteConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"],
        num_experts=config["published"]["num_local_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        intermediate_size=config["intermediate_size"],
        shared_intermediate_size=config["shared_intermediate_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        expert_shard=int(config["expert_shard"]),
        expert_shards=int(config["expert_shards"]),
        max_seq_len=max_seq_len, norm_eps=float(config["rms_norm_eps"]),
        dtype=config.get("torch_dtype", "bfloat16"))


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses and in the published order. Matrices are [in, out]; a leaf of
    ``layers`` is stacked over the layers that have it, in layer order.

    The program keeps ``W_in``'s columns of ``xBC`` and then of ``z`` as
    ``in_xbcz`` and those of ``dt`` as ``in_dt``; the published matrix is
    ``z | xBC | dt`` and the reference splits it so (``z`` is as wide as
    ``out_proj`` is deep). The taps are the published ``conv1d`` weight,
    [channels, taps]. An expert's and the shared SwiGLU's ``input_linear``
    (published: gate and then up in one) stay the two leaves they are: the
    reference takes them apart, and no expert stack is copied."""
    import jax.numpy as jnp

    lay = params["layers"]
    di = lay["out_proj"].shape[1]
    xbc, z = lay["in_xbcz"][..., :-di], lay["in_xbcz"][..., -di:]
    return {"embed": params["embed_tokens"],
            "final_norm": params["final_norm"],
            "layers": {"input_norm": lay["input_norm"],
                       "post_norm": lay["post_norm"],
                       "in_proj": jnp.concatenate(
                           [z, xbc, lay["in_dt"]], axis=-1),
                       "conv": lay["conv_w"].transpose(0, 2, 1),
                       "conv_bias": lay["conv_b"],
                       "dt_bias": lay["dt_bias"], "a_log": lay["a_log"],
                       "d": lay["d_skip"], "norm": lay["ssm_norm"],
                       "out": lay["out_proj"],
                       "q": lay["wq"], "k": lay["wk"], "v": lay["wv"],
                       "o": lay["wo"],
                       "router": lay["router"],
                       "s_gate": lay["ws_gate"], "s_up": lay["ws_up"],
                       "s_down": lay["ws_down"],
                       "e_gate": lay["we_gate"], "e_up": lay["we_up"],
                       "e_down": lay["we_down"]}}
