"""Adapter for Phi-4-mini-flash configurations (``model_type: "phi4flash"``),
which run through the program's ``Phi4FlashConfig``, ``models/phi4flash.py``,
``ops/selective_scan.py``, ``llm/phi4flash_serving.py`` and the one
``llm/engine.py``.

The shape arithmetic lives here, with the yardstick, and imports nothing of
the program; the two functions at the bottom are the only ones that touch
it. The model is whole: every layer, the whole vocabulary, nothing cut.

Layer ``l`` of ``L``: ``l`` even and ``<= L/2`` a scan operator (9 of 32);
``l`` odd and ``< L/2`` differential attention over a window (8); ``l = L/2 +
1`` differential attention, full (1); ``l`` even above it a gated memory
unit (7); ``l`` odd above it a cross attention on that one full layer's keys
and values (7).

**What a position occupies and what a step reads are two numbers here.**
``kv_bytes_per_token`` is what a cached position occupies: one line, a key
and a value of every KV head (5,120 bytes). A decode step reads that line
``line_readers`` times (8: the full layer and the seven cross attentions),
so ``decode_step_bytes`` counts the live positions eight times, each ring up
to ``sliding_window`` rows, the states in and out and the weights once.

**``depth`` and the decode kernel's roofline.** ``depth`` is the decode
kernel's calls a step, 16: eight on the full line (``line_readers``) and one
on a ring in each of the 8 window layers. ``decode_attention_roofline``
takes ``depth`` for the kernel's calls a step on the time's side (the mean
event times ``depth`` is all the kernel's time of a step) and hands it to
``decode_attention_bytes``, which does not use it: the bytes are the live
positions of the one line once a reader, eight times, and the rings' reads
are left out. The share is a floor of bytes over all the kernel's time, so
it stays under 100. (With ``depth`` 8 the time would be eight mean events,
half of which are the short ring calls: the share would read up to twice
too high.)

What the four points of ``adapters/__init__.py`` needed: nothing new. The
cache is a dict of six leaves (``k``, ``v``, ``rk``, ``rv``, ``state``,
``conv``) and dropping the name frees them all; ``stats()`` carries this
model's counters (``ssm_state_updates``, ``ssm_chunk_tokens``,
``cross_decoder_chunks_skipped``) and the constants ``ssm_lines``,
``window_lines``, ``full_lines``, ``line_readers``, ``window``,
``ssm_state_bytes``.
"""

from __future__ import annotations

REFERENCE = "reference.phi4flash"

# The family's defaults (configuration_phi4flash.py) for what the catalog's
# config leaves out; the configuration file states them under ``assumed``.
SSM_DEFAULTS = {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2}


def _ssm(c: dict, key: str) -> int:
    return int(c.get(key, SSM_DEFAULTS[key]))


def half(c: dict) -> int:
    return c["num_hidden_layers"] // 2


def ssm_lines(c: dict) -> int:
    """Scan layers: the even layers up to ``L/2``."""
    return half(c) // 2 + 1


def window_lines(c: dict) -> int:
    return half(c) // 2


def cross_lines(c: dict) -> int:
    """Gated memory units, and as many cross attentions."""
    return (c["num_hidden_layers"] - half(c) - 2) // 2


def line_readers(c: dict) -> int:
    """Layers that read the one full line a step."""
    return 1 + cross_lines(c)


def depth(config: dict, use: str) -> int:
    """The decode kernel's calls a step (the module's docstring). Nothing
    depends on the use."""
    return window_lines(config) + line_readers(config)


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def kv_dim(c: dict) -> int:
    return c["num_key_value_heads"] * head_dim(c)


def d_inner(c: dict) -> int:
    return _ssm(c, "mamba_expand") * c["hidden_size"]


def dt_rank(c: dict) -> int:
    rank = c.get("mamba_dt_rank", "auto")
    return -(-c["hidden_size"] // 16) if rank == "auto" else int(rank)


def ssm_params(c: dict) -> int:
    """One scan operator: in_proj, the taps and their bias, x_proj, dt_proj
    with its bias, A_log, D, out_proj."""
    h, di, n, r = (c["hidden_size"], d_inner(c), _ssm(c, "mamba_d_state"),
                   dt_rank(c))
    return (h * 2 * di + di * _ssm(c, "mamba_d_conv") + di
            + di * (r + 2 * n) + r * di + di + di * n + di + di * h)


def lambda_params(c: dict) -> int:
    """Four ``lambda`` vectors of a head and the output norm of a pair."""
    return 6 * head_dim(c)


def attention_params(c: dict) -> int:
    """One self attention: Wqkv and out_proj with their biases."""
    h = c["hidden_size"]
    return (h * (h + 2 * kv_dim(c)) + h + 2 * kv_dim(c) + h * h + h
            + lambda_params(c))


def gmu_params(c: dict) -> int:
    return 2 * c["hidden_size"] * d_inner(c)


def cross_params(c: dict) -> int:
    h = c["hidden_size"]
    return 2 * (h * h + h) + lambda_params(c)


def mlp_params(c: dict) -> int:
    """gate_up and down, and the layer's two LayerNorms."""
    return 3 * c["hidden_size"] * c["intermediate_size"] \
        + 4 * c["hidden_size"]


def params_held(c: dict) -> int:
    """Every parameter: the operators, every layer's feed-forward and
    norms, the embedding (tied to the head) and the final norm."""
    h = c["hidden_size"]
    return (ssm_lines(c) * ssm_params(c)
            + (window_lines(c) + 1) * attention_params(c)
            + cross_lines(c) * (gmu_params(c) + cross_params(c))
            + c["num_hidden_layers"] * mlp_params(c)
            + c["vocab_size"] * h + 2 * h)


def kv_bytes_per_token(c: dict, layers: int, dtype_bytes: int = 2) -> int:
    """One cached position of the one full line: a key and a value of every
    KV head (20 x 64 x 2 x 2 bytes = 5,120). ``layers`` is not used: the
    model has one line that grows, whatever reads it."""
    return 2 * kv_dim(c) * dtype_bytes


def ring_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """One slot's ring in one window layer."""
    return c["sliding_window"] * 2 * kv_dim(c) * dtype_bytes


def ssm_state_bytes(c: dict) -> int:
    """One slot's state in one scan layer: d_inner x d_state float32
    (5,120 x 16 x 4 = 327,680)."""
    return d_inner(c) * _ssm(c, "mamba_d_state") * 4


def conv_window_bytes(c: dict, dtype_bytes: int = 2) -> int:
    return (_ssm(c, "mamba_d_conv") - 1) * d_inner(c) * dtype_bytes


def decode_step_bytes(c: dict, layers: int, live_kv_tokens: float,
                      dtype_bytes: int = 2, slots: int = 64) -> float:
    """Bytes one decode step must read: every weight once for the whole
    batch (the tied embedding once, as the head), the live positions of the
    one line once a reader, every slot's rings (a floor: a ring read before
    it is full is shorter) and every slot's states and windows, read and
    written."""
    weights = params_held(c) * dtype_bytes
    state = ssm_lines(c) * slots * (ssm_state_bytes(c)
                                    + conv_window_bytes(c, dtype_bytes))
    rings = window_lines(c) * slots * ring_bytes(c, dtype_bytes)
    return (weights
            + live_kv_tokens * kv_bytes_per_token(c, layers, dtype_bytes)
            * line_readers(c) + rings + 2 * state)


def decode_attention_bytes(c: dict, layers: int, positions: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes ``ops/decode_attention.py``'s kernel fetches from the full line
    for ``positions`` cached positions a step: a position's 5,120 bytes
    once a reader, eight readers. ``layers`` (``depth``: all the kernel's
    calls) is not used, and the rings' reads are left out, so the count is
    a floor (the module's docstring)."""
    return (positions * kv_bytes_per_token(c, layers, dtype_bytes)
            * line_readers(c))


def ssm_token_work(c: dict, dtype_bytes: int = 4) -> dict:
    """What the selective scan needs for ONE token in ONE scan layer,
    whichever form computes it: the recurrence's FLOPs (the decay's product
    and the state's multiply-add, the input's two products, the read's
    multiply-add: 6 a state and channel; the exponential is not counted)
    and the bytes of ``x``, ``dt`` and ``z`` (a channel each), ``B`` and
    ``C`` in and ``y`` out once, float32 as the scan takes them. The state
    is not counted: the chunk form keeps it on the chip from token to
    token."""
    di, n = d_inner(c), _ssm(c, "mamba_d_state")
    return {"flops": 6 * di * n,
            "bytes": (4 * di + 2 * n) * dtype_bytes}


def ssm_step_bytes(c: dict, updates: float) -> float:
    """What ``updates`` (slot, scan layer) pairs of a decode step must
    move: each state read once and written once."""
    return 2 * ssm_state_bytes(c) * updates


# ------------------------------------------------------------ the program

def model_config(config: dict, use: str, max_seq_len: int):
    from ray_tpu.models.phi4flash import Phi4FlashConfig

    if config["hidden_act"] != "silu" or not config["tie_word_embeddings"] \
            or config["mlp_bias"] or config["lm_head_bias"] \
            or not config.get("mamba_conv_bias", True) \
            or config.get("mamba_proj_bias", False):
        raise ValueError("Phi4FlashConfig runs silu, a tied head without "
                         "bias, a feed-forward without bias, a convolution "
                         "with bias and scan projections without")
    return Phi4FlashConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        sliding_window=config["sliding_window"],
        mb_per_layer=config["mb_per_layer"],
        mamba_d_state=_ssm(config, "mamba_d_state"),
        mamba_d_conv=_ssm(config, "mamba_d_conv"),
        mamba_expand=_ssm(config, "mamba_expand"),
        mamba_dt_rank=dt_rank(config), max_seq_len=max_seq_len,
        norm_eps=float(config["layer_norm_eps"]),
        dtype=config.get("torch_dtype", "bfloat16"))


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the names the plain reference
    uses. Matrices are [in, out]; a leaf of ``layers`` is stacked over the
    layers that have it, in layer order. No large leaf is copied: the fused
    ``Wqkv`` and ``gate_up_proj`` go as the column blocks the program keeps
    (the reference takes them so). Two small leaves go back to the
    published order: ``A_log`` [channels, states] and the taps [channels,
    taps] (``conv1d.weight``)."""
    lay = params["layers"]
    return {"embed": params["embed_tokens"],
            "final_norm": params["final_norm_w"],
            "final_norm_bias": params["final_norm_b"],
            "layers": {
                "norm1": lay["norm1_w"], "norm1_bias": lay["norm1_b"],
                "norm2": lay["norm2_w"], "norm2_bias": lay["norm2_b"],
                "gate": lay["w_gate"], "up": lay["w_up"],
                "down": lay["w_down"],
                "in_proj": lay["in_proj"],
                "conv": lay["conv_w"].transpose(0, 2, 1),
                "conv_bias": lay["conv_b"], "x_proj": lay["x_proj"],
                "dt_proj": lay["dt_proj"], "dt_bias": lay["dt_bias"],
                "a_log": lay["a_log"].transpose(0, 2, 1), "d": lay["d"],
                "ssm_out": lay["ssm_out"],
                "q": lay["wq"], "q_bias": lay["bq"], "k": lay["wk"],
                "k_bias": lay["bk"], "v": lay["wv"], "v_bias": lay["bv"],
                "o": lay["wo"], "o_bias": lay["bo"], "lam": lay["lam"],
                "subln": lay["subln"],
                "gmu_in": lay["gmu_in"], "gmu_out": lay["gmu_out"],
                "cross_q": lay["cross_wq"], "cross_q_bias": lay["cross_bq"],
                "cross_o": lay["cross_wo"], "cross_o_bias": lay["cross_bo"],
                "cross_lam": lay["cross_lam"],
                "cross_subln": lay["cross_subln"]}}
