"""Phi-4-mini-flash family (SambaY): a decoder-hybrid-decoder with
differential attention.

The family of ``model_type: "phi4flash"`` (huggingface.co/microsoft/
Phi-4-mini-flash-reasoning; arXiv:2507.06607). ``L`` layers, input ``h``,
``LN`` a LayerNorm with bias (statistics in float32)::

    a  = h + Mix_l(LN(h))
    h' = a + F(LN(a))          F(u) = W_down (silu(W_gate u) * W_up u)

The first ``L/2 + 2`` layers are the **self-decoder** and each keeps
something of every position it has seen; the last ``L/2 - 2`` are the
**cross-decoder** and keep nothing:

- ``l`` even, ``l <= L/2``: the **scan operator** (Mamba-1). ``[x | z] =
  W_in u``; ``x = silu(conv(x) + b_c)``, a depthwise causal convolution of
  ``d_conv`` taps; ``[dt_r | B | C] = W_x x``; ``dt = softplus(W_dt dt_r +
  b_dt)``, ``A = -exp(A_log)``, float32; then the selective scan
  (ops/selective_scan.py) from a zero state gives ``y``, and the operator
  ``W_out (y * silu(z))``. Layer ``L/2``'s ``y``, before the gate, is the
  **memory** ``m`` that the cross-decoder reads. What a token leaves behind
  is the scan's state and the last ``d_conv - 1`` rows of ``x`` before the
  convolution.
- ``l`` odd, ``l < L/2``: differential attention over a **window**: a
  position attends the ``sliding_window`` positions that end at itself.
- ``l = L/2 + 1``: differential attention, causal, **full**. Its keys and
  values are the model's only full cache line.
- ``l`` even, ``l >= L/2 + 2``: the **gated memory unit**, ``W_out
  (silu(W_in u) * m)`` with ``m`` the memory at the same position.
- ``l`` odd, ``l >= L/2 + 3``: **cross** differential attention: a query
  and an output projection of its own, the keys and values of layer
  ``L/2 + 1``, causal.

So nothing a cross-decoder layer computes is read at a later position, and
a prompt needs those layers at its last position only.

**Differential attention.** ``num_heads`` query heads and ``num_kv_heads``
KV heads of ``head_dim`` are ``num_heads / 2`` query pairs and
``num_kv_heads / 2`` KV pairs; head ``2i`` is ``q1_i`` and head ``2i + 1``
``q2_i`` (keys and values alike). With ``S1 = softmax(q1 k1^T / sqrt(d))``,
``S2 = softmax(q2 k2^T / sqrt(d))`` and ``V = [v1 | v2]``::

    o_i = (S1 - lambda S2) V
    o_i = RMS(o_i; w, eps) (1 - lambda_init)

``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, four learned
vectors a layer, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``. No rotary and no
other positional term: only the masks know positions.

**The pairs are packed.** A KV pair is stored and attended as one head of
``2 head_dim``: ``k = [k1 | k2]``, ``v = [v1 | v2]``, which is the
projection's output as it lies. A query pair is two query heads of that
width, ``[q1 | 0]`` and ``[0 | q2]``: their products with a packed key are
``q1 . k1`` and ``q2 . k2``, so both softmaxes run through the kernels of
ops/decode_attention.py and ops/prefill_attention.py at a head of 128 with
``sm_scale = head_dim^-1/2``, and a cached byte is read once for both.

Params: a flat pytree, every leaf stacked over the layers that have it (the
norms and the feed-forward over all layers, the scan's leaves over the scan
layers, the attention's over the window layers and then the full one, the
unit's and the cross attention's over theirs) and indexed by the loop's
counter where it is used. The published ``Wqkv`` and ``gate_up_proj`` are
kept as their parts (``wq``, ``wk``, ``wv``; ``w_gate``, ``w_up``) and
``A_log`` transposed to ``[d_state, d_inner]``: the same numbers
(benchmark/rtbench/adapters/phi4flash.reference_weights puts them back).
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.lfm2 import swiglu
from ray_tpu.models.routed import layer_of
from ray_tpu.ops.decode_attention import NEG_INF
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import layer_norm, rms_norm_reference
from ray_tpu.ops.selective_scan import selective_scan_chunk
from ray_tpu.util import tracing

SSM_LEAVES = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias",
              "a_log", "d", "ssm_out")
ATTN_LEAVES = ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo", "lam",
               "subln")
GMU_LEAVES = ("gmu_in", "gmu_out")
CROSS_LEAVES = ("cross_wq", "cross_bq", "cross_wo", "cross_bo", "cross_lam",
                "cross_subln")


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0              # 0: "auto", ceil(hidden / 16)
    max_seq_len: int = 262144
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.mb_per_layer != 2:
            raise ValueError("mb_per_layer must be 2: scan and attention "
                             "layers alternate")
        if self.num_layers % 4 or self.num_layers < 8:
            raise ValueError(
                f"{self.num_layers} layers: the self-decoder is L/2 + 2 "
                "layers in (scan, attention) pairs, L a multiple of 4 and "
                "at least 8")
        if self.num_heads % 2 or self.num_kv_heads % 2 \
                or self.num_heads % self.num_kv_heads \
                or self.hidden_size % self.num_heads:
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} KV "
                "heads: differential attention pairs both")
        if self.mamba_d_conv < 2:
            raise ValueError("mamba_d_conv under 2 leaves no window")

    @staticmethod
    def tiny(**kw) -> "Phi4FlashConfig":
        """Test-size config with every mechanism: layers 0 to 3 scan and
        window by turns, 4 the memory layer, 5 the full layer, 6 a gated
        memory unit, 7 a cross attention; a window of 8; 2 KV pairs for 4
        query pairs."""
        base = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                    num_layers=8, num_heads=8, num_kv_heads=4,
                    sliding_window=8, mamba_d_state=4, max_seq_len=256,
                    dtype="float32")
        base.update(kw)
        return Phi4FlashConfig(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def pair_dim(self) -> int:
        """A packed pair: two heads side by side."""
        return 2 * self.head_dim

    @property
    def kv_pairs(self) -> int:
        return self.num_kv_heads // 2

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    @property
    def half(self) -> int:
        """The memory layer's index: the self-decoder's last scan."""
        return self.num_layers // 2

    @property
    def ssm_lines(self) -> int:
        """Layers that leave a scan state and a convolution window."""
        return self.half // 2 + 1

    @property
    def window_lines(self) -> int:
        """Layers that leave the last ``sliding_window`` keys and values."""
        return self.half // 2

    @property
    def cross_lines(self) -> int:
        """Gated memory units, and as many cross attentions."""
        return (self.num_layers - self.half - 2) // 2

    @property
    def line_readers(self) -> int:
        """Layers that read the one full line: its own and the cross
        attentions."""
        return 1 + self.cross_lines

    @property
    def ssm_state_bytes(self) -> int:
        """One slot's state in one scan layer (float32)."""
        return self.mamba_d_state * self.d_inner * 4

    def lambda_init(self, layer):
        """``0.8 - 0.6 exp(-0.3 l)``, of a static or a run-time index."""
        return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))

    def num_params(self) -> int:
        h, f, di = self.hidden_size, self.intermediate_size, self.d_inner
        n, r, d = self.mamba_d_state, self.dt_rank, self.head_dim
        ssm = (h * 2 * di + di * self.mamba_d_conv + di + di * (r + 2 * n)
               + r * di + di + di * n + di + di * h)
        lam = 4 * d + 2 * d
        attn = h * (h + 2 * self.kv_dim) + h + 2 * self.kv_dim \
            + h * h + h + lam
        gmu = 2 * h * di
        cross = 2 * (h * h + h) + lam
        return (self.ssm_lines * ssm + (self.window_lines + 1) * attn
                + self.cross_lines * (gmu + cross)
                + self.num_layers * (3 * h * f + 4 * h)
                + self.vocab_size * h + 2 * h)


def param_logical_axes(cfg: Phi4FlashConfig) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules).
    ``layers`` is the stacked axis, over whichever layers have the leaf."""
    vec, mat = ("layers", None), ("layers", None, None)
    return {
        "embed_tokens": ("vocab", "embed"),
        "final_norm_w": ("embed",), "final_norm_b": ("embed",),
        "layers": {
            "norm1_w": ("layers", "embed"), "norm1_b": ("layers", "embed"),
            "norm2_w": ("layers", "embed"), "norm2_b": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
            "in_proj": ("layers", "embed", None), "conv_w": mat,
            "conv_b": vec, "x_proj": mat, "dt_proj": mat, "dt_bias": vec,
            "a_log": mat, "d": vec, "ssm_out": ("layers", None, "embed"),
            "wq": ("layers", "embed", "heads"), "bq": vec,
            "wk": ("layers", "embed", "kv_heads"), "bk": vec,
            "wv": ("layers", "embed", "kv_heads"), "bv": vec,
            "wo": ("layers", "heads", "embed"), "bo": vec,
            "lam": mat, "subln": vec,
            "gmu_in": ("layers", "embed", None),
            "gmu_out": ("layers", None, "embed"),
            "cross_wq": ("layers", "embed", "heads"), "cross_bq": vec,
            "cross_wo": ("layers", "heads", "embed"), "cross_bo": vec,
            "cross_lam": mat, "cross_subln": vec,
        },
    }


# The steps a seeded channel is centred on, log-uniform as Mamba's own
# initialiser draws them; with ``A[n] = -(n + 1)`` a token's decay
# ``exp(dt A)`` then spreads over about 0.2 (dt 0.1, n 16) to 0.999.
DT_RANGE = (1e-3, 0.1)


def init_params(cfg: Phi4FlashConfig, key: jax.Array) -> dict:
    """Scaled-normal init that keeps every projection's output at unit
    variance, with every output projection (the operators' and the
    feed-forward's ``down``) scaled by 1 / sqrt(2 L) so that the residual
    stream stays of unit size through the depth. What a trained checkpoint
    has and the published defaults would hide is drawn too, so that a test
    or a margin sees it: the LayerNorms' weights near 1 and biases near 0
    (normal at 0.1), the projections' biases, the convolution's taps at
    1/sqrt(taps) and its bias, ``D`` near 1, the ``lambda`` vectors normal
    at 0.1, the attention's output norm near 1; the scan's decay as
    Mamba's initialiser draws it: ``A[n, c] = -(n + 1)`` and ``dt_bias``
    the inverse softplus of a step log-uniform over DT_RANGE."""
    h, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    di, n, r, d = cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank, cfg.head_dim
    ns, na, nc = cfg.ssm_lines, cfg.window_lines + 1, cfg.cross_lines
    dt = cfg.jnp_dtype
    keys = iter(jax.random.split(key, 48))
    out = 1.0 / math.sqrt(2 * L)

    def matrix(*shape, scale=None, dtype=dt):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def near(centre, *shape, dtype=dt):
        return (centre + 0.1 * jax.random.normal(next(keys), shape,
                                                 jnp.float32)).astype(dtype)

    step = jnp.exp(jax.random.uniform(
        next(keys), (ns, di), jnp.float32, math.log(DT_RANGE[0]),
        math.log(DT_RANGE[1])))
    return {
        # Tied to the head: rows of 1 / sqrt(hidden) give logits of unit
        # variance.
        "embed_tokens": matrix(cfg.vocab_size, h, scale=h ** -0.5),
        "final_norm_w": near(1.0, h), "final_norm_b": near(0.0, h),
        "layers": {
            "norm1_w": near(1.0, L, h), "norm1_b": near(0.0, L, h),
            "norm2_w": near(1.0, L, h), "norm2_b": near(0.0, L, h),
            "w_gate": matrix(L, h, f), "w_up": matrix(L, h, f),
            "w_down": matrix(L, f, h, scale=out / math.sqrt(f)),
            "in_proj": matrix(ns, h, 2 * di),
            "conv_w": matrix(ns, cfg.mamba_d_conv, di,
                             scale=1.0 / math.sqrt(cfg.mamba_d_conv)),
            "conv_b": near(0.0, ns, di),
            "x_proj": matrix(ns, di, r + 2 * n),
            "dt_proj": matrix(ns, r, di, scale=r ** -0.5, dtype=jnp.float32),
            # softplus^-1(step): step + log(1 - exp(-step)).
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None, :,
                                                                 None],
                (ns, n, di)),
            "d": near(1.0, ns, di, dtype=jnp.float32),
            "ssm_out": matrix(ns, di, h, scale=out / math.sqrt(di)),
            "wq": matrix(na, h, h), "bq": near(0.0, na, h),
            "wk": matrix(na, h, cfg.kv_dim), "bk": near(0.0, na, cfg.kv_dim),
            "wv": matrix(na, h, cfg.kv_dim), "bv": near(0.0, na, cfg.kv_dim),
            "wo": matrix(na, h, h, scale=out / math.sqrt(h)),
            "bo": near(0.0, na, h),
            "lam": near(0.0, na, 4, d, dtype=jnp.float32),
            "subln": near(1.0, na, 2 * d),
            "gmu_in": matrix(nc, h, di),
            "gmu_out": matrix(nc, di, h, scale=out / math.sqrt(di)),
            "cross_wq": matrix(nc, h, h), "cross_bq": near(0.0, nc, h),
            "cross_wo": matrix(nc, h, h, scale=out / math.sqrt(h)),
            "cross_bo": near(0.0, nc, h),
            "cross_lam": near(0.0, nc, 4, d, dtype=jnp.float32),
            "cross_subln": near(1.0, nc, 2 * d),
        },
    }


# ---------------------------------------------------------------- blocks

def feed_forward(cfg: Phi4FlashConfig, layers: dict, index, x):
    """``x + F(LN(x))`` with layer ``index``'s second norm."""
    with tracing.part("mlp"):
        u = layer_norm(x, layer_of(layers["norm2_w"], index),
                       layer_of(layers["norm2_b"], index), cfg.norm_eps)
        return x + swiglu(u, *(layer_of(layers[k], index)
                               for k in ("w_gate", "w_up", "w_down")))


def ssm_inputs(cfg: Phi4FlashConfig, sp: dict, xn):
    """xn [..., H] (normed) -> (x [..., d_inner], the convolution's input,
    whose last rows a sequence keeps; z [..., d_inner], the output's
    gate)."""
    with tracing.part("ssm"):
        xz = xn @ sp["in_proj"]
        return xz[..., :cfg.d_inner], xz[..., cfg.d_inner:]


def conv_window(prior, x):
    """``prior`` [B, taps - 1, d_inner] and then the rows ``x`` [B, S,
    d_inner] of this call: what the taps slide over, and what the window
    kept is cut from."""
    with tracing.part("ssm_state"):
        return jnp.concatenate([prior.astype(x.dtype), x], axis=1)


def ssm_scan_inputs(cfg: Phi4FlashConfig, sp: dict, window, s: int):
    """The convolution (with its bias) over ``window`` [B, taps - 1 + S,
    d_inner] at its last ``s`` positions, ``silu``, and what the scan takes
    of it, float32: (x [B, S, d_inner], dt [B, S, d_inner], A [N, d_inner],
    B, C [B, S, N])."""
    with tracing.part("ssm"):
        taps = sp["conv_w"].astype(jnp.float32)          # [taps, d_inner]
        x = jax.nn.silu(sp["conv_b"].astype(jnp.float32) + sum(
            taps[j] * window[:, j:j + s].astype(jnp.float32)
            for j in range(cfg.mamba_d_conv)))
        r, n = cfg.dt_rank, cfg.mamba_d_state
        low = jnp.dot(x.astype(window.dtype), sp["x_proj"],
                      preferred_element_type=jnp.float32)
        dt_r, b, c = jnp.split(low, (r, r + n), axis=-1)
        # The step is a number a channel that an exponential takes: its
        # product runs at true float32 (a TPU's default float32 product is
        # one bfloat16 pass).
        dt = jax.nn.softplus(jnp.dot(
            dt_r, sp["dt_proj"], precision=lax.Precision.HIGHEST)
            + sp["dt_bias"])
        return x, dt, -jnp.exp(sp["a_log"]), b, c


def ssm_output(sp: dict, y, z, dtype):
    """The scan's output y [B, S, d_inner] (the stored dtype) under its
    gate's ``silu`` and projected out."""
    with tracing.part("ssm"):
        gated = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
        return (gated @ sp["ssm_out"]).astype(dtype)


def gated_memory_unit(gp: dict, xn, m):
    """``W_out (silu(W_in xn) * m)``: m [..., d_inner] is the memory at
    xn's positions."""
    with tracing.part("gmu"):
        dt = xn.dtype
        gate = jax.nn.silu((xn @ gp["gmu_in"]).astype(jnp.float32)).astype(dt)
        return ((gate * m.astype(dt)) @ gp["gmu_out"]).astype(dt)


def pack_queries(cfg: Phi4FlashConfig, q):
    """q [B, S, H] as projected (head ``2i`` is ``q1_i``, head ``2i + 1``
    ``q2_i``) -> [B, num_heads, S, 2 d]: ``[q1 | 0]`` and ``[0 | q2]``, a
    pair's two heads one after the other."""
    b, s, _ = q.shape
    q = q.reshape(b, s, cfg.num_heads // 2, 2, cfg.head_dim)
    first = jnp.arange(2)[:, None] == 0                       # [2, 1]
    packed = jnp.concatenate([jnp.where(first, q, 0),
                              jnp.where(first, 0, q)], axis=-1)
    return packed.reshape(b, s, cfg.num_heads,
                          cfg.pair_dim).transpose(0, 2, 1, 3)


def attention_heads(cfg: Phi4FlashConfig, ap: dict, xn):
    """xn [B, S, H] (normed) -> the packed queries [B, num_heads, S, 2 d]
    and the packed keys and values [B, kv_pairs, S, 2 d]: a pair's ``[k1 |
    k2]`` and ``[v1 | v2]`` are the projection's output as it lies."""
    b, s, _ = xn.shape
    # Arrays of their own before they are split into heads
    # (models/lfm2.attention_heads).
    q, k, v = lax.optimization_barrier(
        (xn @ ap["wq"] + ap["bq"], xn @ ap["wk"] + ap["bk"],
         xn @ ap["wv"] + ap["bv"]))
    pairs = lambda a: a.reshape(  # noqa: E731
        b, s, cfg.kv_pairs, cfg.pair_dim).transpose(0, 2, 1, 3)
    return pack_queries(cfg, q), pairs(k), pairs(v)


def differential_output(cfg: Phi4FlashConfig, lam, subln, wo, bo, o, index,
                        dtype):
    """o [B, num_heads, S, 2 d], the two softmaxes' products with ``[v1 |
    v2]`` of every pair one after the other -> ``W_o`` of the pairs'
    ``RMS(o1 - lambda o2) (1 - lambda_init)`` side by side, [B, S, H].
    ``index`` is the layer's (a run-time value): ``lambda_init`` depends on
    it."""
    b, _, s, d2 = o.shape
    o = o.astype(jnp.float32).reshape(b, cfg.num_heads // 2, 2, s, d2)
    lam = lam.astype(jnp.float32)
    init = cfg.lambda_init(index)
    full = (jnp.exp(jnp.sum(lam[0] * lam[1]))
            - jnp.exp(jnp.sum(lam[2] * lam[3])) + init)
    diff = o[:, :, 0] - full * o[:, :, 1]                 # [B, pairs, S, 2d]
    diff = rms_norm_reference(diff, subln, cfg.norm_eps) * (1.0 - init)
    diff = diff.transpose(0, 2, 1, 3).reshape(b, s, -1).astype(dtype)
    return (diff @ wo + bo).astype(dtype)


def attention_output(cfg: Phi4FlashConfig, ap: dict, o, index, dtype):
    return differential_output(cfg, ap["lam"], ap["subln"], ap["wo"],
                               ap["bo"], o, index, dtype)


def cross_queries(cfg: Phi4FlashConfig, cp: dict, xn):
    return pack_queries(cfg, xn @ cp["cross_wq"] + cp["cross_bq"])


def cross_output(cfg: Phi4FlashConfig, cp: dict, o, index, dtype):
    return differential_output(cfg, cp["cross_lam"], cp["cross_subln"],
                               cp["cross_wo"], cp["cross_bo"], o, index,
                               dtype)


def packed_attention(q, k, v, visible, sm_scale: float):
    """Masked softmax attention in jnp, grouped like the kernels (no
    repeated K/V): q [B, heads, S, D], k and v [B, kv heads, K, D], visible
    [S, K] or [B, S, K] bool -> [B, heads, S, D]. Float32 scores and
    accumulation; a row that sees nothing gives zeros."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, (h // hkv) * s, d)
    scores = jnp.einsum("bhrd,bhkd->bhrk", qg, k.astype(q.dtype),
                        preferred_element_type=jnp.float32) * sm_scale
    vis = jnp.broadcast_to(visible, (b, s, visible.shape[-1]))
    vis = jnp.tile(vis, (1, h // hkv, 1))[:, None]        # rows g * S + t
    scores = jnp.where(vis, scores, NEG_INF)
    p = jnp.where(vis, jnp.exp(scores - scores.max(-1, keepdims=True)), 0.0)
    denom = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhrk,bhkd->bhrd", p.astype(q.dtype), v.astype(q.dtype),
                     preferred_element_type=jnp.float32) / denom
    return out.astype(q.dtype).reshape(b, h, s, d)


def window_visible(qpos, kpos, window: int):
    """qpos [..., S], kpos [..., K] -> [..., S, K]: a query sees the
    ``window`` positions that end at its own; a key position under 0 is
    nobody's."""
    q, k = qpos[..., :, None], kpos[..., None, :]
    return (k <= q) & (k > q - window) & (k >= 0)


# ----------------------------------------------------------------- stack

def _norm1(cfg, layers, index, x):
    return layer_norm(x, layer_of(layers["norm1_w"], index),
                      layer_of(layers["norm1_b"], index), cfg.norm_eps)


def _leaves(layers, names, line):
    with tracing.part("stack"):
        return {k: layer_of(layers[k], line) for k in names}


def ssm_layer(cfg, layers, index, line, x, operator, state):
    """Scan layer ``index`` (scan line ``line``) on x [B, S, H].
    ``operator(line, sp, xn, state) -> (y [B, S, d_inner], z, state)`` runs
    the scan on normed input. Returns (x, m, state): ``m`` is the scan's
    output before the gate."""
    sp = _leaves(layers, SSM_LEAVES, line)
    with tracing.part("attn"):
        xn = _norm1(cfg, layers, index, x)
        y, z, state = operator(line, sp, xn, state)
        x = x + ssm_output(sp, y, z, x.dtype)
    return feed_forward(cfg, layers, index, x), y, state


def attention_layer(cfg, layers, index, line, x, operator, state,
                    part: str | None):
    """Attention layer ``index`` (attention line ``line``: the window
    layers, then the full one) on x [B, S, H]. ``operator(line, q, k, v,
    state) -> (o, state)`` attends; ``part`` names the scope inside
    ``attn`` (``window_attn``, or None for the full layer)."""
    ap = _leaves(layers, ATTN_LEAVES, line)
    with tracing.part("attn"), (tracing.part(part) if part
                                else contextlib.nullcontext()):
        xn = _norm1(cfg, layers, index, x)
        o, state = operator(line, *attention_heads(cfg, ap, xn), state)
        x = x + attention_output(cfg, ap, o, index, x.dtype)
    return feed_forward(cfg, layers, index, x), state


def gmu_layer(cfg, layers, index, line, x, m):
    gp = _leaves(layers, GMU_LEAVES, line)
    with tracing.part("attn"):
        x = x + gated_memory_unit(gp, _norm1(cfg, layers, index, x), m)
    return feed_forward(cfg, layers, index, x)


def cross_layer(cfg, layers, index, line, x, operator):
    """Cross attention layer ``index`` on x [B, S, H]: ``operator(q) -> o``
    attends the full line."""
    cp = _leaves(layers, CROSS_LEAVES, line)
    with tracing.part("attn"), tracing.part("cross_attn"):
        xn = _norm1(cfg, layers, index, x)
        o = operator(cross_queries(cfg, cp, xn))
        x = x + cross_output(cfg, cp, o, index, x.dtype)
    return feed_forward(cfg, layers, index, x)


def self_decoder(cfg: Phi4FlashConfig, params, x, operators: dict, state):
    """Layers 0 to ``L/2 + 1`` over x [B, S, H] with ``state`` as carry: a
    scan over the (scan, window) pairs, then the memory layer and the full
    layer. ``operators``: ``ssm`` (see :func:`ssm_layer`), ``window`` and
    ``full`` (see :func:`attention_layer`). Returns (x, m, state)."""
    layers = params["layers"]

    def pair(carry, p):
        x, state = carry
        x, _, state = ssm_layer(cfg, layers, 2 * p, p, x, operators["ssm"],
                                state)
        x, state = attention_layer(cfg, layers, 2 * p + 1, p, x,
                                   operators["window"], state, "window_attn")
        return (x, state), None

    with tracing.part("stack"):
        (x, state), _ = lax.scan(pair, (x, state),
                                 jnp.arange(cfg.window_lines))
    x, m, state = ssm_layer(cfg, layers, cfg.half, cfg.window_lines, x,
                            operators["ssm"], state)
    x, state = attention_layer(cfg, layers, cfg.half + 1, cfg.window_lines,
                               x, operators["full"], state, None)
    return x, m, state


def cross_decoder(cfg: Phi4FlashConfig, params, x, m, cross):
    """Layers ``L/2 + 2`` to ``L - 1`` over x [B, S, H]: a scan over the
    (gated memory unit, cross attention) pairs. ``m`` [B, S, d_inner] is
    the memory at x's positions, ``cross(q) -> o`` attends the full
    line. Nothing is kept."""
    layers = params["layers"]

    def pair(x, p):
        at = cfg.half + 2 + 2 * p
        x = gmu_layer(cfg, layers, at, p, x, m)
        return cross_layer(cfg, layers, at + 1, p, x, cross), None

    with tracing.part("stack"):
        x, _ = lax.scan(pair, x, jnp.arange(cfg.cross_lines))
    return x


@tracing.part("head")
def lm_head(cfg: Phi4FlashConfig, params, x, kmesh=None):
    """x: [..., H] -> float32 logits [..., V]: the final norm and the
    embedding transposed (tied, no bias)."""
    x = layer_norm(x, params["final_norm_w"], params["final_norm_b"],
                   cfg.norm_eps)
    return lax.dot_general(
        x, params["embed_tokens"], (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def forward(cfg: Phi4FlashConfig, params: dict, tokens, *,
            kmesh: KernelMesh | None = None):
    """tokens [B, S] -> float32 logits [B, S, V]. Whole sequences, no cache
    and no state: the convolution and the scan start from zeros, every
    layer runs at every position."""
    b, s = tokens.shape
    scale = cfg.head_dim ** -0.5
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    in_window = window_visible(pos, pos, cfg.sliding_window)

    def ssm(line, sp, xn, state):
        x_in, z = ssm_inputs(cfg, sp, xn)
        prior = jnp.zeros((b, cfg.mamba_d_conv - 1, cfg.d_inner), xn.dtype)
        xc, dt, a, bb, cc = ssm_scan_inputs(cfg, sp,
                                            conv_window(prior, x_in), s)
        zero = jnp.zeros((cfg.mamba_d_state, cfg.d_inner), jnp.float32)
        with tracing.part("ssm"), tracing.part("ssm_scan"):
            y = jax.vmap(lambda *v: selective_scan_chunk(
                *v[:2], a, *v[2:], sp["d"], zero)[0])(xc, dt, bb, cc)
        return y.astype(xn.dtype), z, state

    def window(line, q, k, v, state):
        return packed_attention(q, k, v, in_window, scale), state

    def full(line, q, k, v, state):
        return packed_attention(q, k, v, causal, scale), (k, v)

    x, m, (k, v) = self_decoder(cfg, params, x,
                                {"ssm": ssm, "window": window, "full": full},
                                None)
    x = cross_decoder(cfg, params, x, m,
                      lambda q: packed_attention(q, k, v, causal, scale))
    return lm_head(cfg, params, x, kmesh)
