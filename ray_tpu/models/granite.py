"""Granite 4.0-H family: Mamba-2 layers beside an attention without
positions every few layers, routed experts beside a shared SwiGLU in every
layer, four multipliers.

The family of ``model_type: "granitemoehybrid"`` (huggingface.co/ibm-granite/
granite-4.0-h-small). ``h_0 = embedding_multiplier E[token]``. Layer ``l``,
input ``h``, ``N`` an RMSNorm with a plain weight (statistics in float32),
``r = residual_multiplier`` on both branches::

    a  = h + r Mix_l(N(h))
    h' = a + r F(N(a))

``Mix_l`` is what ``layer_types[l]`` names, which is data (a tuple of
``"mamba"`` and ``"attention"``; the published 40 are a period of ten: five
Mamba, one attention, four Mamba):

- **Mamba-2.** ``mamba_n_heads`` heads of ``mamba_d_head`` channels
  (``d_inner``, all heads side by side), a state of ``mamba_d_state`` a
  channel, one group: one ``B`` and one ``C`` for all heads. ``[z | xBC |
  dt] = u W_in``; ``xBC`` passes a depthwise causal convolution of
  ``mamba_d_conv`` taps **with a bias** (zeros before position 0), then
  ``silu``, and splits into ``x`` (``d_inner``), ``B`` and ``C``
  (``mamba_d_state`` each). ``dt = softplus(dt + dt_bias)`` a head, with no
  upper clamp, ``A = -exp(A_log)`` a head, float32. Then the rule of
  ops/ssd.py a head from a zero state, ``y_t = S_t^T C_t + D x_t``. The
  output: ``y silu(z)`` (the gate first), an RMSNorm over all ``d_inner``
  channels, ``W_out``. What a token leaves behind is the state of every
  head and the last ``taps - 1`` rows of ``xBC`` before the convolution.
- **Attention.** Grouped-query, no bias, **no rotary and no other
  positional term**, scores ``q . k`` times ``attention_multiplier`` (1/128
  published: not ``head_dim^-1/2``), causal, softmax in float32.

``F(u) = Shared(u) + sum_e w_e E_e(u)``: the routed layer of
models/routed.py (the ``num_experts_per_tok`` largest of the router's
logits, their weights the softmax over those: ``RouterRule(score="softmax",
renormalize=True, renorm_eps=0)`` says the same, the top of a softmax being
the top of its logits and the picks renormalised the softmax over the picks)
told which experts it holds (``expert_shard`` of ``expert_shards``), and
beside it one shared SwiGLU added as it is. After the last layer ``N``;
``logits = N(h) E^T / logits_scaling`` (the embedding tied).

Params: a flat pytree, every leaf stacked over the layers that have it and
indexed by the loop's counter where it is used. Stored apart or in another
order than the published matrices, the same numbers: ``in_xbcz`` holds
``W_in``'s columns of ``xBC`` and then of ``z``, ``in_dt`` those of ``dt``
(published: ``z | xBC | dt`` in one), so that the convolution's input is one
slice and the step's projection a float32 product of its own; an expert's
and the shared SwiGLU's fused ``input_linear`` are ``we_gate`` and ``we_up``
(``ws_gate``, ``ws_up``), the gate the first half
(benchmark/rtbench/adapters/granite.reference_weights puts them back).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.lfm2 import swiglu
from ray_tpu.models.qwen3_next import conv_window, short_conv_silu
from ray_tpu.models.routed import (
    MOE_COUNTERS,
    RouterRule,
    layer_of,
    moe_block,
)
from ray_tpu.ops import ssd
from ray_tpu.ops.attention import blockwise_attention
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.norms import rms_norm, rms_norm_reference
from ray_tpu.util import tracing

MAMBA, ATTENTION = "mamba", "attention"
PUBLISHED_LAYER_TYPES = ((MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4) * 4

MAMBA_LEAVES = ("in_xbcz", "in_dt", "conv_w", "conv_b", "dt_bias", "a_log",
                "d_skip", "ssm_norm", "out_proj")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")
SHARED_LEAVES = ("ws_gate", "ws_up", "ws_down")


@dataclass(frozen=True)
class GraniteConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_layers: int = 40
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    num_heads: int = 32
    num_kv_heads: int = 8
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    num_experts: int = 72                  # in the whole model, all shards
    num_experts_per_tok: int = 10
    intermediate_size: int = 768           # one routed expert's width
    shared_intermediate_size: int = 1536
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    # What this program holds of the routed experts (models/routed.py).
    expert_shard: int = 0
    expert_shards: int = 1
    max_seq_len: int = 131072
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.layer_types) != self.num_layers or set(
                self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"layer_types {self.layer_types}: {self.num_layers} of "
                f"{MAMBA!r} and {ATTENTION!r}")
        if self.hidden_size % self.num_heads \
                or self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_heads} heads over {self.num_kv_heads} of a "
                f"hidden size of {self.hidden_size}")
        if self.mamba_n_heads * self.mamba_d_head \
                != self.mamba_expand * self.hidden_size:
            raise ValueError(
                f"{self.mamba_n_heads} heads of {self.mamba_d_head} are not "
                f"{self.mamba_expand} x {self.hidden_size}")
        if self.mamba_n_groups != 1:
            raise ValueError(
                f"mamba_n_groups {self.mamba_n_groups}: ops/ssd.py takes one "
                "B and one C for all heads")
        if self.mamba_d_conv < 2:
            raise ValueError("mamba_d_conv under 2 leaves no window")
        self.router_rule  # refuses a share the experts do not divide into

    @staticmethod
    def tiny(**kw) -> "GraniteConfig":
        """Test-size config with every mechanism: both kinds of mixer in a
        period (of 3) that repeats, heads that pair in a stored state's
        lanes, 8 experts of 32 with 3 a token, a shared SwiGLU, the four
        multipliers at values of their own."""
        base = dict(vocab_size=512, hidden_size=64, num_layers=6,
                    layer_types=(MAMBA, ATTENTION, MAMBA) * 2, num_heads=4,
                    num_kv_heads=2, mamba_n_heads=8, mamba_d_head=16,
                    mamba_d_state=16, num_experts=8, num_experts_per_tok=3,
                    intermediate_size=32, shared_intermediate_size=48,
                    embedding_multiplier=6.0, residual_multiplier=0.3,
                    attention_multiplier=0.125, logits_scaling=4.0,
                    max_seq_len=256, dtype="float32")
        base.update(kw)
        return GraniteConfig(**base)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def period(self) -> int:
        """Layers of the shortest run that ``layer_types`` repeats whole:
        the stack is one scan over its repeats."""
        return next(p for p in range(1, self.num_layers + 1)
                    if self.num_layers % p == 0 and self.layer_types
                    == self.layer_types[:p] * (self.num_layers // p))

    @property
    def periods(self) -> int:
        return self.num_layers // self.period

    def kind(self, layer: int) -> str:
        return self.layer_types[layer]

    def lines_a_period(self, kind: str) -> int:
        return self.layer_types[:self.period].count(kind)

    def rank(self, at: int) -> int:
        """Place ``at`` of a period among the period's layers of its kind."""
        return self.layer_types[:at].count(self.layer_types[at])

    @property
    def attention_lines(self) -> int:
        """Layers that leave keys and values a position."""
        return self.layer_types.count(ATTENTION)

    @property
    def linear_lines(self) -> int:
        """Layers that leave a state and a convolution window a slot."""
        return self.layer_types.count(MAMBA)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``x``, ``B`` and ``C``."""
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def state_shape(self) -> tuple[int, int, int]:
        """A slot's state in one Mamba layer as stored (ops/ssd.py)."""
        return ssd.state_shape(self.mamba_n_heads, self.mamba_d_state,
                               self.mamba_d_head)

    @property
    def linear_state_bytes(self) -> int:
        """One slot's state in one Mamba layer (float32)."""
        return self.d_inner * self.mamba_d_state * 4

    @property
    def experts_held(self) -> int:
        return self.router_rule.held

    @property
    def router_rule(self) -> RouterRule:
        return RouterRule(
            experts=self.num_experts, topk=self.num_experts_per_tok,
            score="softmax", use_bias=False, renormalize=True,
            renorm_eps=0.0, expert_shard=self.expert_shard,
            expert_shards=self.expert_shards)

    def num_params(self) -> int:
        """Parameters held here (this shard's experts; the embedding once:
        the head is tied)."""
        h, di, nh = self.hidden_size, self.d_inner, self.mamba_n_heads
        mamba = (h * (di + self.conv_dim + nh)
                 + self.conv_dim * (self.mamba_d_conv + 1) + 3 * nh + di
                 + di * h)
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        attn = 2 * h * qd + 2 * h * kvd
        ffn = (h * self.num_experts + 3 * h * self.shared_intermediate_size
               + self.experts_held * 3 * h * self.intermediate_size)
        return (self.linear_lines * mamba + self.attention_lines * attn
                + self.num_layers * (ffn + 2 * h) + self.vocab_size * h + h)


def param_logical_axes(cfg: GraniteConfig) -> dict:
    """Logical-axis names per param leaf (see parallel/sharding.py rules).
    ``layers`` is the stacked axis, over whichever layers have the leaf."""
    return {
        "embed_tokens": ("vocab", "embed"),
        "final_norm": ("embed",),
        "layers": {
            "input_norm": ("layers", "embed"),
            "post_norm": ("layers", "embed"),
            "in_xbcz": ("layers", "embed", None),
            "in_dt": ("layers", "embed", None),
            "conv_w": ("layers", None, None),
            "conv_b": ("layers", None),
            "dt_bias": ("layers", None),
            "a_log": ("layers", None),
            "d_skip": ("layers", None),
            "ssm_norm": ("layers", None),
            "out_proj": ("layers", None, "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "router": ("layers", "embed", None),
            "ws_gate": ("layers", "embed", "mlp"),
            "ws_up": ("layers", "embed", "mlp"),
            "ws_down": ("layers", "mlp", "embed"),
            "we_gate": ("layers", "expert", "embed", "mlp"),
            "we_up": ("layers", "expert", "embed", "mlp"),
            "we_down": ("layers", "expert", "mlp", "embed"),
        },
    }


# A seeded head's step is log-uniform over DT_RANGE (Mamba-2's own
# initialiser) and its rate ``A`` runs from 1 to A_MAX over the heads, so
# that ``exp(dt A)`` runs from 0.999 (a head that remembers a thousand
# tokens) to under 1e-5 (a head that forgets in a token).
DT_RANGE = (1e-3, 1e-1)
A_MAX = 128.0
# ``h_0``'s seeded size after ``embedding_multiplier``, in units of a
# branch's output (init_params says why it is not 1).
EMBED_SIZE = 0.02
# The step projection's seeded scale, in units of a unit-variance output: a
# token moves a head's step about its centre and not across the range.
DT_INPUT_SCALE = 0.5


def init_params(cfg: GraniteConfig, key: jax.Array) -> dict:
    """Scaled-normal init that keeps every projection's output at unit
    variance **after its multiplier**:

    - the embedding's rows so that ``h_0``, after ``embedding_multiplier``,
      is EMBED_SIZE (0.02) of a branch's size, models/lfm2.py's tied
      embedding and for a reason that a tied head makes pressing: the head
      reads a token's own embedding back out of the stream, ``sqrt(hidden)``
      standard deviations of a logit for every unit of ``h_0`` in a stream
      of unit size, so with ``h_0`` of unit size the seeded model's top
      logit is its input token at 45 against the others' 1, it repeats its
      last token whatever the layers compute, and a comparison of logits'
      tops sees nothing (my chip run, PR 62: every compared request read a
      margin of exactly 0). At 0.02 the token's own logit stands 1.3
      deviations up and the layers decide. Neither ``h_0`` nor the tied
      head is then of unit size under a plain final norm (``N(h) E^T /
      logits_scaling`` reads ``sqrt(hidden) EMBED_SIZE /
      (embedding_multiplier logits_scaling)``, 0.0067 at the published
      numbers): the final norm's weight is centred on the inverse of that,
      150, so that the logits are of unit variance;
    - the branches' output projections at unit variance and not scaled down
      by depth: under ``residual_multiplier`` 0.22 a branch adds 0.05 to the
      stream's variance, which is of unit size after ten layers (0.97) and
      3.9 after 40;
    - ``W_q`` and ``W_k`` at ``(1 / attention_multiplier)^1/2 head_dim^-1/4``
      of the usual scale, so that a score ``q . k attention_multiplier`` has
      unit variance: trained weights have grown into a multiplier that is not
      ``head_dim^-1/2``, and seeded ones at the usual scale would make every
      attention a mean over the context, under which a wrong mask or a
      rotary that should not be there moves nothing;
    - the scan as Mamba-2's own initialiser draws it: ``A`` from 1 to A_MAX
      over the heads, ``dt_bias`` the inverse softplus of a step log-uniform
      over DT_RANGE, the step's projection at DT_INPUT_SCALE; ``D`` and the
      norms' weights at ``1 + 0.1 x normal``, the convolution's taps at
      ``1/sqrt(taps)`` and its bias at ``0.1 x normal``;
    - the routed experts' down-projections scaled by ``1 / sqrt(2 x
      layers)`` (models/qwen3_next.py's and for its reason): the tenth pick
      is a discrete choice between two logits that are nearly equal, which
      falls differently in bfloat16 and in the float32 reference for some
      tokens; scaled, such a swap is of rounding's size."""
    h, d, L = cfg.hidden_size, cfg.head_dim, cfg.num_layers
    nl, na = cfg.linear_lines, cfg.attention_lines
    nh, di = cfg.mamba_n_heads, cfg.d_inner
    fe, fs, E = (cfg.intermediate_size, cfg.shared_intermediate_size,
                 cfg.experts_held)
    qd, kvd = cfg.num_heads * d, cfg.num_kv_heads * d
    dt = cfg.jnp_dtype
    keys = iter(jax.random.split(key, 32))

    def matrix(*shape, dtype=dt, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def norm(*shape, centre=1.0, dtype=dt):
        return (centre * (1.0 + 0.1 * jax.random.normal(
            next(keys), shape, jnp.float32))).astype(dtype)

    step = jnp.exp(jax.random.uniform(
        next(keys), (nl, nh), jnp.float32, math.log(DT_RANGE[0]),
        math.log(DT_RANGE[1])))
    qk = math.sqrt(1.0 / (cfg.attention_multiplier * math.sqrt(d)))
    head = math.sqrt(h) * EMBED_SIZE / (cfg.embedding_multiplier
                                        * cfg.logits_scaling)
    return {
        "embed_tokens": matrix(cfg.vocab_size, h,
                               scale=EMBED_SIZE / cfg.embedding_multiplier),
        "final_norm": norm(h, centre=1.0 / head),
        "layers": {
            "input_norm": norm(L, h),
            "post_norm": norm(L, h),
            "in_xbcz": matrix(nl, h, cfg.conv_dim + di),
            "in_dt": matrix(nl, h, nh, scale=DT_INPUT_SCALE / math.sqrt(h)),
            "conv_w": matrix(nl, cfg.mamba_d_conv, cfg.conv_dim,
                             scale=1.0 / math.sqrt(cfg.mamba_d_conv)),
            "conv_b": matrix(nl, cfg.conv_dim, scale=0.1),
            # softplus^-1(step): step + log(1 - exp(-step)).
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.linspace(1.0, A_MAX, nh)), (nl, nh)),
            "d_skip": norm(nl, nh, dtype=jnp.float32),
            "ssm_norm": norm(nl, di),
            "out_proj": matrix(nl, di, h),
            "wq": matrix(na, h, qd, scale=qk / math.sqrt(h)),
            "wk": matrix(na, h, kvd, scale=qk / math.sqrt(h)),
            "wv": matrix(na, h, kvd),
            "wo": matrix(na, qd, h),
            # The router stays float32: its top-k is a discrete choice.
            "router": matrix(L, h, cfg.num_experts, dtype=jnp.float32),
            "ws_gate": matrix(L, h, fs),
            "ws_up": matrix(L, h, fs),
            "ws_down": matrix(L, fs, h),
            "we_gate": matrix(L, E, h, fe),
            "we_up": matrix(L, E, h, fe),
            "we_down": matrix(L, E, fe, h,
                              scale=1.0 / math.sqrt(2 * L * fe)),
        },
    }


# ---------------------------------------------------------------- blocks

def embed(cfg: GraniteConfig, params, tokens):
    """``embedding_multiplier E[token]``."""
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
        return (x * cfg.embedding_multiplier).astype(x.dtype)


def mamba_inputs(cfg: GraniteConfig, lp: dict, xn):
    """The mixer's projections in, on xn [..., H] (normed) -> (xbc [...,
    conv_dim], the convolution's input, whose last rows a sequence keeps;
    z [..., d_inner], the output's gate; dt [..., heads] float32, the step
    after its softplus)."""
    with tracing.part("linear_attn"):
        xbcz = xn @ lp["in_xbcz"]
        dt = jax.nn.softplus(jnp.dot(
            xn, lp["in_dt"], preferred_element_type=jnp.float32)
            + lp["dt_bias"])
        return xbcz[..., :cfg.conv_dim], xbcz[..., cfg.conv_dim:], dt


def mamba_heads(cfg: GraniteConfig, lp: dict, window, s: int):
    """The depthwise causal convolution with its bias over ``window`` [B,
    taps - 1 + S, conv_dim] at its last ``s`` positions, ``silu``, and the
    split: x [B, S, heads, P], b and c [B, S, N] (every head's), float32."""
    with tracing.part("linear_attn"):
        with tracing.part("conv"):
            mixed = short_conv_silu(lp["conv_w"], window, s, lp["conv_b"])
        x, b, c = jnp.split(
            mixed, (cfg.d_inner, cfg.d_inner + cfg.mamba_d_state), axis=-1)
        return x.reshape(*x.shape[:2], cfg.mamba_n_heads, -1), b, c


def mamba_output(cfg: GraniteConfig, lp: dict, y, x, z, dtype):
    """The rule's output y [B, S, heads, P] float32 with the skip ``D x``,
    gated by ``silu(z)``, normed over all ``d_inner`` channels and
    projected out."""
    with tracing.part("linear_attn"):
        b, s = y.shape[:2]
        y = (y + lp["d_skip"][:, None] * x).reshape(b, s, -1)
        y = y * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm_reference(y, lp["ssm_norm"], cfg.norm_eps)
        return (y.astype(dtype) @ lp["out_proj"]).astype(dtype)


def attention_heads(cfg: GraniteConfig, ap: dict, xn):
    """xn [B, S, H] (normed) -> queries [B, nh, S, D], keys and values [B,
    nkv, S, D]. No rotary: only the mask knows positions."""
    b, s, _ = xn.shape
    nh, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # Arrays of their own before they are split into heads
    # (models/lfm2.attention_heads).
    q, k, v = lax.optimization_barrier(
        (xn @ ap["wq"], xn @ ap["wk"], xn @ ap["wv"]))
    return (q.reshape(b, s, nh, d).transpose(0, 2, 1, 3),
            k.reshape(b, s, nkv, d).transpose(0, 2, 1, 3),
            v.reshape(b, s, nkv, d).transpose(0, 2, 1, 3))


def attention_output(ap: dict, o, dtype):
    """The attention's output o [B, S, nh * D] projected out."""
    return (o @ ap["wo"]).astype(dtype)


def shared_expert(layers: dict, index, u):
    """``Shared(u)`` on u [T, H]: the SwiGLU every token passes, added as
    it is."""
    with tracing.part("mlp"), tracing.part("moe_shared"):
        return swiglu(u, *(layer_of(layers[k], index) for k in SHARED_LEAVES))


def layer(cfg: GraniteConfig, layers: dict, at: int, repeat, x,
          operators: dict, state, valid, kmesh=None):
    """Layer ``repeat * period + at`` on x [B, S, H]: ``at`` is the layer's
    place in the period (static), ``repeat`` the period's index (a run-time
    value). ``layers`` is the whole stacked ``params["layers"]``: every leaf
    is indexed where it is used. ``operators[kind](line, p, xn, state) ->
    (y, state)`` runs the layer's mixer on normed input with its own params
    ``p``; ``line`` is the layer's rank among the layers of its kind (its
    cache line) and ``state`` whatever the operators thread. Returns (x,
    state, counts)."""
    b, s, hid = x.shape
    index = repeat * cfg.period + at
    kind = cfg.kind(at)
    line = repeat * cfg.lines_a_period(kind) + cfg.rank(at)
    r = cfg.residual_multiplier
    with tracing.part("stack"):
        p = {k: layer_of(layers[k], line)
             for k in (ATTENTION_LEAVES if kind == ATTENTION
                       else MAMBA_LEAVES)}
        input_norm = layer_of(layers["input_norm"], index)
        post_norm = layer_of(layers["post_norm"], index)
    # The mixer's place is ``attn`` for either kind; Mamba-2 opens
    # ``linear_attn``, ``conv``, ``ssd`` and ``linear_state`` inside it
    # (tracing.SUBPARTS).
    with tracing.part("attn"):
        y, state = operators[kind](
            line, p, rms_norm(x, input_norm, cfg.norm_eps, kmesh), state)
        x = x + (r * y).astype(x.dtype)
    with tracing.part("moe_route"):
        u = rms_norm(x, post_norm, cfg.norm_eps, kmesh).reshape(b * s, hid)
    m, counts = moe_block(cfg.router_rule, layers, index, u,
                          valid.reshape(b * s))
    shared = shared_expert(layers, index, u)
    with tracing.part("moe_combine"):
        x = x + (r * (m + shared)).astype(x.dtype).reshape(b, s, hid)
    return x, state, counts


def run_layers(cfg: GraniteConfig, params, x, operators: dict, state, valid,
               kmesh=None):
    """Every layer over x [B, S, H], ``state`` as carry: one scan over the
    repeats of the period, whose layers are written out. Returns (x, state,
    counts int32[6] summed over the layers)."""
    def body(carry, repeat):
        x, state, counts = carry
        for at in range(cfg.period):
            x, state, c = layer(cfg, params["layers"], at, repeat, x,
                                operators, state, valid, kmesh)
            with tracing.part("moe_combine"):
                counts = counts + c
        return (x, state, counts), None

    counts = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    with tracing.part("stack"):
        (x, state, counts), _ = lax.scan(
            body, (x, state, counts), jnp.arange(cfg.periods))
    return x, state, counts


@tracing.part("head")
def lm_head(cfg: GraniteConfig, params, x, kmesh=None):
    """x: [..., H] -> float32 logits [..., V]: the tied embedding read as it
    lies (contracted over its columns, no transposed copy), divided by
    ``logits_scaling``."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh)
    logits = lax.dot_general(
        x, params["embed_tokens"], (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


def forward(cfg: GraniteConfig, params: dict, tokens, *,
            kmesh: KernelMesh | None = None):
    """tokens [B, S] -> (float32 logits [B, S, V], router counts int32[6]).
    Whole sequences, no cache and no state: the convolution and the rule
    start from zeros, the attention is causal over the sequence."""
    b, s = tokens.shape
    x = embed(cfg, params, tokens)
    valid = jnp.ones(tokens.shape, bool)

    def mamba(line, lp, xn, state):
        xbc, z, dt = mamba_inputs(cfg, lp, xn)
        prior = jnp.zeros((b, cfg.mamba_d_conv - 1, cfg.conv_dim), xn.dtype)
        xs, bs, cs = mamba_heads(cfg, lp, conv_window(prior, xbc), s)
        zero = jnp.zeros(cfg.state_shape, jnp.float32)
        a = -jnp.exp(lp["a_log"])
        with tracing.part("linear_attn"), tracing.part("ssd"):
            y = jax.vmap(lambda *v: ssd.ssd_chunk(
                v[0], v[1], a, v[2], v[3], zero)[0])(xs, dt, bs, cs)
        return mamba_output(cfg, lp, y, xs, z, xn.dtype), state

    def attention(line, ap, xn, state):
        q, k, v = attention_heads(cfg, ap, xn)
        o = blockwise_attention(q, k, v, causal=True,
                                sm_scale=cfg.attention_multiplier)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return attention_output(ap, o, xn.dtype), state

    x, _, counts = run_layers(cfg, params, x,
                              {MAMBA: mamba, ATTENTION: attention}, None,
                              valid, kmesh)
    return lm_head(cfg, params, x, kmesh), counts
