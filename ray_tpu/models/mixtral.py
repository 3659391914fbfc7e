"""Mixtral-family sparse Mixture-of-Experts transformer.

Covers the reference's MoE serving/training capability (reference: BASELINE
config 5 runs Mixtral via vLLM engine kwargs + ray.util.collective all-to-all;
the reference has no first-class MoE implementation — SURVEY.md §2.4 EP row).
Here MoE is first-class and TPU-native:

- GShard/Switch-style capacity-based routing: top-k gates, per-expert token
  slots ``[E, C, H]`` of STATIC shape, filled and read back by index: a small
  integer plan (``routing_plan``: which slot each claim holds, which token
  each slot holds) and two row gathers, so no tensor of tokens x experts x
  capacity exists. Under expert parallelism the layers keep the batch
  replicated over the mesh's ``ep`` axis (it shards over dp/fsdp only), so no
  token changes chips and there is no all-to-all: each chip serves the experts
  it holds and the parts are summed, one all-reduce of [T, H] a layer forward
  and one backward. Past the last layer nothing needs every token everywhere:
  the head and the loss run on each ``ep`` chip's own share (``_head_spec``).
- Attention/rope/norms are shared with the Llama family; only the MLP is
  replaced by the expert layer; layers still scan-stacked.
- Load-balancing auxiliary loss (Switch Transformer form) returned alongside
  the LM loss; ``routing_stats`` reads the routers' load and drops.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import llama as _llama
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.util import tracing


@dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    max_seq_len: int = 8192
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.02
    dtype: str = "bfloat16"

    @staticmethod
    def mixtral_8x7b() -> "MixtralConfig":
        return MixtralConfig()

    @staticmethod
    def tiny() -> "MixtralConfig":
        """Test-size: compiles in seconds, exercises routing + all code paths."""
        return MixtralConfig(vocab_size=256, hidden_size=64,
                             intermediate_size=128, num_layers=2, num_heads=4,
                             num_kv_heads=2, head_dim=16, max_seq_len=256,
                             num_experts=4, top_k=2, dtype="float32")

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    def capacity(self, num_tokens: int) -> int:
        """Per-expert token slots for a batch of ``num_tokens``."""
        return max(1, int(math.ceil(
            self.capacity_factor * self.top_k * num_tokens / self.num_experts)))


def param_logical_axes(cfg: MixtralConfig) -> dict:
    return {
        "embed_tokens": ("vocab", "embed"),
        "lm_head": ("embed", "vocab"),
        "final_norm": ("embed",),
        "layers": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "router": ("layers", "embed", None),
            # Expert weights carry the ``expert`` logical axis → mesh ``ep``.
            "we_gate": ("layers", "expert", "embed", "mlp"),
            "we_up": ("layers", "expert", "embed", "mlp"),
            "we_down": ("layers", "expert", "mlp", "embed"),
            "attn_norm": ("layers", "embed"),
            "mlp_norm": ("layers", "embed"),
        },
    }


def init_params(cfg: MixtralConfig, key: jax.Array) -> dict:
    h, L, E = cfg.hidden_size, cfg.num_layers, cfg.num_experts
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    i = cfg.intermediate_size
    dt = cfg.jnp_dtype
    keys = jax.random.split(key, 12)

    def norm_init(k, *shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    return {
        "embed_tokens": (jax.random.normal(keys[0], (cfg.vocab_size, h),
                                           jnp.float32) * 0.02).astype(dt),
        "lm_head": norm_init(keys[1], h, cfg.vocab_size,
                             scale=1.0 / math.sqrt(h)),
        "final_norm": jnp.ones((h,), dt),
        "layers": {
            "wq": norm_init(keys[2], L, h, qd),
            "wk": norm_init(keys[3], L, h, kvd),
            "wv": norm_init(keys[4], L, h, kvd),
            "wo": norm_init(keys[5], L, qd, h, scale=1.0 / math.sqrt(qd * 2 * L)),
            "router": norm_init(keys[6], L, h, E, scale=0.02),
            "we_gate": norm_init(keys[7], L, E, h, i),
            "we_up": norm_init(keys[8], L, E, h, i),
            "we_down": norm_init(keys[9], L, E, i, h,
                                 scale=1.0 / math.sqrt(i * 2 * L)),
            "attn_norm": jnp.ones((L, h), dt),
            "mlp_norm": jnp.ones((L, h), dt),
        },
    }


class RoutingPlan(NamedTuple):
    """Who holds which capacity slot, read from both sides. Slots are numbered
    ``e * capacity + c``; the two maps are inverse permutations with holes."""

    gate: jax.Array           # [T, K] float32, renormalised top-k weights
    slot_of_claim: jax.Array  # [T, K] int32; E * C for a dropped claim
    token_of_slot: jax.Array  # [E * C] int32; T for an empty slot
    gate_of_slot: jax.Array   # [E * C] float32; 0 for an empty slot
    claims: jax.Array         # [E] int32, claims made on each expert
    aux: jax.Array            # Switch load-balancing loss


def routing_plan(cfg: MixtralConfig, logits: jax.Array,
                 capacity: int) -> RoutingPlan:
    """Router logits [T, E] -> the plan of a capacity-routed layer.

    Top-k gates renormalised to sum to 1 a token; a claim's position in its
    expert is the running count of earlier claims on that expert (token-major,
    first choice before second); claims at or past ``capacity`` are dropped.
    A stable sort of the claims by expert lists every expert's claims in that
    same order, which is the inverse map.
    """
    T = logits.shape[0]
    E, K, C = cfg.num_experts, cfg.top_k, capacity
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, gate_idx = lax.top_k(probs, K)  # [T, K]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    expert = gate_idx.reshape(T * K)  # priority: token-major, k-minor
    running = jnp.cumsum(
        (expert[:, None] == jnp.arange(E)).astype(jnp.int32), axis=0)
    position = jnp.take_along_axis(running, expert[:, None], axis=1)[:, 0] - 1
    slot_of_claim = jnp.where(position < C, expert * C + position, E * C)

    claims = running[-1]
    first = jnp.cumsum(claims) - claims  # an expert's first claim, sorted
    c = jnp.arange(C)
    claim_of_slot = jnp.argsort(expert, stable=True)[
        jnp.minimum(first[:, None] + c, T * K - 1)]  # [E, C]
    filled = c < claims[:, None]
    token_of_slot = jnp.where(filled, claim_of_slot // K, T)
    gate_of_slot = jnp.where(filled, gate.reshape(-1)[claim_of_slot], 0.0)

    # Switch load-balancing loss: E * sum_e (share of kept claims) x (mean
    # router probability).
    kept = jnp.minimum(claims, C).astype(jnp.float32)
    aux = E * jnp.sum(kept / jnp.maximum(kept.sum(), 1.0) * probs.mean(0))
    return RoutingPlan(gate, slot_of_claim.reshape(T, K).astype(jnp.int32),
                       token_of_slot.reshape(E * C).astype(jnp.int32),
                       gate_of_slot.reshape(E * C), claims, aux)


def _rows(src, idx):
    """src[idx] [N, H]; zeros where ``idx == len(src)``."""
    m = src.shape[0]
    rows = src.at[jnp.minimum(idx, m - 1)].get(mode="promise_in_bounds")
    return jnp.where((idx < m)[:, None], rows, 0)


def _gather_sum(src, w, idx):
    """out[n] = sum_j w[n, j] * src[idx[n, j]], summed in float32 and rounded
    once; ``idx == len(src)`` adds nothing; ``w`` None weighs every row 1.
    One gather of [N, H] a column of ``idx`` (top_k of them at most): a
    gathered [N, J, H] would be tiled with J as a minor dimension and copied
    to be summed."""
    out = 0.0
    for j in range(idx.shape[1]):
        rows = _rows(src, idx[:, j]).astype(jnp.float32)
        out = out + (rows if w is None else rows * w[:, j, None])
    return out.astype(src.dtype)


@jax.custom_vjp
def gather_rows(src, w, idx, inv_w, inv_idx):
    """Rows of ``src`` [M, H] moved by index: ``_gather_sum(src, w, idx)``
    with ``idx`` [N, J]. ``(inv_w, inv_idx)`` [M, J'] describe the same
    pairing from ``src``'s side (row m goes to ``inv_idx[m, :]``), so the
    gradient to ``src`` is the same gather of the cotangent the other way
    round and no scatter-add is ever built."""
    return _gather_sum(src, w, idx)


def _gather_rows_fwd(src, w, idx, inv_w, inv_idx):
    return _gather_sum(src, w, idx), (src, w, idx, inv_w, inv_idx)


def _gather_rows_bwd(res, d_out):
    src, w, idx, inv_w, inv_idx = res
    d_w = None
    if w is not None:
        d_w = jnp.stack([jnp.einsum(
            "nh,nh->n", d_out, _rows(src, idx[:, j]),
            preferred_element_type=jnp.float32)
            for j in range(idx.shape[1])], axis=1)
    return _gather_sum(d_out, inv_w, inv_idx), d_w, None, None, None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def _routed(cfg: MixtralConfig, x: jax.Array, lp: dict, kmesh=None):
    """Capacity-routed expert MLP. x: [B, S, H] -> ([B, S, H], its plan).

    Rows move by index over static ``[E, C, H]`` slots: dispatch gathers each
    slot's token, the per-expert SwiGLU runs as batched einsums on the MXU,
    combine gathers each token's slots back with the gate weights. Claims
    beyond an expert's capacity are dropped (the residual stream passes
    through unchanged): Switch/GShard semantics.

    The slots are handled in ``G`` groups of whole experts, one group to a
    chip of the ``ep`` axis of ``kmesh``'s mesh (one group without one). In
    this layer, as in the whole scan of layers, the batch does not shard over
    ``ep``: every chip holds every token, fills and reads back the slots of
    its own group, and the groups' parts of y are summed, which XLA lowers to
    one all-reduce of [T, H] over ``ep``; the gradient to x is summed the
    same way in the backward pass. (Only what follows the layers splits the
    tokens over ``ep``: ``_head_spec``.)
    """
    b, s, h = x.shape
    T, E = b * s, cfg.num_experts
    C = cfg.capacity(T)
    G = kmesh.mesh.shape.get("ep", 1) if kmesh is not None else 1
    n = E // G * C  # slots a group
    dt = x.dtype
    xt = x.reshape(T, h)

    def plan_of(xt, router):
        return routing_plan(cfg, (xt @ router).astype(jnp.float32), C)

    if G > 1:
        # Every chip plans the whole routing from its own copy of the tokens.
        # Left to itself XLA splits the top-k over the copies and passes the
        # pieces round: eight all-to-all and a dozen small sums a layer.
        plan_of = jax.shard_map(plan_of, mesh=kmesh.mesh, axis_names={"ep"},
                                in_specs=P(), out_specs=P())
    with tracing.part("moe_route"):
        plan = plan_of(xt, lp["router"])
        token_of_slot = plan.token_of_slot.reshape(G, n, 1)
        gate_of_slot = plan.gate_of_slot.reshape(G, n, 1)
        # A claim's slot as each group sees it: its own, or n (not held
        # here).
        local = plan.slot_of_claim - (jnp.arange(G) * n)[:, None, None]
        slot_of_claim = jnp.where((local >= 0) & (local < n), local, n)

    # Dispatch: slot (e, c) reads its token's row; an empty slot reads zeros.
    with tracing.part("moe_dispatch"):
        expert_in = jax.vmap(gather_rows, (None, None, 0, None, 0))(
            xt, None, token_of_slot, None, slot_of_claim).reshape(E, C, h)
    with tracing.part("moe_experts"):
        gate = jax.nn.silu(jnp.einsum(
            "ech,ehi->eci", expert_in,
            lp["we_gate"]).astype(jnp.float32)).astype(dt)
        up = jnp.einsum("ech,ehi->eci", expert_in, lp["we_up"])
        expert_out = jnp.einsum("eci,eih->ech", gate * up, lp["we_down"])
    # Combine: a token reads back the slots of its kept claims, gate-weighted.
    with tracing.part("moe_combine"):
        y = jax.vmap(gather_rows, (0, None, 0, 0, 0))(
            expert_out.reshape(G, n, h), plan.gate, slot_of_claim,
            gate_of_slot, token_of_slot).sum(0)
        return y.reshape(b, s, h), plan


def moe_block(cfg: MixtralConfig, x: jax.Array, lp: dict, kmesh=None):
    """``_routed`` as a layer uses it: x [B, S, H] -> ([B, S, H], aux_loss)."""
    y, plan = _routed(cfg, x, lp, kmesh)
    return y, plan.aux


@tracing.part("attn")
def _attend(cfg: MixtralConfig, x, lp, inv_freq, positions, attn_impl, kmesh):
    """The attention half of a layer, residual included."""
    b, s, h = x.shape
    dt = x.dtype
    xn = rms_norm(x, lp["attn_norm"], cfg.norm_eps, kmesh)
    q = (xn @ lp["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = (xn @ lp["wk"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = (xn @ lp["wv"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    o = _llama._attention(cfg, q, k, v, attn_impl, None, kmesh)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.num_heads * cfg.head_dim)
    return x + (o @ lp["wo"]).astype(dt)


def _layer(cfg: MixtralConfig, x, lp, inv_freq, positions, attn_impl,
           kmesh=None):
    x = _attend(cfg, x, lp, inv_freq, positions, attn_impl, kmesh)
    with tracing.part("moe_route"):    # the routed layer's input norm
        xn = rms_norm(x, lp["mlp_norm"], cfg.norm_eps, kmesh)
    y, aux = moe_block(cfg, xn, lp, kmesh)
    with tracing.part("moe_combine"):
        return x + y.astype(x.dtype), aux


def _head_spec(kmesh, b: int, s: int) -> P | None:
    """Where the tokens [B, S] go once the layers are done, under a mesh with
    an ``ep`` axis: split over it as well, along the sequence where ``ep``
    divides it (past the last layer no position depends on another), else
    along the batch beside its own axes. The layers keep every token on
    every ``ep`` chip (``_routed``); the head and the loss do not need to,
    and a chip that runs them on all of them repeats its neighbours' work.
    None, the layers' layout as it is: no mesh, ``ep`` of 1, or a shape
    ``ep`` divides on neither dimension."""
    ep = kmesh.mesh.shape.get("ep", 1) if kmesh is not None else 1
    if ep == 1:
        return None
    if s % ep == 0:
        return P(kmesh.batch or None, "ep")
    if b % (ep * math.prod(kmesh.mesh.shape[ax] for ax in kmesh.batch)) == 0:
        return P((*kmesh.batch, "ep"))
    return None


def _lay(a: jax.Array, kmesh, spec: P | None) -> jax.Array:
    """``a`` laid out by ``spec`` on ``kmesh``'s mesh; None asks for nothing."""
    if spec is None:
        return a
    return lax.with_sharding_constraint(a, NamedSharding(kmesh.mesh, spec))


def forward(cfg: MixtralConfig, params: dict, tokens: jax.Array,
            positions: jax.Array | None = None, attn_impl: str = "flash",
            remat: bool = True, kmesh=None):
    """tokens [B, S] → (logits [B, S, V] fp32, mean aux loss). ``kmesh``:
    the caller's mesh for the Pallas kernels (ops/kernels.py); where it has
    an ``ep`` axis the logits come back with their tokens split over it
    (``_head_spec``), one global array all the same."""
    b, s = tokens.shape
    head = _head_spec(kmesh, b, s)
    if positions is None:
        with tracing.part("attn"):
            positions = jnp.arange(s)
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]
    with tracing.part("attn"):
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, None)

    from ray_tpu.models.llama import _remat_wrap

    layer_fn = _remat_wrap(
        partial(_layer, cfg, inv_freq=inv_freq, positions=positions,
                attn_impl=attn_impl, kmesh=kmesh),
        remat)

    def scan_body(x, lp):
        x, aux = layer_fn(x, lp)
        return x, aux

    with tracing.part("stack"):
        x, aux = lax.scan(scan_body, x, params["layers"])
        # The split below stops here, forward and backward: left to itself
        # XLA carries it into the scan and gathers the tokens again round
        # every kernel of every layer.
        x = _lay(x, kmesh, None if head is None else kmesh.rows_spec(3))
    with tracing.part("head"):
        x = _lay(rms_norm(x, params["final_norm"], cfg.norm_eps, kmesh),
                 kmesh, head)
        # bf16 MXU matmul with f32 accumulation — casting both operands to
        # f32 would fall off the MXU fast path (see llama.forward).
        logits = jnp.einsum("bsh,hv->bsv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
    with tracing.part("moe_combine"):
        return logits, aux.mean()


def loss_fn(cfg: MixtralConfig, params: dict, tokens: jax.Array,
            targets: jax.Array, mask: jax.Array | None = None,
            **fwd_kwargs) -> jax.Array:
    """LM cross-entropy + router load-balancing loss. The token losses are
    computed where ``forward`` left the logits: under an ``ep`` axis, on each
    chip's own share of the tokens."""
    logits, aux = forward(cfg, params, tokens, **fwd_kwargs)
    kmesh = fwd_kwargs.get("kmesh")
    head = _head_spec(kmesh, *tokens.shape)
    with tracing.part("loss"):
        targets = _lay(targets, kmesh, head)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        if mask is None:
            mask = jnp.ones_like(targets, jnp.float32)
        mask = _lay(mask.astype(jnp.float32), kmesh, head)
        lm = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return lm + cfg.router_aux_coef * aux


@partial(jax.jit, static_argnums=0, static_argnames=("attn_impl", "kmesh"))
def routing_stats(cfg: MixtralConfig, params: dict, tokens: jax.Array,
                  attn_impl: str = "flash", kmesh=None) -> dict:
    """What the routers of ``forward`` ask for on tokens [B, S] and what
    capacity refuses, layer by layer: ``expert_load`` [L, E], the claims on
    each expert over all B * S * top_k claims (1 / E each when balanced), and
    ``dropped_share`` [L], the claims past an expert's capacity over all
    claims. A reading beside the train step, not a part of it."""
    b, s = tokens.shape
    claims_all = b * s * cfg.top_k
    C = cfg.capacity(b * s)
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, None)

    def scan_body(x, lp):
        x = _attend(cfg, x, lp, inv_freq, jnp.arange(s), attn_impl, kmesh)
        with tracing.part("moe_route"):
            xn = rms_norm(x, lp["mlp_norm"], cfg.norm_eps, kmesh)
        y, plan = _routed(cfg, xn, lp, kmesh)
        with tracing.part("moe_combine"):
            return x + y.astype(x.dtype), plan.claims

    _, claims = lax.scan(scan_body, params["embed_tokens"][tokens],
                         params["layers"])
    return {"expert_load": claims / claims_all,
            "dropped_share": jnp.maximum(claims - C, 0).sum(-1) / claims_all}
