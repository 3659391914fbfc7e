"""The routed expert layer that keeps every token, shared by the models
that have one (models/longcat.py, models/lfm2.py, models/sdar.py).

What differs between the families is the router's rule, and a
:class:`RouterRule` states it: how a router output becomes a score (softmax
over all outputs, or a sigmoid of each), whether a selection bias is added
for the choice (never for the weights), whether the chosen weights are
renormalised to sum to one, the factor they are scaled by, whether the
choice is limited to the best groups of experts (``groups`` consecutive
groups of equal size, of which the ``topk_groups`` best are kept: a token's
experts then lie on at most that many devices of a deployment that holds a
group a device; a group scores as its largest score where the rule has no
bias (DeepSeek-V2's), and as the sum of its two largest ``score + bias``
where it has one (the only form any family has for the two together:
DeepSeek-V3's, Ling's), how many of the
router's outputs are zero-compute experts (the identity: a weighted add of
the layer's input), and which of the routed experts this program holds
(``expert_shard`` of ``expert_shards`` equal shares).

What is shared is everything after the rule: the layer routes over all the
router's outputs, keeps every pick that falls on a held expert (no capacity,
no drop), sorts the picks by expert into tiles of rows
(:func:`dispatch_plan`; the kernel fetches an expert's weights once a tile,
so the tile follows the rows a call's shapes promise an expert:
:func:`row_tile`, ``MOE_TILE`` at the least), multiplies the live tiles only
(ops/grouped_matmul.py) and computes the part of ``sum_e w_e E_e(u)`` that
its own experts give, plus the zero experts' part, which every shard
computes for its own tokens. What the absent shards' experts would add is
an expert-parallel exchange this module does not have.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.util import tracing

# The fewest rows of one tile of the grouped matmul: a packed sublane tile of
# bfloat16. row_tile picks among these.
MOE_TILE = 16
ROW_TILES = (MOE_TILE, 32, 64, 128)
# What moe_block counts, in this order (llm/engine.py adds them up).
MOE_COUNTERS = ("moe_picks", "moe_picks_local", "moe_picks_zero",
                "moe_experts_touched", "moe_layer_steps", "moe_tiles")


@dataclass(frozen=True)
class RouterRule:
    """A routed layer's rule, from the model's configuration."""

    experts: int                  # routed experts in the whole model
    topk: int                     # experts a token
    score: str = "softmax"        # or "sigmoid"
    use_bias: bool = True         # added to the score for the choice alone
    renormalize: bool = False     # chosen weights / (their sum + renorm_eps)
    renorm_eps: float = 1e-6      # 0 where the family's code adds nothing
    scaling_factor: float = 1.0
    zero_experts: int = 0         # router outputs after the routed experts
    expert_shard: int = 0
    expert_shards: int = 1
    groups: int = 1               # consecutive groups of routed experts
    topk_groups: int = 1          # groups a token may choose among

    def __post_init__(self):
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"router score {self.score!r}: softmax or "
                             "sigmoid")
        if self.experts % self.expert_shards:
            raise ValueError(
                f"{self.experts} routed experts do not divide "
                f"into {self.expert_shards} shards")
        if not 0 <= self.expert_shard < self.expert_shards:
            raise ValueError(f"expert_shard {self.expert_shard} outside "
                             f"0..{self.expert_shards - 1}")
        if self.groups > 1:
            if self.experts % self.groups or self.zero_experts:
                raise ValueError(
                    f"{self.experts} routed experts (+ {self.zero_experts} "
                    f"zero experts) do not divide into {self.groups} groups")
            if self.use_bias and self.experts // self.groups < 2:
                raise ValueError(
                    "a grouped rule with a selection bias scores a group "
                    "by its two best experts: groups of "
                    f"{self.experts // self.groups} have none")
            if not (1 <= self.topk_groups <= self.groups and self.topk
                    <= self.topk_groups * (self.experts // self.groups)):
                raise ValueError(
                    f"{self.topk_groups} of {self.groups} groups do not "
                    f"hold {self.topk} experts a token")

    @property
    def held(self) -> int:
        return self.experts // self.expert_shards

    @property
    def outputs(self) -> int:
        return self.experts + self.zero_experts


def row_tile(tokens: int, topk: int, outputs: int) -> int:
    """Rows of a tile for a call on ``tokens`` tokens: the smallest of
    ``ROW_TILES`` that holds twice the rows an expert gets on average,
    ``tokens * topk / outputs``, so that an expert fuller than the mean is
    still one tile and its weights one fetch; the largest where none does.
    All three are static: a program's shape and its rule's integers."""
    for tm in ROW_TILES:
        if tm * outputs >= 2 * tokens * topk:
            return tm
    return ROW_TILES[-1]


def layer_of(stack, index):
    """One layer of a stacked leaf, by a run-time index."""
    return lax.dynamic_index_in_dim(stack, index, 0, keepdims=False)


def route(rule: RouterRule, router, bias, u):
    """u: [T, H] -> (idx [T, topk] over all router outputs, w [T, topk]
    float32). Scores in float32 (true float32: a TPU's default float32
    matmul is one bfloat16 pass), the choice by score (+ bias), among the
    best groups where the rule has groups, the weights by score alone,
    renormalised or not, then scaled."""
    logits = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if rule.score == "softmax":
        p = jax.nn.softmax(logits, axis=-1)
    else:
        p = jax.nn.sigmoid(logits)
    choice = p + bias if rule.use_bias else p
    if rule.groups > 1 and rule.use_bias:
        # A group's score is the sum of its two largest ``score + bias``;
        # outside the kept groups a pick never falls: a bias may be
        # negative, so no finite number is under every ``score + bias``.
        best = lax.top_k(choice.reshape(
            -1, rule.groups, rule.experts // rule.groups), 2)[0].sum(axis=-1)
        outside = -jnp.inf
    elif rule.groups > 1:
        # A group's score is its best expert's; outside the kept groups a
        # score is 0, under every score a softmax or a sigmoid gives.
        best = choice.reshape(-1, rule.groups,
                              rule.experts // rule.groups).max(axis=-1)
        outside = 0.0
    if rule.groups > 1:
        _, kept = lax.top_k(best, rule.topk_groups)
        keep = jnp.any(kept[:, :, None] == jnp.arange(rule.groups), axis=1)
        choice = jnp.where(
            jnp.repeat(keep, rule.experts // rule.groups, axis=1), choice,
            outside)
    _, idx = lax.top_k(choice, rule.topk)
    w = jnp.take_along_axis(p, idx, axis=-1)
    if rule.renormalize:
        w = w / (w.sum(axis=-1, keepdims=True) + rule.renorm_eps)
    return idx, rule.scaling_factor * w


def dispatch_plan(keys, held: int, tm: int):
    """Where each local pick goes among rows sorted by expert in tiles of
    ``tm``. keys: [P] int32, a pick's held expert (0..held-1) or ``held``
    (not here). Returns ``pick_of_row`` [Mp] (-1: an empty row),
    ``row_of_pick`` [P] (meaningless for a pick that is not here),
    ``tile_expert`` [Mp // tm], ``n_live`` and the group sizes [held].
    Mp = (P // tm + held) * tm holds the worst case: every pick local.

    The index work is done once a pick (P values) or once a tile
    (Mp // tm), never once a row of Mp: a scalar lookup costs a v5e 8 ns
    an element, and a sort of 4,096 pairs what 900 lookups do. One stable
    sort puts the picks in expert order; a sorted pick's row is its place
    plus the padding of the groups that end before it; the rows' picks are
    those P picks written to their rows over -1 (the one operation that is
    Mp wide), and the picks' rows the same values sorted back by pick.
    The two ``[.., P]`` comparisons below are summed where they are made:
    XLA fuses each into one pass and no ``[P, held]`` array exists."""
    p = keys.shape[0]
    max_tiles = p // tm + held
    place = jnp.arange(p, dtype=jnp.int32)
    sorted_keys, order = lax.sort((keys, place), num_keys=1, is_stable=True)
    # Expert e's group is sorted picks ends[e] - sizes[e] to ends[e].
    starts = jnp.searchsorted(
        sorted_keys, jnp.arange(held + 1, dtype=jnp.int32), side="left",
        method="compare_all")
    ends, sizes = starts[1:], starts[1:] - starts[:-1]
    tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n_live = tile_end[-1]
    # Tile t belongs to the first expert whose tiles end past it; a dead
    # tile to the last live tile's expert (a fetch it repeats, not a new
    # one, where a backend visits dead tiles at all).
    t = jnp.minimum(jnp.arange(max_tiles), jnp.maximum(n_live - 1, 0))
    tile_expert = jnp.minimum(jnp.searchsorted(
        tile_end, t, side="right", method="compare_all"), held - 1)
    padding = tiles * tm - sizes
    row_of_sorted = place + jnp.sum(jnp.where(
        ends[None, :] <= place[:, None], padding[None, :], 0), axis=1)
    rows = max_tiles * tm
    pick_of_row = jnp.full((rows,), -1, jnp.int32).at[
        jnp.where(sorted_keys < held, row_of_sorted, rows)].set(
            order, mode="drop")
    _, row_of_pick = lax.sort((order, row_of_sorted), num_keys=1)
    return pick_of_row, row_of_pick, tile_expert.astype(jnp.int32), \
        n_live.astype(jnp.int32), sizes


def tile_rows(u, pick_of_row, topk: int):
    """u [T, H] laid out as the plan says: [Mp, H], a pick's row its
    token's, an empty row zeros. One gather under one mask: an empty row
    reads the row of zeros laid after the tokens' rows (a select over the
    gathered rows was a second pass over all of them)."""
    return jnp.concatenate([u, jnp.zeros_like(u[:1])])[
        jnp.where(pick_of_row >= 0, pick_of_row // topk, u.shape[0])]


def moe_block(rule: RouterRule, layers: dict, layer, u, valid):
    """:func:`moe_block_picks` without the picks."""
    return moe_block_picks(rule, layers, layer, u, valid)[:2]


def moe_block_picks(rule: RouterRule, layers: dict, layer, u, valid):
    """The routed layer on u [T, H]: this shard's experts' part and the
    zero experts' part of ``sum_e w_e E_e(u)``. ``layers`` holds the stacked
    ``router``, ``router_bias`` (a rule with ``use_bias``), ``we_gate``,
    ``we_up`` and ``we_down`` (the expert stacks are read in place), ``layer`` is the routed layer's index
    on their leading axis. A token with ``valid`` false
    (padding, an idle slot) is routed nowhere and counted nowhere. Returns
    (y [T, H], counts int32[6] in the order of MOE_COUNTERS, local
    [T, topk] bool: the picks that fell on a held expert, for a model that
    counts something of its own from them)."""
    t, _ = u.shape
    held, topk = rule.held, rule.topk
    tm = row_tile(t, topk, rule.outputs)
    with tracing.part("moe_route"):
        idx, w = route(rule, layer_of(layers["router"], layer),
                       layer_of(layers["router_bias"], layer)
                       if rule.use_bias else None, u)
        lo = rule.expert_shard * held
        chosen = valid[:, None]
        local = chosen & (idx >= lo) & (idx < lo + held)
        zero = chosen & (idx >= rule.experts)
        keys = jnp.where(local, idx - lo, held).reshape(-1).astype(jnp.int32)
        pick_of_row, row_of_pick, tile_expert, n_live, sizes = dispatch_plan(
            keys, held, tm)
    with tracing.part("moe_dispatch"):
        x_rows = tile_rows(u, pick_of_row, topk)
    with tracing.part("moe_experts"):
        hidden = grouped_matmul(x_rows, layers["we_gate"], layer, tile_expert,
                                n_live, tm=tm, w2=layers["we_up"])
        out_rows = grouped_matmul(hidden, layers["we_down"], layer,
                                  tile_expert, n_live, tm=tm)
    with tracing.part("moe_combine"):
        # A select, not a product: rows of dead tiles were never written.
        picked = out_rows[jnp.where(local, row_of_pick.reshape(t, topk), 0)]
        y = jnp.sum(jnp.where(local[..., None],
                              w[..., None] * picked.astype(jnp.float32), 0.0),
                    axis=1)
        if rule.zero_experts:
            y += jnp.sum(jnp.where(zero, w, 0.0), axis=1,
                         keepdims=True) * u.astype(jnp.float32)
        counts = jnp.stack([
            valid.sum() * topk, local.sum(), zero.sum(), (sizes > 0).sum(),
            jnp.ones((), jnp.int32), n_live]).astype(jnp.int32)
        return y.astype(u.dtype), counts, local
