"""The routed layer both families share (``models/routed.py``), under each
family's rule, against a dense sum over all experts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import routed
from ray_tpu.models.lfm2 import Lfm2Config
from ray_tpu.models.longcat import LongcatConfig
from ray_tpu.models.sdar import SdarConfig
from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.ops.kernels import force_kernel_backend

H, F, LAYERS, TOKENS = 32, 48, 2, 40

RULES = {
    # LongCat's: softmax over routed and zero experts, bias, no
    # renormalisation, a factor; this shard holds experts 4 to 7 of 8.
    "longcat": LongcatConfig.tiny(
        hidden_size=H, expert_ffn_hidden_size=F, n_routed_experts=8,
        zero_expert_num=4, moe_topk=3, routed_scaling_factor=2.5,
        expert_shards=2, expert_shard=1).router_rule,
    # LFM2's: sigmoid, bias, renormalised, factor 1, every expert held.
    "lfm2": Lfm2Config.tiny(hidden_size=H, moe_intermediate_size=F,
                            num_experts=8, num_experts_per_tok=3).router_rule,
    "lfm2, a factor and no bias": Lfm2Config.tiny(
        hidden_size=H, moe_intermediate_size=F, num_experts=8,
        num_experts_per_tok=3, use_expert_bias=False,
        routed_scaling_factor=1.5).router_rule,
    # SDAR's (Qwen3-MoE's): softmax, no bias, renormalised with nothing
    # added to the sum, factor 1, every expert held.
    "sdar": SdarConfig.tiny(hidden_size=H, moe_intermediate_size=F,
                            num_experts=8,
                            num_experts_per_tok=3).router_rule,
}


def _layers(rule, key):
    keys = jax.random.split(key, 5)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)  # noqa: E731
    bias = {"router_bias": 0.2 * normal(keys[1], LAYERS, rule.outputs)}
    # a rule without a bias is handed no such leaf, and asks for none
    return {"router": normal(keys[0], LAYERS, H, rule.outputs) / np.sqrt(H),
            **(bias if rule.use_bias else {}),
            "we_gate": normal(keys[2], LAYERS, rule.held, H, F) / np.sqrt(H),
            "we_up": normal(keys[3], LAYERS, rule.held, H, F) / np.sqrt(H),
            "we_down": normal(keys[4], LAYERS, rule.held, F, H) / np.sqrt(F)}


def _dense(rule, layers, layer, u, valid):
    """Every held expert on every token, weighted by the rule's own weight
    where the token chose it and zero elsewhere; a zero expert is the
    identity. numpy float64 from the router's scores on."""
    logits = np.asarray(u, np.float64) @ np.asarray(layers["router"][layer],
                                                    np.float64)
    if rule.score == "softmax":
        e = np.exp(logits - logits.max(-1, keepdims=True))
        s = e / e.sum(-1, keepdims=True)
    else:
        s = 1.0 / (1.0 + np.exp(-logits))
    by = s + np.asarray(layers["router_bias"][layer]) if rule.use_bias else s
    idx = np.argsort(-by, axis=-1, kind="stable")[:, :rule.topk]
    w = np.take_along_axis(s, idx, axis=-1)
    if rule.renormalize:
        w = w / (w.sum(-1, keepdims=True) + rule.renorm_eps)
    weights = np.zeros_like(s)
    np.put_along_axis(weights, idx, w * rule.scaling_factor, axis=-1)
    weights *= np.asarray(valid)[:, None]
    x = np.asarray(u, np.float64)
    out = weights[:, rule.experts:].sum(-1, keepdims=True) * x
    lo = rule.expert_shard * rule.held
    for e in range(rule.held):
        gate = x @ np.asarray(layers["we_gate"][layer, e], np.float64)
        up = x @ np.asarray(layers["we_up"][layer, e], np.float64)
        y = (gate / (1.0 + np.exp(-gate)) * up) @ np.asarray(
            layers["we_down"][layer, e], np.float64)
        out += weights[:, lo + e][:, None] * y
    local = ((idx >= lo) & (idx < lo + rule.held)
             & np.asarray(valid)[:, None])
    zero = (idx >= rule.experts) & np.asarray(valid)[:, None]
    sizes = np.bincount(idx[local] - lo, minlength=rule.held)
    tm = routed.row_tile(len(x), rule.topk, rule.outputs)
    return out, [int(np.asarray(valid).sum()) * rule.topk, int(local.sum()),
                 int(zero.sum()), int((sizes > 0).sum()), 1,
                 int((-(-sizes // tm)).sum())]


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("name", list(RULES))
def test_the_shared_layer_is_the_dense_sum_under_each_family_s_rule(name,
                                                                   backend):
    rule = RULES[name]
    layers = _layers(rule, jax.random.PRNGKey(len(name)))
    u = jax.random.normal(jax.random.PRNGKey(9), (TOKENS, H), jnp.float32)
    valid = jnp.arange(TOKENS) % 7 != 3          # padding is routed nowhere
    want, want_counts = _dense(rule, layers, 1, u, valid)
    with force_kernel_backend(backend):
        got, counts = jax.jit(routed.moe_block, static_argnums=0)(
            rule, layers, 1, u, valid)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert [int(c) for c in counts] == want_counts
    assert not np.asarray(got)[~np.asarray(valid)].any()
    if name == "longcat":
        assert 0 < want_counts[2] and want_counts[1] < want_counts[0]
    else:
        assert want_counts[2] == 0 and want_counts[1] == want_counts[0]


@pytest.mark.parametrize("tokens,topk,outputs,want", [
    (512, 4, 64, 64),      # LFM2's prefill chunk: 32 rows an expert
    (64, 4, 64, 16),       # its decode step of 64 lines: 4
    (16, 4, 64, 16),       # its smallest bucket: 1
    (512, 12, 768, 16),    # LongCat's chunk: 8
    (32, 12, 768, 16),     # its decode step: 0.5
    (128, 4, 64, 16), (129, 4, 64, 32), (256, 4, 64, 32), (257, 4, 64, 64),
    (1024, 4, 64, 128), (8192, 8, 64, 128),    # the largest where none holds
    (512, 8, 128, 64),     # SDAR's forward of 128 lines x 4 rows: 32
    (256, 8, 128, 32),     # its chunk of 256: 16
])
def test_the_row_tile_holds_twice_the_mean_fill(tokens, topk, outputs, want):
    assert routed.row_tile(tokens, topk, outputs) == want
    assert want in routed.ROW_TILES and routed.ROW_TILES[0] == routed.MOE_TILE


# Group sizes of 5 held experts at tile tm, and picks that are not here.
FILLS = {"none, a tile, a tile and a row, a few":
         lambda tm: ([0, tm, tm + 1, 3, 0], 7),
         "every pick on one expert": lambda tm: ([0, 0, 3 * tm + 5, 0, 0], 0),
         "no pick here": lambda tm: ([0, 0, 0, 0, 0], 9)}


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("fill", list(FILLS))
@pytest.mark.parametrize("tm", routed.ROW_TILES)
def test_a_plan_at_any_tile_gives_each_pick_its_own_expert_s_product(
        tm, fill, backend):
    sizes, absent = FILLS[fill](tm)
    held = len(sizes)
    rng = np.random.default_rng(tm + len(fill))
    keys = rng.permutation(np.repeat(np.arange(held + 1), sizes + [absent]))
    x = rng.standard_normal((len(keys), H)).astype(np.float32)
    w = {k: rng.standard_normal(shape).astype(np.float32) / 6
         for k, shape in (("gate", (LAYERS, held, H, F)),
                          ("up", (LAYERS, held, H, F)),
                          ("down", (LAYERS, held, F, H)))}

    @jax.jit
    def run(keys, x):
        pick_of_row, row_of_pick, tile_expert, n_live, got_sizes = \
            routed.dispatch_plan(keys, held, tm)
        x_rows = jnp.where((pick_of_row >= 0)[:, None],
                           x[jnp.maximum(pick_of_row, 0)], 0)
        hidden = grouped_matmul(x_rows, w["gate"], 1, tile_expert, n_live,
                                tm=tm, w2=w["up"])
        out = grouped_matmul(hidden, w["down"], 1, tile_expert, n_live, tm=tm)
        return pick_of_row, row_of_pick, tile_expert, n_live, got_sizes, \
            out[jnp.where(keys < held, row_of_pick, 0)]

    with force_kernel_backend(backend):
        pick_of_row, row_of_pick, tile_expert, n_live, got_sizes, y = \
            (np.asarray(a) for a in run(jnp.asarray(keys, jnp.int32), x))
    assert got_sizes.tolist() == sizes
    assert int(n_live) == sum(-(-n // tm) for n in sizes)
    assert len(pick_of_row) == (len(keys) // tm + held) * tm
    here = np.flatnonzero(keys < held)
    # every local pick has a row of its own, in a live tile of its expert
    assert sorted(pick_of_row[pick_of_row >= 0]) == here.tolist()
    assert (pick_of_row[row_of_pick[here]] == here).all()
    assert (tile_expert[row_of_pick[here] // tm] == keys[here]).all()
    assert (row_of_pick[here] // tm < n_live).all()
    for p in here:
        xe = x[p].astype(np.float64)
        gate = xe @ w["gate"][1, keys[p]]
        want = (gate / (1 + np.exp(-gate)) * (xe @ w["up"][1, keys[p]])) \
            @ w["down"][1, keys[p]]
        np.testing.assert_allclose(y[p], want, atol=2e-5)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("name,tokens,tm", [
    ("lfm2", 16, 16), ("lfm2", 40, 32), ("lfm2", 80, 64), ("lfm2", 160, 128),
    ("longcat", 100, 64)])
def test_the_shared_layer_is_the_dense_sum_at_the_tile_its_fill_picks(
        name, tokens, tm, backend):
    """A router biased so that every token picks held expert 1 (more rows
    than a tile where tokens > tm), and none picks held expert 2."""
    rule = RULES[name]
    assert routed.row_tile(tokens, rule.topk, rule.outputs) == tm
    layers = _layers(rule, jax.random.PRNGKey(tokens))
    lo = rule.expert_shard * rule.held
    layers["router_bias"] = layers["router_bias"].at[:, lo + 1].set(30.0) \
        .at[:, lo + 2].set(-30.0)
    u = jax.random.normal(jax.random.PRNGKey(tm), (tokens, H), jnp.float32)
    valid = jnp.arange(tokens) % 11 != 5
    want, want_counts = _dense(rule, layers, 0, u, valid)
    with force_kernel_backend(backend):
        got, counts = jax.jit(routed.moe_block, static_argnums=0)(
            rule, layers, 0, u, valid)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert [int(c) for c in counts] == want_counts
    n_valid = int(valid.sum())
    assert want_counts[5] >= -(-n_valid // tm) + want_counts[3] - 1
    assert want_counts[3] < rule.held            # expert 2 got no row


def test_the_softmax_rule_without_bias_sums_to_one_exactly():
    """SDAR's rule: the chosen weights are the softmax at the chosen over
    their sum, with nothing added to it (LFM2's adds 1e-6, and says so)."""
    rule = RULES["sdar"]
    assert (rule.score, rule.use_bias, rule.renormalize, rule.renorm_eps) \
        == ("softmax", False, True, 0.0)
    assert RULES["lfm2"].renorm_eps == 1e-6
    gate = jax.random.normal(jax.random.PRNGKey(0), (H, rule.outputs))
    u = jax.random.normal(jax.random.PRNGKey(1), (TOKENS, H))
    idx, w = routed.route(rule, gate, None, u)
    p = jax.nn.softmax(np.asarray(u, np.float64) @ np.asarray(gate, np.float64))
    want = np.argsort(-np.asarray(p), axis=-1, kind="stable")[:, :rule.topk]
    np.testing.assert_array_equal(np.asarray(idx), want)
    picked = np.take_along_axis(np.asarray(p), want, axis=-1)
    np.testing.assert_allclose(np.asarray(w),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)


def test_a_rule_refuses_what_it_cannot_be():
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        routed.RouterRule(experts=8, topk=2, score="relu")
    with pytest.raises(ValueError, match="do not divide"):
        routed.RouterRule(experts=8, topk=2, expert_shards=3)
    with pytest.raises(ValueError, match="outside"):
        routed.RouterRule(experts=8, topk=2, expert_shards=2, expert_shard=2)
    rule = routed.RouterRule(experts=8, topk=2, zero_experts=4,
                             expert_shards=2)
    assert (rule.held, rule.outputs) == (4, 12)


def test_longcat_s_names_for_the_shared_layer_are_the_shared_layer_s():
    from ray_tpu.models import longcat

    assert longcat.dispatch_plan is routed.dispatch_plan
    assert longcat.moe_block is routed.moe_block
    assert longcat.MOE_COUNTERS is routed.MOE_COUNTERS
    assert longcat.MOE_TILE == routed.MOE_TILE == 16
    rule = longcat.LongcatConfig().router_rule
    assert (rule.score, rule.use_bias, rule.renormalize, rule.scaling_factor,
            rule.zero_experts, rule.outputs, rule.topk) == \
        ("softmax", True, False, 6.0, 256, 768, 12)


# --- the plan, a tile and a pick (PR 42) -----------------------------------

def _plan_a_row(keys, held: int, tm: int):
    """``dispatch_plan`` as it stood until PR 42, kept as the plain
    reference: ranks from a one-hot ``[P, held]`` and its running sum, the
    order from a stable argsort beside it, and five lookups a row of the
    worst-case layout."""
    p = keys.shape[0]
    max_tiles = p // tm + held
    onehot = keys[:, None] == jnp.arange(held)[None, :]
    csum = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    sizes = csum[-1]
    rank = jnp.take_along_axis(
        csum, jnp.minimum(keys, held - 1)[:, None], axis=1)[:, 0] - 1
    tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    n_live = tile_end[-1]
    group_start = jnp.cumsum(sizes) - sizes
    t = jnp.minimum(jnp.arange(max_tiles), jnp.maximum(n_live - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, t, side="right"), held - 1)
    order = jnp.argsort(keys, stable=True)
    rows = jnp.arange(max_tiles * tm)
    e = tile_expert[rows // tm]
    r = rows - tile_start[e] * tm
    live = (rows // tm < n_live) & (r < sizes[e])
    pick_of_row = jnp.where(
        live, order[jnp.clip(group_start[e] + r, 0, p - 1)], -1)
    row_of_pick = tile_start[jnp.minimum(keys, held - 1)] * tm + rank
    return pick_of_row, row_of_pick, tile_expert.astype(jnp.int32), \
        n_live.astype(jnp.int32), sizes


def _picks(tokens: int, topk: int, outputs: int, held: int, seed: int):
    """Keys as ``moe_block`` makes them: every token picks ``topk``
    distinct outputs, some liked more than others; the first ``held`` are
    here."""
    rng = np.random.default_rng(seed)
    liking = rng.normal(size=outputs) + rng.gumbel(size=(tokens, outputs))
    idx = np.argsort(-liking, axis=1)[:, :topk]
    return np.where(idx < held, idx, held).reshape(-1).astype(np.int32)


def _of_sizes(sizes, absent, seed):
    held = len(sizes)
    return np.random.default_rng(seed).permutation(
        np.repeat(np.arange(held + 1), list(sizes) + [absent])
    ).astype(np.int32), held


# The cells' shapes (P, held, tm): SDAR's forward, LFM2's chunk and decode
# step, LongCat's decode step and chunk (its chip holds 16 of 768 outputs).
PLAN_CASES = {
    "sdar forward": lambda: (_picks(512, 8, 128, 128, 1), 128, 64),
    "lfm2 chunk": lambda: (_picks(512, 4, 64, 64, 2), 64, 64),
    "lfm2 step": lambda: (_picks(64, 4, 64, 64, 3), 64, 16),
    "longcat step": lambda: (_picks(32, 12, 768, 16, 4), 16, 16),
    "longcat chunk": lambda: (_picks(512, 12, 768, 16, 5), 16, 16),
}
for _tm in routed.ROW_TILES:
    PLAN_CASES.update({
        f"tile {_tm}, random keys": lambda tm=_tm: (
            np.random.default_rng(tm).integers(0, 8, 5 * tm + 3)
            .astype(np.int32), 7, tm),
        f"tile {_tm}, every pick on one expert": lambda tm=_tm: (
            *_of_sizes([0, 0, 3 * tm + 5, 0], 0, tm), tm),
        f"tile {_tm}, no pick here": lambda tm=_tm: (
            *_of_sizes([0, 0, 0], 2 * tm + 1, tm), tm),
        f"tile {_tm}, fewer picks than a tile": lambda tm=_tm: (
            *_of_sizes([1, 0, tm // 2 - 2, 0, 1], 0, tm), tm),
        f"tile {_tm}, a tile and a tile and a row": lambda tm=_tm: (
            *_of_sizes([tm, 0, tm + 1, 2 * tm, 1], 4, tm), tm),
    })


@pytest.mark.parametrize("case", list(PLAN_CASES))
def test_the_plan_is_the_plan_a_row_value_for_value(case):
    keys, held, tm = PLAN_CASES[case]()
    want = [np.asarray(a) for a in jax.jit(
        _plan_a_row, static_argnums=(1, 2))(jnp.asarray(keys), held, tm)]
    got = [np.asarray(a) for a in jax.jit(
        routed.dispatch_plan, static_argnums=(1, 2))(
            jnp.asarray(keys), held, tm)]
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
    here, n_live = keys < held, int(want[3])
    np.testing.assert_array_equal(got[0], want[0])            # pick_of_row
    np.testing.assert_array_equal(got[1][here], want[1][here])
    np.testing.assert_array_equal(got[2][:n_live], want[2][:n_live])
    assert int(got[3]) == n_live
    np.testing.assert_array_equal(got[4], want[4])
    assert n_live == sum(-(-int(n) // tm) for n in want[4])


def _moe_block_a_row(rule, layers, layer, u, valid):
    """``moe_block`` as it stood until PR 42, over ``_plan_a_row``."""
    t, _ = u.shape
    held, topk = rule.held, rule.topk
    tm = routed.row_tile(t, topk, rule.outputs)
    idx, w = routed.route(
        rule, routed.layer_of(layers["router"], layer),
        routed.layer_of(layers["router_bias"], layer)
        if rule.use_bias else None, u)
    lo = rule.expert_shard * held
    chosen = valid[:, None]
    local = chosen & (idx >= lo) & (idx < lo + held)
    zero = chosen & (idx >= rule.experts)
    keys = jnp.where(local, idx - lo, held).reshape(-1).astype(jnp.int32)
    pick_of_row, row_of_pick, tile_expert, n_live, sizes = _plan_a_row(
        keys, held, tm)
    x_rows = jnp.where((pick_of_row >= 0)[:, None],
                       u[jnp.maximum(pick_of_row, 0) // topk], 0)
    hidden = grouped_matmul(x_rows, layers["we_gate"], layer, tile_expert,
                            n_live, tm=tm, w2=layers["we_up"])
    out_rows = grouped_matmul(hidden, layers["we_down"], layer, tile_expert,
                              n_live, tm=tm)
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
    return y.astype(u.dtype), counts


# A cell's decode shape at small widths: tokens a call and the rule.
DECODE_SHAPES = {
    "sdar: 128 lines x 4 rows, 8 of 128": (512, SdarConfig.tiny(
        hidden_size=H, moe_intermediate_size=F, num_experts=128,
        num_experts_per_tok=8).router_rule, 64),
    "lfm2: 64 lines, 4 of 64": (64, Lfm2Config.tiny(
        hidden_size=H, moe_intermediate_size=F, num_experts=64,
        num_experts_per_tok=4).router_rule, 16),
    "longcat: 32 lines, 12 of 24 + 8 zero, shard 1 of 2": (
        32, LongcatConfig.tiny(
            hidden_size=H, expert_ffn_hidden_size=F, n_routed_experts=24,
            zero_expert_num=8, moe_topk=12, routed_scaling_factor=2.5,
            expert_shards=2, expert_shard=1).router_rule, 32),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(DECODE_SHAPES))
def test_the_layer_is_bit_equal_to_the_layer_over_the_plan_a_row(shape,
                                                                 dtype):
    tokens, rule, tm = DECODE_SHAPES[shape]
    assert routed.row_tile(tokens, rule.topk, rule.outputs) == tm
    layers = jax.tree.map(lambda a: a.astype(dtype),
                          _layers(rule, jax.random.PRNGKey(tokens)))
    u = jax.random.normal(jax.random.PRNGKey(3), (tokens, H), dtype)
    valid = jnp.arange(tokens) % 13 != 4
    with force_kernel_backend("reference"):
        want, want_counts = jax.jit(_moe_block_a_row, static_argnums=0)(
            rule, layers, 1, u, valid)
        got, counts = jax.jit(routed.moe_block, static_argnums=0)(
            rule, layers, 1, u, valid)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))
    assert int(counts[1]) > 0 and np.asarray(got, np.float32).any()


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _sizes(eqn):
    return [int(np.prod(v.aval.shape)) for v in (*eqn.invars, *eqn.outvars)
            if hasattr(v.aval, "shape")]


# What moves a value to a place another value names, one element at a time
# or by a comparison network: 8 ns an element looked up, on this chip.
_INDEXED = ("gather", "scatter", "sort", "cum", "dynamic_slice",
            "dynamic_update_slice", "while", "scan")
# What XLA fuses into the sum that follows it, so that its operand never
# exists in memory.
_FUSED_INTO_A_SUM = {"lt", "le", "gt", "ge", "eq", "ne", "lt_to", "le_to",
                     "and", "select_n",
                     "broadcast_in_dim", "convert_element_type",
                     "reduce_sum", "iota", "reshape", "squeeze"}
_CALLS = {"pjit", "jit", "closed_call", "core_call", "custom_jvp_call"}


def test_the_plan_looks_nothing_up_a_padded_row():
    """At SDAR's forward (4,096 picks over 128 experts in tiles of 64:
    12,288 rows) the plan's index work is done a pick or a tile. Allowed,
    and why:

    - two sorts of P pairs (the picks into expert order; their rows back
      into pick order) and running sums over ``held`` values;
    - ONE operation as wide as the rows: the scatter that writes the P
      sorted picks to their rows over 12,288 of -1 (P scalar writes; the
      row-wise form it replaced was five lookups of 12,288);
    - comparisons of ``[held + 1, P]`` and ``[P, held]`` only where they
      are summed at once (a group's start is a count of sorted keys, a
      sorted pick's padding a sum over the groups before it): XLA fuses
      the comparison into the sum, nothing of that shape is stored, and
      none of them is a gather, a sort, a running sum or a loop.
    """
    p, held, tm = 4096, 128, 64
    mp = (p // tm + held) * tm
    jaxpr = jax.make_jaxpr(lambda k: routed.dispatch_plan(k, held, tm))(
        jnp.zeros((p,), jnp.int32)).jaxpr
    indexed = [e for e in _equations(jaxpr)
               if any(word in e.primitive.name for word in _INDEXED)]
    scatters = [e for e in indexed if "scatter" in e.primitive.name]
    assert len(scatters) == 1
    assert sorted(_sizes(scatters[0])) == sorted([mp, p, p, mp])
    for eqn in indexed:
        if eqn is not scatters[0]:
            assert max(_sizes(eqn)) <= p, eqn
    sorts = [e for e in indexed if e.primitive.name == "sort"]
    assert [len(e.invars) for e in sorts] == [2, 2]
    assert not [e for e in indexed
                if e.primitive.name in ("while", "scan", "gather")], \
        "a search by halving or a lookup came back"
    for eqn in _equations(jaxpr):
        if eqn.primitive.name not in _CALLS and max(_sizes(eqn),
                                                    default=0) >= mp:
            assert eqn is scatters[0] or \
                eqn.primitive.name in _FUSED_INTO_A_SUM, eqn
    # what is as wide as the rows is the rows' picks, and nothing else
    assert [v.aval.shape for v in jaxpr.outvars] == [
        (mp,), (p,), (mp // tm,), (), (held,)]


def test_the_rows_are_one_gather_under_one_mask():
    """``moe_dispatch`` at SDAR's forward: the tokens' rows and a row of
    zeros after them, gathered once by a row's token; no select over
    ``[12288, H]`` follows it (it was a second pass over every row)."""
    tokens, rule, tm = DECODE_SHAPES["sdar: 128 lines x 4 rows, 8 of 128"]
    mp = (tokens * rule.topk // tm + rule.held) * tm
    layers = _layers(rule, jax.random.PRNGKey(0))
    with force_kernel_backend("reference"):
        jaxpr = jax.make_jaxpr(lambda u: routed.moe_block(
            rule, layers, 0, u, jnp.ones((tokens,), bool)))(
                jnp.zeros((tokens, H), jnp.float32)).jaxpr
    dispatch = [e for e in _equations(jaxpr)
                if "moe_dispatch" in str(e.source_info.name_stack)
                and e.primitive.name not in _CALLS
                and any(getattr(v.aval, "shape", None) == (mp, H)
                        for v in e.outvars)]
    assert [e.primitive.name for e in dispatch] == ["gather"]
