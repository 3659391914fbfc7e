"""Mixtral MoE model + expert parallelism.

The reference has no first-class MoE (SURVEY.md §2.4 EP row: vLLM kwargs +
collective all-to-all); these tests pin down the TPU-native one: routing
semantics, training convergence, and numerical equivalence between the
single-device and expert-parallel (ep) sharded runs.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.models.mixtral import (
    MixtralConfig,
    forward,
    init_params,
    loss_fn,
    moe_block,
    param_logical_axes,
    routing_plan,
    routing_stats,
)


@pytest.fixture(scope="module")
def cfg():
    return MixtralConfig.tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


# ---- the oracle: GShard's dense form, one-hot masks of [T, E, C] ----------
def dense_routing(cfg, logits, C):
    """(dispatch [T,E,C], combine [T,E,C], aux): what the routed layer means.
    dispatch[t, e, c] = 1 where token t owns slot c of expert e."""
    E, K = cfg.num_experts, cfg.top_k
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, idx = jax.lax.top_k(probs, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    expert = jax.nn.one_hot(idx, E, dtype=jnp.int32)             # [T, K, E]
    flat = expert.reshape(-1, E)             # priority: token-major, k-minor
    position = ((jnp.cumsum(flat, 0) - flat) * flat).sum(-1).reshape(idx.shape)
    slot = jax.nn.one_hot(position, C) * (position < C)[..., None]  # [T, K, C]
    dispatch = jnp.einsum("tke,tkc->tec", expert.astype(jnp.float32), slot)
    combine = jnp.einsum("tk,tke,tkc->tec", gate, expert.astype(jnp.float32),
                         slot)
    frac = dispatch.sum((0, 2)) / jnp.maximum(dispatch.sum(), 1.0)
    return dispatch, combine, E * jnp.sum(frac * probs.mean(0))


def dense_moe_block(cfg, x, lp):
    b, s, h = x.shape
    xt = x.reshape(b * s, h)
    dispatch, combine, aux = dense_routing(
        cfg, (xt @ lp["router"]).astype(jnp.float32), cfg.capacity(b * s))
    expert_in = jnp.einsum("tec,th->ech", dispatch, xt)
    hidden = jax.nn.silu(jnp.einsum("ech,ehi->eci", expert_in, lp["we_gate"])
                         ) * jnp.einsum("ech,ehi->eci", expert_in, lp["we_up"])
    expert_out = jnp.einsum("eci,eih->ech", hidden, lp["we_down"])
    return jnp.einsum("tec,ech->th", combine, expert_out).reshape(b, s, h), aux


LAYER_KEYS = ("router", "we_gate", "we_up", "we_down")
# capacity factor -> slots an expert at T = 32, K = 2, E = 4
CAPACITIES = {"ample": 4.0, "drops": 1.25, "one_slot": 1e-6}


@pytest.fixture(scope="module")
def against_oracle(cfg, params):
    """name -> (program's, oracle's) for the block's output, auxiliary loss
    and gradients, at each capacity; the tokens share a component, so the
    experts' loads differ (12, 26, 10, 16 claims) and 1.25 (20 slots) drops."""
    lp = {k: params["layers"][k][0] for k in LAYER_KEYS}
    lp["router"] = lp["router"] * 30.0
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.hidden_size)) + 0.5
    weight = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    out = {}
    for name, factor in CAPACITIES.items():
        c = dataclasses.replace(cfg, capacity_factor=factor)

        def run(block):
            def f(x, lp):
                y, aux = block(c, x, lp)
                return (y * weight).sum() + 3.0 * aux, (y, aux)
            (_, (y, aux)), (dx, dlp) = jax.jit(jax.value_and_grad(
                f, argnums=(0, 1), has_aux=True))(x, lp)
            return {"y": y, "aux": aux, "x": dx, **dlp}

        got, want = run(moe_block), run(dense_moe_block)
        logits = (x.reshape(32, -1) @ lp["router"]).astype(jnp.float32)
        got["dropped"] = (routing_plan(c, logits, c.capacity(32)).slot_of_claim
                          == cfg.num_experts * c.capacity(32)).sum()
        out[name] = got, want
    return out


class TestMoeBlock:
    def test_routing_capacity_and_shapes(self, cfg, params):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.hidden_size),
                              jnp.float32)
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        y, aux = moe_block(cfg, x, lp)
        assert y.shape == x.shape
        assert jnp.isfinite(y).all()
        # Balanced-ish router on random init: aux loss near 1.0 (its minimum
        # for a uniform router is exactly 1.0), never below.
        assert 0.99 <= float(aux) < float(cfg.num_experts)

    def test_topk_gates_renormalized(self, cfg):
        """With ample capacity every claim is kept: a token's weights sum to
        exactly 1 (renormalized top-k) from either side of the plan, and it
        holds exactly top_k slots."""
        T, E = 16, cfg.num_experts
        logits = jax.random.normal(jax.random.PRNGKey(3), (T, E))
        plan = routing_plan(cfg, logits, capacity=T)
        assert (np.asarray(plan.slot_of_claim) < E * T).all()
        np.testing.assert_allclose(np.asarray(plan.gate.sum(-1)), np.ones(T),
                                   rtol=1e-5)
        token_of_slot = np.asarray(plan.token_of_slot)
        np.testing.assert_array_equal(
            np.bincount(token_of_slot, minlength=T + 1)[:T],
            np.full(T, cfg.top_k))
        np.testing.assert_allclose(
            np.bincount(token_of_slot, np.asarray(plan.gate_of_slot),
                        minlength=T + 1)[:T], np.ones(T), rtol=1e-5)
        assert float(plan.aux) >= 0.99

    def test_capacity_drops_overflow(self, cfg):
        """With capacity 1, at most one claim per expert is kept, and a
        dropped claim holds no slot and weighs nothing."""
        T, E = 16, cfg.num_experts
        logits = jnp.zeros((T, E))  # uniform router
        plan = routing_plan(cfg, logits, capacity=1)
        slot_of_claim = np.asarray(plan.slot_of_claim)
        token_of_slot = np.asarray(plan.token_of_slot)
        assert token_of_slot.shape == (E,)        # one slot an expert
        kept = slot_of_claim < E
        assert kept.sum() == (token_of_slot < T).sum() <= E
        assert len(set(slot_of_claim[kept])) == kept.sum()
        # what the slots weigh is what the kept claims weigh, token by token
        by_slot = np.bincount(token_of_slot, np.asarray(plan.gate_of_slot),
                              minlength=T + 1)[:T]
        np.testing.assert_allclose(
            by_slot, (np.asarray(plan.gate) * kept).sum(-1), rtol=1e-6)
        assert (by_slot <= 1.0 + 1e-5).all()

    def test_plan_maps_are_inverse(self, cfg):
        """slot_of_claim and token_of_slot say the same thing: slot s holds
        token t iff one of t's claims holds s; slots fill in token order."""
        T, E, C = 24, cfg.num_experts, 7
        logits = jax.random.normal(jax.random.PRNGKey(5), (T, E)) * 3.0
        plan = routing_plan(cfg, logits, capacity=C)
        slot_of_claim = np.asarray(plan.slot_of_claim)
        token_of_slot = np.asarray(plan.token_of_slot)
        assert (slot_of_claim == E * C).any()     # this draw drops claims
        for t, k in np.ndindex(T, cfg.top_k):
            if slot_of_claim[t, k] < E * C:
                assert token_of_slot[slot_of_claim[t, k]] == t
        for s in np.flatnonzero(token_of_slot < T):
            assert s in slot_of_claim[token_of_slot[s]]
        held = token_of_slot.reshape(E, C)
        for row in held:
            live = row[row < T]
            assert (row[:len(live)] == live).all() and (np.diff(live) > 0).all()
        np.testing.assert_array_equal(
            np.minimum(np.asarray(plan.claims), C), (held < T).sum(-1))

    @pytest.mark.parametrize("what", ["y", "aux", "x", *LAYER_KEYS])
    @pytest.mark.parametrize("capacity", list(CAPACITIES))
    def test_block_matches_dense_oracle(self, against_oracle, capacity, what):
        """Output, auxiliary loss and every gradient of the index form equal
        the one-hot form's: same top-2, gates, slot order and drops."""
        got, want = against_oracle[capacity]
        dropped = int(got["dropped"])
        assert (dropped == 0) if capacity == "ample" else (dropped > 0)
        assert np.abs(np.asarray(want[what])).max() > 0
        np.testing.assert_allclose(np.asarray(got[what]),
                                   np.asarray(want[what]),
                                   rtol=1e-5, atol=1e-5)

    def test_routing_stats_counts_claims(self, cfg, params):
        """Load and drops of each layer's router against a count by hand."""
        tokens = (jnp.arange(24, dtype=jnp.int32).reshape(2, 12) * 7
                  ) % cfg.vocab_size
        c = dataclasses.replace(cfg, capacity_factor=1.0)
        p = dict(params, layers=dict(params["layers"],
                                     router=params["layers"]["router"] * 30.0))
        stats = routing_stats(c, p, tokens, attn_impl="blockwise")
        T, E, K, C = 24, c.num_experts, c.top_k, c.capacity(24)
        assert stats["expert_load"].shape == (c.num_layers, E)
        assert stats["dropped_share"].shape == (c.num_layers,)
        # By hand: the routers' inputs layer by layer, then plain counting.
        from ray_tpu.models.mixtral import _attend
        from ray_tpu.ops.norms import rms_norm
        from ray_tpu.ops.rope import rope_frequencies

        x = p["embed_tokens"][tokens]
        inv_freq = rope_frequencies(c.head_dim, c.rope_theta, None)
        for layer in range(c.num_layers):
            lp = jax.tree.map(lambda a: a[layer], p["layers"])
            x = _attend(c, x, lp, inv_freq, jnp.arange(12), "blockwise", None)
            xn = rms_norm(x, lp["mlp_norm"], c.norm_eps, None)
            logits = np.asarray(xn.reshape(T, -1) @ lp["router"])
            counts = np.zeros(E, int)
            for row in logits:
                for e in np.argsort(-row, kind="stable")[:K]:
                    counts[e] += 1
            np.testing.assert_allclose(np.asarray(stats["expert_load"][layer]),
                                       counts / (T * K), rtol=1e-6)
            np.testing.assert_allclose(
                float(stats["dropped_share"][layer]),
                np.maximum(counts - C, 0).sum() / (T * K), rtol=1e-6)
            x = x + moe_block(c, xn, lp)[0]
        assert float(stats["dropped_share"].sum()) > 0

    def test_forward_and_loss(self, cfg, params):
        tokens = jnp.arange(16, dtype=jnp.int32).reshape(1, 16) % cfg.vocab_size
        logits, aux = forward(cfg, params, tokens, attn_impl="blockwise",
                              remat=False)
        assert logits.shape == (1, 16, cfg.vocab_size)
        loss = loss_fn(cfg, params, tokens, tokens, attn_impl="blockwise",
                       remat=False)
        assert jnp.isfinite(loss)


class TestMoeTraining:
    def test_loss_decreases(self, cfg):
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.train.spmd import make_mixtral_train_step

        mesh = build_mesh(MeshSpec(), jax.devices("cpu")[:1])
        step_fn, init_state, shard = make_mixtral_train_step(
            cfg, mesh, optimizer=optax.adamw(3e-3), attn_impl="blockwise",
            remat=False)
        state = init_state()
        tokens = shard(np.random.randint(0, cfg.vocab_size, (4, 16)))
        targets = shard(np.roll(np.asarray(tokens), -1, axis=1))
        state, m0 = step_fn(state, tokens, targets)
        for _ in range(5):
            state, m = step_fn(state, tokens, targets)
        assert float(m["loss"]) < float(m0["loss"])

    @pytest.mark.parametrize("hand_mesh_down", [True, False])
    def test_expert_parallel_matches_single_device(self, cfg, hand_mesh_down):
        """ep-sharded forward and gradients must be numerically equivalent to
        one device: sharding the experts is a layout change, not math. With
        the mesh handed down (as the step factory does) each chip fills the
        slots of its own experts; without it XLA partitions one group."""
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.parallel.sharding import (ShardingRules, kernel_mesh,
                                               tree_shardings)

        devs = jax.devices("cpu")
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % cfg.vocab_size
        targets = jnp.roll(tokens, -1, axis=1)

        def run(p, kmesh=None):
            kw = dict(attn_impl="blockwise", remat=False, kmesh=kmesh)
            logits, aux = jax.jit(lambda p, t: forward(cfg, p, t, **kw))(
                p, tokens)
            grads = jax.jit(jax.grad(
                lambda p: loss_fn(cfg, p, tokens, targets, **kw)))(p)
            return logits, aux, grads

        ref_logits, ref_aux, ref_grads = run(params)

        mesh = build_mesh(MeshSpec(ep=4), devs[:4])
        sh = tree_shardings(mesh, param_logical_axes(cfg), ShardingRules())
        sharded = jax.tree.map(jax.device_put, params, sh)
        ep_logits, ep_aux, ep_grads = run(
            sharded, kernel_mesh(mesh) if hand_mesh_down else None)

        np.testing.assert_allclose(np.asarray(ref_logits),
                                   np.asarray(ep_logits), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(float(ref_aux), float(ep_aux), rtol=1e-4)
        jax.tree.map(
            lambda want, got: np.testing.assert_allclose(
                np.asarray(want), np.asarray(got), rtol=2e-4, atol=2e-5),
            ref_grads, ep_grads)

    def test_ep_train_step_matches_single_device(self, cfg):
        """Under ep the head and the loss run on each chip's own quarter of
        the tokens (a batch of 2 over ep=4: the sequence splits) and the
        head's gradients are summed over ep: one whole step gives the loss,
        the gradient norm and the weights next to the head that one device
        gives. SGD at rate 1, so a weight moves by exactly its gradient."""
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.train.spmd import make_mixtral_train_step

        tokens = np.arange(32, dtype=np.int32).reshape(2, 16) % cfg.vocab_size
        targets = np.roll(tokens, -1, axis=1)

        def one_step(spec, n):
            mesh = build_mesh(spec, jax.devices("cpu")[:n])
            step_fn, init_state, shard = make_mixtral_train_step(
                cfg, mesh, optimizer=optax.sgd(1.0), attn_impl="blockwise",
                remat=False)
            state, metrics = step_fn(init_state(), shard(tokens),
                                     shard(targets))
            return state.params, metrics

        want, want_m = one_step(MeshSpec(), 1)
        got, got_m = one_step(MeshSpec(ep=4), 4)
        for name in ("loss", "grad_norm"):
            np.testing.assert_allclose(float(want_m[name]),
                                       float(got_m[name]), rtol=2e-4)
        for name in ("lm_head", "final_norm", "embed_tokens"):
            np.testing.assert_allclose(
                np.asarray(want[name]), np.asarray(got[name]),
                rtol=2e-4, atol=2e-5, err_msg=name)

    @pytest.mark.parametrize("shape, spec", [
        ((2, 16), P(("dp", "fsdp"), "ep")),   # ep divides the sequence
        ((4, 15), P(("dp", "fsdp", "ep"))),   # only the batch
        ((3, 15), None),    # neither: the layers' layout as it is
    ])
    def test_head_layout_follows_the_shape(self, cfg, shape, spec):
        from jax.sharding import NamedSharding

        from ray_tpu.models.mixtral import _head_spec
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.parallel.sharding import (ShardingRules, kernel_mesh,
                                               tree_shardings)

        mesh = build_mesh(MeshSpec(ep=4), jax.devices("cpu")[:4])
        kmesh = kernel_mesh(mesh)
        assert _head_spec(kmesh, *shape) == spec

        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = (jnp.arange(shape[0] * shape[1], dtype=jnp.int32)
                  .reshape(shape) % cfg.vocab_size)
        targets = jnp.roll(tokens, -1, axis=1)

        def loss(p, kmesh=None):
            return loss_fn(cfg, p, tokens, targets, attn_impl="blockwise",
                           remat=False, kmesh=kmesh)

        # A shape ep cannot split asks for no layout at all.
        pins = str(jax.make_jaxpr(partial(loss, kmesh=kmesh))(params)).count(
            "sharding_constraint")
        assert pins == (0 if spec is None else 4), pins

        want_l, want = jax.jit(jax.value_and_grad(loss))(params)
        sharded = jax.tree.map(
            jax.device_put, params,
            tree_shardings(mesh, param_logical_axes(cfg), ShardingRules()))
        got_l, got = jax.jit(jax.value_and_grad(partial(loss, kmesh=kmesh)))(
            sharded)
        if spec is not None:
            logits, _ = jax.jit(partial(
                forward, cfg, attn_impl="blockwise", remat=False,
                kmesh=kmesh))(sharded, tokens)
            assert logits.sharding.is_equivalent_to(
                NamedSharding(mesh, spec), logits.ndim), logits.sharding
        np.testing.assert_allclose(float(want_l), float(got_l), rtol=2e-4)
        jax.tree.map(
            lambda w, g: np.testing.assert_allclose(
                np.asarray(w), np.asarray(g), rtol=2e-4, atol=2e-5),
            want, got)

    def test_ep_plus_dp_train_step(self, cfg):
        """Combined dp×ep mesh runs a full train step and improves."""
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.train.spmd import make_mixtral_train_step

        mesh = build_mesh(MeshSpec(dp=2, ep=2, tp=2), jax.devices("cpu")[:8])
        step_fn, init_state, shard = make_mixtral_train_step(
            cfg, mesh, optimizer=optax.adamw(3e-3), attn_impl="blockwise",
            remat=False)
        state = init_state()
        tokens = shard(np.random.randint(0, cfg.vocab_size, (4, 16)))
        targets = shard(np.roll(np.asarray(tokens), -1, axis=1))
        state, m0 = step_fn(state, tokens, targets)
        state, m1 = step_fn(state, tokens, targets)
        assert float(m1["loss"]) < float(m0["loss"])
        assert np.isfinite(float(m1["grad_norm"]))
