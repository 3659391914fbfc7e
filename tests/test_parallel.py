"""Mesh construction and sharding-rule tables."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.mesh import MeshSpec, build_mesh, hybrid_mesh
from ray_tpu.parallel.sharding import (
    ShardingRules,
    shard_params,
    tree_shardings,
)


def test_mesh_spec_sizes():
    spec = MeshSpec(dp=2, tp=4)
    assert spec.num_devices == 8
    assert spec.axis_sizes()["dp"] == 2
    assert spec.with_total(16, grow="dp").dp == 4


def test_build_mesh(cpu_mesh_devices):
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2), cpu_mesh_devices)
    assert mesh.shape["dp"] == 2
    assert mesh.shape["tp"] == 2
    assert mesh.devices.size == 8


def test_mesh_too_big_raises(cpu_mesh_devices):
    with pytest.raises(ValueError):
        build_mesh(MeshSpec(dp=100), cpu_mesh_devices)


def test_hybrid_mesh_dcn_outermost(cpu_mesh_devices):
    spec = MeshSpec(dp=2, fsdp=4, dcn_axes=("dp",))
    mesh = hybrid_mesh(spec, num_slices=2, devices_per_slice=4,
                       devices=cpu_mesh_devices)
    # each dp row (slice) must hold a contiguous run of devices
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    flat = ids.reshape(2, -1)
    for s in range(2):
        assert set(flat[s]) == set(range(s * 4, (s + 1) * 4))


def test_sharding_rules_spec():
    rules = ShardingRules()
    assert rules.spec("batch", "seq", "act_embed") == P(("dp", "fsdp"), "sp", None)
    assert rules.spec("embed", "mlp") == P(("fsdp",), "tp")
    assert rules.spec(None, "heads") == P(None, "tp")


def test_sharding_rules_no_duplicate_axis():
    rules = ShardingRules()
    # same mesh axis twice in one spec must not repeat
    s = rules.spec("mlp", "heads")  # both map to tp
    assert s == P("tp", None)


def test_rules_override():
    rules = ShardingRules().override(embed="tp")
    assert rules.spec("embed") == P("tp")


def test_shard_params_places_on_mesh(cpu_mesh_devices):
    mesh = build_mesh(MeshSpec(fsdp=2, tp=4), cpu_mesh_devices)
    params = {
        "wq": np.ones((16, 32), np.float32),
        "wo": np.ones((32, 16), np.float32),
    }
    logical = {"wq": ("embed", "heads"), "wo": ("heads", "embed")}
    sharded = shard_params(params, mesh, logical)
    assert sharded["wq"].sharding.spec == P(("fsdp",), "tp")
    # value preserved
    np.testing.assert_allclose(np.asarray(sharded["wq"]), params["wq"])


def test_tree_shardings_structure(cpu_mesh_devices):
    mesh = build_mesh(MeshSpec(dp=8), cpu_mesh_devices)
    tree = {"a": ("batch", None), "b": {"c": ("embed",)}}
    sh = tree_shardings(mesh, tree)
    assert sh["a"].spec == P(("dp", "fsdp"), None)
    assert sh["b"]["c"].spec == P("fsdp")


# ---------------------------------------------------------------------------
# pipeline parallelism (parallel/pipeline.py)
# ---------------------------------------------------------------------------

def test_pp_matches_single_device(cpu_mesh_devices):
    """pp=2 (x dp=2) pipeline loss/step must match the plain single-device
    step numerically (same init, same batch)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.pipeline import make_pp_train_step

    cfg = LlamaConfig.tiny()  # 2 layers -> 2 stages of 1
    mesh = build_mesh(MeshSpec(pp=2, dp=2), cpu_mesh_devices[:4])
    opt = optax.sgd(0.1)
    step_fn, init_state, shard = make_pp_train_step(
        cfg, mesh, num_microbatches=2, optimizer=opt, attn_impl="blockwise")
    state = init_state()

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (4, 16), dtype=np.int32)
    targets = np.roll(tokens, -1, axis=1)

    state, metrics = step_fn(state, shard(tokens), shard(targets))
    pp_loss = float(metrics["loss"])

    # Reference: plain loss on one device with identical params.
    params = init_params(cfg, jax.random.PRNGKey(0))
    ref_loss = float(loss_fn(cfg, params, jnp.asarray(tokens),
                             jnp.asarray(targets), attn_impl="blockwise",
                             remat=False, fused_ce=False))
    # 5e-4: the pipeline's f32 accumulation order differs from the
    # single-device reference; real grad bugs show up orders of magnitude
    # larger (the trajectory check below would also catch them).
    np.testing.assert_allclose(pp_loss, ref_loss, rtol=5e-4, atol=5e-4)

    # And training makes progress over a few steps.
    for _ in range(3):
        state, metrics = step_fn(state, shard(tokens), shard(targets))
    assert float(metrics["loss"]) < ref_loss


def test_pp_grads_match_single_device(cpu_mesh_devices):
    """One SGD step under the pipeline must produce the same loss trajectory
    as the plain step (grad correctness incl. tied-embedding psum)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.pipeline import make_pp_train_step
    from ray_tpu.train.spmd import make_llama_train_step

    cfg = LlamaConfig.tiny()
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab_size, (4, 16), dtype=np.int32)
    targets = np.roll(tokens, -1, axis=1)

    # pipeline step
    mesh_pp = build_mesh(MeshSpec(pp=2), cpu_mesh_devices[:2])
    opt = optax.sgd(0.1)
    pstep, pinit, pshard = make_pp_train_step(
        cfg, mesh_pp, num_microbatches=2, optimizer=opt,
        attn_impl="blockwise")
    pstate = pinit()
    pstate, _ = pstep(pstate, pshard(tokens), pshard(targets))
    pstate, pm = pstep(pstate, pshard(tokens), pshard(targets))

    # plain step
    mesh_1 = build_mesh(MeshSpec(dp=1), cpu_mesh_devices[:1])
    sstep, sinit, sshard = make_llama_train_step(
        cfg, mesh_1, optimizer=optax.sgd(0.1), attn_impl="blockwise",
        remat=False)
    sstate = sinit()
    sstate, _ = sstep(sstate, sshard(tokens), sshard(targets))
    sstate, sm = sstep(sstate, sshard(tokens), sshard(targets))

    # after one identical update, the second-step losses must agree
    np.testing.assert_allclose(float(pm["loss"]), float(sm["loss"]),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# multi-slice fast path: ZeRO-1 sharded update, hierarchical/quantized DCN
# gradient sync, microbatch accumulation (train/spmd.py + parallel/sharding)
# ---------------------------------------------------------------------------

def test_hlo_stats_cost_model():
    """collective_stats prices sync and async (-start, tuple-result) forms
    identically, counts reduce-scatter against its full INPUT (the output is
    the 1/group shard), and zeroes intra-slice ops."""
    from ray_tpu.parallel.hlo_stats import collective_stats, mesh_slice_map

    slice_of = mesh_slice_map(8, 2)  # partitions 0-3 slice 0, 4-7 slice 1
    groups = "replica_groups={{0,1,2,3,4,5,6,7}}"
    sync = f"%r = f32[256]{{0}} all-reduce(f32[256]{{0}} %p), {groups}"
    async_ = (f"%r = (f32[256]{{0}}, f32[256]{{0}}) all-reduce-start("
              f"f32[256]{{0}} %p), {groups}")
    s_sync = collective_stats(sync, slice_of)
    s_async = collective_stats(async_, slice_of)
    # ring all-reduce over m=2 slices: 2*(m-1)/m*1024B*8 members = 8192B;
    # the async tuple's operand alias must not double it
    assert s_sync.dcn_bytes == s_async.dcn_bytes == 8192
    # reduce-scatter: output is the 1/8 shard (128B) but the ring moves
    # (m-1)/m of the full 1024B input per member
    rs = collective_stats(
        f"%r = f32[32]{{0}} reduce-scatter(f32[256]{{0}} %p), {groups}",
        slice_of)
    assert rs.dcn_bytes == int(0.5 * 128 * 8) * 8
    # previously-unmatched async spellings are now counted
    rs2 = collective_stats(
        f"%r = (f32[256]{{0}}, f32[32]{{0}}) reduce-scatter-start("
        f"f32[256]{{0}} %p), {groups}", slice_of)
    assert rs2.dcn_bytes == rs.dcn_bytes
    # multi-operand async start: nested ((operands...), (results...)) tuple
    # prices the results, same as two sync ops would
    multi = collective_stats(
        f"%r = ((f32[256]{{0}}, f32[128]{{0}}), (f32[256]{{0}}, "
        f"f32[128]{{0}})) all-reduce-start(f32[256]{{0}} %p0, "
        f"f32[128]{{0}} %p1), {groups}", slice_of)
    assert multi.dcn_bytes == 12288 and multi.skipped_ops == 0
    # TPU tiled layouts put parens INSIDE shapes ({0:T(8,128)}); operand
    # subtraction must span the whole call, not stop at the first ")"
    tiled = collective_stats(
        f"%r = ((f32[256]{{0:T(8,128)}}, f32[128]{{0:T(8,128)}}), "
        f"(f32[256]{{0:T(8,128)}}, f32[128]{{0:T(8,128)}})) all-reduce-start("
        f"f32[256]{{0:T(8,128)}} %p0, f32[128]{{0:T(8,128)}} %p1), {groups}",
        slice_of)
    assert tiled.dcn_bytes == 12288 and tiled.skipped_ops == 0
    # intra-slice group: no DCN bytes
    intra = collective_stats(
        "%r = f32[256]{0} all-reduce(f32[256]{0} %p), "
        "replica_groups={{0,1,2,3},{4,5,6,7}}", slice_of)
    assert intra.dcn_bytes == 0 and not intra.ops[0].crosses_slices
    # iota form spans slices the same way the explicit list does
    iota = collective_stats(
        "%r = f32[256]{0} all-reduce(f32[256]{0} %p), "
        "replica_groups=[1,8]<=[8]", slice_of)
    assert iota.dcn_bytes == 8192
    # replica_groups={} = one group of everyone: priced when n_partitions
    # is known, surfaced as skipped (never silently dropped) when not
    empty = "%r = f32[256]{0} all-reduce(f32[256]{0} %p), replica_groups={}"
    priced = collective_stats(empty, slice_of, n_partitions=8)
    assert priced.dcn_bytes == 8192 and priced.skipped_ops == 0
    unpriced = collective_stats(empty, slice_of)
    assert unpriced.dcn_bytes == 0 and unpriced.skipped_ops == 1


@pytest.mark.multidevice
def test_zero1_spec_dim_choice():
    """zero1_spec shards the largest divisible dim, skipping scan ("layers")
    and gather-indexed ("vocab") dims, and leaves non-divisible leaves
    replicated."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import zero1_spec

    mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
    axes = ("dp", "fsdp")
    # stacked layer leaf: layers dim skipped, embed (largest) sharded
    assert zero1_spec(P(), (2, 128, 8, 16), mesh, axes,
                      logical=("layers", "embed", "heads", "head_dim")) == \
        P(None, ("dp", "fsdp"))
    # embedding: vocab skipped even though largest
    assert zero1_spec(P(), (512, 64), mesh, axes,
                      logical=("vocab", "embed")) == P(None, ("dp", "fsdp"))
    # existing sharded axis is kept and extended on its dim when divisible
    assert zero1_spec(P("tp"), (64, 16), mesh, axes) == P(("tp", "dp", "fsdp"))
    # nothing divisible -> unchanged (update stays replicated)
    assert zero1_spec(P(), (3, 5), mesh, axes) == P()
    # without logical info: plain largest-divisible-dim choice
    assert zero1_spec(P(), (16, 64), mesh, axes) == P(None, ("dp", "fsdp"))


@pytest.mark.multidevice
def test_multislice_step_parity_and_sharded_state(cpu_mesh_devices):
    """The sync modes on the 2-slice hybrid mesh: hier and zero1 match the
    flat step exactly (fp32 hierarchy is a pure reorder), the int8 DCN
    stage stays within its documented tolerance, microbatch accumulation
    matches the one-shot step, grad_norm_every gates the norm metric, and
    zero1 moments live 1/8-sized per device sharded over the whole dp
    world."""
    import jax
    import numpy as np
    import optax

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel.mesh import MeshSpec, hybrid_mesh
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.train.optim import optimizer_state_bytes
    from ray_tpu.train.spmd import make_llama_train_step

    spec = MeshSpec(dp=2, fsdp=4, dcn_axes=("dp",))
    mesh = hybrid_mesh(spec, num_slices=2, devices_per_slice=4,
                       devices=cpu_mesh_devices)
    ddp = ShardingRules().override(vocab=None, embed=None, mlp=None,
                                   heads=None, kv_heads=None)
    cfg = LlamaConfig.tiny()
    opt = optax.adamw(1e-2)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (16, 16), dtype=np.int32)
    targets = np.roll(tokens, -1, axis=1)

    losses = {}
    states = {}
    for name, kw in [
        ("flat", {}),
        ("hier", dict(dcn_axes=("dp",))),
        ("zero1", dict(zero1=True, dcn_axes=("dp",))),
        ("zero1_q8", dict(zero1=True, dcn_axes=("dp",), dcn_quant="int8")),
        ("accum", dict(zero1=True, dcn_axes=("dp",), grad_accum=2,
                       grad_norm_every=2)),
    ]:
        step, init, shard = make_llama_train_step(
            cfg, mesh, rules=ddp, optimizer=opt, attn_impl="blockwise",
            remat=False, **kw)
        state = init()
        tr, gn = [], []
        for _ in range(3):
            state, m = step(state, shard(tokens), shard(targets))
            tr.append(float(m["loss"]))
            gn.append(float(m["grad_norm"]))
        losses[name] = tr
        states[name] = state
        if name == "accum":
            # grad_norm_every=2: step counter 0 computes, 1 skips (-1), 2
            # computes again.
            assert gn[0] > 0 and gn[2] > 0
            assert gn[1] == -1.0
        else:
            assert all(v > 0 for v in gn)

    # fp32 hierarchy + zero1: exact parity with the flat allreduce path
    np.testing.assert_allclose(losses["hier"], losses["flat"],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(losses["zero1"], losses["flat"],
                               rtol=1e-6, atol=1e-6)
    # microbatch accumulation: same math as the one-shot zero1 step
    np.testing.assert_allclose(losses["accum"], losses["zero1"],
                               rtol=1e-5, atol=1e-5)
    # int8 DCN stage: documented tolerance, and visibly quantized
    np.testing.assert_allclose(losses["zero1_q8"], losses["flat"],
                               rtol=0, atol=2e-2)
    assert losses["zero1_q8"][1] != losses["flat"][1]

    # zero1 optimizer moments: every leaf sharded over the full dp world
    # (dp x fsdp = 8), so per-device state is 1/8 of the replicated one.
    mu = states["zero1"].opt_state[0].mu
    for leaf in jax.tree.leaves(mu):
        used = set()
        for entry in leaf.sharding.spec:
            used.update(entry if isinstance(entry, tuple) else (entry,))
        assert {"dp", "fsdp"} <= used, leaf.sharding.spec
    z1_bytes = optimizer_state_bytes(
        opt, states["zero1"].params,
        shardings=jax.tree.map(lambda l: l.sharding,
                               states["zero1"].opt_state))
    flat_bytes = optimizer_state_bytes(opt, states["flat"].params)
    assert z1_bytes < flat_bytes / 6  # ~1/8 plus padding

    # params come back identical across replicas (fully replicated)
    p0 = jax.tree.leaves(states["zero1"].params)[0]
    assert p0.sharding.is_fully_replicated


def test_llama_train_step_lowmem_optimizer(cpu_mesh_devices):
    """adamw_lowmem (compact-moment AdamW, train/optim.py) drops into the
    SPMD step factory: moments come back in bf16, shardings mirror params,
    and a few steps reduce the loss like stock adamw does."""
    import numpy as np
    import optax

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train.optim import adamw_lowmem
    from ray_tpu.train.spmd import make_llama_train_step

    cfg = LlamaConfig.tiny()
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, cfg.vocab_size, (4, 16), dtype=np.int32)
    targets = np.roll(tokens, -1, axis=1)
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2), cpu_mesh_devices[:4])

    losses = {}
    for name, opt in [("lowmem", adamw_lowmem(1e-2, weight_decay=0.1)),
                      ("adamw", optax.adamw(1e-2, weight_decay=0.1))]:
        step, init, shard = make_llama_train_step(
            cfg, mesh, optimizer=opt, attn_impl="blockwise", remat=False)
        state = init()
        tr = []
        for _ in range(6):
            state, m = step(state, shard(tokens), shard(targets))
            tr.append(float(m["loss"]))
        losses[name] = tr
        if name == "lowmem":
            import jax
            import jax.numpy as jnp

            mu_leaf = jax.tree.leaves(state.opt_state[0].mu)[0]
            nu_leaf = jax.tree.leaves(state.opt_state[0].nu)[0]
            assert mu_leaf.dtype == jnp.bfloat16
            assert nu_leaf.dtype == jnp.bfloat16
    assert losses["lowmem"][-1] < losses["lowmem"][0]
    # Tracks stock adamw closely over a short horizon.
    assert abs(losses["lowmem"][-1] - losses["adamw"][-1]) < 0.35
