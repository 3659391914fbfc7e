"""Olmo-Hybrid (``models/olmo_hybrid.py``): the program's loss and the
gradient of every leaf against the plain float32 reference
(``benchmark/reference/olmo_hybrid.py``: token-by-token recurrence, no
kernel), float32 on the CPU, at a size that keeps every mechanism: two
periods of two Gated DeltaNet layers and a full attention, three heads (no
multiple of 8), keys of 8 beside values of 16, steps over (0, 2), two
sequences.

Tolerances. The loss: both sides are float32 and differ in the order of
their sums (the chunked rule against the recurrence, the fused cross
entropy against a log-softmax): observed 1.5e-7 relative, held to 2e-6. A
leaf's gradient, its largest difference over the reference's largest value:
observed 6e-7 to 3e-5 (the norms over all heads and the decay's leaves the
largest, six layers deep), held to 2e-4; a linear layer whose ``beta`` is
``sigmoid`` and not ``2 sigmoid``, a norm before the operator or a
convolution a position late move a leaf by 1e-2 and more. What a bfloat16
state does to the rule's gradients is held in tests/test_gated_delta.py,
where a chunk has a boundary.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import olmo_hybrid as ref  # noqa: E402
from rtbench.adapters import olmo_hybrid as adapter  # noqa: E402

from ray_tpu.models import olmo_hybrid as oh  # noqa: E402
from ray_tpu.parallel.mesh import MeshSpec, build_mesh  # noqa: E402
from ray_tpu.train import optim  # noqa: E402
from ray_tpu.train.spmd import make_olmo_hybrid_train_step  # noqa: E402

CFG = oh.OlmoHybridConfig.tiny()
LOSS_RTOL, LEAF_RTOL = 2e-6, 2e-4
BATCH, SEQ = 2, 100


def config_json(cfg=CFG) -> dict:
    """The configuration as a benchmark file states it, for the reference."""
    return {"layer_types": [cfg.kind(l) for l in range(cfg.num_layers)],
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.norm_eps,
            "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "vocab_size": cfg.vocab_size,
            "linear_num_key_heads": cfg.linear_num_key_heads,
            "linear_num_value_heads": cfg.linear_num_value_heads,
            "linear_key_head_dim": cfg.linear_key_head_dim,
            "linear_value_head_dim": cfg.linear_value_head_dim,
            "linear_conv_kernel_dim": cfg.linear_conv_kernel_dim,
            "linear_allow_neg_eigval": cfg.linear_allow_neg_eigval,
            "rope_parameters": {"rope_theta": None}}


@pytest.fixture(scope="module")
def seeded():
    params = oh.init_params(CFG, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (BATCH, SEQ),
                                      dtype=np.int32))
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def program_grads(seeded, remat=True):
    params, tokens, targets = seeded
    return jax.value_and_grad(lambda p: oh.loss_fn(
        CFG, p, tokens, targets, attn_impl="xla", remat=remat))(params)


@pytest.fixture(scope="module")
def program(seeded):
    return program_grads(seeded)


@pytest.fixture(scope="module")
def reference(seeded):
    params, tokens, targets = seeded
    loss, grads = jax.value_and_grad(lambda p: ref.loss_array(
        config_json(), adapter.reference_weights(p), tokens, targets))(
            params)
    return loss, grads


def test_the_loss_is_the_reference_s(program, reference):
    assert abs(float(program[0]) - float(reference[0])) \
        < LOSS_RTOL * float(reference[0])


LEAVES = sorted(["embed_tokens", "lm_head", "final_norm",
                 *(("layers", n) for n in oh.LINEAR_LEAVES
                   + oh.ATTENTION_LEAVES + oh.LAYER_LEAVES)], key=str)


def _leaf(tree, path):
    return tree[path] if isinstance(path, str) else tree[path[0]][path[1]]


@pytest.mark.parametrize("path", LEAVES, ids=str)
def test_every_leaf_s_gradient_is_the_reference_s(program, reference, path):
    got, want = _leaf(program[1], path), _leaf(reference[1], path)
    assert got.shape == want.shape and float(jnp.abs(want).max()) > 0
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) \
        < LEAF_RTOL


@pytest.mark.parametrize("remat", [False, "dots"])
def test_every_remat_policy_gives_the_same_gradients(seeded, program, remat):
    """Full remat (``program``) against none and against the cell's policy,
    which keeps every product's output."""
    loss, grads = program_grads(seeded, remat=remat)
    assert float(loss) == pytest.approx(float(program[0]), rel=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(program[1])):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max())
    with pytest.raises(ValueError):
        oh.OlmoHybridConfig.tiny(num_layers=5)


def test_a_period_is_linear_layers_and_then_one_attention():
    assert [CFG.kind(l) for l in range(CFG.num_layers)] == [
        oh.LINEAR, oh.LINEAR, oh.ATTENTION] * 2
    published = oh.OlmoHybridConfig()
    assert [published.kind(l) for l in range(4)] == [oh.LINEAR] * 3 + [
        oh.ATTENTION]
    assert (published.periods, published.linear_lines) == (8, 24)


def test_the_parameters_are_counted_to_the_unit(seeded):
    """``num_params`` is the tree's size, at the tiny size by the arrays
    and at the published one by their shapes: 7,430,870,688 whole, and
    928,862,196 for one period and an eighth of the vocabulary."""
    assert CFG.num_params() == sum(a.size for a in
                                   jax.tree.leaves(seeded[0]))
    for cfg, want in (
            (oh.OlmoHybridConfig(), 7_430_870_688),
            (oh.OlmoHybridConfig(num_layers=4, vocab_size=12544),
             928_862_196)):
        shapes = jax.eval_shape(lambda k, c=cfg: oh.init_params(c, k),
                                jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(shapes)) \
            == cfg.num_params() == want
        axes = oh.param_logical_axes(cfg)
        for a, ax in zip(jax.tree.leaves(shapes), jax.tree.leaves(
                axes, is_leaf=lambda x: isinstance(x, tuple))):
            assert len(ax) == a.ndim


def test_the_seeded_gates_are_spread_in_every_layer():
    """What an init at the defaults would hide: steps over (0, 2) with a
    good share above 1, and decays from heads that forget in a few tokens
    to heads that remember a thousand, in the first linear layer and in the
    last (whose stream is larger)."""
    cfg = oh.OlmoHybridConfig.tiny(hidden_size=128, linear_num_key_heads=8,
                                   linear_num_value_heads=8)
    params = oh.init_params(cfg, jax.random.PRNGKey(1))
    lay = params["layers"]
    for period, at, stream in ((0, 0, 1.0), (1, 1, 9.0 ** 0.5)):
        x = stream * jax.random.normal(jax.random.PRNGKey(2),
                                       (4096, cfg.hidden_size))
        beta = 2 * jax.nn.sigmoid(x @ lay["lin_wb"][period, at])
        assert 0.3 < float((beta > 1).mean()) < 0.7
        assert float((beta < 0.5).mean()) > 0.1
        assert float((beta > 1.5).mean()) > 0.1
        g = -jnp.exp(lay["a_log"][period, at]) * jax.nn.softplus(
            x @ lay["lin_wa"][period, at] + lay["dt_bias"][period, at])
        keep = jnp.exp(g).mean(axis=0)                       # a head
        assert float(keep.min()) < 0.9 and float(keep.max()) > 0.99
        assert float(jnp.exp(g).min()) >= 0.0


def test_it_trains_through_the_step_factory():
    """``make_olmo_hybrid_train_step`` -> ``make_train_step``, the path
    Mistral and Mixtral take: the loss of one batch falls step by step."""
    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    step, init_state, shard = make_olmo_hybrid_train_step(
        CFG, mesh, optimizer=optim.adamw_lowmem(3e-3, weight_decay=0.1),
        attn_impl="xla", remat=True, seed=3)
    state = init_state()
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, CFG.vocab_size, (2, 64), dtype=np.int32)
    batch = shard(tokens), shard(np.roll(tokens, -1, axis=1))
    losses = []
    for _ in range(4):
        state, metrics = step(state, *batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses == sorted(losses,
                                                         reverse=True)
    assert losses[-1] < losses[0] - 0.05


def test_the_logits_are_the_reference_s(seeded):
    params, tokens, _ = seeded
    got = oh.forward(CFG, params, tokens[:1], attn_impl="xla", remat=False)
    want = ref.logits(config_json(), adapter.reference_weights(params),
                      tokens[0])
    # logits of up to about 5, six layers of float32 sums in another order:
    # observed 5e-5
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-4)
