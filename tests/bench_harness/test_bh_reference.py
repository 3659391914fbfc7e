"""The plain references against the program at tiny sizes on the CPU.

Both sides compute in float32 here, so they differ only by the order of
sums: logits of magnitude ~1 agree to 1e-4 absolute (measured 2e-6), a mean
loss to 1e-5 relative (measured 1e-7). On the chip the program runs bf16
and the run compares with the tolerances its traffic file states.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH
from reference import dense, sparse
from rtbench.adapters import llama as llama_adapter
from rtbench.adapters import mixtral as mixtral_adapter

LOGIT_ATOL = 1e-4
LOSS_RTOL = 1e-5


def tiny_config(name, **over):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        c = json.load(f)
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=512,
             torch_dtype="float32")
    c["num_hidden_layers"] = {"published": 32, "train": 2, "serve": 2}
    c.update(over)
    return c


@pytest.fixture(scope="module")
def dense_case():
    from ray_tpu.models.llama import init_params

    c = tiny_config("mistral-7b-v0.3")
    cfg = llama_adapter.model_config(c, "train", 128)
    params = init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 96), 0, 512)
    return c, cfg, params, tokens


def test_dense_reference_matches_models_llama_logits(dense_case):
    from ray_tpu.models.llama import forward

    c, cfg, params, tokens = dense_case
    want = forward(cfg, params, tokens, attn_impl="blockwise", remat=False)
    weights = llama_adapter.reference_weights(params)
    for b in range(tokens.shape[0]):
        got = dense.logits(c, weights, tokens[b])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[b]),
                                   atol=LOGIT_ATOL, rtol=0)


def test_dense_reference_matches_models_llama_loss(dense_case):
    from ray_tpu.models.llama import loss_fn

    c, cfg, params, tokens = dense_case
    targets = jnp.roll(tokens, -1, axis=1)
    want = float(loss_fn(cfg, params, tokens, targets,
                         attn_impl="blockwise", remat=False))
    got = dense.loss(c, llama_adapter.reference_weights(params), tokens,
                     targets)
    assert got == pytest.approx(want, rel=LOSS_RTOL)


def test_dense_reference_sees_a_wrong_mask(dense_case):
    """The margin check of a serving run rests on this: logits computed
    without the causal mask differ by whole tenths, not by rounding."""
    c, _cfg, params, tokens = dense_case
    weights = llama_adapter.reference_weights(params)
    a = np.asarray(dense.logits(c, weights, tokens[0]))
    b = np.asarray(dense.logits(c, weights, tokens[0][::-1]))[::-1]
    assert np.abs(a - b).max() > 0.05


def test_engine_prefill_then_decode_matches_the_reference(dense_case):
    """Prefill in two chunks, then three single decode steps through the
    cache, against one full forward pass of the reference."""
    from ray_tpu.llm import engine

    c, cfg, params, tokens = dense_case
    seq = [int(t) for t in tokens[0][:40]]
    prompt, rest = seq[:37], seq[37:]
    slots, max_seq, slot = 3, 128, 1
    cache = engine.init_kv_cache(cfg, slots, max_seq)
    ref = np.asarray(dense.logits(
        c, llama_adapter.reference_weights(params),
        jnp.asarray(seq, jnp.int32)))

    done = 0
    for size in (32, 16):          # 32 tokens, then 5 in a bucket of 16
        take = min(size, len(prompt) - done)
        chunk = np.zeros((size,), np.int32)
        chunk[:take] = prompt[done:done + take]
        cache, last = engine.prefill_chunk(
            cfg, params, cache, jnp.asarray(chunk), jnp.int32(done),
            jnp.int32(len(prompt)), jnp.int32(slot))
        done += take
    np.testing.assert_allclose(np.asarray(last), ref[len(prompt) - 1],
                               atol=LOGIT_ATOL, rtol=0)

    for i, tok in enumerate(rest):
        toks = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        write = np.zeros((slots,), bool)
        toks[slot], pos[slot], write[slot] = tok, len(prompt) + i, True
        cache, logits = engine.decode_step(
            cfg, params, cache, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(write))
        np.testing.assert_allclose(np.asarray(logits[slot]),
                                   ref[len(prompt) + i],
                                   atol=LOGIT_ATOL, rtol=0)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_fp8_control_comes_out_as_not_correct(seed):
    """benchmark/control.py at a size a test run can hold: the reference on
    weights rounded through fp8 chooses tokens that lie further under the
    float32 reference's top logit than the serving cells allow, while the
    precision the configuration states (bfloat16 weights) stays far inside.
    At the cells' own sizes the readings are PERF.md's (section 4)."""
    import importlib.util

    from ray_tpu.models.llama import init_params

    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(BENCH, "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    with open(os.path.join(BENCH, "traffic", "serve-chat.json")) as f:
        limit = json.load(f)["check"]["margin"]

    c = tiny_config("mistral-7b-v0.3")
    cfg = llama_adapter.model_config(c, "serve", 128)
    weights = llama_adapter.reference_weights(
        init_params(cfg, jax.random.PRNGKey(seed)))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (96,), 0, 512)
    want = dense.logits(c, weights, tokens)
    fp8 = control.margin(
        want, dense.logits(c, control.to_fp8(weights), tokens), 24)
    bf16 = control.margin(want, dense.logits(c, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), weights), tokens), 24)
    assert fp8 > limit          # the control is not correct
    assert bf16 < limit / 5     # the stated precision is, with room
    assert fp8 > 3 * max(bf16, 0.02)


@pytest.fixture(scope="module")
def sparse_case():
    from ray_tpu.models import mixtral

    c = tiny_config("mixtral-8x7b", num_local_experts=4)
    cfg = mixtral_adapter.model_config(c, "train", 128)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (4, 64), 0, 512)
    return c, cfg, params, tokens


def test_sparse_reference_matches_models_mixtral_loss(sparse_case):
    from ray_tpu.models import mixtral

    c, cfg, params, tokens = sparse_case
    targets = jnp.roll(tokens, -1, axis=1)
    want = float(mixtral.loss_fn(cfg, params, tokens, targets,
                                 attn_impl="blockwise", remat=False))
    got = sparse.loss(c, mixtral_adapter.reference_weights(params), tokens,
                      targets)
    assert got == pytest.approx(want, rel=LOSS_RTOL)


def test_sparse_reference_drops_claims_beyond_capacity(sparse_case):
    """With the capacity factor cut to 0.25 most claims are dropped, in the
    reference as in the program: the departure is mirrored, not ignored."""
    from ray_tpu.models import mixtral

    c, cfg, params, tokens = sparse_case
    c = json.loads(json.dumps(c))
    c["departures"]["capacity_factor"]["value"] = 0.25
    cfg = dataclasses.replace(cfg, capacity_factor=0.25)
    targets = jnp.roll(tokens, -1, axis=1)
    want = float(mixtral.loss_fn(cfg, params, tokens, targets,
                                 attn_impl="blockwise", remat=False))
    got = sparse.loss(c, mixtral_adapter.reference_weights(params), tokens,
                      targets)
    assert got == pytest.approx(want, rel=LOSS_RTOL)
    assert sparse.capacity(c, 256) == 32


def test_routing_weights_sum_to_one_for_a_kept_token():
    c = {"num_experts_per_tok": 2}
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 4))
    weights, aux = sparse.routing(c, logits, capacity=64)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    assert (np.asarray(weights) > 0).sum(-1).tolist() == [2] * 32
    assert float(aux) > 0
    dropped, _ = sparse.routing(c, logits, capacity=1)
    assert float(dropped.sum()) < float(weights.sum())
