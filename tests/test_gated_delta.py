"""The gated delta rule (``ops/gated_delta.py``): the chunked form and the
one-token step against the token-by-token recurrence, float32 on the CPU.

Tolerances: every form computes in float32 and the products at true
float32, so what is left is the order of the sums: observed 2e-7 on outputs
of about 1 and 8e-7 on states of about 3. 1e-5 would not pass a decay
applied a position late, a correction without ``beta`` or a sub-chunk that
starts from another state.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta as gd

H, DK, DV = 3, 16, 8
ATOL = 1e-5


def inputs(t, seed=0, state=True):
    """Unit keys, scaled unit queries, decays from 0.0009 to 1.6 a token
    (``exp(g)`` from 0.2 to 0.999), steps in (0, 1), a random state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (t, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (t, H, DK)))
    v = jax.random.normal(ks[2], (t, H, DV))
    g = -jnp.exp(jax.random.uniform(ks[3], (t, H), minval=-7.0, maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, H)))
    s = jax.random.normal(ks[5], (H, DK, DV)) if state \
        else jnp.zeros((H, DK, DV))
    return q, k, v, g, beta, s


def close(got, want):
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=ATOL)


@pytest.mark.parametrize("t", [1, 5, 63, 64, 65, 100, 128, 200])
@pytest.mark.parametrize("state", [False, True], ids=["zero", "carried"])
def test_the_chunk_form_is_the_recurrence(t, state):
    """At lengths that are and are not whole sub-chunks, from zeros and from
    a carried state."""
    a = inputs(t, seed=t, state=state)
    close(gd.gated_delta_chunk(*a), gd.gated_delta_recurrence(*a))


def test_the_state_is_handed_from_run_to_run():
    """Two runs, the second from the state the first left, are one run: a
    prefill chunk after a prefill chunk."""
    q, k, v, g, beta, s = inputs(150, seed=3)
    want_o, want_s = gd.gated_delta_recurrence(q, k, v, g, beta, s)
    cut = 70
    o1, s1 = gd.gated_delta_chunk(q[:cut], k[:cut], v[:cut], g[:cut],
                                  beta[:cut], s)
    o2, s2 = gd.gated_delta_chunk(q[cut:], k[cut:], v[cut:], g[cut:],
                                  beta[cut:], s1)
    close((jnp.concatenate([o1, o2]), s2), (want_o, want_s))


def test_rows_that_are_not_valid_change_no_state():
    """A padded chunk: rows past the last valid one enter with ``g = 0`` and
    ``beta = 0`` and leave the state as the last valid row left it,
    whatever their q, k and v."""
    q, k, v, g, beta, s = inputs(96, seed=5)
    n = 41
    valid = jnp.arange(96) < n
    _, want = gd.gated_delta_recurrence(q[:n], k[:n], v[:n], g[:n], beta[:n],
                                        s)
    o, got = gd.gated_delta_chunk(
        q, k, jnp.where(valid[:, None, None], v, 100.0 * v),
        jnp.where(valid[:, None], g, 0.0),
        jnp.where(valid[:, None], beta, 0.0), s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    want_o, _ = gd.gated_delta_recurrence(q[:n], k[:n], v[:n], g[:n],
                                          beta[:n], s)
    np.testing.assert_allclose(np.asarray(o[:n]), np.asarray(want_o),
                               atol=ATOL)


def test_the_inverse_by_halves_is_the_substitution():
    """``(I + A)^-1`` by halves against forward substitution a row at a
    time (``x_i = b_i - sum_{j<i} A_ij x_j``, here in float64 on the host),
    on matrices with entries up to 0.3 under the diagonal."""
    low = np.tril(np.random.default_rng(0).uniform(-0.3, 0.3, (2, 64, 64)),
                  -1)
    want = np.broadcast_to(np.eye(64), low.shape).copy()
    for i in range(1, 64):
        want[:, i] -= np.einsum("bj,bjd->bd", low[:, i], want)
    got = gd.unit_lower_inverse(jnp.asarray(low, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_keys_that_are_alike_cost_no_precision():
    """Neighbouring keys nearly parallel and steps near 1: ``A`` has entries
    near 1 all under its diagonal. The product ``(I - A)(I + A^2)(I + A^4)
    ...`` equals the inverse in exact arithmetic and loses it here in
    float32 (its powers grow like binomial coefficients before they cancel:
    it read 1e-3 off on a served model's logits); the inverse by halves
    stays at the recurrence's 1e-5."""
    q, k, v, g, beta, s = inputs(128, seed=15)
    k = k[:1] + 0.05 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jnp.full_like(beta, 0.98)
    g = jnp.full_like(g, -1e-3)
    close(gd.gated_delta_chunk(q, k, v, g, beta, s),
          gd.gated_delta_recurrence(q, k, v, g, beta, s))


def test_a_long_decay_overflows_nothing():
    """Every exponent is a difference ``G_i - G_j <= 0``: a sub-chunk whose
    running decay reaches exp(-64 x 20) still gives the recurrence."""
    q, k, v, g, beta, s = inputs(128, seed=9)
    g = jnp.full_like(g, -20.0)
    got = gd.gated_delta_chunk(q, k, v, g, beta, s)
    assert np.isfinite(np.asarray(got[0])).all()
    close(got, gd.gated_delta_recurrence(q, k, v, g, beta, s))


def test_the_step_is_the_recurrence_s_one_token():
    """Every slot its own state, a token each; a slot with ``g = 0`` and
    ``beta = 0`` keeps its state bit for bit."""
    slots = 4
    q, k, v, g, beta, _ = inputs(slots, seed=11)
    states = jax.random.normal(jax.random.PRNGKey(1), (slots, H, DK, DV))
    idle = jnp.arange(slots) == 2
    g = jnp.where(idle[:, None], 0.0, g)
    beta = jnp.where(idle[:, None], 0.0, beta)
    o, new = gd.gated_delta_step(q, k, v, g, beta, states)
    for b in range(slots):
        want_o, want_s = gd.gated_delta_recurrence(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], g[b:b + 1], beta[b:b + 1],
            states[b])
        close((o[b], new[b]), (want_o[0], want_s))
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(states[2]))


def test_the_forms_compute_in_float32_whatever_they_are_given():
    """bfloat16 inputs are cast up, the state and the output are float32:
    the rule's state is never kept below float32."""
    q, k, v, g, beta, s = inputs(70, seed=13)
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v))
    o, new = gd.gated_delta_chunk(*low, g, beta, s)
    assert o.dtype == new.dtype == jnp.float32
    close((o, new), gd.gated_delta_recurrence(*low, g, beta, s))
    o, new = gd.gated_delta_step(*(a[:4] for a in low), g[:4], beta[:4],
                                 jnp.broadcast_to(s, (4, *s.shape)))
    assert o.dtype == new.dtype == jnp.float32
