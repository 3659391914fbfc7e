"""``ops/ssd.py``: Mamba-2's rule in its three forms. The recurrence (two
lines under a scan) is the yardstick; the chunked form and the one-token
step are held to it, and the step's Pallas kernel (its body through the
interpreter) to the step's jnp body.

Tolerances: float32 against float32 at true-float32 products. 1e-5 on
outputs and states of unit size is the order of the sums alone (observed
3e-6 relative); a form that clamped a decay, dropped a sub-chunk's carry or
read the state before its update misses by 1e-2 or more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta, ssd
from ray_tpu.ops.kernels import force_kernel_backend

ATOL = 1e-5


@pytest.fixture(scope="module", autouse=True)
def release_the_compiled_programs():
    """After the module: this file's programs are its own (their
    configuration is a static argument), and a compiled program keeps its
    memory mappings as long as JAX's caches hold it: 17,000 of them after
    tests/test_granite.py alone, where a process may have 65,530
    (``vm.max_map_count``) and a worker of the suite runs some seventy
    files. Past the limit XLA's CPU compile dies of a segmentation fault
    under whichever test comes next (PERF.md section 7, PR 62)."""
    yield
    jax.clear_caches()


def inputs(t, h, p, n, seed=0, dt_a=None):
    """x [T, H, P], dt [T, H] > 0, a [H] < 0, b, c [T, N] and a stored state
    of unit size. ``dt_a`` pins ``dt A`` of heads 0 and 1 (a head that
    forgets in a token beside one that hardly forgets)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (t, h, p))
    dt = jnp.exp(jax.random.uniform(ks[1], (t, h), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    a = -jnp.linspace(1.0, 16.0, h)
    if dt_a is not None:
        dt = dt.at[:, 0].set(1.0).at[:, 1].set(1.0)
        a = a.at[0].set(dt_a[0]).at[1].set(dt_a[1])
    b = jax.random.normal(ks[2], (t, n)) * n ** -0.5
    c = jax.random.normal(ks[3], (t, n)) * n ** -0.5
    state = jax.random.normal(ks[4], ssd.state_shape(h, n, p))
    return x, dt, a, b, c, state


def test_the_stored_layout_packs_heads_into_the_lanes_and_back():
    assert ssd.lane_heads(128, 64) == 2 and ssd.lane_heads(32, 128) == 1
    assert ssd.lane_heads(8, 16) == 8 and ssd.lane_heads(6, 32) == 3
    assert ssd.state_shape(128, 128, 64) == (64, 128, 128)
    s = jnp.arange(4 * 3 * 5.0).reshape(4, 3, 5)             # [H, N, P]
    stored = ssd.to_stored(s, 2)
    assert stored.shape == (2, 3, 10)
    # group 1 holds heads 2 and 3 side by side
    np.testing.assert_array_equal(np.asarray(stored[1, :, :5]),
                                  np.asarray(s[2]))
    np.testing.assert_array_equal(np.asarray(stored[1, :, 5:]),
                                  np.asarray(s[3]))
    np.testing.assert_array_equal(np.asarray(ssd.from_stored(stored, 2)),
                                  np.asarray(s))


def test_the_recurrence_is_the_two_lines():
    """Written out by hand in numpy float64 for three tokens."""
    x, dt, a, b, c, state = inputs(3, 4, 8, 16)
    y, s1 = ssd.ssd_recurrence(x, dt, a, b, c, state)
    s = np.asarray(ssd.from_stored(state, 4), np.float64)
    xs, dts, as_, bs, cs = (np.asarray(v, np.float64)
                            for v in (x, dt, a, b, c))
    for t in range(3):
        s = np.exp(dts[t] * as_)[:, None, None] * s + np.einsum(
            "n,hp->hnp", bs[t], dts[t][:, None] * xs[t])
        np.testing.assert_allclose(np.asarray(y[t]),
                                   np.einsum("hnp,n->hp", s, cs[t]),
                                   atol=ATOL)
    np.testing.assert_allclose(np.asarray(ssd.from_stored(s1, 4)), s,
                               atol=ATOL)


CHUNKS = {"a sub-chunk": (64, None), "two and a bit": (150, None),
          "shorter than one": (5, None), "one token": (1, None),
          "a head at -12 a token beside one at -0.001": (150, (-12.0, -1e-3)),
          "the published heads": (70, None)}


@pytest.mark.parametrize("name", list(CHUNKS))
def test_the_chunked_form_is_the_recurrence(name):
    """Lengths that are no multiple of the sub-chunk, continued from a state
    that is not zero; a head that forgets in a token (``dt A`` of -12: every
    ``exp(G_i - G_j)`` but the diagonal underflows, none overflows) beside
    one that forgets nothing; heads of 64 two to a lane row."""
    t, dt_a = CHUNKS[name]
    h, p, n = (8, 64, 128) if name == "the published heads" else (4, 8, 16)
    x, dt, a, b, c, state = inputs(t, h, p, n, dt_a=dt_a)
    want_y, want_s = jax.jit(ssd.ssd_recurrence)(x, dt, a, b, c, state)
    y, s = jax.jit(ssd.ssd_chunk)(x, dt, a, b, c, state)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=ATOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=ATOL)
    assert np.isfinite(np.asarray(y)).all()
    if dt_a is not None:
        # the fast head keeps nothing of the given state, the slow one all
        pack = h // state.shape[0]
        got, was = ssd.from_stored(s, pack), ssd.from_stored(state, pack)
        assert float(jnp.abs(got[0]).max()) < 2.0
        assert float(jnp.abs(got[1] - was[1]).max()) > 0.0


def test_a_chunk_continued_from_a_state_is_one_chunk():
    """The state handed from chunk to chunk, cut anywhere."""
    x, dt, a, b, c, state = inputs(150, 4, 8, 16, seed=3)
    want_y, want_s = ssd.ssd_chunk(x, dt, a, b, c, state)
    ys, s = [], state
    for lo, hi in ((0, 1), (1, 70), (70, 133), (133, 150)):
        y, s = ssd.ssd_chunk(x[lo:hi], dt[lo:hi], a, b[lo:hi], c[lo:hi], s)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys)),
                               np.asarray(want_y), atol=ATOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=ATOL)


def test_a_row_without_a_step_changes_no_state():
    """``dt = 0``: a padded chunk's tail. The state after the chunk is the
    state after its last row with a step, bit for bit against the same
    chunk cut there (a row's ``exp(0) S + B 0``)."""
    x, dt, a, b, c, state = inputs(64, 4, 8, 16, seed=4)
    dt = dt.at[40:].set(0.0)
    _, padded = ssd.ssd_chunk(x, dt, a, b, c, state)
    _, cut = ssd.ssd_recurrence(x[:40], dt[:40], a, b[:40], c[:40], state)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(cut),
                               atol=ATOL)
    _, kept = ssd.ssd_chunk(x, jnp.zeros_like(dt), a, b, c, state)
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(state))


def test_a_state_rounded_between_chunks_shows():
    """What the float32 leaf is for: a state rounded to bfloat16 between two
    chunks leaves the second chunk's outputs and state by far more than the
    tolerance."""
    x, dt, a, b, c, state = inputs(128, 4, 8, 16, seed=5)
    _, mid = ssd.ssd_chunk(x[:64], dt[:64], a, b[:64], c[:64], state)
    want_y, _ = ssd.ssd_chunk(x[64:], dt[64:], a, b[64:], c[64:], mid)
    low = mid.astype(jnp.bfloat16).astype(jnp.float32)
    y, _ = ssd.ssd_chunk(x[64:], dt[64:], a, b[64:], c[64:], low)
    assert float(jnp.abs(y - want_y).max()) > 50 * ATOL


STEPS = {"tiny heads": (4, 8, 16), "the published heads": (8, 64, 128)}


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("name", list(STEPS))
def test_the_step_is_one_token_of_the_recurrence_in_place(name, backend):
    """On line 1 of a leaf of three lines: every slot's state after the
    token and its output are the recurrence's, the other lines are as they
    were bit for bit, and a slot without a step (``dt = 0``) keeps its
    state bit for bit. ``interpret`` runs the kernel's body (at the
    published heads: at tiny widths the jnp body runs on every backend)."""
    h, p, n = STEPS[name]
    slots = 3
    x, dt, a, b, c, _ = inputs(slots, h, p, n, seed=6)
    dt = dt.at[2].set(0.0)
    leaf = jax.random.normal(jax.random.PRNGKey(7),
                             (3, slots, *ssd.state_shape(h, n, p)))
    with force_kernel_backend(backend):
        y, out = jax.jit(ssd.ssd_step)(x, dt, a, b, c, leaf, jnp.int32(1))
    for line in (0, 2):
        np.testing.assert_array_equal(np.asarray(out[line]),
                                      np.asarray(leaf[line]))
    np.testing.assert_array_equal(np.asarray(out[1, 2]),
                                  np.asarray(leaf[1, 2]))
    for slot in range(slots):
        want_y, want_s = ssd.ssd_recurrence(
            x[slot:slot + 1], dt[slot:slot + 1], a, b[slot:slot + 1],
            c[slot:slot + 1], leaf[1, slot])
        np.testing.assert_allclose(np.asarray(y[slot]),
                                   np.asarray(want_y[0]), atol=ATOL)
        np.testing.assert_allclose(np.asarray(out[1, slot]),
                                   np.asarray(want_s), atol=ATOL)


def test_the_step_s_kernel_shares_the_delta_rule_s_scaffolding():
    """One scaffolding for both step kernels: ``ops/ssd.py`` imports the
    block of ``STEP_BLOCK_BYTES`` and the in-place call from
    ``ops/gated_delta.py``; at the cell's shapes a grid step holds 32
    groups of 128 x 128 (2 MiB, half a slot's layer)."""
    assert ssd.states_a_step is gated_delta.states_a_step
    assert ssd.step_in_place is gated_delta.step_in_place
    assert gated_delta.states_a_step(64, 128, 128) == 32
    assert gated_delta.STEP_BLOCK_BYTES == 32 * 128 * 128 * 4
