"""ops/selective_scan.py on the CPU: the chunk form's kernel (its body
through the Pallas interpreter) and the one-token step against the
recurrence they stand in for, in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import selective_scan as ss
from ray_tpu.ops.kernels import force_kernel_backend

N = 16
# exp, a product and a sum a token in float32, a few hundred tokens deep:
# the kernel and the recurrence order the same operations the same way, so
# what is left is the interpreter's and XLA's fusions (fma or not).
ATOL = 2e-5


def inputs(t: int, ch: int, seed: int = 0, n: int = N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (t, ch))
    # steps log-uniform over 0.001 to 0.1 and A = -(n + 1): decays from
    # 0.2 to 0.999 a token, as the model's own initialiser draws them
    dt = jnp.exp(jax.random.uniform(ks[1], (t, ch), minval=-6.9, maxval=-2.3))
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1)[:, None], (n, ch)) \
        * jnp.exp(0.1 * jax.random.normal(ks[2], (n, ch)))
    b = jax.random.normal(ks[3], (t, n))
    c = jax.random.normal(ks[4], (t, n))
    d = jax.random.normal(ks[5], (ch,))
    h0 = jax.random.normal(ks[6], (n, ch))
    return x, dt, a, b, c, d, h0


def recurrence_numpy(x, dt, a, b, c, d, h0):
    """The equations of the module's docstring, a token at a time in
    float64: what ``selective_scan_recurrence`` is itself held to."""
    x, dt, a, b, c, d, h = (np.asarray(v, np.float64)
                            for v in (x, dt, a, b, c, d, h0))
    ys = []
    for t in range(x.shape[0]):
        h = np.exp(dt[t][None] * a) * h + (dt[t] * x[t])[None] * b[t][:, None]
        ys.append((h * c[t][:, None]).sum(0) + d * x[t])
    return np.stack(ys), h


def kernel(*args):
    with force_kernel_backend("interpret"):
        return jax.jit(ss.selective_scan_chunk)(*args)


def test_the_recurrence_is_the_equations():
    args = inputs(19, 24, n=4)
    y, h = ss.selective_scan_recurrence(*args)
    want_y, want_h = recurrence_numpy(*args)
    np.testing.assert_allclose(y, want_y, atol=ATOL)
    np.testing.assert_allclose(h, want_h, atol=ATOL)


# 8: one block of time; 24: three of 8; 37: padded to 40 (not a multiple of
# any block); 256: two of 128; 2,048 channels are two blocks of channels.
@pytest.mark.parametrize("t", [8, 24, 37, 256])
def test_the_chunk_kernel_is_the_recurrence_from_a_carried_state(t):
    args = inputs(t, 2048, seed=t)
    y, h = kernel(*args)
    want_y, want_h = ss.selective_scan_recurrence(*args)
    assert y.shape == (t, 2048) and h.shape == (N, 2048)
    np.testing.assert_allclose(y, want_y, atol=ATOL)
    np.testing.assert_allclose(h, want_h, atol=ATOL)


def test_two_chunks_hand_the_state_on():
    """A run cut anywhere is the run: the state a chunk leaves is the one
    the next starts from."""
    args = inputs(48, 1024, seed=3)
    x, dt, a, b, c, d, h0 = args
    y1, h1 = kernel(x[:29], dt[:29], a, b[:29], c[:29], d, h0)
    y2, h2 = kernel(x[29:], dt[29:], a, b[29:], c[29:], d, h1)
    want_y, want_h = ss.selective_scan_recurrence(*args)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), want_y, atol=ATOL)
    np.testing.assert_allclose(h2, want_h, atol=ATOL)


@pytest.mark.parametrize("backend", ["interpret", "reference"])
def test_an_invalid_row_changes_no_state_and_gives_nothing(backend):
    """A padded chunk's tail: the state after 21 valid rows of 32 is the
    state after a run of 21, bit for bit what the rows before left, and
    the tail's output is zero."""
    args = inputs(32, 1024, seed=5)
    x, dt, a, b, c, d, h0 = args
    valid = jnp.arange(32) < 21
    with force_kernel_backend(backend):
        y, h = jax.jit(ss.selective_scan_chunk)(*args, valid)
        want_y, want_h = jax.jit(ss.selective_scan_chunk)(
            x[:21], dt[:21], a, b[:21], c[:21], d, h0)
    np.testing.assert_allclose(y[:21], want_y, atol=ATOL)
    assert not np.asarray(y[21:]).any()
    np.testing.assert_allclose(h, want_h, atol=ATOL)


def test_tiny_widths_and_the_cpu_take_the_recurrence():
    """Channels that are not whole blocks of 1,024 (the tiny model's 128),
    and any width off a TPU, run the recurrence: same numbers exactly."""
    args = inputs(12, 128, n=4)
    with force_kernel_backend("interpret"):
        got = ss.selective_scan_chunk(*args)
    want = ss.selective_scan_recurrence(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    big = inputs(8, 1024)
    for g, w in zip(ss.selective_scan_chunk(*big),
                    ss.selective_scan_recurrence(*big)):
        np.testing.assert_array_equal(g, w)


def test_the_step_is_one_token_of_the_recurrence_on_every_slot():
    slots, ch = 5, 256
    x, dt, a, b, c, d, _ = inputs(slots, ch, seed=7)
    h = jax.random.normal(jax.random.PRNGKey(9), (slots, N, ch))
    y, h1 = jax.jit(ss.selective_scan_step)(x, dt, a, b, c, d, h)
    for s in range(slots):
        want_y, want_h = ss.selective_scan_recurrence(
            x[s:s + 1], dt[s:s + 1], a, b[s:s + 1], c[s:s + 1], d, h[s])
        np.testing.assert_allclose(y[s], want_y[0], atol=ATOL)
        np.testing.assert_allclose(h1[s], want_h, atol=ATOL)


def test_a_slot_that_does_not_step_keeps_its_state_bit_for_bit():
    slots, ch = 4, 256
    x, dt, a, b, c, d, _ = inputs(slots, ch, seed=11)
    h = jax.random.normal(jax.random.PRNGKey(13), (slots, N, ch))
    valid = jnp.array([True, False, True, False])
    y, h1 = jax.jit(ss.selective_scan_step)(x, dt, a, b, c, d, h, valid)
    np.testing.assert_array_equal(h1[1], h[1])
    np.testing.assert_array_equal(h1[3], h[3])
    assert not np.asarray(y[1]).any() and not np.asarray(y[3]).any()
    assert np.abs(np.asarray(h1[0] - h[0])).max() > 1e-3


def test_d_is_the_skip_and_a_decays():
    """``D`` reaches the output and not the state; a more negative ``A``
    forgets faster."""
    x, dt, a, b, c, d, h0 = inputs(16, 64, n=4)
    y0, h_0 = ss.selective_scan_recurrence(x, dt, a, b, c, d, h0)
    y1, h_1 = ss.selective_scan_recurrence(x, dt, a, b, c, d + 1.0, h0)
    np.testing.assert_allclose(y1 - y0, x, atol=ATOL)
    np.testing.assert_array_equal(h_0, h_1)
    zero = jnp.zeros_like(x)
    _, slow = ss.selective_scan_recurrence(zero, dt, a, b, c, d, h0)
    _, fast = ss.selective_scan_recurrence(zero, dt, 4 * a, b, c, d, h0)
    assert np.abs(np.asarray(fast)).sum() < np.abs(np.asarray(slow)).sum()
