"""``ops/sparse_attention.py``: the index scores, the selection as a
threshold and a tie's cut, the attention under the selected set and the two
writes of the index key's leaf, each against its plain form; the Pallas
bodies through the interpreter against the jnp references, a prefill
chunk's shape (one line of many rows) and a decode step's (a row of every
slot, one of them idle) alike. The scores are held to their contract below
the bound they are written to, and their readers to reading nothing above.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.kernels import force_kernel_backend

L, B, S, DI, J, K = 2, 3, 256, 64, 4, 16
H, HKV, D = 4, 2, 128
# A line long enough for several of the attention's key blocks (1,024 keys a
# grid step), and the positions of slot 1 whose index keys are zero there:
# they score 0.0, below every kept score of a row that sees thousands.
LONG, BLANK = 4096, 2048
# Two of a step's blocks of 4,096 index keys: a step whose lines all end in
# the first has no grid step, and writes nothing, in the second.
LONGER = 8192
# (lines, rows a line, their slots, first positions, limits, the lines'
# length): a chunk in the middle of a prompt, a prompt's padded last chunk
# (its rows past the limit see what the last real row sees), a decode step
# with an idle slot; a step over several key blocks whose second line keeps
# nothing in its first two (the running maximum stays at its floor through
# them), and a chunk of two tiles across a key block's edge, the first
# tile's last query (position 1,021) short of it; a step of eight lines of
# different lengths (the slots read twice over, one line idle) that ends
# inside the first of its two blocks of index keys, and one that ends inside
# the second.
SHAPES = {"chunk": (1, 24, [2], [100], [124], S),
          "padded chunk": (1, 24, [1], [100], [110], S),
          "step": (3, 1, [0, 1, 2], [5, 200, 17], [6, 201, 0], S),
          "long step": (3, 1, [0, 1, 2], [1500, 3900, 17], [1501, 3901, 0],
                        LONG),
          "long chunk": (1, 520, [2], [510], [1030], LONG),
          "step of eight": (8, 1, [0, 1, 2, 0, 1, 2, 0, 1],
                            [1500, 3000, 17, 2500, 3, 1200, 640, 100],
                            [1501, 3001, 0, 2501, 4, 1201, 641, 101], LONGER),
          "long step of eight": (8, 1, [0, 1, 2, 0, 1, 2, 0, 1],
                                 [1500, 6100, 17, 4500, 3, 5000, 640, 4095],
                                 [1501, 6101, 0, 4501, 4, 5001, 641, 4096],
                                 LONGER)}


@pytest.fixture(scope="module", autouse=True)
def release_the_compiled_programs():
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def caches():
    """{a line's length: (index keys, keys, values)}."""
    def make(s, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        return (jax.random.normal(ks[0], (L, B, 1, DI, s)),
                jax.random.normal(ks[1], (L, B, HKV, s, D)),
                jax.random.normal(ks[2], (L, B, HKV, s, D)))
    index_k, k, v = make(LONG, 1)
    return {S: make(S, 0),
            LONG: (index_k.at[:, 1, :, :, :BLANK].set(0.0), k, v),
            LONGER: make(LONGER, 2)}


@pytest.fixture
def leaves(caches, name):
    return caches[SHAPES[name][5]]


def _inputs(name):
    n, c, slots, q0, lim, _ = SHAPES[name]
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    return (jax.random.normal(ks[0], (n, J, c, DI)),
            jax.random.normal(ks[1], (n, J, c)),
            jax.random.normal(ks[2], (n, H, c, D)),
            jnp.asarray(slots), jnp.asarray(q0), jnp.asarray(lim))


def _fresh_jit(fn, **static):
    """``jax.jit`` of a function nothing has traced: jax keeps an op's
    traces by the function, and the backend is read while tracing, so
    ``jax.jit(sa.op)`` under "interpret" at the shapes "reference" ran
    first would run the reference's trace again."""
    return jax.jit(lambda *args: fn(*args), **static)


def _bound(name):
    """One past the last column ``index_scores`` owes: the longest line's
    last seen position, up to whole chunks of the selection's columns
    (2,048, or the line where it is shorter), one at the least."""
    _, c, _, q0, lim, s = SHAPES[name]
    ch = min(s, 2048)
    seen = max(min(first + c, limit) for first, limit in zip(q0, lim))
    return max(-(-seen // ch), 1) * ch


def _live(name):
    """One past the last position each row sees, [lines x rows]."""
    _, c, _, q0, lim, _ = SHAPES[name]
    return np.minimum(np.asarray(q0)[:, None] + np.arange(1, c + 1)[None],
                      np.asarray(lim)[:, None]).reshape(-1)


def _plain_scores(q, w, index_k, layer, slots, q0, lim):
    """The equation, a loop a row: sum_j w relu(q . k) over what it sees."""
    n, _, c, _ = q.shape
    out = np.full((n, c, index_k.shape[4]), -np.inf, np.float32)
    for i in range(n):
        keys = np.asarray(index_k[layer, int(slots[i]), 0]).T      # [S, Di]
        for t in range(c):
            seen = min(int(q0[i]) + t + 1, int(lim[i]))
            dots = np.maximum(np.asarray(q[i, :, t]) @ keys[:seen].T, 0.0)
            out[i, t, :seen] = np.asarray(w[i, :, t]) @ dots
    return out


def _top_k_sets(scores, k):
    """The sets ``lax.top_k`` gives (0.0 and -0.0 one number), as a mask."""
    flat = np.where(scores == 0, 0.0, scores).astype(np.float32)
    _, idx = lax.top_k(jnp.asarray(flat), min(k, flat.shape[-1]))
    want = np.zeros(flat.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    return want & np.isfinite(flat)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_index_scores_are_the_equation_over_what_a_row_sees(leaves, name,
                                                            backend):
    q, w, _, slots, q0, lim = _inputs(name)
    want = _plain_scores(q, w, leaves[0], 1, slots, q0, lim)
    with force_kernel_backend(backend):
        got = np.asarray(_fresh_jit(sa.index_scores)(q, w, leaves[0], 1,
                                                     slots, q0, lim))
    # (above the bound the kernel writes nothing: the interpreter leaves nan)
    assert got.shape == want.shape
    got, want = got[..., :_bound(name)], want[..., :_bound(name)]
    seen = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), seen)
    assert (got[~seen] == -np.inf).all()
    np.testing.assert_allclose(got[seen], want[seen], atol=2e-5)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("name", list(SHAPES))
def test_the_threshold_and_the_cut_are_top_k_s_sets(leaves, name, ties,
                                                    backend):
    """Exactly ``lax.top_k``'s sets, where scores are all different and
    where they are rounded to halves (ties at the k-th place in most rows,
    zeros of both signs among them); a row that sees no more than k keeps
    all it sees, an idle row nothing."""
    q, w, _, slots, q0, lim = _inputs(name)
    n, _, c, _ = q.shape
    scores = _plain_scores(q, w, leaves[0], 1, slots, q0, lim).reshape(
        n * c, -1)
    if ties:
        scores = np.where(np.isfinite(scores), np.round(scores * 2) / 2,
                          scores).astype(np.float32)
        assert (scores == 0).any() and np.signbit(scores[scores == 0]).any()
    live = _live(name)
    with force_kernel_backend(backend):
        thr, pcut = _fresh_jit(sa.topk_threshold, static_argnums=1)(
            jnp.asarray(scores), K, jnp.asarray(live))
    got = np.asarray(sa.kept(jnp.asarray(scores), thr, pcut))
    np.testing.assert_array_equal(got, _top_k_sets(scores, K))
    assert (got.sum(axis=1) == np.minimum(live, K)).all()
    if ties:
        # some row's cut fell inside a run of equal scores
        equal = (scores == np.asarray(thr)[:, None]).sum(axis=1)
        assert (equal > (got & (scores == np.asarray(thr)[:, None])
                         ).sum(axis=1)).any()


def test_a_line_no_longer_than_k_keeps_all_it_sees():
    scores = jnp.where(jnp.arange(12)[None, :] <= jnp.arange(4)[:, None],
                       1.0, -jnp.inf)
    for backend in ("reference", "interpret"):
        with force_kernel_backend(backend):
            thr, pcut = sa.topk_threshold(scores, 16)
        assert (np.asarray(thr) == -np.inf).all()
        np.testing.assert_array_equal(np.asarray(sa.kept(scores, thr, pcut)),
                                      np.isfinite(np.asarray(scores)))


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_the_attention_is_a_softmax_over_each_row_s_set(leaves, name,
                                                        backend):
    q, w, qq, slots, q0, lim = _inputs(name)
    n, _, c, _ = q.shape
    index_k, kc, vc = leaves
    scores = np.round(_plain_scores(q, w, index_k, 1, slots, q0, lim) * 2) / 2
    scores = jnp.asarray(scores.astype(np.float32))
    thr, pcut = sa.topk_threshold_reference(scores.reshape(n * c, -1), K)
    thr, pcut = thr.reshape(n, c), pcut.reshape(n, c)
    keep = _top_k_sets(np.asarray(scores), K)
    with force_kernel_backend(backend):
        got = np.asarray(_fresh_jit(sa.sparse_attention)(
            qq, kc, vc, scores, thr, pcut, 1, slots, q0, lim))
    group = H // HKV
    for i in range(n):
        for t in range(c):
            at = np.flatnonzero(keep[i, t])
            for h in range(H):
                if not len(at):        # an idle slot: zeros, not NaN
                    assert not got[i, h, t].any()
                    continue
                k = np.asarray(kc[1, int(slots[i]), h // group])[at]
                v = np.asarray(vc[1, int(slots[i]), h // group])[at]
                p = np.asarray(qq[i, h, t]) @ k.T / np.sqrt(D)
                p = np.exp(p - p.max())
                np.testing.assert_allclose(got[i, h, t], (p / p.sum()) @ v,
                                           atol=2e-5)


@pytest.mark.parametrize("poison", [np.nan, np.inf])
@pytest.mark.parametrize("name", ["long chunk", "step of eight"])
def test_no_reader_looks_above_the_bound_the_scores_are_written_to(
        leaves, name, poison):
    """``index_scores`` writes nothing at or above its bound, so whatever
    lies there (``nan`` and ``+inf``, which would win every count and every
    comparison) moves no bit of the selection's two numbers under ``live``
    nor of the attention, for a chunk and for a step; scores in halves, so
    that the tie's cut runs over the positions too."""
    q, w, qq, slots, q0, lim = _inputs(name)
    n, _, c, _ = q.shape
    index_k, kc, vc = leaves
    bound = _bound(name)
    assert bound < index_k.shape[4]
    clean = (np.round(_plain_scores(q, w, index_k, 1, slots, q0, lim) * 2)
             / 2).astype(np.float32)
    dirty = clean.copy()
    dirty[..., bound:] = poison
    live = jnp.asarray(_live(name))
    got = []
    with force_kernel_backend("interpret"):
        select = _fresh_jit(sa.topk_threshold, static_argnums=1)
        attend = _fresh_jit(sa.sparse_attention)
        for scores in (clean, dirty):
            scores = jnp.asarray(scores)
            thr, pcut = select(scores.reshape(n * c, -1), K, live)
            out = attend(qq, kc, vc, scores, thr.reshape(n, c),
                         pcut.reshape(n, c), 1, slots, q0, lim)
            got.append([np.asarray(a) for a in (thr, pcut, out)])
    assert (got[0][1] >= 0).any() and np.isfinite(got[0][2]).all()
    for want, have in zip(*got):
        np.testing.assert_array_equal(have.view(np.int32),
                                      want.view(np.int32))


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_the_index_key_s_writes_land_where_the_rows_are(caches, backend):
    index_k = caches[S][0]
    new = jax.random.normal(jax.random.PRNGKey(7), (B, DI))
    pos, mask = jnp.asarray([5, 200, 130]), jnp.asarray([True, False, True])
    with force_kernel_backend(backend):
        got = np.asarray(_fresh_jit(sa.index_rows_write)(index_k, new, 1,
                                                         pos, mask))
    want = np.asarray(index_k).copy()
    want[1, 0, 0, :, 5] = np.asarray(new[0])
    want[1, 2, 0, :, 130] = np.asarray(new[2])
    np.testing.assert_array_equal(got, want)
    chunk = jax.random.normal(jax.random.PRNGKey(8), (24, DI))
    got = np.asarray(jax.jit(sa.index_chunk_write)(index_k, chunk, 0, 2, 96))
    want = np.asarray(index_k).copy()
    want[0, 2, 0, :, 96:120] = np.asarray(chunk).T
    np.testing.assert_array_equal(got, want)
