"""The gated delta rule (``ops/gated_delta.py``): the chunked form and the
one-token step against the token-by-token recurrence, float32 on the CPU.

The chunked form twice: the jnp body that runs off a TPU, at widths of 16
and 8 (which the kernel does not take), and the kernel's body through the
Pallas interpreter at widths of 128 (``FORMS``), held to the same
recurrence at the same tolerance. The step the same way: its jnp body, and
its kernel's body on a line of a state leaf (``STEP_CASES``).

Tolerances: every form computes in float32 and the products at true
float32, so what is left is the order of the sums: observed 2e-7 on outputs
of about 1 and 8e-7 on states of about 3 (1.1e-6 from the kernel's body at
512 positions). 1e-5 would not pass a decay applied a position late, a
correction without ``beta`` or a sub-chunk that starts from another state.

A decay a key channel (``g`` of [T, H, Dk], Kimi Delta Attention; form
``channel``) goes through the same cases at the same tolerance: its chunk
form pairs rows about the middle of a block's decay (blocks of 16 inside the
sub-chunk of 64), so the lengths sit on both sides of 16 and of 64 too,
channel 0 is at the gate's floor (-5 every token: a block's decay is
``exp(-75)``, its factors ``exp(37.5)`` and ``exp(-37.5)``) beside channel 1
at -0.001, and the scalar cases are what they were.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import gated_delta as gd
from ray_tpu.ops.kernels import force_kernel_backend

H, DK, DV = 3, 16, 8
ATOL = 1e-5

# form: (value heads, key heads, Dk, Dv). The kernel takes heads of whole
# 128-lane columns two by two: two pairs that each share a key head (what
# Qwen3-Next has), a pair of heads with keys of their own, and a step of
# eight heads on two key heads.
FORMS = {"jnp": (H, H, DK, DV), "kernel": (4, 2, 128, 128),
         "kernel_own_keys": (2, 2, 128, 128), "kernel_of_8": (8, 2, 128, 128),
         # a decay a key channel, a key head a value head (KDA): the jnp
         # body, and the kernel's body at two pairs of heads and at eight
         "channel": (H, H, DK, DV), "channel_kernel": (4, 4, 128, 128),
         "channel_kernel_of_8": (8, 8, 128, 128)}
# The floor of the channel form's gate (``kda_lower_bound``).
FLOOR = -5.0

_jitted = jax.jit(gd.gated_delta_chunk)
_jitted_channel = jax.jit(lambda *a: gd.gated_delta_chunk(*a, g_floor=FLOOR))


def chunk(form, *a):
    """``gated_delta_chunk`` as ``form`` runs it: as it is off a TPU, or the
    kernel's body through the interpreter (one jitted program a shape: its
    cache holds only what was traced under the forced backend, and no
    kernel form has a jnp form's widths)."""
    if form == "jnp":
        return _jitted(*a)
    if form == "channel":
        return _jitted_channel(*a)
    with force_kernel_backend("interpret"):
        return (_jitted_channel if form.startswith("channel")
                else _jitted)(*a)


def inputs(t, seed=0, state=True, form="jnp"):
    """Unit keys, scaled unit queries, decays from 0.0009 to 1.6 a token
    (``exp(g)`` from 0.2 to 0.999), steps in (0, 1), a random state
    (:func:`wide_steps` draws them over (0, 2))."""
    h, hk, dk, dv = FORMS.get(form, form)    # a name, or the four numbers
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (t, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (t, hk, dk)))
    v = jax.random.normal(ks[2], (t, h, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (t, h), minval=-7.0, maxval=0.5))
    if str(form).startswith("channel"):
        # log-uniform over 0.001 to 5 a channel and token, channel 0 at the
        # floor every token and channel 1 at -0.001
        g = -jnp.exp(jax.random.uniform(
            ks[3], (t, h, dk), minval=np.log(1e-3), maxval=np.log(-FLOOR)))
        g = g.at[..., 0].set(FLOOR).at[..., 1].set(-1e-3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, h)))
    s = jax.random.normal(ks[5], (h, dk, dv)) if state \
        else jnp.zeros((h, dk, dv))
    return q, k, v, g, beta, s


def close(got, want):
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=ATOL)


@pytest.mark.parametrize("form,t", [
    *(("jnp", t) for t in (1, 5, 63, 64, 65, 100, 128, 200)),
    *(("kernel", t) for t in (64, 330, 512)),
    ("kernel_own_keys", 330), ("kernel_of_8", 130),
    *(("channel", t) for t in (1, 5, 15, 16, 17, 63, 64, 65, 100, 128,
                               200)),
    *(("channel_kernel", t) for t in (64, 330, 512)),
    ("channel_kernel_of_8", 130)])
@pytest.mark.parametrize("state", [False, True], ids=["zero", "carried"])
def test_the_chunk_form_is_the_recurrence(form, t, state):
    """At lengths that are and are not whole sub-chunks, from zeros and from
    a carried state."""
    a = inputs(t, seed=t, state=state, form=form)
    close(chunk(form, *a), gd.gated_delta_recurrence(*a))


@pytest.mark.parametrize("form", ["jnp", "kernel", "channel",
                                  "channel_kernel"])
def test_the_state_is_handed_from_run_to_run(form):
    """Two runs, the second from the state the first left, are one run: a
    prefill chunk after a prefill chunk."""
    q, k, v, g, beta, s = inputs(150, seed=3, form=form)
    want_o, want_s = gd.gated_delta_recurrence(q, k, v, g, beta, s)
    cut = 70
    o1, s1 = chunk(form, q[:cut], k[:cut], v[:cut], g[:cut], beta[:cut], s)
    o2, s2 = chunk(form, q[cut:], k[cut:], v[cut:], g[cut:], beta[cut:], s1)
    close((jnp.concatenate([o1, o2]), s2), (want_o, want_s))


@pytest.mark.parametrize("form", ["jnp", "kernel", "channel",
                                  "channel_kernel"])
def test_rows_that_are_not_valid_change_no_state(form):
    """A padded chunk: rows past the last valid one enter with ``g = 0`` and
    ``beta = 0`` and leave the state as the last valid row left it,
    whatever their q, k and v."""
    q, k, v, g, beta, s = inputs(96, seed=5, form=form)
    n = 41
    valid = jnp.arange(96) < n
    _, want = gd.gated_delta_recurrence(q[:n], k[:n], v[:n], g[:n], beta[:n],
                                        s)
    o, got = chunk(
        form, q, k, jnp.where(valid[:, None, None], v, 100.0 * v),
        jnp.where(valid.reshape((-1,) + (1,) * (g.ndim - 1)), g, 0.0),
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


@pytest.mark.parametrize("form", ["jnp", "kernel", "kernel_own_keys",
                                  "channel", "channel_kernel"])
def test_keys_that_are_alike_cost_no_precision(form):
    """Neighbouring keys nearly parallel and steps near 1: ``A`` has entries
    near 1 all under its diagonal. The product ``(I - A)(I + A^2)(I + A^4)
    ...`` equals the inverse in exact arithmetic and loses it here in
    float32 (its powers grow like binomial coefficients before they cancel:
    it read 1e-3 off on a served model's logits); the inverse by halves
    stays at the recurrence's 1e-5."""
    q, k, v, g, beta, s = inputs(128, seed=15, form=form)
    k = k[:1] + 0.05 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jnp.full_like(beta, 0.98)
    g = jnp.full_like(g, -1e-3)
    close(chunk(form, q, k, v, g, beta, s),
          gd.gated_delta_recurrence(q, k, v, g, beta, s))


@pytest.mark.parametrize("form", ["jnp", "kernel", "channel",
                                  "channel_kernel"])
def test_a_long_decay_overflows_nothing(form):
    """Every exponent is a difference ``G_i - G_j <= 0``: a sub-chunk whose
    running decay reaches exp(-64 x 20) still gives the recurrence. A decay
    a channel at its floor everywhere: a block's factors reach exp(37.5) and
    exp(-37.5), a sub-chunk's decay exp(-320)."""
    q, k, v, g, beta, s = inputs(128, seed=9, form=form)
    g = jnp.full_like(g, FLOOR if form.startswith("channel") else -20.0)
    got = chunk(form, q, k, v, g, beta, s)
    assert np.isfinite(np.asarray(got[0])).all()
    close(got, gd.gated_delta_recurrence(q, k, v, g, beta, s))


def test_a_decay_a_channel_is_chunked_under_a_stated_floor_alone():
    """No floor is refused, and so is one at which a block's factor would
    not fit float32 (16 x 5.5 = 88); nothing is clamped to make it fit. The
    scalar decay needs none."""
    a = inputs(70, form="channel")
    with pytest.raises(ValueError, match="g_floor"):
        gd.gated_delta_chunk(*a)
    with pytest.raises(ValueError, match="float32 holds"):
        gd.gated_delta_chunk(*a, g_floor=-5.5)
    gd.gated_delta_chunk(*a, g_floor=-5.49)
    gd.gated_delta_chunk(*inputs(70))


def test_a_decay_the_same_in_every_channel_is_the_scalar_rule():
    """The scalar rule is the channel rule's case: ``g`` a head broadcast
    over its channels gives what ``g`` a head gives, in all three forms."""
    q, k, v, g, beta, s = inputs(100, seed=21)
    g = jnp.maximum(g, FLOOR)
    wide = jnp.broadcast_to(g[..., None], g.shape + (DK,))
    close(gd.gated_delta_chunk(q, k, v, wide, beta, s, g_floor=FLOOR),
          gd.gated_delta_chunk(q, k, v, g, beta, s))
    close(gd.gated_delta_recurrence(q, k, v, wide, beta, s),
          gd.gated_delta_recurrence(q, k, v, g, beta, s))
    states = jnp.broadcast_to(s, (1, 4, *s.shape))
    close(gd.gated_delta_step(q[:4], k[:4], v[:4], wide[:4], beta[:4],
                              states, 0),
          gd.gated_delta_step(q[:4], k[:4], v[:4], g[:4], beta[:4], states,
                              0))


@pytest.mark.parametrize("form", ["jnp", "channel"])
def test_the_step_is_the_recurrence_s_one_token(form):
    """Every slot its own state, a token each; a slot with ``g = 0`` and
    ``beta = 0`` keeps its state bit for bit."""
    slots = 4
    q, k, v, g, beta, _ = inputs(slots, seed=11, form=form)
    states = jax.random.normal(jax.random.PRNGKey(1), (slots, H, DK, DV))
    idle = jnp.arange(slots) == 2
    g = jnp.where(idle.reshape((-1,) + (1,) * (g.ndim - 1)), 0.0, g)
    beta = jnp.where(idle[:, None], 0.0, beta)
    o, new = gd.gated_delta_step(q, k, v, g, beta, states[None], 0)
    new, = new
    for b in range(slots):
        want_o, want_s = gd.gated_delta_recurrence(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], g[b:b + 1], beta[b:b + 1],
            states[b])
        close((o[b], new[b]), (want_o[0], want_s))
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(states[2]))


@pytest.mark.parametrize("form", ["jnp", "kernel", "channel",
                                  "channel_kernel"])
def test_the_forms_compute_in_float32_whatever_they_are_given(form):
    """bfloat16 inputs are cast up, the state and the output are float32:
    the rule's state is never kept below float32."""
    q, k, v, g, beta, s = inputs(70, seed=13, form=form)
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v))
    o, new = chunk(form, *low, g, beta, s)
    assert o.dtype == new.dtype == jnp.float32
    close((o, new), gd.gated_delta_recurrence(*low, g, beta, s))
    q, k = gd._a_value_head(*low[:2], v.shape[1])
    low = (q, k, low[2])
    o, new = gd.gated_delta_step(*(a[:4] for a in low), g[:4], beta[:4],
                                 jnp.broadcast_to(s, (1, 4, *s.shape)), 0)
    assert o.dtype == new.dtype == jnp.float32


# The step's kernel: (decay, lines of the leaf, the line stepped: a traced
# index where the leaf has several).
STEP_CASES = [("kernel", 1, 0), ("kernel", 3, 1), ("channel_kernel", 1, 0),
              ("channel_kernel", 3, 2)]


def step_inputs(form, lines, seed=31, slots=3):
    """A token a slot at the kernel's widths, slot 1 idle (``g = 0``,
    ``beta = 0``), and a leaf of ``lines`` lines of states."""
    q, k, v, g, beta, _ = inputs(slots, seed=seed, form=form)
    q, k = gd._a_value_head(q, k, v.shape[1])
    idle = jnp.arange(slots) == 1
    g = jnp.where(idle.reshape((-1,) + (1,) * (g.ndim - 1)), 0.0, g)
    beta = jnp.where(idle[:, None], 0.0, beta)
    leaf = jax.random.normal(jax.random.PRNGKey(seed + 1),
                             (lines, slots, *v.shape[1:], v.shape[-1]))
    return (q, k, v, g, beta), leaf


def kernel_step(a, leaf, line):
    """``gated_delta_step``'s kernel through the interpreter, the line a
    traced index (a function of its own a trace: the forced backend is no
    part of the tracing cache's key)."""
    with force_kernel_backend("interpret"):
        return jax.jit(lambda *x: gd.gated_delta_step(*x))(
            *a, leaf, jnp.int32(line))


@pytest.mark.parametrize("form,lines,line", STEP_CASES)
def test_the_step_s_kernel_is_the_recurrence_s_one_token(form, lines, line):
    """Outputs and the line stepped against the recurrence's one token, a
    slot at a time, and against the jnp step."""
    a, leaf = step_inputs(form, lines)
    o, new = kernel_step(a, leaf, line)
    assert o.dtype == new.dtype == jnp.float32 and new.shape == leaf.shape
    for b in range(leaf.shape[1]):
        want_o, want_s = gd.gated_delta_recurrence(
            *(x[b:b + 1] for x in a), leaf[line, b])
        close((o[b], new[line, b]), (want_o[0], want_s))
    close((o, new), gd.gated_delta_step_reference(*a, leaf, line))


@pytest.mark.parametrize("form,lines,line", STEP_CASES)
def test_the_step_s_kernel_leaves_what_it_does_not_step_bit_for_bit(
        form, lines, line):
    """Every other line of the leaf, and on the line stepped the slot that
    does not decode (``g = 0``, ``beta = 0``): the kernel visits no other
    line's blocks, and an idle state goes through ``1 * S + k 0``."""
    a, leaf = step_inputs(form, lines, seed=33)
    _, new = kernel_step(a, leaf, line)
    for other in range(lines):
        if other != line:
            np.testing.assert_array_equal(np.asarray(new[other]),
                                          np.asarray(leaf[other]))
    np.testing.assert_array_equal(np.asarray(new[line, 1]),
                                  np.asarray(leaf[line, 1]))
    assert not np.array_equal(np.asarray(new[line, 0]),
                              np.asarray(leaf[line, 0]))


@pytest.mark.parametrize("form,lines,line", STEP_CASES)
def test_the_step_s_kernel_computes_in_float32_whatever_it_is_given(
        form, lines, line):
    """bfloat16 queries, keys and values are cast up before the kernel:
    what it gives is the recurrence's on the same rounded operands, at the
    float32 tolerance."""
    a, leaf = step_inputs(form, lines, seed=35)
    low = tuple(x.astype(jnp.bfloat16) for x in a[:3]) + a[3:]
    o, new = kernel_step(low, leaf, line)
    assert o.dtype == new.dtype == jnp.float32
    for b in range(leaf.shape[1]):
        want_o, want_s = gd.gated_delta_recurrence(
            *(x[b:b + 1] for x in low), leaf[line, b])
        close((o[b], new[line, b]), (want_o[0], want_s))


def _step_traced(heads, dk, dv, channel, backend="interpret"):
    shape = jax.ShapeDtypeStruct
    g = shape((2, heads, dk) if channel else (2, heads), jnp.float32)
    with force_kernel_backend(backend):
        return list(_equations(jax.make_jaxpr(
            lambda *a: gd.gated_delta_step(*a))(
                shape((2, heads, dk), jnp.float32),
                shape((2, heads, dk), jnp.float32),
                shape((2, heads, dv), jnp.float32), g,
                shape((2, heads), jnp.float32),
                shape((3, 2, heads, dk, dv), jnp.float32),
                shape((), jnp.int32)).jaxpr))


@pytest.mark.parametrize("channel", [False, True], ids=["a_head", "a_channel"])
def test_the_step_s_kernel_is_chosen_by_the_backend_and_the_operands_shapes(
        channel):
    """One call, named for the trace, the leaf its in-place operand, where
    the backend is not the reference's and a state's two sides are
    multiples of 128; the jnp body (a line sliced out and written back) off
    a TPU and at every other width. The block is a function of the shapes:
    32 states of 128 x 128 (2 MiB) a step, eight of 256 x 256."""
    call, = (e for e in _step_traced(4, 128, 128, channel)
             if e.primitive.name == "pallas_call")
    assert call.params["name"] == "gated_delta_step"
    assert dict(call.params["input_output_aliases"]) == {6: 1}
    assert call.invars[6].aval.shape == call.outvars[1].aval.shape \
        == (3, 2, 4, 128, 128)
    # the decay as wide as the keys, or a number a head
    assert call.invars[4].aval.shape == \
        ((2, 4, 128) if channel else (2, 1, 1, 4))
    for shape in ((4, 128, 128), (3, 16, 8)):
        names = {e.primitive.name
                 for e in _step_traced(*shape, channel, backend="reference")}
        assert "pallas_call" not in names
        assert "dynamic_update_slice" in names
    for h, dk, dv in ((3, 16, 8), (4, 128, 64), (4, 64, 128)):
        assert "pallas_call" not in {
            e.primitive.name for e in _step_traced(h, dk, dv, channel)}
    assert "pallas_call" in {
        e.primitive.name for e in _step_traced(3, 256, 128, channel)}
    assert [gd.states_a_step(*a) for a in (
        (32, 128, 128), (64, 128, 128), (4, 128, 128), (48, 128, 128),
        (16, 256, 256), (12, 256, 256), (2, 1024, 1024))] == \
        [32, 32, 4, 24, 8, 0, 0]


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (a kernel's
    body, a loop's)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) \
                    else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _traced(form, t=128, backend="interpret"):
    # a function of its own a trace: the forced backend is no part of the
    # tracing cache's key
    floor = FLOOR if str(form).startswith("channel") else None
    with force_kernel_backend(backend):
        return list(_equations(jax.make_jaxpr(
            lambda *a: gd.gated_delta_chunk(*a, g_floor=floor))(
                *inputs(t, form=form)).jaxpr))


def test_the_kernel_is_chosen_by_the_backend_and_the_operands_shapes():
    """One call, named for the trace, where the backend is not the
    reference's and a head's keys and values are whole 128-lane columns,
    the value heads even in number and a step's heads whole key heads; the
    jnp body off a TPU and at every other shape (``Qwen3NextConfig.tiny``'s
    16 and 8, three heads, a key head for three value heads)."""
    calls = [e for e in _traced("kernel") if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].params["name"] == "gated_delta_chunk"
    for form in FORMS:
        names = {e.primitive.name for e in _traced(form, backend="reference")}
        assert "pallas_call" not in names, form
    for form in ("jnp", "channel"):
        assert "pallas_call" not in {e.primitive.name for e in _traced(form)}
    # a decay a channel has a kernel of its own under the same name, where
    # a key head is a value head
    calls = [e for e in _traced("channel_kernel")
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1 and calls[0].params["name"] == "gated_delta_chunk"
    assert calls[0].invars[3].aval.shape == calls[0].invars[1].aval.shape
    for h, hk in ((3, 3), (6, 2), (2, 1), (8, 8), (16, 2)):
        names = {e.primitive.name for e in _traced((h, hk, 128, 128))}
        fits = h % 2 == 0 and gd._heads_a_step(h) % (h // hk) == 0
        assert ("pallas_call" in names) == fits, (h, hk)


def _traced_backward():
    """The trained form's backward at the kernel's widths, as the
    interpreter's backend traces it."""
    a, weights = batch_inputs(128, widths=TRAINED_FORMS["kernel_of_128"])
    with force_kernel_backend("interpret"):
        return list(_equations(jax.make_jaxpr(jax.grad(
            _scalar(lambda *a: gd.gated_delta_chunk(*a), weights),
            argnums=tuple(range(6))))(*a).jaxpr))


@pytest.mark.parametrize("form", ["kernel", "kernel_own_keys",
                                  "channel_kernel", "backward"])
def test_every_product_of_the_kernel_is_true_float32(form):
    """The configuration states the precision (``departures.state_dtype``):
    the state float32, the rule's products at true float32. Every product
    inside the kernel is float32 by float32 into float32 at
    ``Precision.HIGHEST`` (a TPU's default is one bfloat16 pass), the state
    goes in and comes out float32, and nothing in the kernel is cast below
    float32. The trained form's backward kernel (``backward``) the same:
    its gradients, the state's among them, leave float32."""
    if form == "backward":
        call, = (e for e in _traced_backward()
                 if e.primitive.name == "pallas_call"
                 and e.params["name"] == "gated_delta_chunk_bwd")
    else:
        call, = (e for e in _traced(form) if e.primitive.name == "pallas_call")
    body = list(_equations(call.params["jaxpr"]))
    dots = [e for e in body if e.primitive.name == "dot_general"]
    assert len(dots) >= 8
    for e in dots:
        assert e.params["precision"] in (
            lax.Precision.HIGHEST,
            (lax.Precision.HIGHEST, lax.Precision.HIGHEST)), e
        assert e.params["preferred_element_type"] == jnp.float32
        assert all(v.aval.dtype == jnp.float32 for v in e.invars)
    for e in body:
        if e.primitive.name == "convert_element_type":
            assert e.params["new_dtype"] in (jnp.float32, jnp.int32), e
    assert [v.aval.dtype for v in call.outvars] == [jnp.float32] * (
        6 if form == "backward" else 2)
    # the state, or its gradient, as it came in
    assert call.outvars[-1].aval.shape == call.invars[-1].aval.shape


def test_the_kernel_under_vmap_is_a_sequence_each():
    """``models/qwen3_next.forward`` runs whole sequences under
    ``jax.vmap``: ``pallas_call``'s batching rule makes the batch a grid
    axis, and each sequence reads what it reads alone."""
    batch = [inputs(130, seed=20 + b, form="kernel") for b in range(2)]
    stacked = tuple(jnp.stack(a) for a in zip(*batch))
    with force_kernel_backend("interpret"):
        got = jax.jit(jax.vmap(gd.gated_delta_chunk))(*stacked)
    for b, a in enumerate(batch):
        close((got[0][b], got[1][b]), gd.gated_delta_recurrence(*a))


# --------------------------------------- steps up to 2, and the form that trains

def wide_steps(a, seed=0):
    """``a`` with its steps drawn anew over (0, 2): ``I - beta k k^T`` then
    has the eigenvalue ``1 - beta`` in (-1, 1) (Olmo-Hybrid's
    ``linear_allow_neg_eigval``), and about half of the tokens overshoot."""
    q, k, v, g, beta, s = a
    beta = jax.random.uniform(jax.random.PRNGKey(1000 + seed), beta.shape,
                              minval=0.02, maxval=1.98)
    return q, k, v, g, beta, s


@pytest.mark.parametrize("form,t", [("jnp", 200), ("kernel", 330),
                                    ("channel", 100)])
def test_steps_up_to_two_are_the_recurrence(form, t):
    """The chunked form holds whatever ``beta``: ``I + A`` stays unit lower
    triangular (``beta`` scales ``A``'s rows, all under the diagonal), so
    the substitution that inverts it divides by nothing. Same tolerance as
    steps in (0, 1)."""
    a = wide_steps(inputs(t, seed=t, form=form), t)
    assert float(a[4].max()) > 1.5
    close(chunk(form, *a), gd.gated_delta_recurrence(*a))


# The trained form's widths: a batch of 2, three heads (no multiple of 8 or
# of 2), keys of 12 beside values of 24.
TRAINED = (2, 3, 12, 24)
# A gradient's largest difference from ``jax.grad`` through the recurrence,
# over that gradient's largest value. Both compute in float32 at true
# float32 products: observed 3e-7 to 1.1e-6 at 40 to 700 positions (the
# order of the sums). A state rounded to bfloat16 at a chunk's boundary
# moves every gradient by 1e-4 to 2e-3 and must not pass.
GRAD_RTOL = 1e-5
OPERANDS = ("q", "k", "v", "g", "beta", "state")


def batch_inputs(t, seed=0, state=True, widths=TRAINED):
    """A batch for the trained form: unit keys, scaled unit queries,
    ``exp(g)`` from 0.5 to 0.999, steps over (0, 2), a random state, and
    the weights of a scalar that reads every output and every state."""
    b, h, dk, dv = widths
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = jnp.log(jax.random.uniform(ks[3], (b, t, h), minval=0.5,
                                   maxval=0.999))
    beta = jax.random.uniform(ks[4], (b, t, h), minval=0.02, maxval=1.98)
    s = jax.random.normal(ks[5], (b, h, dk, dv)) if state \
        else jnp.zeros((b, h, dk, dv))
    weights = (jax.random.normal(ks[6], (b, t, h, dv)),
               jax.random.normal(ks[7], (b, h, dk, dv)))
    return (q, k, v, g, beta, s), weights


def _scalar(rule, weights, beside=False):
    """The scalar that reads every output and state of ``rule``; with
    ``beside``, (the scalar, what it read)."""
    def read(*a):
        o, s = rule(*a)
        loss = jnp.sum(o * weights[0]) + jnp.sum(s * weights[1])
        return (loss, (o, s)) if beside else loss
    return read


def _grads(rule, a, weights):
    # (a fresh function a call: nothing traced under a patched module is
    # found again)
    return jax.jit(jax.value_and_grad(_scalar(rule, weights),
                                      argnums=tuple(range(6))))(*a)


# The trained form twice, as ``FORMS`` has the chunk form: the jnp chunks and
# their ``jax.vjp`` at widths no kernel takes, and both kernels' bodies
# through the interpreter (the forward's, and since PR 69 the backward's,
# ``gated_delta_chunk_bwd``), held to the same recurrence at the same
# tolerance: Olmo-Hybrid's 96 x 192 with six heads padded to the eight of a
# grid step, and one sequence of two heads of 128 x 128 with nothing padded
# but the heads.
TRAINED_FORMS = {"jnp": TRAINED, "kernel": (2, 3, 96, 192),
                 "kernel_of_128": (1, 2, 128, 128)}

# (one jitted program a shape: it is traced under the interpreter's backend
# alone, and the weights are operands)
_kernel_grads = jax.jit(lambda a, weights: jax.value_and_grad(
    _scalar(lambda *a: gd.gated_delta_chunk(*a), weights, beside=True),
    argnums=tuple(range(6)), has_aux=True)(*a))


def trained_grads(form, a, weights):
    """((outputs, states), the six gradients of the scalar that reads them)
    as ``form`` runs the trained form."""
    if form == "jnp":
        return (gd.gated_delta_chunk(*a),
                _grads(gd.gated_delta_chunk, a, weights)[1])
    with force_kernel_backend("interpret"):
        (_, results), grads = _kernel_grads(a, weights)
    return results, grads


def worst(got, want):
    return {name: float(jnp.abs(x - y).max() / jnp.abs(y).max())
            for name, x, y in zip(OPERANDS, got, want)}


@pytest.mark.parametrize("form,t,state", [
    *(("jnp", t, state) for t in (40, 512, 700) for state in (False, True)),
    ("kernel", 512, True), ("kernel", 700, False),
    ("kernel_of_128", 40, False), ("kernel_of_128", 1024, True)],
    ids=lambda x: {False: "zero", True: "carried"}.get(x, str(x)))
def test_the_trained_form_and_its_backward_are_the_recurrence_s(form, t,
                                                                state):
    """``gated_delta_chunk`` on a batch against ``jax.vmap`` of the
    recurrence: outputs and states, and by ``jax.grad`` the gradients of all
    five operands and of the initial state, at lengths under a sub-chunk's,
    one whole chunk, more than a chunk that is not whole chunks, and two
    whole chunks; steps over (0, 2). The jnp chunks, and both kernels'
    bodies through the interpreter."""
    a, weights = batch_inputs(t, seed=t, state=state,
                              widths=TRAINED_FORMS[form])
    assert float(a[4].max()) > 1.5
    results, got = trained_grads(form, a, weights)
    close(results, jax.vmap(gd.gated_delta_recurrence)(*a))
    _, want = _grads(jax.vmap(gd.gated_delta_recurrence), a, weights)
    assert max(worst(got, want).values()) < GRAD_RTOL, worst(got, want)


@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_a_state_rounded_to_bfloat16_at_a_chunk_s_boundary_fails(monkeypatch,
                                                                 form):
    """The control of the tolerance: the same comparison with the state a
    chunk starts from rounded to bfloat16, forward and backward (the next
    step down from the float32 state the configuration states). With the
    kernels in, the states rounded are those the forward's kernel keeps and
    the backward's walks back from (the forward's own, and the gradient of
    the state, stay float32 in VMEM): every gradient that reads a state
    fails, the queries' by 150 times the tolerance; the values' and the
    first state's read none (the rule is linear in the two together) and
    are the only ones that pass."""
    a, weights = batch_inputs(700, seed=5, widths=TRAINED_FORMS[form])
    _, want = _grads(jax.vmap(gd.gated_delta_recurrence), a, weights)
    low = lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    sound, sound_kernel = gd._a_chunk, gd._batch_forward_kernel
    monkeypatch.setattr(gd, "_a_chunk", lambda q, k, v, g, beta, s: sound(
        q, k, v, g, beta, low(s)))

    def rounded_kernel(*a):
        o, state, starts = sound_kernel(*a)
        return o, state, low(starts)

    monkeypatch.setattr(gd, "_batch_forward_kernel", rounded_kernel)
    with force_kernel_backend("reference" if form == "jnp" else "interpret"):
        _, got = _grads(gd.gated_delta_chunk, a, weights)
    errs = worst(got, want)
    if form == "kernel":
        assert errs.pop("v") < GRAD_RTOL and errs.pop("state") < GRAD_RTOL
        assert errs["q"] > 100 * GRAD_RTOL
        assert all(err > 2 * GRAD_RTOL for err in errs.values()), errs
    else:
        assert all(err > 3 * GRAD_RTOL for err in errs.values()), errs


def test_the_backward_s_kernel_gives_the_jnp_backward_s_six_gradients():
    """What the kernel is held to beside the recurrence: the backward it
    took the place of on a TPU, the chunks in reverse and ``jax.vjp`` of
    :func:`_a_chunk`, from the same kept operands and states and the same
    cotangents, to 1e-6 of a gradient's largest value (two chunks, so the
    state's gradient crosses a chunk's boundary in both); and it is chosen
    as the forward's is: one call named for the trace where the backend is
    not the reference's."""
    a, weights = batch_inputs(1024, seed=41, widths=TRAINED_FORMS["kernel"])
    saved = gd._batch_rule_fwd(*a)[1]
    with force_kernel_backend("interpret"):
        got = jax.jit(lambda *x: gd._batch_rule_bwd(*x))(saved, weights)
        calls = [e.params["name"] for e in _equations(jax.make_jaxpr(
            lambda *x: gd._batch_rule_bwd(*x))(saved, weights).jaxpr)
            if e.primitive.name == "pallas_call"]
    want = jax.jit(lambda *x: gd._batch_rule_bwd(*x))(saved, weights)
    assert calls == ["gated_delta_chunk_bwd"]
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *x: gd._batch_rule_bwd(*x))(saved, weights))
    assert max(worst(got, want).values()) < 1e-6, worst(got, want)


def test_the_trained_form_keeps_a_state_a_chunk_and_walks_them_back():
    """What the forward hands the backward: its operands and the state at
    each chunk's start (two for 700 positions), not a state a sub-chunk;
    and the backward is matrix products, with no loop a token: its loops
    are the chunks' and a chunk's sub-chunks'."""
    a, weights = batch_inputs(700)
    b, h, dk, dv = TRAINED
    _, saved = jax.eval_shape(gd._batch_rule_fwd, *(
        jnp.pad(x, ((0, 0), (0, 1024 - 700)) + ((0, 0),) * (x.ndim - 2))
        for x in a[:5]), a[5])
    assert saved[5].shape == (2, b, h, dk, dv)
    assert [x.shape[1] for x in saved[:5]] == [1024] * 5
    jaxpr = jax.make_jaxpr(jax.grad(_scalar(gd.gated_delta_chunk, weights),
                                    argnums=(0, 1, 2, 3, 4, 5)))(*a)
    lengths = {e.params["length"] for e in _equations(jaxpr.jaxpr)
               if e.primitive.name == "scan"}
    assert lengths == {2, gd.TRAIN_CHUNK // gd.SUB}


def test_the_inverse_s_backward_is_two_products_with_its_transpose():
    """``dA = -T^T dT T^T`` against the transposes of the six levels, on a
    system like a sub-chunk's (entries of a few tenths)."""
    a = 0.2 * jnp.tril(jax.random.normal(jax.random.PRNGKey(3),
                                         (2, 64, 64)), -1)
    w = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64))
    # (jitted: op by op the six levels' transposes take 12 s to dispatch)
    got = jax.jit(jax.grad(
        lambda a: jnp.sum(gd._inverse_with_transposes(a) * w)))(a)
    want = jax.jit(jax.grad(
        lambda a: jnp.sum(gd.unit_lower_inverse(a) * w)))(a)
    low = jnp.tril(jnp.ones((64, 64), bool), -1)
    want = jnp.where(low, want, 0.0)
    assert float(jnp.abs(jnp.where(low, got, 0.0) - want).max()
                 / jnp.abs(want).max()) < GRAD_RTOL


@pytest.mark.parametrize("form", ["jnp", "kernel_of_128"])
def test_a_batch_takes_key_heads_that_serve_several_value_heads(form):
    """q and k a key head, repeated for its value heads outside the rule's
    backward: the gradient of a key head is the sum over them (three value
    heads through the jnp chunks, two through the kernels)."""
    (q, k, v, g, beta, s), weights = batch_inputs(
        100, seed=9, widths=TRAINED_FORMS[form])
    narrow = (q[:, :, :1], k[:, :, :1], v, g, beta, s)
    got = (_scalar(gd.gated_delta_chunk, weights)(*narrow),
           trained_grads(form, narrow, weights)[1])
    rep = lambda x: jnp.repeat(x[:, :, :1], v.shape[2], axis=2)  # noqa: E731
    want = _grads(jax.vmap(gd.gated_delta_recurrence),
                  (rep(q), rep(k), v, g, beta, s), weights)
    assert float(abs(got[0] - want[0])) < 1e-3 * float(abs(want[0]))
    for x, y in zip(got[1][:2], want[1][:2]):
        y = y.sum(axis=2, keepdims=True)
        assert float(jnp.abs(x - y).max() / jnp.abs(y).max()) < GRAD_RTOL


def test_the_trained_forward_s_kernel_is_the_recurrence_at_padded_widths():
    """On a TPU the forward is one call of the chunk kernel with the batch
    folded into the heads, keys of 96 and values of 192 zero-padded to 128 and 256
    and six heads to eight: its body through the interpreter gives the
    recurrence's outputs, states and (the backward being its own kernel
    from the forward kernel's kept states) gradients; off a TPU, and where
    eight padded states would not fit a grid step, the jnp body runs."""
    a, weights = batch_inputs(700, seed=77, widths=TRAINED_FORMS["kernel"])
    wide = tuple(jnp.zeros(shape) for shape in (
        (1, 64, 2, 512), (1, 64, 2, 512), (1, 64, 2, 1024), (1, 64, 2),
        (1, 64, 2), (1, 2, 512, 1024)))
    with force_kernel_backend("interpret"):
        # (a fresh function a trace: what was traced under another backend
        # is not found again)
        calls = [e for e in _equations(jax.make_jaxpr(
            lambda *a: gd.gated_delta_chunk(*a))(*a).jaxpr)
            if e.primitive.name == "pallas_call"]
    results, got = trained_grads("kernel", a, weights)
    with force_kernel_backend("interpret"):
        wide = jax.make_jaxpr(lambda *a: gd.gated_delta_chunk(*a))(*wide)
    assert len(calls) == 1 and calls[0].params["name"] == "gated_delta_chunk"
    # one call for both chunks of both sequences, and a third result: the
    # states the two chunks start from
    assert [v.aval.shape for v in calls[0].invars[:3]] == [
        (1024, 8 * 128), (1024, 8 * 128), (1024, 8 * 256)]
    assert [v.aval.shape for v in calls[0].outvars] == [
        (1024, 8 * 256), (8, 128, 256), (2, 8, 128, 256)]
    assert "pallas_call" not in str(wide)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: gd.gated_delta_chunk(*a))(*a))
    close(results, jax.vmap(gd.gated_delta_recurrence)(*a))
    _, want = _grads(jax.vmap(gd.gated_delta_recurrence), a, weights)
    assert max(worst(got, want).values()) < GRAD_RTOL, worst(got, want)


def test_what_no_form_takes_is_refused_where_it_is_traced():
    """A batch with a decay a key channel (chunked one sequence a call),
    and states of a grid step that no VMEM holds: a message at trace time,
    not a failure inside Mosaic."""
    (q, k, v, g, beta, s), _ = batch_inputs(70)
    with pytest.raises(ValueError, match="a batch of sequences takes a "
                                         "decay a head"):
        gd.gated_delta_chunk(q, k, v, g[..., None] * jnp.ones(12), beta, s)
    big = inputs(64, form=(8, 8, 256, 1024))
    with force_kernel_backend("interpret"), \
            pytest.raises(ValueError, match="MiB of states a grid step"):
        jax.eval_shape(gd.gated_delta_chunk, *big)
