"""A decode step that carries a prefill chunk (``ServedModel.mixed_burst``),
held once for every model that offers the entry: the step against the chunk
and then the step, a burst against its chunks and then the burst, and the
engine with and without the entry.

A model joins by adding its fixture to ``MODELS`` (its tiny configuration,
the leaves of its cache, its tolerances, what its engine's counters must
add up to) and its rides to ``RIDES``: the tests' bodies know no model. What
a model's halves compute is its own file's business (tests/test_llm.py,
tests/test_lfm2.py, tests/test_deepseek.py).
"""

import dataclasses
import functools
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_served_model import (
    chunks_that_ride_leave_every_answer_as_it_was,
    counts_arrive_with_the_program_behind_their_chunk)

from ray_tpu.ops.kernels import force_kernel_backend


@dataclasses.dataclass(frozen=True)
class Fixture:
    """A model that offers ``mixed_burst``, at a small size on the CPU."""
    serving: Any                  # its llm/<name>_serving module
    cfg: Any
    params: Any
    tokens: np.ndarray            # 48 ids
    # the cache's leaves that hold a row a position, with the positions'
    # axis, and those that hold a state of fixed size a slot
    rows: dict[str, int]
    states: tuple[str, ...] = ()
    # ``assert_allclose``'s tolerances for the lines' logits
    logits: dict[str, float] = dataclasses.field(
        default_factory=lambda: {"atol": 1e-4})
    # the engine's drive: its configuration and what its two last
    # ``stats()`` (with the entry, without) must hold beside the drive's own
    engine_cfg: Any = None
    engine_holds: Callable = lambda stats: None

    @property
    def counters(self) -> tuple[str, ...]:
        return self.serving.SERVED.counters


def _tokens(key: int, low: int, cfg) -> np.ndarray:
    return np.asarray(jax.random.randint(jax.random.PRNGKey(key), (48,), low,
                                         cfg.vocab_size), np.int32)


def _routed_layers_counted_once(cfg, local: Callable):
    """What the engine's counters of a model with routed layers hold: a
    routed layer is counted once a program's step, a rider's with the step
    that carried it; ``local(stats)`` is the model's own line on its
    picks."""
    def holds(stats):
        assert stats["moe_layer_steps"] == cfg.num_routed_layers * (
            stats["prefill_chunks"] - stats["prefill_chunks_riding"]
            + stats["decode_steps"])
        assert local(stats)
    return holds


@functools.cache
def _llama() -> Fixture:
    from ray_tpu.llm import llama_serving
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny()

    def no_request_fails(stats):
        assert stats["requests_failed"] == 0

    # the tree as the engine places it: the fused leaf beside the three
    params = llama_serving.program_params(
        cfg, init_params(cfg, jax.random.PRNGKey(0)))
    return Fixture(
        llama_serving, cfg, params, _tokens(5, 1, cfg), rows={"k": 3, "v": 3},
        logits={"rtol": 2e-4, "atol": 2e-4},
        engine_cfg=dataclasses.replace(cfg, vocab_size=512),
        engine_holds=no_request_fails)


@functools.cache
def _lfm2() -> Fixture:
    from ray_tpu.llm import lfm2_serving
    from ray_tpu.models import lfm2

    cfg = lfm2.Lfm2Config.tiny()
    return Fixture(
        lfm2_serving, cfg, lfm2.init_params(cfg, jax.random.PRNGKey(0)),
        _tokens(1, 259, cfg), rows={"kv": 3}, states=("conv",),
        engine_cfg=cfg,
        engine_holds=_routed_layers_counted_once(
            cfg, lambda s: s["moe_picks"] == s["moe_picks_local"] > 0))


@functools.cache
def _deepseek(shards: int, shard: int) -> Fixture:
    from ray_tpu.llm import deepseek_serving
    from ray_tpu.models import deepseek

    cfg = deepseek.DeepseekV2Config.tiny(expert_shards=shards,
                                         expert_shard=shard)
    return Fixture(
        deepseek_serving, cfg,
        deepseek.init_params(cfg, jax.random.PRNGKey(0)),
        _tokens(1, 0, cfg), rows={"latent": 2}, logits={"atol": 5e-5},
        engine_cfg=deepseek.DeepseekV2Config.tiny(expert_shards=4,
                                                  max_seq_len=256),
        engine_holds=_routed_layers_counted_once(
            cfg, lambda s: 0 < s["moe_picks_local"] < s["moe_picks"]))


MODELS = {
    "llama": _llama,
    "lfm2": _lfm2,
    "deepseek uncut": functools.partial(_deepseek, 1, 0),
    "deepseek group-1-of-4": functools.partial(_deepseek, 4, 1),
}
# The engine is driven once a model, not once a share of its experts.
DRIVEN = {"llama": _llama, "lfm2": _lfm2,
          "deepseek": functools.partial(_deepseek, 4, 1)}


def _line_prefilled(m: Fixture, cache, prompt, slot, upto=None):
    """``prompt``'s first ``upto`` tokens (all of them by default) through
    ``prefill_chunk`` in one chunk, into ``slot``'s line."""
    upto = len(prompt) if upto is None else upto
    if upto:
        cache, *_ = m.serving.prefill_chunk(
            m.cfg, m.params, cache, jnp.asarray(prompt[:upto], jnp.int32),
            jnp.int32(0), jnp.int32(len(prompt)), jnp.int32(slot))
    return cache


# The chunk is 16 rows of slot 1's prompt; a line that decodes holds a
# prompt of its own. (cached rows, the prompt's length, decoding slots,
# kernel backend)
_START = (0, 40, [0], "reference")
_BETWEEN = (16, 40, [0, 2], "reference")
_NO_LINE = (16, 40, [], "reference")
_KERNELS = (16, 40, [0, 2], "interpret")
RIDES = {
    "llama": {
        "a full chunk at a prompt's start beside one line": _START,
        "a full chunk after cached rows between two lines": _BETWEEN,
        "a chunk with a padded tail": (16, 28, [2], "reference"),
        "cached rows that are no multiple of the chunk, as after an adopted "
        "prefix": (5, 40, [0, 2], "reference"),
        "a slot that does not decode beside the chunk's": (16, 40, [2],
                                                          "reference"),
        "beside no line at all": _NO_LINE,
        "through the kernels' bodies": _KERNELS,
        "a padded chunk after an odd prefix through the kernels' bodies":
            (5, 17, [0], "interpret"),
    },
    "lfm2": {
        "at a prompt's start beside one line": _START,
        "after cached rows beside one line": (16, 40, [2], "reference"),
        "between two decoding neighbours": _BETWEEN,
        "beside no line at all": _NO_LINE,
    },
    "deepseek": {
        "a full chunk at a prompt's start beside one line": _START,
        "a full chunk after cached rows between two lines": _BETWEEN,
        "a chunk with padding": (16, 28, [2], "reference"),
        "beside no line at all": _NO_LINE,
        "through the kernels' bodies": _KERNELS,
        "a padded chunk through the kernels' bodies":
            (16, 28, [0], "interpret"),
    },
}
STEPS = [pytest.param(model, ride, id=f"{model}: {name}")
         for model in MODELS
         for name, ride in RIDES[model.split()[0]].items()]


@pytest.mark.parametrize("model,ride", STEPS)
def test_a_step_that_carries_a_chunk_is_the_chunk_and_then_the_step(model,
                                                                    ride):
    """``_mixed_impl`` on [chunk rows; a row a line] against
    ``prefill_chunk`` on the chunk's slot and then ``decode_step`` on the
    lines, from the same cache: every leaf of the cache (the chunk's rows in
    its slot, a line's new one at its position, a slot that neither prefills
    nor decodes as it was, every slot's state), the lines' logits, and a
    routed model's counts, a routed layer counted once for both. Under
    ``interpret`` the mixed step runs the kernels' bodies (the two programs
    apart are traced once a shape, whatever backend that was under)."""
    m = MODELS[model]()
    cfg, params, serving, t = m.cfg, m.params, m.serving, m.tokens
    kv_len, length, lines, backend = ride
    slots, chunk = 3, 16
    held = {0: t[3:23], 2: t[5:38]}
    with force_kernel_backend(backend):
        # every line holds junk, then what was prefilled into it
        cache = jax.tree.map(lambda a: jnp.full_like(a, 3.0),
                             serving.SERVED.init_cache(cfg, slots, 64))
        cache = _line_prefilled(m, cache, t[:length], 1, kv_len)
        for slot in lines:
            cache = _line_prefilled(m, cache, held[slot], slot)
        write = jnp.asarray([slot in lines for slot in range(slots)])
        tok = jnp.asarray([int(t[35 + slot]) for slot in range(slots)])
        pos = jnp.asarray([len(held[slot]) if slot in lines else 0
                           for slot in range(slots)], jnp.int32)
        rows = np.zeros(chunk, np.int32)
        take = min(chunk, length - kv_len)
        rows[:take] = t[kv_len:kv_len + take]
        rider = (jnp.asarray(rows), jnp.int32(kv_len), jnp.int32(length),
                 jnp.int32(1))
        before = jax.tree.map(np.asarray, cache)
        apart, _, *chunk_counts = serving.prefill_chunk(
            cfg, params, jax.tree.map(jnp.copy, cache), *rider)
        apart, want_logits, *step_counts = serving.decode_step(
            cfg, params, apart, tok, pos, write)
        # a function of its own: traced here, under this backend
        got, logits, *counts = jax.jit(
            lambda *a: serving._mixed_impl(cfg, *a))(
            params, cache, tok, pos, write, *rider)
    assert logits.shape == (slots, cfg.vocab_size)
    assert set(got) == set(m.rows) | set(m.states)
    for leaf in got:
        np.testing.assert_allclose(np.asarray(got[leaf]),
                                   np.asarray(apart[leaf]), atol=1e-5,
                                   err_msg=leaf)
    idle = set(range(slots)) - set(lines) - {1}
    for leaf in m.states:
        for slot in idle:
            np.testing.assert_array_equal(np.asarray(got[leaf])[:, slot],
                                          before[leaf][:, slot])
    for leaf, axis in m.rows.items():
        new = np.asarray(got[leaf])

        def moved(slot, positions):
            """How far ``slot``'s rows at ``positions`` are from what the
            line held."""
            at = [np.take(a[:, slot], positions, axis=axis - 1)
                  for a in (new, before[leaf])]
            return np.abs(at[0] - at[1]).max()

        # the rows are there: the chunk's in its slot, a line's at its
        # position; the slots that wrote nothing hold what they held
        assert moved(1, range(kv_len, kv_len + take)) > 0.1
        assert moved(1, range(kv_len + chunk, 64)) == 0
        for slot in lines:
            assert moved(slot, [len(held[slot])]) > 0.1
        for slot in idle:
            np.testing.assert_array_equal(new[:, slot],
                                          before[leaf][:, slot])
    np.testing.assert_allclose(np.asarray(logits)[lines],
                               np.asarray(want_logits)[lines], **m.logits)
    if not m.counters:
        return
    counts, chunk_counts, step_counts = (
        dict(zip(m.counters, (int(n) for n in c)))
        for c in (counts[0], chunk_counts[0], step_counts[0]))
    nm = cfg.num_routed_layers
    for key in {"moe_picks", "moe_picks_local", "moe_picks_zero",
                "moe_tokens_local"} & set(m.counters):
        assert counts[key] == chunk_counts[key] + step_counts[key], key
    # a padded row and a line that does not decode are routed nowhere
    assert counts["moe_picks"] == (take + len(lines)) \
        * cfg.num_experts_per_tok * nm
    # one layer-step a routed layer, where the two programs count two
    assert counts["moe_layer_steps"] == nm
    assert chunk_counts["moe_layer_steps"] + step_counts["moe_layer_steps"] \
        == 2 * nm
    # an expert both touched is touched, and fetched, once
    assert max(chunk_counts["moe_experts_touched"],
               step_counts["moe_experts_touched"]) \
        <= counts["moe_experts_touched"] \
        <= chunk_counts["moe_experts_touched"] \
        + step_counts["moe_experts_touched"]
    assert counts["moe_experts_touched"] <= counts["moe_tiles"]


@pytest.mark.parametrize("riders", [0, 2, 4], ids=lambda n: f"{n} riders")
@pytest.mark.parametrize("model", list(MODELS))
def test_a_mixed_burst_is_its_chunks_and_then_the_burst(model, riders):
    """Consecutive chunks of one prompt and a chunk of another ride the
    first steps of one burst, each with its own slot, cached length and
    length; the steps after them carry none (all of them, with no rider:
    the program is then ``decode_burst``). Tokens, rows and states are those
    of the chunks through ``prefill_chunk`` and then the burst; a routed
    model counts four steps' layer-steps whatever rode, and every rider's
    picks."""
    m = MODELS[model]()
    cfg, params, serving, t = m.cfg, m.params, m.serving, m.tokens
    slots, chunk = 4, 8
    cache = _line_prefilled(m, serving.SERVED.init_cache(cfg, slots, 64),
                            t[3:23], 0)
    # (slot, cached rows, the prompt): slot 1's three chunks, slot 3's first
    prompts = {1: t[:28], 3: t[7:40]}
    rode = [(1, 0), (1, 8), (3, 0), (1, 16)][:riders]
    rows = np.zeros((4, chunk), np.int32)
    at, kv_lens, lengths = (np.zeros((4,), np.int32) for _ in range(3))
    apart = jax.tree.map(jnp.copy, cache)
    for j, (slot, kv_len) in enumerate(rode):
        rows[j] = prompts[slot][kv_len:kv_len + chunk]
        at[j], kv_lens[j], lengths[j] = slot, kv_len, len(prompts[slot])
        apart, *_ = serving.prefill_chunk(
            cfg, params, apart, jnp.asarray(rows[j]), jnp.int32(kv_len),
            jnp.int32(lengths[j]), jnp.int32(slot))
    write = jnp.asarray([True, False, False, False])
    tok = jnp.zeros((slots,), jnp.int32).at[0].set(int(t[30]))
    pos = jnp.zeros((slots,), jnp.int32).at[0].set(20)
    burst = (tok, pos, write, jnp.zeros((slots,)), jnp.ones((slots,)),
             jax.random.PRNGKey(0))
    apart, want, *apart_counts = serving.decode_burst(cfg, params, apart,
                                                      *burst, 4, False)
    got, toks, *counts = serving.mixed_burst(
        cfg, params, cache, *burst,
        tuple(jnp.asarray(a) for a in (rows, at, kv_lens, lengths))
        + (jnp.int32(riders),), 4, False)
    # (a slot that does not decode samples from logits that mean nothing)
    np.testing.assert_array_equal(np.asarray(toks[:, 0]),
                                  np.asarray(want[:, 0]))
    for leaf in got:
        np.testing.assert_allclose(np.asarray(got[leaf]),
                                   np.asarray(apart[leaf]), atol=1e-5,
                                   err_msg=leaf)
    if m.counters:
        nm = cfg.num_routed_layers
        steps, picks = (m.counters.index(key)
                        for key in ("moe_layer_steps", "moe_picks"))
        assert int(counts[0][steps]) == int(apart_counts[0][steps]) == 4 * nm
        assert int(counts[0][picks]) == int(apart_counts[0][picks]) \
            + riders * chunk * cfg.num_experts_per_tok * nm


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("model", list(DRIVEN))
def test_the_device_is_told_which_steps_of_a_burst_carried_a_chunk(model):
    """The riding loop of a lowered ``mixed_burst`` carries ``mixed_step``
    (``tracing.STEP_KINDS``) on the path of every operation, inside
    ``stack`` and around the step's own parts, and the loop of plain steps
    on none: a device trace has one event a program, and the path is what
    tells a step that took a chunk from one that did not."""
    import re

    m = DRIVEN[model]()
    cfg, serving = m.cfg, m.serving
    slots, chunk, steps = 4, 8, 4
    i32 = jnp.int32
    args = (m.params, serving.SERVED.init_cache(cfg, slots, 64),
            jnp.zeros((slots,), i32), jnp.zeros((slots,), i32),
            jnp.ones((slots,), bool), jnp.zeros((slots,)),
            jnp.ones((slots,)), jax.random.PRNGKey(0),
            (jnp.zeros((steps, chunk), i32),
             *(jnp.zeros((steps,), i32) for _ in range(3)), i32(2)))
    text = serving.mixed_burst.lower(cfg, *args, steps, False).as_text(
        debug_info=True)
    paths = set(re.findall(r'loc\("(jit\(decode_burst\)/[^"]*)"', text))
    # the burst's two loops, by what their operations' paths begin with
    loops = {}
    for path in paths:
        if "/while/" in path:
            loops.setdefault(path.split("/while/")[0], []).append(path)
    kind, plain = "jit(decode_burst)/stack/mixed_step", \
        "jit(decode_burst)/stack"
    assert set(loops) == {kind, plain}
    # the kind is opened there and nowhere else, and a step's parts lie
    # under it as they lie under the plain loop
    assert all(p.startswith(kind + "/while") for p in paths
               if "mixed_step" in p)
    for loop in (kind, plain):
        for part in ("embed", "attn", "head", "sample"):
            assert any(re.search(rf"/while/body/(.*/)?{part}/", p)
                       for p in loops[loop]), (loop, part)
    # and it is the riding loop that has it: the loop whose body joins the
    # chunk's rows to the lines' (served.mixed_rows)
    (burst,) = jax.make_jaxpr(
        lambda *a: serving.mixed_burst(cfg, *a, steps, False))(*args).eqns
    whiles = [e for e in burst.params["jaxpr"].eqns
              if e.primitive.name == "while"]
    assert [str(e.source_info.name_stack) for e in whiles] == [
        "stack/mixed_step", "stack"]
    joined = [any(e.primitive.name == "concatenate"
                  and e.outvars[0].aval.shape == (chunk + slots,)
                  for e in _eqns(w.params["body_jaxpr"].jaxpr))
              for w in whiles]
    assert joined == [True, False]


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["look-ahead", "serial"])
@pytest.mark.parametrize("model", list(DRIVEN))
def test_chunks_that_ride_leave_every_answer_as_it_was(monkeypatch, model,
                                                       pipeline):
    """Long prompts arrive while a line decodes: with ``mixed_burst`` their
    full chunks ride the line's bursts, never a prompt's last chunk, and
    every request gets token for token what it gets from the engine whose
    model offers no such program (tests/test_served_model.py holds the
    drive), the model's own counters adding up in both."""
    m = DRIVEN[model]()
    for stats in chunks_that_ride_leave_every_answer_as_it_was(
            monkeypatch, m.serving, m.engine_cfg, pipeline):
        m.engine_holds(stats)


def _routed(model):
    """(serving module, configuration with lines of 256) of a model with
    counters of its own."""
    m = DRIVEN[model]()
    return m.serving, dataclasses.replace(m.engine_cfg, max_seq_len=256)


# What ``stats()`` held of each model's own counters, in ``counters``' order,
# after counts_arrive_with_the_program_behind_their_chunk's requests on the
# tree before a chunk's counts left its request (recorded there, PR 65's):
# (its mixed_burst offered, the look-ahead on) -> the totals.
_TOTALS_BEFORE = {
    "lfm2": {(True, True): [1312, 1312, 0, 353, 108, 353],
             (True, False): [1280, 1280, 0, 329, 96, 329],
             (False, True): [1312, 1312, 0, 377, 120, 379],
             (False, False): [1280, 1280, 0, 345, 104, 347]},
    "deepseek": {(True, True): [1266, 331, 0, 129, 124, 131, 220],
                 (True, False): [1266, 331, 0, 132, 124, 134, 220],
                 (False, True): [1266, 331, 0, 135, 130, 137, 220],
                 (False, False): [1266, 331, 0, 139, 130, 141, 220]},
}


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["look-ahead", "serial"])
@pytest.mark.parametrize("mixed", [True, False],
                         ids=["chunks ride", "no chunk rides"])
@pytest.mark.parametrize("model", list(_TOTALS_BEFORE))
def test_a_chunk_s_counts_come_with_the_program_queued_behind_it(
        monkeypatch, model, mixed, pipeline):
    """The model's own counts of a prefill chunk reach ``stats()`` with the
    first program dispatched after the chunk whose result the host reads (a
    burst, a first token's sample, a single step), in that program's fetch:
    never with one dispatched before it, whose fetch would then wait a
    burst longer than its tokens take, and every count once. What a run
    adds up to is what it added up to while a prompt's counts waited for
    its first token."""
    serving, cfg = _routed(model)
    reads, ticks, made, entries, stats = \
        counts_arrive_with_the_program_behind_their_chunk(
            monkeypatch, serving, cfg, mixed, pipeline)
    # every count a program returned was fetched, and once
    assert sorted(at for _, at, _, _ in reads) == [at for _, at, _ in made]
    for kind, at, entry, _ in reads:
        later = [e for e in entries if e > at]
        if kind == "chunk":
            # with the entry that went in flight next, and no older one
            assert entry == min(later), (at, entry, entries)
        else:
            # a program's own: with its tokens
            assert entry == min(e for e in entries if e >= at)
    assert any(kind == "chunk" for kind, *_ in reads)
    counters = serving.SERVED.counters
    assert [stats[k] for k in counters] == list(
        sum(values for _, _, values in made)) \
        == _TOTALS_BEFORE[model][mixed, pipeline]
    # between two ticks ``stats()`` holds every routed layer of what was
    # fetched, chunks that rode with their steps
    nm = cfg.num_routed_layers
    for n, _, steps_read, then in ticks:
        chunks_read = sum(kind == "chunk" for kind, *_ in reads[:n])
        assert then["moe_layer_steps"] == nm * (chunks_read + steps_read)


@pytest.mark.parametrize("pipeline,chunks", [(False, 3), (True, 7)],
                         ids=["serial", "look-ahead"])
def test_stats_between_two_chunks_of_a_prompt_hold_the_earlier_chunk_s_counts(
        monkeypatch, pipeline, chunks):
    """A line decodes beside a long prompt whose chunks go out call for
    call: once the burst queued behind the prompt's first chunk is read,
    ``moe_layer_steps`` has grown by that chunk's layers, and the prompt's
    last chunk is not dispatched yet. Serial, a tick reads the burst it
    dispatched, so three chunks show it; under the look-ahead the burst
    behind a chunk is read two ticks after it (the last burst stays in
    flight), of two chunks each, so the prompt has seven."""
    serving, cfg = _routed("deepseek")
    reads, ticks, *_ = counts_arrive_with_the_program_behind_their_chunk(
        monkeypatch, serving, cfg, mixed=False, pipeline=pipeline,
        chunks=chunks)
    (first,) = [i for i, (*_, first) in enumerate(reads) if first]
    n, last_dispatched, steps_read, stats = next(
        t for t in ticks if t[0] > first)
    assert not last_dispatched
    # it came with a burst, the program queued behind it
    kinds = {at: kind for kind, at, _, _ in reads}
    fetched_with = reads[first][2]
    assert kinds[max(at for at in kinds if at < fetched_with)] == "burst"
    chunks_read = sum(kind == "chunk" for kind, *_ in reads[:n])
    assert chunks_read >= 2     # the line's own prompt, and this one
    assert stats["moe_layer_steps"] == cfg.num_routed_layers * (
        chunks_read + steps_read)


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["look-ahead", "serial"])
def test_alone_in_the_engine_a_prompt_s_counts_come_with_its_first_token(
        monkeypatch, pipeline):
    """No program goes in flight between the chunks of a prompt that has
    the engine to itself: all its chunks' counts come with the first
    token's sample, as they did."""
    serving, cfg = _routed("lfm2")
    reads, ticks, _, entries, _ = \
        counts_arrive_with_the_program_behind_their_chunk(
            monkeypatch, serving, cfg, pipeline=pipeline, beside=False)
    assert [kind for kind, *_ in reads[:3]] == ["chunk"] * 3
    assert {entry for _, _, entry, _ in reads[:3]} == {entries[0]}
    assert all(entries[0] > at for _, at, _, _ in reads[:3])
    # nothing is counted until the prompt's last chunk is out
    assert all(last for n, last, _, _ in ticks if n)
    assert [t[3]["moe_layer_steps"] for t in ticks if not t[0]] == [0] * sum(
        not t[0] for t in ticks)
