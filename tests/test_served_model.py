"""What the engine holds a served model to, for every model it serves.

The scheduler (llm/engine.py) reaches a model only through
``served.ServedModel``: four jitted programs that take the slot cache
donated and give it back, under the names the benchmark's readers look for
in a device trace (``jit_prefill_chunk``, ``jit_decode_step``,
``jit_decode_burst``), found for a configuration by one table
(``config.SERVING_MODULES``). The engine has one KV layout, slot lines;
``kv_block_size`` is a field that accepts 0.
"""

import ast
import dataclasses
import importlib
import pathlib
import re
import typing

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams
from ray_tpu.llm import deepseek_serving, granite_serving, keye_serving
from ray_tpu.llm import lfm2_serving
from ray_tpu.llm import llama_serving
from ray_tpu.llm import ling_serving, longcat_serving, mimo_serving
from ray_tpu.llm import ouro_serving, phi4flash_serving, qwen3_next_serving
from ray_tpu.llm import sdar_serving
from ray_tpu.llm.config import SERVING_MODULES, ModelConfig
from ray_tpu.llm.served import ServedModel, served_model
from ray_tpu.models.deepseek import DeepseekV2Config
from ray_tpu.models.granite import GraniteConfig
from ray_tpu.models.keye import KeyeConfig
from ray_tpu.models.lfm2 import Lfm2Config
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.longcat import LongcatConfig
from ray_tpu.models.ling import LingConfig
from ray_tpu.models.mimo import MimoConfig
from ray_tpu.models.ouro import OuroConfig
from ray_tpu.models.phi4flash import Phi4FlashConfig
from ray_tpu.models.qwen3_next import Qwen3NextConfig
from ray_tpu.models.sdar import SdarConfig

SLOTS, MAX_SEQ, CHUNK = 3, 64, 16


@pytest.fixture(scope="module", autouse=True)
def release_the_compiled_programs():
    """After the module: its programs are its own (twelve models at sizes no
    other file uses), and a compiled program keeps its memory mappings for
    as long as JAX's caches hold it; a worker of the suite that never lets
    one go runs into ``vm.max_map_count`` and XLA's CPU compile dies under
    whichever test comes next (tests/test_granite.py, PERF.md section 7)."""
    yield
    jax.clear_caches()


def _llama():
    return llama_serving, dataclasses.replace(LlamaConfig.tiny(),
                                              vocab_size=512)


def _longcat():
    return longcat_serving, LongcatConfig.tiny(expert_shards=2,
                                               max_seq_len=MAX_SEQ)


def _ouro():
    return ouro_serving, OuroConfig.tiny(max_seq_len=MAX_SEQ)


def _lfm2():
    return lfm2_serving, Lfm2Config.tiny(max_seq_len=MAX_SEQ)


def _sdar():
    return sdar_serving, SdarConfig.tiny(max_seq_len=MAX_SEQ)


def _deepseek():
    return deepseek_serving, DeepseekV2Config.tiny(expert_shards=2,
                                                   max_seq_len=MAX_SEQ)


def _qwen3_next():
    return qwen3_next_serving, Qwen3NextConfig.tiny(expert_shards=2,
                                                    max_seq_len=MAX_SEQ)


def _phi4flash():
    return phi4flash_serving, Phi4FlashConfig.tiny(max_seq_len=MAX_SEQ)


def _mimo():
    return mimo_serving, MimoConfig.tiny(max_seq_len=MAX_SEQ)


def _ling():
    return ling_serving, LingConfig.tiny(expert_shards=2,
                                         max_seq_len=MAX_SEQ)


def _granite():
    return granite_serving, GraniteConfig.tiny(expert_shards=2,
                                               max_seq_len=MAX_SEQ)


def _keye():
    # 8 positions a query, fewer than a line holds
    return keye_serving, KeyeConfig.tiny(expert_shards=2,
                                         max_seq_len=MAX_SEQ)


# The models of a token a step, and all of them.
MODELS = dict(argvalues=[_llama, _longcat, _ouro, _lfm2, _deepseek,
                         _qwen3_next, _phi4flash, _mimo, _ling, _granite,
                         _keye],
              ids=["llama", "longcat", "ouro", "lfm2", "deepseek",
                   "qwen3_next", "phi4flash", "mimo", "ling", "granite",
                   "keye"])
ALL_MODELS = dict(argvalues=MODELS["argvalues"] + [_sdar],
                  ids=MODELS["ids"] + ["sdar"])


def _arguments(program, params, step_positions=1, pending_step=False):
    """The arguments of ``program`` after (cfg, [params,] cache): slot 0
    holds CHUNK rows, slots 0 and 1 decode. A burst's tokens are [slots],
    or [slots, K] all open (-1) where a step is a block of K positions,
    with the step before it (slot 0 has one pending, the prompt's last)
    where the model takes one in (``ServedModel.pending_step``)."""
    i32 = jnp.int32
    slots = jnp.zeros((SLOTS,), i32)
    if step_positions > 1:
        slots = jnp.full((SLOTS, step_positions), -1, i32)
    if pending_step:
        slots = (slots, jnp.zeros((SLOTS, step_positions), i32),
                 jnp.array([True, False, False]))
    # (a block's start is a multiple of its length)
    positions = jnp.array([CHUNK, 1 if step_positions == 1 else 0, 0], i32)
    write = jnp.array([True, True, False])
    return {
        "prefill_chunk": (params, jnp.arange(CHUNK, dtype=i32), i32(0),
                          i32(CHUNK), i32(0)),
        "decode_step": (params, slots, positions, write),
        "decode_burst": (params, slots, positions, write,
                         jnp.zeros((SLOTS,), jnp.float32),
                         jnp.ones((SLOTS,), jnp.float32),
                         jax.random.PRNGKey(0), 2, False),
        "copy_prefix_kv": (i32(0), i32(2)),
    }[program]


@pytest.mark.parametrize("program", ["prefill_chunk", "decode_step",
                                     "decode_burst", "copy_prefix_kv"])
@pytest.mark.parametrize("model", **ALL_MODELS)
def test_a_program_keeps_its_name_and_gives_the_donated_cache_back(model,
                                                                    program):
    module, cfg = model()
    served = served_model(cfg)
    if getattr(served, program) is None:
        # Only a model whose prefix cannot be adopted from a line may lack
        # the program that copies one, and only one whose step samples on
        # the device the single step.
        assert (program == "copy_prefix_kv" and not served.prefix_from_line
                or program == "decode_step" and served.step is not None)
        return
    params = served.init_params(cfg, jax.random.PRNGKey(0))
    cache = served.init_cache(cfg, SLOTS, MAX_SEQ)
    went_in = jax.tree.map(lambda a: (a.shape, a.dtype), cache)
    head = (cfg, cache)
    rest = _arguments(program, params,
                      served.step(cfg)[0] if served.step else 1,
                      served.pending_step)
    if program != "copy_prefix_kv":
        head, rest = (cfg, rest[0], cache), rest[1:]

    # The entry the scheduler calls is the jitted program itself, and it
    # carries the name a trace shows: Llama's single step too.
    assert getattr(served, program) is getattr(module, program)
    lowered = getattr(served, program).lower(*head, *rest)
    named = re.search(r"module @(\w+)", lowered.as_text()).group(1)
    assert named == f"jit_{program}"

    # The cache is the first result.
    out = getattr(served, program)(*head, *rest)
    came_back = out
    if program != "copy_prefix_kv":
        # (cache, tokens or logits), and the model's counts if it has any
        came_back = out[0]
        assert len(out) == 2 + bool(served.counters)
    assert jax.tree.structure(came_back) == jax.tree.structure(cache)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), came_back) == went_in
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(cache))


def _cumsums(jaxpr, in_loop=False):
    """(outside, inside): cumulative sums of a jaxpr outside and inside its
    loops, through every nested jaxpr."""
    outside = inside = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cumsum":
            outside, inside = outside + (not in_loop), inside + in_loop
        loop = in_loop or eqn.primitive.name in ("scan", "while")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            o, i = _cumsums(sub, loop)
            outside, inside = outside + o, inside + i
    return outside, inside


@pytest.mark.parametrize("model",
                         argvalues=[_llama, _ouro, _lfm2, _phi4flash, _mimo],
                         ids=["llama", "ouro", "lfm2", "phi4flash", "mimo"])
def test_a_decode_step_plans_its_walk_once_before_the_layer_loop(model):
    """``decode_attention`` walks the live blocks of every line by a plan
    that depends on the lengths alone, so the step builds it once
    (``decode_plan``: a cumulative sum over the slots) and every layer, or
    every one of a looped stack's 192 cache lines, is handed the same. A
    model with lines of two lengths (a full line and rings of a window)
    plans twice, once a length: the full line's eight readers share one
    walk, the eight rings the other; where the two kinds of line also have
    different head counts (MiMo-V2), it is still two: a plan knows lengths
    and blocks, not heads."""
    from ray_tpu.ops.kernels import force_kernel_backend

    module, cfg = model()
    served = served_model(cfg)
    params = jax.eval_shape(lambda: served.init_params(
        cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: served.init_cache(cfg, SLOTS, MAX_SEQ))
    tokens, positions, write = _arguments("decode_step", None)[1:]
    if module in (lfm2_serving, phi4flash_serving, mimo_serving):
        # one token a slot (and, for the first and the last, a router's
        # plans)
        def step(p, c):
            return module._decode_impl(cfg, p, c, tokens, positions, write)
    else:
        def step(p, c):
            return module._multi_token_impl(cfg, p, c, tokens[:, None],
                                            positions, write)
    with force_kernel_backend("interpret"):
        jaxpr = jax.make_jaxpr(step)(params, cache)
    outside, inside = _cumsums(jaxpr.jaxpr)
    # the routed layer's dispatch plan sums too, inside the layer loop: its
    # picks differ a layer; the walk of the cache is the one outside
    assert outside == (2 if module in (phi4flash_serving, mimo_serving)
                       else 1)
    assert inside == 0 or module in (lfm2_serving, mimo_serving)


def test_the_engine_has_one_kv_layout_and_refuses_the_block_pool():
    with pytest.raises(ValueError, match=r"block pool.*R3"):
        LLMEngine(LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=64,
                            kv_block_size=16))
    assert not hasattr(LLMConfig(), "kv_num_blocks")


def test_stats_after_mixed_requests_carry_the_slot_layout_only():
    """Long and short prompts, greedy and sampled, more requests than
    slots: every request ends, no key of the block pool is left in stats(),
    and the decode kernel never reads more of the lines than they hold."""
    eng = LLMEngine(LLMConfig(model="tiny", max_num_seqs=3, max_seq_len=128,
                              prefill_chunk=32, decode_burst=4))
    try:
        prompts = [list(range(260, 330)), [261, 262, 263],
                   list(range(260, 300)), list(range(300, 345)), [270] * 9]
        sampling = [SamplingParams(max_tokens=11),
                    SamplingParams(max_tokens=5, temperature=0.8, top_p=0.9),
                    SamplingParams(max_tokens=17),
                    SamplingParams(max_tokens=3, top_k=4, temperature=1.0),
                    SamplingParams(max_tokens=8)]
        reqs = [eng.submit(p, s) for p, s in zip(prompts, sampling)]
        assert all(r.done.wait(120) for r in reqs)
        # (a sampled request may draw the end-of-sequence token early)
        assert all(1 <= len(r.out_tokens) <= s.max_tokens and not r.error
                   for r, s in zip(reqs, sampling))
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert not {"kv_blocks_total", "kv_blocks_free", "kv_block_size",
                "preemptions"} & set(stats)
    assert stats["finished"] == stats["admitted"] == 5
    assert stats["requests_failed"] == stats["device_failures"] == 0
    assert 0 < stats["kv_positions_read"] <= stats["kv_positions_reserved"]
    assert 0 < stats["prefill_kv_positions_read"] <= \
        stats["prefill_kv_positions_reserved"]


@pytest.mark.parametrize("model", **MODELS)
def test_the_look_ahead_schedule_gives_the_serial_schedules_tokens(model):
    """The scheduler knows no model: with either one's programs behind it,
    lines that join bursts in flight (prompts of several chunks, more
    requests than slots) get the tokens the strictly serial schedule gives
    them, and a model's own counters still add up to the same picks."""
    _, cfg = model()
    prompts = [[7 + i for i in range(n)] for n in (5, 40, 23, 9, 31)]
    budgets = [30, 17, 22, 9, 13]
    outs, stats = [], []
    for pipelined in (False, True):
        eng = LLMEngine(LLMConfig(
            model=cfg, max_num_seqs=SLOTS, max_seq_len=MAX_SEQ,
            prefill_chunk=CHUNK, decode_burst=4, decode_pipeline=pipelined))
        try:
            reqs = [eng.submit(p, SamplingParams(max_tokens=n))
                    for p, n in zip(prompts, budgets)]
            assert all(r.done.wait(180) and not r.error for r in reqs)
            outs.append([(r.out_tokens, r.finish_reason) for r in reqs])
            stats.append(eng.stats())
        finally:
            eng.shutdown()
    assert outs[0] == outs[1]
    serial, ahead = stats
    assert ahead["decode_dispatches_ahead"] > serial["decode_dispatches_ahead"]
    # every token but a request's first, which prefill gives
    assert ahead["decode_tokens"] == serial["decode_tokens"] == \
        sum(len(toks) - 1 for toks, _ in outs[0])
    served = served_model(cfg)
    rule = getattr(cfg, "router_rule", None)
    for name in served.counters:   # counted for valid tokens only
        if name == "moe_picks_zero" and not rule.zero_experts:
            assert ahead[name] == 0    # a router with no zero expert
        else:
            assert ahead[name] > 0


# ---- a step that is not a token --------------------------------------------

@pytest.mark.parametrize("model", **MODELS)
def test_a_token_a_step_is_what_a_model_is_served_by_unless_it_says(model):
    """The models the engine served before one said otherwise: no ``step``
    (one position by one forward), a prefill that gives the first token, a
    single step of their own. The scheduler's arithmetic in steps of K
    positions is theirs at K = 1, and their programs take the same
    arguments as ever: int32[slots] tokens."""
    _, cfg = model()
    served = served_model(cfg)
    assert served.step is None and served.prefill_token
    assert served.decode_step is not None
    eng = LLMEngine(LLMConfig(model=cfg, max_num_seqs=SLOTS,
                              max_seq_len=MAX_SEQ))
    try:
        assert (eng._step_positions, eng._step_forwards) == (1, 1)
        # nothing said of a burst: its steps are what they are alone
        assert served.burst_forwards is None
        assert eng._burst_forwards(8).tolist() == [1] * 8
        req = eng.submit([5, 6, 7], SamplingParams(max_tokens=2))
        assert eng._prefill_len(req) == 3
        assert eng._input_tokens({0: req}).shape == (SLOTS,)
        assert req.done.wait(60)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("mix", [
    {"step": lambda cfg: (4, 5)},          # a block step AND a single step
    {"decode_step": None},                 # neither
], ids=["both", "neither"])
def test_a_step_and_a_single_step_program_exclude_each_other(mix):
    """One statement tells the two kinds of model apart (``step``), and
    what follows from it cannot be set against it: a mix the scheduler has
    no arithmetic for is refused where the ServedModel is built, and
    ``prefill_token`` is no field to set."""
    from dataclasses import replace as dc_replace

    # (without its ``mixed_burst``, which a stated ``step`` is refused first)
    with pytest.raises(ValueError, match="one of the two"):
        dc_replace(llama_serving.SERVED, mixed_burst=None, **mix)
    with pytest.raises(TypeError):
        dc_replace(llama_serving.SERVED, prefill_token=False)


@pytest.mark.parametrize("model", **ALL_MODELS)
def test_a_mixed_burst_is_offered_by_a_token_a_step_model_or_by_none(model):
    """``mixed_burst`` is the contract's one optional program. A model that
    states its ``step`` is refused one where its ServedModel is built (a
    step that carries a chunk is a token a line); one that offers it keeps
    ``decode_burst``'s name for it, which a device trace is read by, and
    takes the cache donated and gives it back; the others' engines never
    let a chunk ride (``_ride_steps`` 0)."""
    from dataclasses import replace as dc_replace

    module, cfg = model()
    served = served_model(cfg)
    eng = LLMEngine(LLMConfig(model=cfg, max_num_seqs=SLOTS,
                              max_seq_len=MAX_SEQ, prefill_chunk=CHUNK,
                              decode_burst=4))
    eng.shutdown()
    if served.step is not None:
        assert served.mixed_burst is None and eng._ride_steps == 0
        with pytest.raises(ValueError, match="offers no mixed_burst"):
            dc_replace(served, mixed_burst=lfm2_serving.mixed_burst)
        return
    # nothing else refuses the entry: a token-a-step model may offer one
    assert dc_replace(served, mixed_burst=lfm2_serving.mixed_burst)
    if served.mixed_burst is None:
        assert eng._ride_steps == 0
        return
    assert served.mixed_burst is module.mixed_burst
    assert (eng._ride_steps, eng._ride_rows) == (4, CHUNK)
    params = served.init_params(cfg, jax.random.PRNGKey(0))
    cache = served.init_cache(cfg, SLOTS, MAX_SEQ)
    went_in = jax.tree.map(lambda a: (a.shape, a.dtype), cache)
    i32 = jnp.int32
    riders = (jnp.zeros((2, CHUNK), i32), jnp.array([2, 2], i32),
              jnp.array([0, CHUNK], i32), jnp.full((2,), 3 * CHUNK, i32),
              i32(2))
    args = (cfg, params, cache, *_arguments("decode_burst", params)[1:-2],
            riders, 2, False)
    named = re.search(r"module @(\w+)",
                      served.mixed_burst.lower(*args).as_text()).group(1)
    assert named == "jit_decode_burst"
    came_back, toks, *counts = served.mixed_burst(*args)
    assert toks.shape == (2, SLOTS)
    # a model with counters returns their counts, one array of them
    assert [c.shape for c in counts] == [(len(served.counters),)] * bool(
        served.counters)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), came_back) == went_in
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(cache))


def counts_arrive_with_the_program_behind_their_chunk(
        monkeypatch, serving, cfg, mixed=True, pipeline=True, beside=True,
        chunks=3):
    """A prompt of ``chunks`` chunks (the last a tail) through an engine
    whose scheduler's thread is stopped and whose ticks are made by hand,
    with a line decoding beside it and a shorter prompt behind it
    (``beside``) or alone; ``mixed`` false takes the model's ``mixed_burst``
    away. The model's programs return their counts wrapped: a count knows
    the instant it was dispatched and records the instant it was fetched
    and the entry in flight it was fetched with, all on one counter of
    events. Returns ``(reads, ticks, made, entries, stats)``:

    - ``reads``: (``chunk`` | ``burst`` | ``step``, dispatched, the stamp of
      the entry it was fetched with, whether it is the long prompt's first
      chunk) of every count fetched, in order;
    - ``ticks``: after every tick, (how many of ``reads`` had been made,
      whether the long prompt's last chunk has been dispatched, the decode
      steps whose tokens the host has read, ``stats()``);
    - ``made``: every count a program returned, (kind, dispatched, values);
    - ``entries``: the stamps of the entries that went in flight (a single
      step, which is fetched where it is dispatched, among them)."""
    import itertools
    from collections import deque
    from dataclasses import replace

    if not mixed:
        monkeypatch.setattr(serving, "SERVED",
                            replace(serving.SERVED, mixed_burst=None))
    eng = LLMEngine(LLMConfig(
        model=cfg, max_num_seqs=3, max_seq_len=256, prefill_chunk=32,
        decode_burst=4, prefill_chunks_per_tick=1, decode_pipeline=pipeline,
        seed=3))
    eng.shutdown()
    clock = itertools.count()
    reads, made, entries, reading = [], [], [], [None]

    class Counted:
        def __init__(self, kind, counts, first):
            self.kind, self.at, self.counts = kind, next(clock), counts
            self.first = first
            made.append((kind, self.at, np.asarray(counts)))

        def __array__(self, *args, **kw):
            reads.append((self.kind, self.at, reading[0], self.first))
            return np.asarray(self.counts)

    def counted(name, kind):
        program = getattr(eng.model, name)
        if program is None:
            return None

        def call(*args, **kw):
            *out, counts = program(*args, **kw)
            # a chunk's (tokens, cached rows, length, slot) follow the cache
            first = kind == "chunk" and (int(args[4]), int(args[5])) == (
                0, len(long))
            counts = Counted(kind, counts, first)
            if kind == "step":      # fetched where it is dispatched
                reading[0] = counts.at
                entries.append(counts.at)
            return (*out, counts)
        return call

    class Stamped(deque):
        def append(self, entry):
            entry.at = next(clock)
            entries.append(entry.at)
            super().append(entry)

    read = eng._read

    def stamped_read(entry, why):
        reading[0] = entry.at
        try:
            return read(entry, why)
        finally:
            reading[0] = None

    eng.model = replace(
        eng.model, prefill_chunk=counted("prefill_chunk", "chunk"),
        decode_step=counted("decode_step", "step"),
        decode_burst=counted("decode_burst", "burst"),
        mixed_burst=counted("mixed_burst", "burst"))
    eng._in_flight, eng._read = Stamped(), stamped_read
    rng = np.random.default_rng(11)
    line, long, short = ([int(t) for t in rng.integers(259, cfg.vocab_size, n)]
                         for n in (20, 32 * chunks - 16, 40))
    reqs = []
    if beside:
        reqs.append(eng.submit(line, SamplingParams(max_tokens=60)))
        while not reqs[0].out_tokens:
            eng._tick()
    prompt = eng.submit(long, SamplingParams(max_tokens=9))
    reqs.append(prompt)
    if beside:
        reqs.append(eng.submit(short, SamplingParams(max_tokens=5)))
    ticks = []
    for _ in range(400):
        if all(r.done.is_set() for r in reqs):
            break
        eng._tick()
        stats = eng.stats()
        ticks.append((
            len(reads), prompt.prefilled_len >= len(long),
            stats["decode_steps"] - sum(e.steps for e in eng._in_flight),
            stats))
    assert all(r.done.is_set() and not r.error for r in reqs)
    eng._read_all("stop")
    assert not eng._chunk_counts
    return reads, ticks, made, entries, eng.stats()


def chunks_that_ride_leave_every_answer_as_it_was(monkeypatch, serving, cfg,
                                                  pipeline):
    """What a model's test of its ``mixed_burst`` through the engine holds
    (tests/test_lfm2.py, tests/test_deepseek.py): long prompts arrive while
    a line decodes, their full chunks ride the line's bursts, never a
    prompt's last chunk (one that is full among them), and every request
    gets token for token what it gets from the engine whose model offers no
    such program, which counts no chunk as riding. Returns the two engines'
    last ``stats()``, with and without the entry."""
    import time
    from dataclasses import replace

    import numpy as np

    from ray_tpu.util import tracing

    rng = np.random.default_rng(11)
    # 150 and 97: full chunks and a tail; 96: three full chunks, the last
    # one the prompt's last; 20: a tail alone
    prompts = [[int(t) for t in rng.integers(259, cfg.vocab_size, n)]
               for n in (20, 150, 97, 96, 20)]
    rode = []

    def recording(*args, **kw):
        chunks, slots, kv_lens, lengths, n = args[9]
        rode.extend((int(kv_lens[j]) + chunks.shape[1], int(lengths[j]))
                    for j in range(int(n)))
        return serving.mixed_burst(*args, **kw)

    def run(entry):
        monkeypatch.setattr(serving, "SERVED",
                            replace(serving.SERVED, mixed_burst=entry))
        eng = LLMEngine(LLMConfig(
            model=cfg, max_num_seqs=3, max_seq_len=256, prefill_chunk=32,
            decode_burst=4, decode_pipeline=pipeline, seed=3))
        try:
            first = eng.submit(prompts[0], SamplingParams(max_tokens=70))
            end = time.monotonic() + 120
            while not first.out_tokens and time.monotonic() < end:
                time.sleep(0.005)
            assert first.out_tokens
            reqs = [first] + [eng.submit(p, SamplingParams(max_tokens=12))
                              for p in prompts[1:]]
            assert all(r.done.wait(300) for r in reqs)
            assert not any(r.error for r in reqs)
            return [list(r.out_tokens) for r in reqs], eng.stats()
        finally:
            eng.shutdown()

    tracing.clear()
    tracing.enable_tracing()
    try:
        with_entry, stats = run(recording)
        dispatches = [s.attributes for s in tracing.spans()
                      if s.name == "engine.decode_dispatch"]
    finally:
        tracing.disable_tracing()
        tracing.clear()
    without, plain = run(None)
    assert with_entry == without
    # each burst's dispatch phase says how many of its steps took a chunk
    assert sum(d["riders"] for d in dispatches) == \
        stats["prefill_chunks_riding"]
    assert all(0 <= d["riders"] <= d["steps"] for d in dispatches)
    chunks = sum(-(-len(p) // 32) for p in prompts)
    assert stats["prefill_chunks"] == plain["prefill_chunks"] == chunks
    assert stats["prompt_tokens_prefilled"] == \
        plain["prompt_tokens_prefilled"] == sum(len(p) for p in prompts)
    assert plain["prefill_chunks_riding"] == \
        plain["prefill_tokens_riding"] == 0
    assert 0 < stats["prefill_chunks_riding"] == len(rode) <= chunks - 5
    assert stats["prefill_tokens_riding"] == 32 * len(rode)
    # a rider ends before its prompt does
    assert all(end < length for end, length in rode), rode
    return stats, plain


def test_a_model_may_say_its_step_is_a_block_and_its_prefill_gives_no_token():
    """SDAR's: 4 positions by 4 forwards. Its two programs keep the names a
    trace is read by and give the donated cache back; the prefill's logits
    are None; a burst takes int32[slots, 4] (with the block before it and
    who has one: ``pending_step``) and gives [steps, slots, 4]."""
    module, cfg = _sdar()
    served = served_model(cfg)
    assert served.step(cfg) == (4, 4) and not served.prefill_token
    assert served.pending_step
    assert served.decode_step is None and served.copy_prefix_kv is None
    params = served.init_params(cfg, jax.random.PRNGKey(0))
    i32 = jnp.int32
    for program, rest in (
            ("prefill_chunk", (jnp.arange(CHUNK, dtype=i32), i32(0),
                               i32(CHUNK), i32(0))),
            ("decode_burst", ((jnp.full((SLOTS, 4), -1, i32),
                               jnp.zeros((SLOTS, 4), i32),
                               jnp.array([True, False, False])),
                              jnp.array([CHUNK, 0, 0], i32),
                              jnp.array([True, True, False]),
                              jnp.zeros((SLOTS,), jnp.float32),
                              jnp.ones((SLOTS,), jnp.float32),
                              jax.random.PRNGKey(0), 2, False))):
        cache = served.init_cache(cfg, SLOTS, MAX_SEQ)
        went_in = jax.tree.map(lambda a: (a.shape, a.dtype), cache)
        lowered = getattr(module, program).lower(cfg, params, cache, *rest)
        assert re.search(r"module @(\w+)", lowered.as_text()).group(1) \
            == f"jit_{program}"
        came_back, result, counts = getattr(served, program)(
            cfg, params, cache, *rest)
        assert jax.tree.map(lambda a: (a.shape, a.dtype), came_back) \
            == went_in
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(cache))
        assert counts.shape == (len(served.counters),)
        if program == "prefill_chunk":
            assert result is None
        else:
            assert result.shape == (2, SLOTS, 4) and result.dtype == i32


def test_a_model_may_say_what_a_burst_costs_and_is_counted_so():
    """SDAR's bursts: 4 n forwards, a block's commit among the next
    block's (a step alone would state 4 too since PR 63; a model whose
    burst is cheaper than its steps states what each costs, as SDAR's did
    from PR 61: 4 n + 1). ``decode_steps``, the dispatch phase's ``steps`` and
    ``kv_positions_read`` (a kernel call a layer a forward) count what the
    model states, step by step at that step's lengths; a model that states
    nothing counts its step's forwards each, as it did."""
    from dataclasses import replace as dc_replace

    from ray_tpu.util import tracing

    _, cfg = _sdar()
    served = served_model(cfg)
    assert served.burst_forwards(cfg, 3) == [4, 4, 4]
    eng = LLMEngine(LLMConfig(model=cfg, max_num_seqs=SLOTS,
                              max_seq_len=MAX_SEQ, prefill_chunk=CHUNK,
                              decode_burst=2, decode_pipeline=False))
    tracing.clear()
    tracing.enable_tracing()
    try:
        assert eng._burst_forwards(1).tolist() == [4]
        assert eng._burst_forwards(2).tolist() == [4, 4]
        out = eng.generate([7 + i for i in range(9)],
                           SamplingParams(max_tokens=15))
        assert len(out.token_ids) == 15
        # a first block of 1 + 3, then 12: four blocks as two bursts of two
        stats = eng.stats()
        assert (stats["decode_dispatches"], stats["decode_steps"]) == (2, 16)
        assert [s.attributes["steps"] for s in tracing.spans()
                if s.name == "engine.decode_dispatch"] == [8, 8]
        # one block of the line (MAX_SEQ) a kernel call
        assert stats["kv_positions_read"] == 16 * eng._kv_block
        # the second burst committed the first's last block, the host
        # having made the pending block from its own tokens (no look-ahead)
        assert (stats["diffusion_commits"], stats["diffusion_commits_riding"],
                stats["diffusion_blocks"]) == (0, 3, 4)
        # scripted, in blocks shorter than the line: a burst of 2 from
        # positions 120 and 300 (slot 2 idle), in blocks of 128 of 512
        eng.max_seq, eng._kv_block = 512, 128
        before = eng.kv_positions_read, eng.kv_positions_reserved
        eng._count_kv_positions(np.array([120, 300, 0]),
                                np.array([True, True, False]), steps=2)
        # lengths 124 and 304, 4 forwards; 128 (a whole block) and 308, 4
        assert eng.kv_positions_read - before[0] == \
            4 * (128 + 384) + 4 * (128 + 384)
        assert eng.kv_positions_reserved - before[1] == 8 * SLOTS * 512
        # a model whose burst costs what it states, step by step at that
        # step's lengths (SDAR's own from PR 61 to 62)
        eng.model = dc_replace(
            eng.model, burst_forwards=lambda cfg, n: [4] * (n - 1) + [5])
        assert eng._burst_forwards(2).tolist() == [4, 5]
        eng._count_kv_positions(np.array([120, 300, 0]),
                                np.array([True, True, False]), steps=2)
        assert eng.kv_positions_read - before[0] == \
            8 * 512 + 4 * (128 + 384) + 5 * (128 + 384)
        assert eng.kv_positions_reserved - before[1] == (8 + 9) * SLOTS * 512
        # stating nothing of a burst: its step's forwards each
        eng.model = dc_replace(eng.model, burst_forwards=None)
        assert eng._burst_forwards(2).tolist() == [4, 4]
        eng._count_kv_positions(np.array([125, 300, 0]),
                                np.array([True, True, False]), steps=2)
        assert eng.kv_positions_read - before[0] == \
            17 * 512 + 4 * (256 + 384) + 4 * (256 + 384)
    finally:
        tracing.disable_tracing()
        tracing.clear()
        eng.shutdown()


def test_the_look_ahead_serves_blocks_as_the_serial_schedule_does():
    """The same equality as above with a step of 4: lines that join bursts
    in flight get the serial schedule's tokens, every token is a decode's,
    and a step counts its 4 forwards: handed over on the device or made
    by the host, every block but a line's last is committed by the forward
    after it."""
    _, cfg = _sdar()
    prompts = [[7 + i for i in range(n)] for n in (5, 40, 23, 9, 31)]
    budgets = [30, 17, 22, 9, 13]
    outs, stats = [], []
    for pipelined in (False, True):
        eng = LLMEngine(LLMConfig(
            model=cfg, max_num_seqs=SLOTS, max_seq_len=MAX_SEQ,
            prefill_chunk=CHUNK, decode_burst=2, decode_pipeline=pipelined))
        try:
            reqs = [eng.submit(p, SamplingParams(max_tokens=n))
                    for p, n in zip(prompts, budgets)]
            assert all(r.done.wait(180) and not r.error for r in reqs)
            outs.append([(r.out_tokens, r.finish_reason) for r in reqs])
            stats.append(eng.stats())
        finally:
            eng.shutdown()
    assert outs[0] == outs[1]
    assert [len(toks) for toks, _ in outs[0]] == [30, 17, 22, 9, 13]
    serial, ahead = stats
    assert ahead["decode_dispatches_ahead"] > serial["decode_dispatches_ahead"]
    assert ahead["decode_tokens"] == serial["decode_tokens"] == sum(budgets)
    for s in stats:
        # a burst of n blocks is 4 n forwards, no commit among them
        assert s["decode_steps"] % 4 == 0
        assert s["diffusion_forwards"] == 4 * s["diffusion_blocks"]
        assert s["diffusion_commits"] == 0
        assert s["diffusion_commits_riding"] == \
            s["diffusion_blocks"] - len(prompts)
        assert s["first_tokens"] == 5
        # whole blocks of the prompts: 4 + 40 + 20 + 8 + 28
        assert s["prompt_tokens_prefilled"] == 100


# ---- the seam: who imports whom, and how a configuration finds its model ---

LLM = pathlib.Path(importlib.import_module("ray_tpu.llm").__file__).parent


def _imports(path, top_level_only=False):
    """The dotted names a file imports (``from a.b import c`` gives ``a.b``
    and ``a.b.c``), anywhere in it or at module level only."""
    tree = ast.parse(path.read_text())
    names = set()
    for node in (tree.body if top_level_only else ast.walk(tree)):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            names |= {node.module} | {f"{node.module}.{a.name}"
                                      for a in node.names}
    return names


def test_the_scheduler_names_no_model_and_the_contract_imports_neither():
    """The arrows point one way. ``engine.py`` imports no model and no
    serving module but Llama's, for the three names another file holds
    there; ``served.py`` imports neither the scheduler nor a serving module
    when it is loaded; a serving module imports the contract, never the
    scheduler and never another model's serving module."""
    engine = _imports(LLM / "engine.py")
    assert not {n for n in engine if n.startswith("ray_tpu.models")}
    assert {n for n in engine if n.endswith("_serving")} \
        == {"ray_tpu.llm.llama_serving"}
    assert {n for n in engine if n.startswith("ray_tpu.llm.llama_serving.")} \
        == {"ray_tpu.llm.llama_serving." + name for name in
            ("init_kv_cache", "prefill_chunk", "decode_step")}
    # no configuration's type is named, so none is asked after
    text = (LLM / "engine.py").read_text()
    assert not [kind.__name__ for kind in SERVING_MODULES
                if kind.__name__ in text]

    contract = _imports(LLM / "served.py", top_level_only=True)
    assert not {n for n in contract
                if "engine" in n or n.endswith("_serving")}

    modules = sorted(LLM.glob("*_serving.py"))
    assert {f"ray_tpu.llm.{p.stem}" for p in modules} \
        == set(SERVING_MODULES.values())
    for path in modules:
        names = _imports(path)
        assert "ray_tpu.llm.served" in names, path.name
        assert not {n for n in names
                    if "llm.engine" in n or "_serving" in n}, path.name


@pytest.mark.parametrize("kind", list(SERVING_MODULES),
                         ids=lambda kind: kind.__name__)
def test_the_table_finds_every_configuration_its_served_model(kind):
    """One line of ``config.SERVING_MODULES`` is what the scheduler's side
    needs of a new model: the configuration's type to the module whose
    ``SERVED`` is its ``ServedModel``; ``ModelConfig`` is those types."""
    module = importlib.import_module(SERVING_MODULES[kind])
    assert isinstance(module.SERVED, ServedModel)
    assert served_model(kind.tiny()) is module.SERVED
    assert set(typing.get_args(ModelConfig)) == set(SERVING_MODULES)
    # a configuration of a subclass is its parent's model
    assert served_model(type("Sub", (kind,), {}).tiny()) is module.SERVED


def test_an_unknown_configuration_is_told_what_is_served():
    with pytest.raises(TypeError) as refused:
        served_model(object())
    assert all(kind.__name__ in str(refused.value)
               for kind in SERVING_MODULES)
    assert "LlamaConfig, LongcatConfig" in str(refused.value)


@pytest.mark.parametrize("model", **ALL_MODELS)
def test_a_draft_is_refused_where_the_two_speculative_programs_are_none(
        model):
    """``draft_propose`` and ``spec_verify_step`` are what a model's author
    supplies, and only Llama's has: the engine refuses a
    ``speculative_model`` for any other target in the same words, and a
    model's own reason comes first where it has one."""
    module, cfg = model()
    served = served_model(cfg)
    config = LLMConfig(model=cfg, max_num_seqs=2, max_seq_len=MAX_SEQ,
                       speculative_model="tiny")
    if module is llama_serving:
        assert served.draft_propose is module.draft_propose
        assert served.spec_verify_step is module.spec_verify_step
        return
    assert served.draft_propose is None and served.spec_verify_step is None
    with pytest.raises(ValueError, match=f"{type(cfg).__name__} does not "
                                         "support a speculative draft"):
        LLMEngine(config)


@pytest.mark.parametrize("model", **ALL_MODELS)
def test_only_a_model_that_says_it_partitions_is_given_a_tp_mesh(model):
    """Whether a model's programs partition is its author's statement
    (``ServedModel.tensor_parallel``, Llama's alone says so), checked once,
    by the contract, at construction: every other model refuses
    ``tensor_parallel_size > 1`` in one sentence that no serving module
    writes."""
    from ray_tpu.llm.served import require_tensor_parallel

    module, cfg = model()
    served = served_model(cfg)
    assert served.tensor_parallel == (module is llama_serving)
    require_tensor_parallel(cfg, 1)
    sentence = (f"{type(cfg).__name__} does not support "
                "tensor_parallel_size > 1: its programs run on one device")
    if served.tensor_parallel:
        require_tensor_parallel(cfg, 2)
    else:
        with pytest.raises(ValueError) as refused:
            LLMEngine(LLMConfig(model=cfg, max_num_seqs=2,
                                max_seq_len=MAX_SEQ, tensor_parallel_size=2))
        assert str(refused.value) == sentence
    assert [p.name for p in LLM.glob("*.py")
            if "its programs run on one device" in p.read_text()] \
        == ["served.py"]


# ---- the tree a model's programs take ---------------------------------------

@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (8, 2), (3, 1)],
                         ids=["mha", "gqa_4_to_1", "one_kv_head"])
def test_llama_s_program_params_adds_the_fused_leaf_and_keeps_the_rest(
        heads, kv_heads):
    """``wqkv``'s columns are ``wq``'s, ``wk``'s and ``wv``'s grouped by KV
    head (a group's query heads in their published order, its key head, its
    value head); every leaf the tree came with is the buffer it was, the
    three projections among them; a tree that has the leaf passes as it is;
    the axes name the leaf, whole groups over ``tp``."""
    import numpy as np

    from ray_tpu.models.llama import init_params

    cfg = dataclasses.replace(LlamaConfig.tiny(), num_heads=heads,
                              num_kv_heads=kv_heads)
    params = init_params(cfg, jax.random.PRNGKey(3))
    tree = llama_serving.program_params(cfg, params)
    assert set(tree["layers"]) == set(params["layers"]) | {"wqkv"}
    given = dict(jax.tree_util.tree_leaves_with_path(params))
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        assert path[-1].key == "wqkv" or leaf is given[path], path
    assert llama_serving.program_params(cfg, tree) is tree

    lay, n_rep, d = params["layers"], heads // kv_heads, cfg.head_dim
    L, h = cfg.num_layers, cfg.hidden_size
    assert tree["layers"]["wqkv"].shape == (L, h, kv_heads * (n_rep + 2) * d)
    cols = np.asarray(tree["layers"]["wqkv"]).reshape(
        L, h, kv_heads, n_rep + 2, d)
    wq, wk, wv = (np.asarray(lay[w]).reshape(L, h, -1, d)
                  for w in ("wq", "wk", "wv"))
    for group in range(kv_heads):
        for r in range(n_rep):
            np.testing.assert_array_equal(cols[:, :, group, r],
                                          wq[:, :, group * n_rep + r])
        np.testing.assert_array_equal(cols[:, :, group, n_rep],
                                      wk[:, :, group])
        np.testing.assert_array_equal(cols[:, :, group, n_rep + 1],
                                      wv[:, :, group])

    axes = llama_serving.SERVED.param_logical_axes(cfg)
    assert axes["layers"]["wqkv"] == ("layers", "embed", "kv_heads")
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(a, tuple)) \
        == jax.tree.structure(tree)


@pytest.mark.parametrize("model", **ALL_MODELS)
def test_the_engine_holds_the_tree_its_model_s_programs_take(model):
    """``ServedModel.program_params`` is the model's statement, called once
    where the engine places a tree: a model without the entry (every one
    but Llama's) is handed the tree it gave, leaf for leaf; Llama's holds
    the fused leaf beside the leaves it was given, which the programs were
    not left to fuse themselves."""
    module, cfg = model()
    served = served_model(cfg)
    assert (served.program_params is not None) == (module is llama_serving)
    params = served.init_params(cfg, jax.random.PRNGKey(0))
    eng = LLMEngine(LLMConfig(model=cfg, max_num_seqs=SLOTS,
                              max_seq_len=MAX_SEQ), params=params)
    try:
        held = dict(jax.tree_util.tree_leaves_with_path(eng.params))
        given = dict(jax.tree_util.tree_leaves_with_path(params))
        assert all(held[path] is leaf for path, leaf in given.items())
        extra = [jax.tree_util.keystr(path) for path in held.keys() - given]
        assert extra == (["['layers']['wqkv']"]
                         if module is llama_serving else [])
    finally:
        eng.shutdown()


def test_a_tensor_parallel_engine_splits_the_fused_leaf_and_serves_the_same(
        cpu_mesh_devices):
    """``tensor_parallel_size=2``: the fused leaf is split over ``tp`` on
    its last axis (a KV head's group a shard at the tiny model's 2 KV
    heads), the three it was made of as ever, and greedy tokens are the one
    device's."""
    _, cfg = _llama()
    prompts = [[5, 6, 7, 8, 9], list(range(40, 40 + CHUNK + 3))]

    def served(tp):
        eng = LLMEngine(LLMConfig(model=cfg, max_num_seqs=SLOTS,
                                  max_seq_len=MAX_SEQ, prefill_chunk=CHUNK,
                                  decode_burst=4, tensor_parallel_size=tp))
        try:
            placed = [eng.params["layers"][w].sharding
                      for w in ("wqkv", "wq", "wk")]
            reqs = [eng.submit(p, SamplingParams(max_tokens=9))
                    for p in prompts]
            assert all(r.done.wait(120) for r in reqs)
            return placed, [r.out_tokens for r in reqs]
        finally:
            eng.shutdown()

    placed, tokens = served(2)
    assert placed[0].spec[-1] == "tp" and placed == placed[:1] * 3
    assert tokens == served(1)[1]


# ---- the latent line: one module a layer, imported by both models ----------

@pytest.mark.parametrize("model", argvalues=[_longcat, _deepseek],
                         ids=["longcat", "deepseek"])
def test_the_latent_cache_is_one_module_s_for_both_models(model):
    """``llm/latent.py`` holds the latent slot cache for the two models that
    keep one: ``init_cache`` at either model's count of lines (two a
    double layer, one a layer) and row, and one ``copy_prefix_kv`` that
    moves a slot's whole line of every cache line at once and touches no
    other slot."""
    from ray_tpu.llm import latent

    module, cfg = model()
    served = served_model(cfg)
    lines = {longcat_serving: 2 * cfg.num_layers,
             deepseek_serving: cfg.num_layers}[module]
    cache = served.init_cache(cfg, SLOTS, MAX_SEQ)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), cache) == jax.tree.map(
        lambda a: (a.shape, a.dtype),
        latent.init_cache(cfg, lines, SLOTS, MAX_SEQ))
    assert cache["latent"].shape == (lines, SLOTS, MAX_SEQ, cfg.latent_row)
    assert served.copy_prefix_kv is module.copy_prefix_kv \
        is latent.copy_prefix_kv
    assert served.kv_block is latent.kv_block
    # every (line, slot, position) its own value
    held = jnp.arange(cache["latent"].size, dtype=jnp.float32).reshape(
        cache["latent"].shape).astype(cache["latent"].dtype)
    got = latent.copy_prefix_kv(cfg, {"latent": jnp.copy(held)},
                                jnp.int32(0), jnp.int32(2))["latent"]
    assert jnp.array_equal(got[:, 2], held[:, 0])
    assert jnp.array_equal(got[:, :2], held[:, :2])


def test_deepseek_imports_nothing_of_longcat_s():
    """What the two latent models share is a module of its own a layer
    (``models/mla.py``, ``llm/latent.py``): neither file of the newer model
    imports the older model's, and the older model's keeps no alias of what
    moved."""
    models = LLM.parent / "models"
    for path in (models / "deepseek.py", LLM / "deepseek_serving.py"):
        names = _imports(path)
        assert not {n for n in names if "longcat" in n}, path.name
        assert {n for n in names if n.startswith("ray_tpu.models.mla")}, \
            path.name
    assert {"ray_tpu.llm.latent", "ray_tpu.models.mla"} \
        <= _imports(LLM / "longcat_serving.py")
    longcat = importlib.import_module("ray_tpu.models.longcat")
    assert not hasattr(longcat, "mla_project")
    assert not hasattr(longcat, "kv_up_projections")
