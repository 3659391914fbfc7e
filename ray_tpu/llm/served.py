"""The contract between the scheduler (llm/engine.py) and the models it
serves (llm/<name>_serving.py): what a model supplies (:class:`ServedModel`),
how a configuration finds its model (:func:`served_model`, by the table
``llm/config.SERVING_MODULES``), and what the models share because the
scheduler gives it one meaning for all of them: the sampler, the per-head
K/V slot cache, the builder of the two programs of a model that takes a
token in and gives a token out a step (:func:`token_step_programs`) and of
the burst whose steps carry a prefill chunk (:func:`mixed_burst_program`).
What two models share and the scheduler knows nothing of is not here: the
latent cache line is llm/latent.py's.

The arrows point one way: ``engine.py`` imports this module, the serving
modules import this module, and this module imports none of them when it is
loaded: a model's module is imported when its configuration is first asked
for.
"""

from __future__ import annotations

import importlib
from collections.abc import Callable
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm.config import SERVING_MODULES
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.util import tracing

NEG_INF = -1e30


@partial(jax.jit, static_argnums=(3, 5))
@tracing.part("sample")
def sample_tokens(logits, temps, top_ps, top_k: int, key,
                  need_top_p: bool = True):
    """logits [B, V] fp32; temps/top_ps [B]. Greedy where temp == 0.

    ``need_top_p=False`` (static) skips the vocab-wide argsort + cumsum of
    nucleus filtering — with top_p == 1.0 the filter keeps every token
    anyway (cum − p < 1 holds for all p > 0), and the sort over V=128k per
    step is BY FAR the most expensive op in the sampler (it dwarfs greedy
    argmax and even rivals a 1B decode forward). The engine passes it
    per-batch: only when some active request actually sets top_p < 1."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    if top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, NEG_INF, scaled)
    if need_top_p:
        # top-p: keep the smallest prefix of sorted probs with cumsum <= p
        sorted_idx = jnp.argsort(-scaled, axis=-1)
        sorted_logits = jnp.take_along_axis(scaled, sorted_idx, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = cum - probs < top_ps[:, None]  # always keep the first
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(logits.shape[0])[:, None], sorted_idx].set(keep_sorted)
        masked = jnp.where(keep, scaled, NEG_INF)
    else:
        masked = scaled
    sampled = jax.random.categorical(key, masked, axis=-1)
    return jnp.where(temps <= 0.0, greedy, sampled)


# ---------------------------------------------------------------------------
# The per-head K/V slot cache: ``{"k", "v"}`` of ``[lines, slots, kv_heads,
# positions, head_dim]``, which ops/prefill_attention.py and
# ops/decode_attention.py read in place. Several models keep this cache
# (a line a layer, or a line for every pass of a looped stack), so it lives
# beside the contract that describes it and not in one model's module.


def init_kv_cache(cfg, max_slots: int, max_seq: int):
    """A zeroed cache of a line a layer."""
    shape = (cfg.num_layers, max_slots, cfg.num_kv_heads, max_seq,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.jnp_dtype),
            "v": jnp.zeros(shape, cfg.jnp_dtype)}


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
@tracing.part("cache")
def copy_prefix_kv(cfg, cache, src_slot, dst_slot):
    """Copy one slot's whole KV line to another slot, all lines at once
    (prefix-cache adoption from a LIVE donor). Copying the full max_seq
    line is safe: positions beyond the adopted prefix are masked by
    ``length``/``positions`` in prefill_chunk/decode_step, and the copy is
    pure HBM bandwidth — orders of magnitude cheaper than recomputing the
    prefix (vLLM APC makes the same recompute-vs-reuse trade)."""
    k_line = lax.dynamic_slice_in_dim(cache["k"], src_slot, 1, 1)
    v_line = lax.dynamic_slice_in_dim(cache["v"], src_slot, 1, 1)
    return {
        "k": lax.dynamic_update_slice(cache["k"], k_line,
                                      (0, dst_slot, 0, 0, 0)),
        "v": lax.dynamic_update_slice(cache["v"], v_line,
                                      (0, dst_slot, 0, 0, 0)),
    }


@dataclass(frozen=True)
class ServedModel:
    """What a model supplies for the engine to serve it. The engine owns
    the schedule (admission, chunked prefill, bursts and the look-ahead,
    sampling, prefix adoption, the counters) and knows a model only through
    this:

    - ``init_params(cfg, key)`` and ``param_logical_axes(cfg)``;
    - ``program_params(cfg, params) -> tree``: the tree its programs take,
      made once from whatever tree the engine was given (an initialiser's,
      a checkpoint's) where the engine places it, its leaves' axes in
      ``param_logical_axes``. None: the tree as it is;
    - ``init_cache(cfg, slots, max_seq)``: the slot cache, a pytree whose
      leaves the programs below take donated and give back. A leaf's
      leading dimension is cache *lines*, of which a model may have more
      than layers (two attentions a layer, or a line for every pass of a
      looped stack); the slot is the second;
    - ``prefill_chunk``, ``decode_step``, ``decode_burst``,
      ``copy_prefix_kv``: jitted programs with the signatures of
      llm/llama_serving.py's, under these very names so that a
      device trace shows ``jit_prefill_chunk`` and ``jit_decode_burst``
      whatever the model. A model with ``counters`` returns one more
      value from the first three: an int32 array of that many counts,
      which the scheduler adds into ``stats()`` under those names where it
      fetches the tokens; ``constants(cfg)`` gives what ``stats()`` carries
      beside them unchanged (a denominator of theirs);
    - ``step(cfg)``: (positions, forwards), the size of one decode step of
      one line: the positions it takes in and gives back decided, and the
      forwards of the stack it costs. None is (1, 1), a token in and a
      token out by one forward: a prompt's last chunk gives the first
      token (``prefill_chunk`` returns that row's logits) and a step's
      input is the token the step before sampled, handed on on the device
      (such a model builds its ``decode_step`` and ``decode_burst`` with
      :func:`token_step_programs`).
      A model that decides a block of K positions by several forwards says
      (K, forwards), and everything else that sets it apart follows from
      this one statement (``prefill_token``; nothing else is looked at):
      the scheduler counts a line's progress, its budget and its cache
      line's end in steps of K positions, a burst is whole steps
      (``decode_burst`` takes tokens ``[slots, K]`` and returns ``[steps,
      slots, K]``; a line's last step may decide more positions than its
      request wants, and the surplus is not emitted), ``decode_steps`` and
      the dispatch phase's ``steps`` count forwards. Such a model samples
      between its forwards, on the device, so it has no ``decode_step``
      (None, and only then: a mix is refused here, where it is built): a
      lone step is a burst of one, and a request with ``top_k`` is
      refused. Its prefill gives no token: the prompt's whole steps are
      prefilled (``len(prompt) - len(prompt) % K`` tokens;
      ``prefill_chunk`` returns None for the logits), nothing is emitted
      for them, and the tokens past them ride into the line's first step
      in their places (-1 at every position a step has to decide); the
      first token comes when that step is read. What a step has to
      decide is then known to the host before the step before it has run;
    - ``pending_step`` (a model that states its ``step``): a burst takes
      in, beside its first step's input, the step each line decided last,
      and finishes what that step left undone (a block's K/V, committed by
      the next block's first forward). ``decode_burst`` then takes
      ``(token0, pending, has_pending)`` where it took ``token0``:
      ``pending`` int32 ``[slots, K]`` and ``has_pending`` bool
      ``[slots]``, true for a line that has been in a decode burst before
      and false for one that joins from its prefill (its ``pending`` row
      means nothing). The scheduler hands the step over as it hands a
      token over for the models of a token a step: the last step of the
      newest burst in flight, left on the device and never read by the
      host before the dispatch; from the host's own tokens where nothing
      is in flight. A line's last step is handed to nobody (a finished
      line is in no later burst) and a slot's new tenant, or a cache
      rebuilt after a device failure, starts with nothing pending; so
      nothing but the line's own next burst may read the positions of its
      newest step (``kv_handoff`` and ``prefix_from_line`` both false);
    - ``burst_forwards(cfg, steps)``: the forwards each step of a burst of
      ``steps`` steps costs, in order, where a burst is cheaper than its
      steps alone (a forward that serves two of them is counted at the
      step whose lengths it attends at). None: ``step``'s forwards each.
      ``decode_steps``, the dispatch phase's ``steps`` and
      ``kv_positions_read`` (a kernel call a layer a forward) count these;
    - ``kv_block(cfg, max_seq)``: the positions its decode attention
      fetches at a time, behind ``kv_positions_read``;
    - ``kv_handoff``: whether a line can be exported and imported as
      per-head K/V (the prefill/decode hand-off);
    - ``tensor_parallel``: whether its programs partition over a ``tp``
      mesh (the engine shards the cache's third axis over it); where they
      do not, :func:`require_tensor_parallel` refuses the mesh;
    - ``prefix_from_line``: whether a prompt's first tokens can be adopted
      from another slot's line, at any common length. False for a model
      that also keeps a state of fixed size a slot (a short convolution's,
      a recurrence's): the state at the adopted length is nowhere unless
      it was saved then. The engine then adopts nothing, counts no hit,
      publishes no prefix to the router and never calls
      ``copy_prefix_kv``, which may be None;
    - ``draft_propose``, ``spec_verify_step``: the two programs of
      speculative decoding, with llm/llama_serving.py's signatures: the
      draft's greedy proposals in one dispatch, and the target's forward
      over them. None where a model's author has written none: the engine
      then refuses a ``speculative_model`` with this model as target
      (no ``spec_verify_step``) or as draft (no ``draft_propose``);
    - ``mixed_burst``: a burst whose steps may each carry one full,
      non-final prefill chunk of a slot mid-prefill beside the decoding
      lines (:func:`mixed_burst_program`, jitted under ``decode_burst``'s
      name), so that what a chunk and a step both fetch is fetched once;
      None: the scheduler alternates chunks and bursts. Where it is
      offered ``stats()`` counts ``prefill_chunks_riding`` and
      ``prefill_tokens_riding`` (of ``prefill_chunks`` and
      ``prompt_tokens_prefilled``: those that rode a decode step). Refused
      beside ``step``: a rider gives no token, a block step's prefill none;
    - ``refuse(config)``: raises ValueError for an ``LLMConfig`` it cannot
      serve (None: it serves them all)."""

    init_params: Callable
    param_logical_axes: Callable
    init_cache: Callable
    prefill_chunk: Callable
    decode_step: Callable | None
    decode_burst: Callable
    kv_block: Callable
    copy_prefix_kv: Callable | None = None
    counters: tuple[str, ...] = ()
    constants: Callable | None = None
    step: Callable | None = None
    burst_forwards: Callable | None = None
    kv_handoff: bool = True
    tensor_parallel: bool = False
    prefix_from_line: bool = True
    draft_propose: Callable | None = None
    spec_verify_step: Callable | None = None
    refuse: Callable | None = None
    mixed_burst: Callable | None = None
    program_params: Callable | None = None
    pending_step: bool = False

    def __post_init__(self):
        if self.mixed_burst is not None and self.step is not None:
            raise ValueError(
                "a ServedModel that states its step offers no mixed_burst: "
                "a step that carries a chunk is a token a line, sampled "
                "from the step's own logits")
        if (self.step is None) == (self.decode_step is None):
            raise ValueError(
                "a ServedModel has a decode_step or states its step, one "
                "of the two: a step of several positions samples on the "
                "device and has no single-step program, and a model with "
                "such a program takes a token in and gives a token out")
        if self.pending_step and (self.step is None or self.kv_handoff
                                  or self.prefix_from_line):
            raise ValueError(
                "a ServedModel with a pending_step states its step and "
                "offers neither kv_handoff nor prefix_from_line: a line's "
                "newest step is unfinished in its cache until the line's "
                "next burst, and a token a step leaves nothing pending")

    @property
    def prefill_token(self) -> bool:
        """Whether a prompt's last chunk gives the first token: for every
        model but one that states its ``step``."""
        return self.step is None


def _burst_sample(logits, temps, top_ps, key, j, need_top_p: bool):
    """Step ``j``'s tokens of a burst: the sampler on the burst's key folded
    with the step's index."""
    with tracing.part("sample"):
        return sample_tokens(logits, temps, top_ps, 0,
                             jax.random.fold_in(key, j),
                             need_top_p).astype(jnp.int32)


def token_step_programs(step: Callable, counters: tuple[str, ...] = ()):
    """(``decode_step``, ``decode_burst``): the two jitted decode programs
    of a model whose step is a token in and a token out, built from its
    single step

        step(cfg, params, cache, tokens [B], positions [B], write_mask [B],
             kmesh) -> (cache, logits [B, V][, counts])

    under the names a device trace is read by (``jit_decode_step``,
    ``jit_decode_burst``), with ``cfg`` and ``kmesh`` static and the cache
    donated. ``counters`` are the model's own (``ServedModel.counters``):
    where it has any its step returns their int32 counts as a third value,
    and so do both programs, the burst's summed over its steps."""

    @partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
             donate_argnums=(2,))
    def decode_step(cfg, params, cache, tokens, positions, write_mask=None,
                    *, kmesh: KernelMesh | None = None):
        """One decode step for EVERY slot.

        tokens: [B] (last sampled token per slot), positions: [B] (where
        each token is written/attends from). write_mask: [B] bool — slots
        mid-prefill or empty must not have garbage K/V written into their
        cache (False = keep the existing cache line; None = every slot
        writes). Returns (cache, logits [B, V]) and the model's counts."""
        if write_mask is None:
            write_mask = jnp.ones(tokens.shape, bool)
        return step(cfg, params, cache, tokens, positions, write_mask, kmesh)

    @partial(jax.jit, static_argnums=(0, 9, 10), static_argnames=("kmesh",),
             donate_argnums=(2,))
    def decode_burst(cfg, params, cache, token0, positions0, write_mask,
                     temps, top_ps, key, steps: int, need_top_p: bool = True,
                     *, kmesh: KernelMesh | None = None):
        """``steps`` chained decode+sample ticks in ONE dispatch: the sampled
        token feeds the next step on device (lax.scan), so the host⇄device
        roundtrip — a large part of per-token latency for small models — is
        paid once per ``steps`` tokens instead of per token.
        Greedy/temperature/top-p sampling only (top-k needs a static k; the
        engine falls back to single-step ticks). Returns (cache, tokens
        [steps, B]) and the model's counts."""

        def tick(carry, j):
            c, tok, pos, *counts = carry
            c, logits, *n = step(cfg, params, c, tok, pos, write_mask, kmesh)
            nxt = _burst_sample(logits, temps, top_ps, key, j, need_top_p)
            with tracing.part("sample"):
                return (c, nxt, pos + 1,
                        *(a + b for a, b in zip(counts, n))), nxt

        zero = ((jnp.zeros((len(counters),), jnp.int32),) if counters
                else ())
        with tracing.part("stack"):
            (cache, _, _, *counts), toks = lax.scan(
                tick, (cache, token0, positions0, *zero), jnp.arange(steps))
        return (cache, toks, *counts)

    return decode_step, decode_burst


def mixed_burst_program(step: Callable, mixed_step: Callable,
                        counters: tuple[str, ...] = ()):
    """``ServedModel.mixed_burst`` of a model whose step is a token in and a
    token out: ``decode_burst`` (of :func:`token_step_programs`, the same
    sampler on the same keys) whose first ``n`` steps each carry a prefill
    chunk too, built from the model's ``step`` and from

        mixed_step(cfg, params, cache, tokens [B], positions [B],
                   write_mask [B], chunk [C], kv_len, length, slot, kmesh)
            -> (cache, logits [B, V][, counts])

    which runs the chunk's C rows (``prefill_chunk``'s arguments; the slot
    has ``write_mask`` false) and the lines' B through every layer as one
    array and gives the lines' logits alone. The program takes, after
    ``decode_burst``'s arguments up to ``key``, ``riders``: (chunks
    [steps, C], slots [steps], kv_lens [steps], lengths [steps], n), each
    step's chunk with its own run-time scalars, so that consecutive chunks
    of one prompt or chunks of several ride one burst; then ``steps`` and
    ``need_top_p``, static. Steps ``n`` to ``steps`` are ``step``'s: two
    loops with run-time bounds, one compiled shape a burst length whatever
    ``n``. It is jitted under the name ``decode_burst``: a device trace is
    read by program name, and these are decode steps (a reader that divides
    ``jit_decode_burst``'s time by the steps the dispatch phase carried
    keeps a true number).

    A ``mixed_step`` is written by a convention, not from a base: it takes
    its rows from :func:`mixed_rows` and projects all ``C + B`` at once
    (what a chunk and a step both fetch), then splits the products' rows
    before their heads. The attention is two halves, the chunk's write and
    attend and the lines', the very functions the model's ``prefill_chunk``
    and ``step`` close over. Their outputs are joined and pass ``wo`` as one
    array."""

    @partial(jax.jit, static_argnums=(0, 10, 11), static_argnames=("kmesh",),
             donate_argnums=(2,))
    def decode_burst(cfg, params, cache, token0, positions0, write_mask,
                     temps, top_ps, key, riders, steps: int,
                     need_top_p: bool = True, *,
                     kmesh: KernelMesh | None = None):
        chunks, slots, kv_lens, lengths, n = riders

        def tick(j, carry, riding: bool):
            c, tok, pos, toks, *counts = carry
            if riding:
                c, logits, *m = mixed_step(
                    cfg, params, c, tok, pos, write_mask, chunks[j],
                    kv_lens[j], lengths[j], slots[j], kmesh)
            else:
                c, logits, *m = step(cfg, params, c, tok, pos, write_mask,
                                     kmesh)
            nxt = _burst_sample(logits, temps, top_ps, key, j, need_top_p)
            with tracing.part("sample"):
                toks = lax.dynamic_update_index_in_dim(toks, nxt, j, 0)
                return (c, nxt, pos + 1, toks,
                        *(a + b for a, b in zip(counts, m)))

        zero = ((jnp.zeros((len(counters),), jnp.int32),) if counters
                else ())
        carry = (cache, token0, positions0,
                 jnp.zeros((steps, *token0.shape), jnp.int32), *zero)
        with tracing.part("stack"):
            # The device says which of its steps carried a chunk: a trace
            # has one event a program, and the riding ticks' operations
            # carry the kind on their paths (tracing.STEP_KINDS).
            with tracing.part("mixed_step"):
                carry = lax.fori_loop(0, n, partial(tick, riding=True),
                                      carry)
            carry = lax.fori_loop(n, steps, partial(tick, riding=False),
                                  carry)
        cache, _, _, toks, *counts = carry
        return (cache, toks, *counts)

    return decode_burst


def mixed_rows(chunk, tokens, kv_len, length, positions, write_mask):
    """The rows of a decode step that carries a prefill chunk, the chunk's C
    first and then a row a line: their ids [C + B]; their positions [C + B];
    ``valid`` [C + B], a chunk's row inside its prompt and a line that
    decodes; the lines' ``lengths`` [B] once their row is written; and
    ``lines_of``, which takes [1, C + B, ...] to the lines' [B, ...]: the
    head reads those alone, a rider gives no token. The same in every model
    that offers a ``mixed_burst``."""
    c = chunk.shape[0]
    ids = jnp.concatenate([chunk, tokens])
    at = jnp.concatenate([kv_len + jnp.arange(c), positions])
    valid = jnp.concatenate([at[:c] < length, write_mask])
    lengths = jnp.where(write_mask, positions + 1, 0)
    return ids, at, valid, lengths, lambda x: x[0, c:]


def served_model(cfg) -> ServedModel:
    """The model behind a configuration, by its type: the ``SERVED`` of
    the module ``llm/config.SERVING_MODULES`` names for it, imported when
    it is first asked for."""
    for kind in type(cfg).__mro__:
        if kind in SERVING_MODULES:
            return importlib.import_module(SERVING_MODULES[kind]).SERVED
    *names, last = (kind.__name__ for kind in SERVING_MODULES)
    raise TypeError(f"the engine serves no {type(cfg).__name__}: it serves "
                    f"{', '.join(names)} and {last}")


def require_kv_handoff(cfg) -> None:
    """Raise unless the model's cache lines can be handed from a prefill
    engine to a decode engine (llm/pd.py asks before it builds one)."""
    if not served_model(cfg).kv_handoff:
        raise ValueError(
            f"{type(cfg).__name__} does not support the prefill/decode "
            "hand-off: its cache is not per-head K/V")


def require_tensor_parallel(cfg, size: int) -> None:
    """Raise where ``tensor_parallel_size`` asks for more than one device
    and the model's programs do not partition."""
    if size > 1 and not served_model(cfg).tensor_parallel:
        raise ValueError(
            f"{type(cfg).__name__} does not support tensor_parallel_size > "
            "1: its programs run on one device")


def init_params(cfg, key):
    """The served model's own initialiser."""
    return served_model(cfg).init_params(cfg, key)
