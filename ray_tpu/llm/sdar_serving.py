"""What models/sdar.py supplies to the scheduler (llm/served.ServedModel):
generation by diffusion over blocks against the per-head K/V slot cache.

The cache is the Llama one, ``{"k", "v"}`` ``[layers, slots, kv_heads,
max_seq, head_dim]``. What differs is the step (``ServedModel.step``): one
step of one line takes a whole block of ``block_length`` positions in and
gives it back decided, and costs ``denoising_steps`` forwards of the stack,
each over the block's rows:

- a **denoising forward** writes the K/V of the block's current content
  (its decided positions, the mask token at the open ones) at the block's
  positions, attends the line through the block's end (every row sees the
  whole block: ops/decode_attention.py with ``positions0`` at the block's
  last position); the row at an open position chooses that position's
  token (no shift by one) and the rule of the configuration
  (models/sdar.open_positions) says which open positions take theirs now.
  The head and the choice run on the rows that rule can read
  (models/sdar.read_positions: under ``sequential`` the leftmost open ones,
  known before the forward; every row under a rule that reads
  confidences), and no draw is made where no line has a temperature. Its
  K/V rows are overwritten by the next forward and never read by another
  block;
- the **commit** runs the decided block once more, clean; its K/V stay,
  and no head is computed. No forward is spent on it: a block's commit
  **rides** the next block's first denoising forward.

The lines of a burst move in lockstep. A block's first denoising forward
takes ``2 K`` rows a line, the clean block before it and then the open
one, writes the K/V of both and attends the line once
(``decode_attention``'s ``rows_a_limit``: the clean rows see keys up to the
open block's start, the open rows through their block's end, so each row
sees what it saw in a forward of its own, the clean block's K/V of the same
layer included). The clean rows' K/V stay; the head runs on the open
block's rows. A burst's **last** block is left decided and not committed:
its tokens go back to the scheduler with the burst's other tokens, stay on
the device as well, and come in again with the next burst
(``ServedModel.pending_step``: ``pending`` [B, K] beside ``token0``, and
``has_pending`` [B]), whose first forward commits them. So a burst of ``n``
blocks is ``n x denoising_steps`` forwards (:func:`burst_forwards`), the
first of every block a wide one.

A line with nothing pending (one that joins from its prefill, a slot's new
tenant) goes through the same wide forward with its clean half **dead**:
those ``K`` rows write no K/V (they would overwrite the prompt's last whole
block, and lie before the line where its prompt is shorter than a block),
are routed to no expert, are counted nowhere, and nothing reads what they
give (an attention over no key gives zeros, not NaN). And a line's last
block is never committed: a finished line is in no later burst, so nobody
carries its commit, and nothing reads a finished line's K/V
(``prefix_from_line`` and ``kv_handoff`` are both false, and have to be).

Which positions are open is a mask by position, carried from forward to
forward: never a comparison of ids with the mask id, which a prompt may
contain like any other. A line's first block may come partly decided: the
prompt's tokens past its last whole block (``token0`` holds them in their
places, -1 at an open position). Every later block starts all open.

A prompt's whole blocks are prefilled under the same block-causal mask
(ops/prefill_attention.py, ``block``) and yield no token
(``ServedModel.prefill_token``).

The programs keep the contract's names (``prefill_chunk``, ``decode_burst``:
a device trace shows ``jit_<name>``) and return their counts beside their
result (:data:`COUNTERS`, int32[12], summed over layers, forwards and
blocks); the scheduler adds them up where it fetches the tokens.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.llm.served import ServedModel, init_kv_cache, sample_tokens
from ray_tpu.models import sdar
from ray_tpu.models.lfm2 import attention_heads
from ray_tpu.models.routed import MOE_COUNTERS
from ray_tpu.models.sdar import SdarConfig
from ray_tpu.ops.decode_attention import (
    decode_attention,
    decode_kv_block,
    decode_plan_of,
    kv_row_write,
)
from ray_tpu.ops.kernels import KernelMesh
from ray_tpu.ops.prefill_attention import prefill_attention, prefill_kv_write
from ray_tpu.ops.rope import rope_frequencies
from ray_tpu.util import tracing

# Line-blocks run, line-forwards (a forward of two blocks' rows once), the
# line-commits that cost a forward of their own (none: the benchmark's
# share of them reads this name), the positions of first blocks that the
# prompt had decided, the rows that went through the head, and the
# line-commits that rode the next block's first forward (a dead clean half
# is no commit of either kind).
DIFFUSION_COUNTERS = ("diffusion_blocks", "diffusion_forwards",
                      "diffusion_commits", "diffusion_given",
                      "diffusion_head_rows", "diffusion_commits_riding")
COUNTERS = MOE_COUNTERS + DIFFUSION_COUNTERS


def _ran(count, *names):
    """int32[6] in the order of DIFFUSION_COUNTERS: ``count`` under each of
    ``names``, added where the thing counted runs."""
    return count * jnp.asarray([n in names for n in DIFFUSION_COUNTERS],
                               jnp.int32)


def _counts(moe, diffusion=None):
    """int32[12] in the order of COUNTERS; a prefill's diffusion counts
    are zeros."""
    if diffusion is None:
        diffusion = jnp.zeros((len(DIFFUSION_COUNTERS),), jnp.int32)
    return jnp.concatenate([moe, diffusion])


@partial(jax.jit, static_argnums=(0,), static_argnames=("kmesh",),
         donate_argnums=(2,))
def prefill_chunk(cfg: SdarConfig, params, cache, tokens, kv_len, length,
                  slot, *, kmesh: KernelMesh | None = None):
    """Prefill ONE chunk of one sequence's whole blocks (the contract's
    program, see llm/llama_serving.prefill_chunk; ``kv_len`` and ``length`` are
    multiples of the block). Returns (cache, None, counts): no row's logits
    choose a token."""
    c = tokens.shape[0]
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens][None]              # [1, C, H]
    with tracing.part("attn"):
        positions = kv_len + jnp.arange(c)
        valid = (positions < length)[None]
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)

    def attention(layer, ap, xn, kv):
        k_all, v_all = kv
        q, k, v = attention_heads(cfg, ap, xn, positions, inv_freq)
        with tracing.part("cache"):
            k_all, v_all = prefill_kv_write(k_all, v_all, k[0], v[0], layer,
                                            slot, kv_len)
        o = prefill_attention(q[0], k_all, v_all, layer, slot, kv_len,
                              length, kmesh=kmesh, block=cfg.block_length)
        o = o.transpose(1, 0, 2).reshape(1, c, -1)
        return (o @ ap["wo"]).astype(xn.dtype), (k_all, v_all)

    _, (k_all, v_all), moe = sdar.run_layers(
        cfg, params, x, attention, (cache["k"], cache["v"]), valid, kmesh)
    return {"k": k_all, "v": v_all}, None, _counts(moe)


def _forward(cfg: SdarConfig, params, cache, tokens, start, write_mask,
             plan, kmesh=None, clean=None):
    """One forward of every line's rows: tokens [B, R] at positions
    ``start + arange(R)``, R one block of K (``start`` the block's, a
    multiple of K) or two of them side by side, the earlier one clean.
    Writes the rows' K/V and attends each line once: a row sees the line
    through its own block's end. Returns (cache, the stack's output [B, R,
    H] before the final norm, the routed layers' counts). A line with
    ``write_mask`` false writes nothing, is routed nowhere, and its rows
    mean nothing. ``clean`` [B] bool (two blocks; None: every line's):
    whether the line's earlier block is there; where it is not, that half
    is dead (no K/V written, its rows routed nowhere and meaning nothing,
    ``start`` may be negative) and the later block is a forward of its
    own."""
    b, r = tokens.shape
    k = cfg.block_length
    with tracing.part("embed"):
        x = params["embed_tokens"][tokens]                    # [B, R, H]
    with tracing.part("attn"):
        positions = start[:, None] + jnp.arange(r)[None, :]
        lengths = jnp.where(write_mask, start + r, 0)
        valid = jnp.broadcast_to(write_mask[:, None], (b, r))
        if clean is not None:
            valid = valid & (clean[:, None] | (jnp.arange(r) >= k)[None, :])
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)

    def write(kv, kk, v, layer):
        k_all, v_all = kv
        if clean is None:
            return kv_row_write(k_all, v_all, kk, v, layer, start,
                                write_mask, kmesh=kmesh)
        # Each half under its own mask: the kernel's mask is a line's.
        k_all, v_all = kv_row_write(
            k_all, v_all, kk[:, :, :k], v[:, :, :k], layer, start,
            write_mask & clean, kmesh=kmesh)
        return kv_row_write(k_all, v_all, kk[:, :, k:], v[:, :, k:], layer,
                            start + k, write_mask, kmesh=kmesh)

    def attention(layer, ap, xn, kv):
        q, kk, v = attention_heads(cfg, ap, xn, positions, inv_freq)
        with tracing.part("cache"):
            k_all, v_all = write(kv, kk, v, layer)
        # The mask's position is the first block's last. One block: row j
        # sees keys through start + k - 1 + j, and none lies past the
        # line's length. Two: the rows of a block share its limit, the
        # clean block's stops where the open one starts (a dead half at the
        # line's very start sees no key, and gives zeros).
        o = decode_attention(q, k_all, v_all, layer, lengths,
                             start + (k - 1), plan=plan, kmesh=kmesh,
                             rows_a_limit=1 if r == k else k)
        o = o.transpose(0, 2, 1, 3).reshape(b, r, -1)
        return (o @ ap["wo"]).astype(xn.dtype), (k_all, v_all)

    x, (k_all, v_all), moe = sdar.run_layers(
        cfg, params, x, attention, (cache["k"], cache["v"]), valid, kmesh)
    return {"k": k_all, "v": v_all}, x, moe


def _logits(cfg: SdarConfig, params, x, read, kmesh=None):
    """The stack's output x [B, K, H] -> float32 logits [B, r, V] of the
    rows ``read`` names (int32 [B, r] positions of the block), of every row
    where it is None."""
    if read is not None:
        with tracing.part("head"):
            x = jnp.take_along_axis(x, read[:, :, None], axis=1)
    return sdar.lm_head(cfg, params, x, kmesh)


@tracing.part("sample")
def _choose(cfg: SdarConfig, logits, temps, top_ps, key, need_top_p: bool):
    """logits [B, r, V] -> (the token each row's logits choose [B, r], by
    the request's temperature and top-p or greedily, and its probability
    under softmax(logits), float32 [B, r]; None under a rule that does not
    read it). The draw is made only where some line has a temperature:
    ``sample_tokens`` computes it for every row and picks afterwards."""
    b, r, v = logits.shape
    flat = logits.reshape(b * r, v)
    x0 = lax.cond(
        (temps > 0).any(),
        lambda: sample_tokens(flat, jnp.repeat(temps, r),
                              jnp.repeat(top_ps, r), 0, key,
                              need_top_p).astype(jnp.int32),
        lambda: jnp.argmax(flat, axis=-1).astype(jnp.int32))
    if not cfg.reads_confidence:
        return x0.reshape(b, r), None
    chosen = jnp.take_along_axis(flat, x0[:, None], axis=-1)[:, 0]
    confidence = jnp.exp(chosen - jax.nn.logsumexp(flat, axis=-1))
    return x0.reshape(b, r), confidence.reshape(b, r)


@partial(jax.jit, static_argnums=(0, 9, 10), static_argnames=("kmesh",),
         donate_argnums=(2,))
def decode_burst(cfg: SdarConfig, params, cache, inputs, positions0,
                 write_mask, temps, top_ps, key, steps: int,
                 need_top_p: bool = True, *,
                 kmesh: KernelMesh | None = None):
    """``steps`` blocks of every line in ONE dispatch, ``denoising_steps``
    forwards a block (:func:`burst_forwards`): a block's first forward
    commits the block before it, and the last block is left for the next
    burst's first forward. ``inputs`` is (token0, pending, has_pending):
    token0 [B, K], what the first block has decided already (a prompt's
    tail in its places, -1 at an open position); pending [B, K], the block
    the line decided last and nobody has committed (the burst before's last
    tokens), at ``positions0 - K``; has_pending [B], whether the line has
    one. positions0 [B]: the first block's start. Returns (cache, tokens
    [steps, B, K], counts); ``tokens[-1]`` is the next burst's ``pending``."""
    token0, pending, has_pending = inputs
    k = token0.shape[1]
    mask_id = jnp.int32(cfg.mask_token_id)
    with tracing.part("attn"):
        lines = write_mask.sum().astype(jnp.int32)
        has_pending = has_pending & write_mask

    def block(carry, j, clean=None):
        """Block j through its denoising forwards. The carry's ``last``
        [B, K] is the block before, decided and not committed: its rows
        ride this block's first forward, and their K/V are its commit.
        ``clean`` [B] (the burst's first block): the lines that have one."""
        cache, last, moe, diffusion = carry
        pos = positions0 + j * k
        with tracing.part("attn"):
            # Every forward of the block attends at the same lengths, so
            # the walk of the live blocks is planned once a block.
            plan = decode_plan_of(jnp.where(write_mask, pos + k, 0),
                                  cache["k"], kmesh=kmesh)
        with tracing.part("sample"):
            is_open = (token0 < 0) | (j > 0)
            tokens = jnp.where(is_open, mask_id, token0)
            given = ((~is_open) & write_mask[:, None]).sum().astype(jnp.int32)
            riding = (lines if clean is None
                      else clean.sum().astype(jnp.int32))
            diffusion = (diffusion + _ran(given, "diffusion_given")
                         + _ran(lines, "diffusion_blocks")
                         + _ran(riding, "diffusion_commits_riding"))

        def decide(x, tokens, still_open, diffusion, d):
            """What forward ``d``'s output x [B, K, H] decides: (tokens,
            the positions still open, diffusion)."""
            with tracing.part("sample"):
                read = sdar.read_positions(cfg, still_open)
            logits = _logits(cfg, params, x, read, kmesh)
            diffusion = (diffusion + _ran(lines, "diffusion_forwards")
                         + _ran(lines * logits.shape[1],
                                "diffusion_head_rows"))
            x0, confidence = _choose(
                cfg, logits, temps, top_ps,
                jax.random.fold_in(jax.random.fold_in(key, j), d),
                need_top_p)
            with tracing.part("sample"):
                # A rule that reads confidences read every row: only the
                # tokens have positions to go back to.
                x0 = sdar.at_positions(still_open, x0)
                take = sdar.open_positions(cfg, confidence, still_open)
                return (jnp.where(take, x0, tokens), still_open & ~take,
                        diffusion)

        def denoise(carry, d):
            cache, tokens, still_open, moe, diffusion = carry
            cache, x, n = _forward(cfg, params, cache, tokens, pos,
                                   write_mask, plan, kmesh)
            tokens, still_open, diffusion = decide(x, tokens, still_open,
                                                   diffusion, d)
            return (cache, tokens, still_open, moe + n, diffusion), None

        # The block's first forward, peeled off the scan: 2 K rows a line,
        # and the head on the open block's alone.
        cache, x, n = _forward(
            cfg, params, cache, jnp.concatenate([last, tokens], axis=1),
            pos - k, write_mask, plan, kmesh, clean)
        tokens, still_open, diffusion = decide(x[:, k:], tokens, is_open,
                                               diffusion, 0)
        with tracing.part("stack"):
            (cache, tokens, _, moe, diffusion), _ = lax.scan(
                denoise, (cache, tokens, still_open, moe + n, diffusion),
                jnp.arange(1, cfg.denoising_steps))
        return (cache, tokens, moe, diffusion), tokens

    with tracing.part("stack"):
        carry, first = block(
            (cache, pending, jnp.zeros((len(MOE_COUNTERS),), jnp.int32),
             jnp.zeros((len(DIFFUSION_COUNTERS),), jnp.int32)), 0,
            has_pending)
        toks = first[None]
        if steps > 1:
            carry, rest = lax.scan(block, carry, jnp.arange(1, steps))
            toks = jnp.concatenate([toks, rest])
        cache, _, moe, diffusion = carry
    return cache, toks, _counts(moe, diffusion)


def burst_forwards(cfg: SdarConfig, steps: int) -> list[int]:
    """The forwards of the stack each block of a burst of ``steps`` costs,
    each a kernel call a layer at that block's lengths: its denoising
    forwards, the first of them the wide one that commits the block before
    (the burst before's last, for the first block)."""
    return [cfg.denoising_steps] * steps


def _refuse(config) -> None:
    """What this model does not run, said at construction."""
    if config.speculative_model is not None:
        raise ValueError(
            "SdarConfig does not support a speculative draft: a step decides "
            "a block by forwards of its own, there is no token to verify")


SERVED = ServedModel(
    init_params=sdar.init_params,
    param_logical_axes=sdar.param_logical_axes,
    init_cache=init_kv_cache,
    prefill_chunk=prefill_chunk,
    # A step samples between its forwards, on the device: a lone step is a
    # burst of one.
    decode_step=None,
    decode_burst=decode_burst,
    kv_block=lambda cfg, max_seq: decode_kv_block(
        max_seq, cfg.head_dim, cfg.jnp_dtype.itemsize),
    counters=COUNTERS,
    constants=lambda cfg: {"moe_experts_held": cfg.num_experts,
                           "attention_lines": cfg.num_layers,
                           "diffusion_block_length": cfg.block_length},
    step=lambda cfg: (cfg.block_length, cfg.denoising_steps),
    burst_forwards=burst_forwards,
    pending_step=True,
    # Both load-bearing: a line's newest block is decided and not committed
    # (the next burst's first forward commits it, and a finished line's last
    # block nobody), so its K/V rows there are a denoising forward's and no
    # reader but the line's own next burst may come by them. A line's
    # committed blocks could be adopted at block-aligned lengths and shipped
    # as per-head K/V once the pending block went with them (ROADMAP R6).
    kv_handoff=False,
    prefix_from_line=False,
    refuse=_refuse,
)
