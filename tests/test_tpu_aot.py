"""Compile for the TPU without one.

``libtpu`` can describe a ``v5e:2x2`` host and compile for it with no chip
attached, so two things stay checked on every PR: the Pallas kernels still
compile (Mosaic runs inside ``.compile()``), and every program that reaches
them still partitions over a mesh of several chips. XLA cannot partition a
Mosaic call by itself; the wrappers in ray_tpu/ops run it per shard under
``jax.shard_map`` (ops/kernels.py), and a caller that forgets to pass its mesh
down fails here with "Mosaic kernels cannot be automatically partitioned".

Compilations, not runs: what the chip does with them is chip_smoke.py's job.
"""

import collections
import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models.llama import LlamaConfig, init_params, param_logical_axes
from ray_tpu.ops.kernels import force_kernel_backend
from ray_tpu.parallel.hlo_stats import collective_stats
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.parallel.sharding import kernel_mesh, tree_shardings
from ray_tpu.train.spmd import (
    TrainState,
    _opt_shardings,
    make_llama_train_step,
    make_mixtral_train_step,
    make_vit_train_step,
)

# Head width 64 like Llama-3.2-1B, GQA 2:1, everything else small. Weights
# stay under the per-device q bytes of the train batch below, so a gathered
# activation cannot hide among gathered weights.
CFG = LlamaConfig(vocab_size=512, hidden_size=256, intermediate_size=512,
                  num_layers=2, num_heads=8, num_kv_heads=4, head_dim=64,
                  max_seq_len=256, dtype="bfloat16", tie_embeddings=True)
BATCH, SEQ = 8, 256
MOSAIC = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 - no libtpu, or it cannot describe
        pytest.skip(f"cannot create a v5e:2x2 topology: {e!r}")
    return topo.devices


@pytest.fixture()
def mosaic(v5e_2x2):
    with force_kernel_backend("mosaic", v5e_2x2[0].device_kind):
        yield v5e_2x2


def _sds(tree, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def _abstract_state(mesh, init_state, logical_axes, optimizer):
    """init_state()'s shapes with the shardings it would place them on
    (compile-only devices hold no arrays)."""
    shapes = jax.eval_shape(init_state)
    repl = NamedSharding(mesh, P())
    param_sh = tree_shardings(mesh, logical_axes)
    opt_sh = jax.tree.map(
        lambda s: s if s is not None else repl,
        _opt_shardings(optimizer, shapes.params, param_sh),
        is_leaf=lambda x: x is None)
    return TrainState(params=_sds(shapes.params, param_sh),
                      opt_state=_sds(shapes.opt_state, opt_sh),
                      step=jax.ShapeDtypeStruct((), jnp.int32, sharding=repl))


def _collectives(text: str, n: int) -> collections.Counter:
    stats = collective_stats(text, lambda p: 0, n_partitions=n)
    assert stats.skipped_ops == 0
    return collections.Counter((o.op, o.payload_bytes) for o in stats.ops)


def _compile_step(factory, cfg, mesh, logical_axes, batch_shapes):
    opt = optax.adamw(3e-4)
    step_fn, init_state, _ = factory(cfg, mesh, optimizer=opt,
                                     attn_impl="flash")
    state = _abstract_state(mesh, init_state, logical_axes, opt)
    batch_sh = NamedSharding(mesh, P(("dp", "fsdp")))
    batch = [jax.ShapeDtypeStruct(shape, dtype, sharding=batch_sh)
             for shape, dtype in batch_shapes]
    return step_fn.lower(state, *batch).compile().as_text()


@pytest.mark.parametrize("head_dim", [64, 128])
def test_kernels_compile_on_one_chip(mosaic, head_dim):
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.ops.norms import rms_norm

    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())
    q = jax.ShapeDtypeStruct((2, 8, 512, head_dim), jnp.bfloat16, sharding=dev)
    kv = jax.ShapeDtypeStruct((2, 4, 512, head_dim), jnp.bfloat16,
                              sharding=dev)
    w = jax.ShapeDtypeStruct((head_dim,), jnp.bfloat16, sharding=dev)

    def loss(q, k, v, w):
        o = flash_attention(rms_norm(q, w), k, v, True, None, True)
        return o.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, w).compile().as_text()
    assert text.count(MOSAIC) == 3  # flash fwd, fused flash bwd, rms_norm


@pytest.mark.parametrize("axis", ["dp", "fsdp", "tp"])
def test_llama_step_partitions_over_four_chips(mosaic, axis):
    mesh = build_mesh(MeshSpec(**{axis: 4}), mosaic)
    text = _compile_step(
        partial(make_llama_train_step, remat="attn"), CFG, mesh,
        param_logical_axes(CFG), [((BATCH, SEQ), jnp.int32)] * 2)
    assert "num_partitions=4" in text
    # Per layer: 2 rms_norm + flash fwd in the forward, the rms_norm pair
    # again under remat, the fused flash bwd; plus the final norm.
    assert text.count(MOSAIC) == 7
    # Nothing as large as one device's q may be gathered: the kernels run on
    # each chip's own batch rows (dp, fsdp) or heads (tp). FSDP gathers
    # weights, and no weight of CFG is that large.
    q_bytes = BATCH * CFG.num_heads * SEQ * CFG.head_dim * 2 // 4
    gathered = [n for (op, n) in _collectives(text, 4) if op == "all-gather"]
    assert all(n < q_bytes for n in gathered), (gathered, q_bytes)
    if axis == "fsdp":
        assert gathered  # the weights
    else:
        assert not gathered  # dp and tp reduce; they gather nothing


def test_mixtral_step_partitions_over_expert_parallel_chips(mosaic):
    from ray_tpu.models import mixtral

    # A vocabulary no other width of the model equals: the logits are the
    # only arrays of [.., 384].
    cfg = dataclasses.replace(
        mixtral.MixtralConfig.tiny(), hidden_size=256, num_heads=8,
        num_kv_heads=4, head_dim=64, vocab_size=384, dtype="bfloat16")
    mesh = build_mesh(MeshSpec(ep=4), mosaic)
    # 1,024 tokens: a mask of tokens x local experts x capacity (640) has
    # more elements than the layer's [T, H] sum has bytes.
    batch, seq = 8, 128
    text = _compile_step(make_mixtral_train_step, cfg, mesh,
                         mixtral.param_logical_axes(cfg),
                         [((batch, seq), jnp.int32)] * 2)
    assert "num_partitions=4" in text and text.count(MOSAIC) > 0
    # The routed layer moves rows by index and every chip holds every token
    # through the layers: nothing is passed round, no collective is as large
    # as a mask, and the largest are the layer's [T, H] sums in bfloat16.
    tokens, local = batch * seq, cfg.num_experts // 4
    mask = tokens * local * cfg.capacity(tokens)
    rows = tokens * cfg.hidden_size * 2
    ops = _collectives(text, 4)
    assert {op for (op, _) in ops} == {"all-reduce", "all-gather"}, ops
    assert max(n for (_, n) in ops) == rows < mask, ops
    assert f"[{tokens},{local},{cfg.capacity(tokens)}]" not in text
    # The head and the loss run on each chip's quarter of every sequence
    # (mixtral._head_spec): no float32 [T, V] exists whole, and the one
    # gather is the [T, H] cotangent on its way back into the layers (one
    # channel, however many pieces the scheduler cuts it into).
    assert f"f32[{batch},{seq // 4},{cfg.vocab_size}]" in text
    for whole in (f"[{batch},{seq},{cfg.vocab_size}]",
                  f"[{tokens},{cfg.vocab_size}]"):
        assert whole not in text, whole
    assert {n for (op, n) in ops if op == "all-gather"} == {rows}, ops
    assert len(set(re.findall(
        r" all-gather(?:-start)?\(.*?channel_id=(\d+)", text))) == 1
    # Attention does not follow: the flash calls keep every sequence whole.
    flash = [line for line in text.splitlines()
             if MOSAIC in line and "= (" in line and "flash_" in line]
    q = (f"bf16[{batch * cfg.num_kv_heads},"
         f"{cfg.num_heads // cfg.num_kv_heads},{seq},{cfg.head_dim}]")
    assert len(flash) >= 2 and all(q in line for line in flash), flash


def test_vit_step_partitions_over_four_chips(mosaic):
    from ray_tpu.models import vit

    # 64 patches + cls = 65 tokens: ViT sequences are not block multiples.
    cfg = dataclasses.replace(vit.ViTConfig.tiny(), image_size=32,
                              hidden_size=128, num_heads=2, dtype="bfloat16")
    mesh = build_mesh(MeshSpec(dp=4), mosaic)
    text = _compile_step(
        make_vit_train_step, cfg, mesh, vit.param_logical_axes(cfg),
        [((8, 32, 32, cfg.num_channels), jnp.float32), ((8,), jnp.int32)])
    assert "num_partitions=4" in text and text.count(MOSAIC) > 0


def _served_llama_tree(cfg, mesh):
    """The tree as the engine hands it to Llama's programs
    (``program_params`` of ``init_params``' tree: the fused ``wqkv`` beside
    the three), its shapes placed by the served model's own axes."""
    from ray_tpu.llm import llama_serving

    return _sds(
        jax.eval_shape(lambda key: llama_serving.program_params(
            cfg, init_params(cfg, key)), jax.random.PRNGKey(0)),
        tree_shardings(mesh, llama_serving.SERVED.param_logical_axes(cfg)))


def _gathers_no_fused_leaf(text: str, cfg, tp: int) -> bool:
    """The fused leaf comes in split on its last axis, ``1 / tp`` of its
    columns a chip, and no all-gather makes it whole."""
    cols = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    shard = f"[{cfg.num_layers},{cfg.hidden_size},{cols // tp}]"
    whole = f"[{cfg.num_layers},{cfg.hidden_size},{cols}]"
    return (any(shard in line and " parameter(" in line
                for line in text.splitlines())
            and whole not in text and f"[1,{cfg.hidden_size},{cols}]"
            not in text)


def test_engine_programs_partition_over_tensor_parallel_chips(mosaic):
    from ray_tpu.llm import llama_serving

    slots, max_seq = 4, 256
    mesh = build_mesh(MeshSpec(tp=4), mosaic)
    kmesh = kernel_mesh(mesh)
    repl = NamedSharding(mesh, P())
    params = _served_llama_tree(CFG, mesh)
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, P(None, None, "tp"))),
        jax.eval_shape(partial(llama_serving.init_kv_cache, CFG, slots, max_seq)))

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    # 40 tokens: a chunk clamped to the cache tail, not a power of two.
    prefill = llama_serving.prefill_chunk.lower(
        CFG, params, cache, arg((40,)), arg(()), arg(()), arg(()),
        kmesh=kmesh).compile().as_text()
    decode = llama_serving.decode_burst.lower(
        CFG, params, cache, arg((slots,)), arg((slots,)),
        arg((slots,), jnp.bool_), arg((slots,), jnp.float32),
        arg((slots,), jnp.float32), arg((2,), jnp.uint32), 4, False,
        kmesh=kmesh).compile().as_text()
    # attn_norm, mlp_norm, final_norm; prefill attends through
    # ops/prefill_attention.py, decode writes its rows and attends through
    # ops/decode_attention.py, each on its shard's heads.
    assert prefill.count(MOSAIC) == 4 and decode.count(MOSAIC) == 5
    assert '"prefill_attention"' in prefill
    for text in (prefill, decode):
        assert "num_partitions=4" in text
        # Activations are replicated over tp; only reductions cross chips
        # (and the sampled tokens' few bytes).
        assert all(op == "all-reduce" or n <= 64
                   for (op, n) in _collectives(text, 4))
        assert _gathers_no_fused_leaf(text, CFG, 4)


def test_mixed_burst_partitions_over_tensor_parallel_chips(mosaic):
    """The burst whose steps carry a chunk, on the mesh of the test above:
    both halves' kernels run on their shard's heads (the rows of the three
    products are split before their heads, which are the sharded axis), the
    activations stay replicated and only reductions cross chips."""
    from ray_tpu.llm import llama_serving

    slots, max_seq, steps, chunk = 4, 256, 4, 32
    mesh = build_mesh(MeshSpec(tp=4), mosaic)
    repl = NamedSharding(mesh, P())
    params = _served_llama_tree(CFG, mesh)
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, P(None, None, "tp"))),
        jax.eval_shape(partial(llama_serving.init_kv_cache, CFG, slots,
                               max_seq)))

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    riders = (arg((steps, chunk)), arg((steps,)), arg((steps,)),
              arg((steps,)), arg(()))
    text = llama_serving.mixed_burst.lower(
        CFG, params, cache, arg((slots,)), arg((slots,)),
        arg((slots,), jnp.bool_), arg((slots,), jnp.float32),
        arg((slots,), jnp.float32), arg((2,), jnp.uint32), riders, steps,
        False, kmesh=kernel_mesh(mesh)).compile().as_text()
    assert "num_partitions=4" in text
    # a riding step's six and a plain step's five (the test above)
    assert text.count(MOSAIC) == 6 + 5
    for name in ("prefill_attention", "decode_attention", "kv_row_write"):
        assert f'"{name}"' in text or f"%{name}." in text, name
    assert all(op == "all-reduce" or n <= 64
               for (op, n) in _collectives(text, 4))
    assert _gathers_no_fused_leaf(text, CFG, 4)


# Mistral-7B widths, two layers: the decode program of the two serving cells.
MISTRAL = LlamaConfig(vocab_size=32768, hidden_size=4096,
                      intermediate_size=14336, num_layers=2, num_heads=32,
                      num_kv_heads=8, head_dim=128, max_seq_len=4096,
                      dtype="bfloat16", tie_embeddings=False, rope_theta=1e6)


def _mistral_state(mesh, slots, max_seq):
    """Shapes of MISTRAL's weights and of a cache of ``slots`` lines on
    ``mesh`` (KV heads over tp), and a maker of replicated arguments."""
    from ray_tpu.llm import llama_serving

    repl = NamedSharding(mesh, P())
    params = _served_llama_tree(MISTRAL, mesh)
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, P(None, None, "tp"))),
        jax.eval_shape(partial(llama_serving.init_kv_cache, MISTRAL, slots,
                               max_seq)))

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    return params, cache, arg


def _decode_burst_compiled(mesh, kmesh, slots, max_seq, steps=8):
    from ray_tpu.llm import llama_serving

    params, cache, arg = _mistral_state(mesh, slots, max_seq)
    return llama_serving.decode_burst.lower(
        MISTRAL, params, cache, arg((slots,)), arg((slots,)),
        arg((slots,), jnp.bool_), arg((slots,), jnp.float32),
        arg((slots,), jnp.float32), arg((2,), jnp.uint32), steps, False,
        kmesh=kmesh).compile()


def _opcodes_with_shape(text: str, shape: str) -> set[str]:
    """Opcodes of the instructions whose result (or a fused computation's
    parameter) has ``shape``; compiled HLO prints operands by name only."""
    import re

    ops = set()
    for line in text.splitlines():
        if shape in line:
            m = re.search(r"\s([a-z][a-z-]*)\(", line.split(" = ", 1)[-1])
            if m:
                ops.add(m.group(1))
    return ops


def _plans_outside_the_layer_loop(text: str) -> bool:
    """``decode_plan`` is a cumulative sum (a ``reduce-window`` here) and
    comparisons: the compiled program has it, once a step, and the one
    computation that holds the ``decode_attention`` call, the body of the
    loop over layers (or over a looped stack's cache lines), has neither it
    nor a sort."""
    import re

    bodies = [c for c in re.split(r"\n(?=(?:ENTRY )?%[\w.-]+ \()", text)
              if '"decode_attention"' in c]
    return (len(bodies) == 1 and "reduce-window(" in text
            and "reduce-window(" not in bodies[0]
            and " sort(" not in bodies[0])


@pytest.mark.parametrize("slots,max_seq", [(32, 2048), (16, 3200)])
def test_decode_burst_moves_no_whole_cache_at_mistral_widths(mosaic, slots,
                                                             max_seq):
    compiled = _decode_burst_compiled(
        build_mesh(MeshSpec(), mosaic[:1]), None, slots, max_seq)
    text = compiled.as_text()
    assert text.count(MOSAIC) == 5
    assert '"decode_attention"' in text and '"kv_row_write"' in text
    hkv, d = MISTRAL.num_kv_heads, MISTRAL.head_dim
    group = MISTRAL.num_heads // hkv
    # No K/V repeated over the query heads of a group.
    assert f"[{slots},{hkv},{group},{max_seq},{d}]" not in text
    # The stacked cache and a layer of it only pass through: program
    # arguments, loop carries, and the two kernels' in-place operands.
    passing = {"parameter", "get-tuple-element", "tuple", "while",
               "custom-call", "bitcast"}
    for shape in (f"[{MISTRAL.num_layers},{slots},{hkv},{max_seq},{d}]",
                  f"[{slots},{hkv},{max_seq},{d}]"):
        assert _opcodes_with_shape(text, shape) <= passing, shape
    # The walk of the live blocks is planned once a step, not once a layer.
    assert _plans_outside_the_layer_loop(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("slots,max_seq",
                         [(32, 2048), (16, 3200), (32, 3072)])
def test_prefill_chunk_moves_no_whole_cache_at_mistral_widths(mosaic, slots,
                                                              max_seq):
    """The three serving shapes of the benchmark, a chunk of 512: the cache
    rides the layer loop as carry, the chunk's rows go in by an in-place
    dynamic-update-slice, attention reads the stack through the kernel."""
    from ray_tpu.llm import llama_serving

    chunk = 512
    params, cache, arg = _mistral_state(
        build_mesh(MeshSpec(), mosaic[:1]), slots, max_seq)
    compiled = llama_serving.prefill_chunk.lower(
        MISTRAL, params, cache, arg((chunk,)), arg(()), arg(()),
        arg(())).compile()
    text = compiled.as_text()
    assert text.count(MOSAIC) == 4 and '"prefill_attention"' in text
    hkv, d, h = MISTRAL.num_kv_heads, MISTRAL.head_dim, MISTRAL.num_heads
    # No K/V repeated over the query heads of a group, no slot's line
    # sliced out, no dense [C, max_seq] scores, no logits of every row.
    for shape in (f"[{slots},{hkv},{h // hkv},{max_seq},{d}]",
                  f"[1,{h},{max_seq},{d}]", f"[1,{hkv},{max_seq},{d}]",
                  f"[1,{h},{chunk},{max_seq}]", f"[{h},{chunk},{max_seq}]",
                  f"[{chunk},{MISTRAL.vocab_size}]"):
        assert shape not in text, shape
    # The stacked cache only passes through, or is written in place; a
    # layer of it is never an operand or a result.
    passing = {"parameter", "get-tuple-element", "tuple", "while",
               "custom-call", "bitcast", "dynamic-update-slice"}
    stack = f"[{MISTRAL.num_layers},{slots},{hkv},{max_seq},{d}]"
    assert _opcodes_with_shape(text, stack) <= passing
    assert not _opcodes_with_shape(text, f"[{slots},{hkv},{max_seq},{d}]")
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# The dense module's programs at the depth and cache of the three Mistral
# serve cells, as the cells compile them: the tree as the engine hands it
# over (devbench/llama_bench.shapes).
LLAMA_CELLS = [("serve_docqa", 16, 16, 3200), ("serve_reason", 12, 32, 3072),
               ("serve_chat", 12, 32, 2048)]


def _llama_cell(mosaic, use, layers, slots, max_seq):
    """(cfg, {program: a function that lowers it}, the shapes to look for)
    of ``use``'s cell on one described chip."""
    from devbench import llama_bench as bench

    cfg, *cell = bench.config(use)
    assert (cfg.num_layers, *cell) == (layers, slots, max_seq)
    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    params, cache = bench.shapes(cfg, slots, max_seq, placed)
    assert "wqkv" in params["layers"]
    return (cfg, bench.lowerings(cfg, params, cache, arg),
            bench.big_shapes(cfg, slots, max_seq))


CARRIED = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}


@pytest.mark.parametrize("program", ["prefill_chunk(512)", "decode_burst(8)",
                                     "mixed_burst(8)"])
@pytest.mark.parametrize("use,layers,slots,max_seq", LLAMA_CELLS)
def test_llama_programs_read_every_projection_in_place(
        mosaic, use, layers, slots, max_seq, program):
    """q, k and v are one product against the fused ``wqkv``, which the
    layer loop reads where it lies, as it reads ``wo``: a stacked projection
    passes through, a layer of it is a ``dynamic-slice`` that feeds the
    product, and nothing else has either shape. With three leaves XLA
    re-laid each stack out once a burst (0.56 GiB of temporaries at 12
    layers), copied a layer's slice of each out in every layer, and, where
    the re-laid ``wk`` fitted the fast memory (96 MiB at 12 layers),
    evicted it whole and fetched it back in four quarters in every layer
    (``copy-done``, four ``slice-done``, a ``ConcatBitcast``): a third of
    chat's and reason's step (PERF.md section 6, PR 57). The three leaves
    the tree still carries are nobody's operand."""
    from devbench import llama_bench as bench

    cfg, lower, big = _llama_cell(mosaic, use, layers, slots, max_seq)
    compiled = lower[program]().compile()
    text = compiled.as_text()
    for leaf in ("wq", "wk", "wo", "wqkv"):
        for shape in (big[leaf], big[f"{leaf}_layer"]):
            ops = set(bench.opcodes_with_shape(text, shape))
            assert ops <= CARRIED | {"dynamic-slice"}, (leaf, shape, ops)
    assert big["wqkv_layer"] in text and big["wk_layer"] not in text
    # no stack in the fast memory, none fetched back from it
    stack = rf"bf16\[{layers},\d{{4,}},\d{{4,}}\]"     # widths, not rows
    assert re.search(stack, text)
    assert not re.search(stack + r"{[^}]*S\(1\)", text)
    assert "slice-done(" not in text and "ConcatBitcast" not in text
    if program != "prefill_chunk(512)":
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 26


@pytest.mark.parametrize("use,layers,slots,max_seq", LLAMA_CELLS)
def test_llama_mixed_burst_copies_no_cache_and_no_weight_of_its_own(
        mosaic, use, layers, slots, max_seq):
    """``mixed_burst(8)`` holds both loops' bodies: the chunk's 512 rows go
    in by an update in place and the lines' by the row kernel on the one
    stack, which only passes through; a layer of it is nobody's operand.
    Arguments and temporaries fit the chip's 15.75 GiB. The steps past the
    riders are ``decode_burst``'s, and neither program copies a stacked
    weight (until PR 57 XLA re-laid ``wq``, ``wk`` and ``wv`` out once a
    burst for a step's 16 or 32 rows: ``PERF.md`` section 6): the mixed
    one's temporaries are the plain burst's, the riding steps' 528 or 544
    rows of activations aside."""
    from devbench import llama_bench as bench

    cfg, lower, big = _llama_cell(mosaic, use, layers, slots, max_seq)
    mixed, plain = (lower[name]().compile()
                    for name in ("mixed_burst(8)", "decode_burst(8)"))
    text = mixed.as_text()
    for name in ("prefill_attention", "decode_attention", "kv_row_write"):
        assert f'"{name}"' in text or f"%{name}." in text, name
    # A riding step's five a layer (two norms, the chunk's attention, the
    # rows' write, the lines' attention) and its final norm on the lines'
    # rows; a plain step's four and its final norm.
    assert text.count(MOSAIC) == 5 + 1 + 4 + 1
    mem, plain_mem = mixed.memory_analysis(), plain.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30 \
        < 15.75
    assert mem.temp_size_in_bytes < plain_mem.temp_size_in_bytes + (1 << 26)
    assert set(bench.opcodes_with_shape(text, big["cache"])) <= \
        CARRIED | {"dynamic-update-slice", "custom-call"}
    assert not bench.opcodes_with_shape(text, big["cache_layer"])
    for leaf in ("wq", "wk", "wo", "wqkv", "w_gate", "w_down"):
        ops = bench.opcodes_with_shape(text, big[leaf])
        assert set(ops) <= CARRIED, leaf    # no copy
    # No logits of the chunk's rows: the head runs on the lines' alone.
    assert f"f32[{slots},{cfg.vocab_size}]" in text
    assert f"[{512 + slots},{cfg.vocab_size}]" not in text
    assert _plans_in_no_layer_loop_of(text, loops=2)


def _plans_in_no_layer_loop_of(text: str, loops: int) -> bool:
    """The plan's cumulative sum (a ``reduce-window``) is in the program,
    and none of the ``loops`` computations that hold a ``decode_attention``
    call (a burst's riding steps' layer loop and its plain steps') has it
    or a sort. A body is found by the call's instruction: the kernel's
    quoted name stands once a program, in the module's header, which is
    the one "body" :func:`_plans_outside_the_layer_loop` finds (ROADMAP
    D8)."""
    bodies = [c for c in re.split(r"\n(?=(?:ENTRY )?%[\w.-]+ \()", text)[1:]
              if re.search(r"%decode_attention\.\d+ = ", c)]
    return (len(bodies) == loops and "reduce-window(" in text
            and not any("reduce-window(" in b or " sort(" in b
                        for b in bodies))


@pytest.mark.parametrize("slots", [32, 16])
def test_token_hand_off_helpers_compile_beside_the_programs_they_feed(mosaic,
                                                                      slots):
    """The two helpers that keep a burst's input tokens on the device, at
    the serving cells' slot counts (32 is chat's, reason's and LongCat's)
    and every burst length the scheduler chooses: a slice and an in-place
    update of int32[slots], nothing else, so a step's time does not see
    them."""
    from ray_tpu.llm import engine

    mesh = build_mesh(MeshSpec(), mosaic[:1])
    repl = NamedSharding(mesh, P())

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=repl)

    for steps in (8, 4, 2):
        text = engine._last_row.lower(arg((steps, slots))).compile().as_text()
        assert f"s32[{slots}]" in text and MOSAIC not in text
    text = engine._join_token.lower(
        arg((slots,)), arg((1,)), arg(())).compile().as_text()
    assert "dynamic-update-slice" in text and MOSAIC not in text


def test_decode_burst_partitions_at_mistral_widths(mosaic):
    mesh = build_mesh(MeshSpec(tp=2), mosaic[:2])
    text = _decode_burst_compiled(mesh, kernel_mesh(mesh), 32, 2048).as_text()
    assert "num_partitions=2" in text and text.count(MOSAIC) == 5
    assert all(op == "all-reduce" or n <= 256
               for (op, n) in _collectives(text, 2))


def test_decode_kernels_compile_for_speculative_verify(mosaic):
    """K = 5 rows a slot (``speculative_tokens + 1``): 20 query rows a KV
    head, and a write that may cross a 16-row window."""
    from ray_tpu.ops.decode_attention import decode_attention, kv_row_write

    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())
    slots, k, s = 32, 5, 2048
    hkv, d, h = MISTRAL.num_kv_heads, MISTRAL.head_dim, MISTRAL.num_heads

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    def step(q, kc, vc, nk, nv, layer, pos, write):
        kc, vc = kv_row_write(kc, vc, nk, nv, layer, pos, write)
        lengths = jnp.where(write, pos + k, 0)
        return kc, vc, decode_attention(q, kc, vc, layer, lengths, pos)

    cache = sds((2, slots, hkv, s, d))
    text = jax.jit(step, donate_argnums=(1, 2)).lower(
        sds((slots, h, k, d)), cache, cache, sds((slots, hkv, k, d)),
        sds((slots, hkv, k, d)), sds((), jnp.int32),
        sds((slots,), jnp.int32), sds((slots,), jnp.bool_)).compile().as_text()
    assert text.count(MOSAIC) == 2
    assert _opcodes_with_shape(text, f"[2,{slots},{hkv},{s},{d}]") <= {
        "parameter", "get-tuple-element", "tuple", "custom-call", "bitcast"}


def _grouped_matmul_rows(text: str) -> set[int]:
    """The rows of every grouped matmul's result in a compiled program:
    (picks // tile + experts held) * tile, so they tell the tile."""
    return {int(n) for n in re.findall(
        r"%moe_grouped_matmul[.\d]* = bf16\[(\d+),", text)}


# LongCat-Flash at the published widths, one double layer, 4 of 512 experts:
# the programs of llm/longcat_serving.py as the serving cell compiles them.
def _longcat_programs(mosaic, slots=32, max_seq=8192):
    from ray_tpu.llm import longcat_serving as serving
    from ray_tpu.models import longcat

    cfg = longcat.LongcatConfig(num_layers=1, vocab_size=16384,
                                max_seq_len=max_seq, expert_shards=128)
    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    params = placed(jax.eval_shape(partial(longcat.init_params, cfg),
                                   jax.random.PRNGKey(0)))
    cache = placed(jax.eval_shape(partial(serving.init_cache, cfg, slots,
                                          max_seq)))
    prefill = serving.prefill_chunk.lower(
        cfg, params, cache, arg((512,)), arg(()), arg(()), arg(())).compile()
    burst = serving.decode_burst.lower(
        cfg, params, cache, arg((slots,)), arg((slots,)),
        arg((slots,), jnp.bool_), arg((slots,), jnp.float32),
        arg((slots,), jnp.float32), arg((2,), jnp.uint32), 8,
        False).compile()
    return cfg, prefill, burst


def test_longcat_programs_move_no_whole_cache_and_copy_no_layer(mosaic):
    """What the first compile of PR 27 got wrong, kept from coming back: a
    576-wide cache is stored positions-innermost and copied whole around
    every kernel call (so rows are 640 wide), and a scanned slice that the
    pair of a double layer shares is copied out of the stack (so every leaf
    is indexed where it is used)."""
    cfg, prefill, burst = _longcat_programs(mosaic)
    assert cfg.latent_row == 640
    stack = f"[2,32,8192,{cfg.latent_row}]"
    # Picks in tiles of 16 in both: a chunk brings a held expert 8 rows, a
    # step of 32 lines half a row (models/routed.row_tile).
    for compiled, kernels, passing, picks in (
            (burst, ("latent_decode_attention", "latent_row_write",
                     "moe_grouped_matmul"),
             {"parameter", "get-tuple-element", "tuple", "while",
              "custom-call", "bitcast"}, 32 * cfg.moe_topk),
            (prefill, ("latent_prefill_attention", "moe_grouped_matmul"),
             {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
              "dynamic-update-slice", "dynamic-slice", "fusion",
              "custom-call"},
             512 * cfg.moe_topk)):
        text = compiled.as_text()
        for name in kernels:
            assert f"%{name}." in text
        assert _grouped_matmul_rows(text) == {
            (picks // 16 + cfg.experts_held) * 16}
        assert _opcodes_with_shape(text, stack) <= passing
        # A block-visit's float32 scores (64 heads x 512 queries x 512 rows)
        # stay in the chunk kernel's VMEM: no operation writes them out.
        assert not _opcodes_with_shape(text, "f32[64,512,512]")
        # No whole dense FFN matrix as the result of a copy or a slice
        # fusion at the top of the layer loop.
        for line in text.splitlines():
            head = line.split(" = ", 1)
            if len(head) == 2 and head[1].startswith("bf16[6144,12288]"):
                assert " convolution(" in line or " parameter(" in line \
                    or "bitcast" in line or "fused_computation" in line \
                    or "dynamic-slice" in line, line[:200]
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# Ouro-2.6B whole at the shapes of its serving cell (8 slots x 768): the
# programs of llm/ouro_serving.py as the cell compiles them.
@pytest.mark.parametrize("program", ["prefill_chunk(512)",
                                     "prefill_chunk(64)", "decode_burst(8)"])
def test_ouro_programs_copy_no_weight_stack_and_fit_the_chip(mosaic,
                                                             program):
    """The looped stack reads its stacked weights once a pass and carries a
    cache of 192 lines through two loops. What the first compile of PR 34
    got wrong, kept from coming back: the split of q, k and v into heads
    folded into their products made XLA copy three stacked matrices
    (bf16[48,2048,2048], 1.1 GiB of temporaries) at the top of every
    program. The cache, a pass's lines of it and every stacked weight only
    pass through; arguments and temporaries fit the chip's 15.75 GiB."""
    from devbench import ouro_bench as bench

    cfg = bench.config()
    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    params, cache = bench.shapes(cfg, placed)
    compiled = bench.lowerings(cfg, params, cache, arg)[program]().compile()
    passing = {"parameter", "get-tuple-element", "tuple", "while",
               "custom-call", "bitcast"}
    if program.startswith("prefill"):
        kernels = ("prefill_attention",)
        passing.add("dynamic-update-slice")
    else:
        kernels = ("decode_attention", "kv_row_write")
    text = compiled.as_text()
    for name in kernels:
        assert f'"{name}"' in text
    if program.startswith("decode"):
        # 192 calls a step: a plan rebuilt at each would spend what the
        # walk of the live blocks saves.
        assert _plans_outside_the_layer_loop(text)
    big = bench.big_shapes(cfg)
    assert cfg.cache_lines == 192 and big["cache"].startswith("[192,8,16,768,")
    assert _opcodes_with_shape(text, big["cache"]) <= passing
    # a pass's 48 lines, one line, one slot's line: never cut out
    for shape in (big["pass_lines"], big["line"],
                  f"[1,{cfg.num_kv_heads},{bench.MAX_SEQ},{cfg.head_dim}]"):
        assert not _opcodes_with_shape(text, shape), shape
    carried = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
               "fusion", "dynamic-slice"}   # a fusion's parameter, its slice
    for name in ("w_attn", "w_up", "w_down"):
        assert _opcodes_with_shape(text, big[name]) <= carried, name
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 28
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30 \
        < 15.5


# The first 10 layers of LFM2-24B-A2B with all 64 experts at the shapes of
# its serving cell (64 slots x 8,192): the programs of llm/lfm2_serving.py
# as the cell compiles them.
@pytest.mark.parametrize("program", ["prefill_chunk(512)", "decode_burst(8)"])
def test_lfm2_programs_move_no_cache_nor_expert_stack_and_fit_the_chip(
        mosaic, program):
    """Two kinds of cache leaf ride every loop as carry: the packed
    attention lines (a head of 64 beside its value in one row of 128, so a
    cached position costs its 4 KiB and not 8) and the convolutions' state.
    Neither, nor a line or a slot of them, nor the 9 GiB of stacked experts
    or a layer of them, is the result of anything but a parameter, a loop's
    tuple, a kernel's in-place operand or an update in place; arguments and
    temporaries fit the chip's 15.75 GiB. What XLA does copy whole, and
    this allows: one of the two small dense stacks (the leading layers'
    SwiGLU, bf16[2,11776,2048], 96 MB) into fast memory at the top of their
    loop's body, which costs a tenth of a millisecond a step (PERF.md)."""
    from devbench import lfm2_bench as bench

    cfg = bench.config()
    assert (cfg.attention_lines, cfg.conv_lines, cfg.experts_held) == \
        (2, 8, 64)
    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    params, cache = bench.shapes(cfg, placed)
    kv = cache["kv"]
    assert kv.shape == (2, bench.SLOTS, 8, bench.MAX_SEQ, 128)
    assert kv.size * kv.dtype.itemsize // (bench.SLOTS * bench.MAX_SEQ) \
        == 4096
    compiled = bench.lowerings(cfg, params, cache, arg)[program]().compile()
    text = compiled.as_text()
    carried = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
    if program.startswith("prefill"):
        kernels = ("prefill_attention", "moe_grouped_matmul")
        # written in place; the attention kernel's line names its operand
        in_place = {"dynamic-update-slice", "custom-call"}
        # 512 x 4 picks over 64 experts are 32 rows each: tiles of 64, so
        # that an expert's weights are fetched once (models/routed.row_tile)
        assert _grouped_matmul_rows(text) == {(2048 // 64 + 64) * 64}
    else:
        kernels = ("decode_attention", "kv_row_write", "moe_grouped_matmul")
        in_place = {"custom-call"}
        assert _plans_outside_the_layer_loop(text)
        # 64 lines x 4 picks are 4 rows an expert: tiles of 16 as ever
        assert _grouped_matmul_rows(text) == {(256 // 16 + 64) * 16}
    for name in kernels:
        assert f'"{name}"' in text or f"%{name}." in text, name
    big = bench.big_shapes(cfg)
    assert _opcodes_with_shape(text, big["kv"]) <= carried | in_place
    # the state: read a line (a fusion's parameter), written in place
    assert _opcodes_with_shape(text, big["conv"]) <= \
        carried | {"dynamic-update-slice", "fusion"}
    for shape in ("kv_line", "kv_slot", "experts_layer_up",
                  "experts_layer_down", "embed_f32"):
        got = _opcodes_with_shape(text, big[shape])
        # inside a fusion the float32 embedding is a convert that is never
        # stored (the temporaries below hold the program to that)
        assert got <= ({"convert", "broadcast", "multiply"}
                       if shape == "embed_f32" else set()), (shape, got)
    for shape in ("experts_up", "experts_down", "conv_in", "conv_out",
                  "embed"):
        assert _opcodes_with_shape(text, big[shape]) <= \
            carried | {"fusion", "custom-call", "dynamic-slice"}, shape
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 28
    assert 11.5 < (mem.argument_size_in_bytes + mem.temp_size_in_bytes) \
        / 2 ** 30 < 12.5


# The first 6 layers of SDAR-30B-A3B-Chat with all 128 experts at the shapes
# of its serving cell (128 slots x 1,536): the programs of
# llm/sdar_serving.py as the cell compiles them.
@pytest.mark.parametrize("program", ["prefill_chunk(512)", "decode_burst(1)",
                                     "decode_burst(2)"])
def test_sdar_programs_move_no_cache_nor_expert_stack_and_fit_the_chip(
        mosaic, program):
    """The Llama cache rides every loop as carry: the blocks' loop, the
    denoising forwards' and the layers'. No leaf of it, nor a layer or a
    line of one, nor the 6.75 GiB of stacked experts or a layer of them,
    nor the head (0.58 GiB, read once a denoising forward), is the result
    of anything but a parameter, a loop's tuple, a kernel's in-place
    operand or an update in place; arguments and temporaries fit the chip's
    15.75 GiB (10.38: weights 8.13, lines 2.25; the float32 logits are
    those of the 128 rows the ``sequential`` rule can read, not of the
    block's 512, and at 74 MiB the compiler keeps them in fast memory, so
    they are no temporary at all). The prefill computes no head, so the
    head is no argument of it. A block's forwards attend at the same
    lengths, the wide one that commits the block before among them: one
    plan a block, outside the forwards' and the layers' loops. Since PR 63
    a burst's first forward is a wide one too (the block the burst before
    handed over, each half written under its own mask), so a burst of one
    block holds the wide forward's temporaries as a burst of two did, and
    neither more than that; both K/V stacks come back in the buffers they
    went in."""
    from devbench import sdar_bench as bench

    cfg = bench.config()
    assert (cfg.num_layers, cfg.num_experts, cfg.block_length) == (6, 128, 4)
    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    params, cache = bench.shapes(cfg, placed)
    k = cache["k"]
    assert k.shape == (6, bench.SLOTS, 4, bench.MAX_SEQ, 128)
    assert 2 * k.size * k.dtype.itemsize // (bench.SLOTS * bench.MAX_SEQ) \
        == 12 * 1024
    compiled = bench.lowerings(cfg, params, cache, arg)[program]().compile()
    text = compiled.as_text()
    carried = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30
    if program.startswith("prefill"):
        kernels = ("prefill_attention", "moe_grouped_matmul")
        in_place = {"dynamic-update-slice", "custom-call"}
        # 512 x 8 picks over 128 experts are 32 rows each: tiles of 64
        assert _grouped_matmul_rows(text) == {(4096 // 64 + 128) * 64}
        assert mem.temp_size_in_bytes < 1 << 27
        assert 9.7 < total < 9.95          # no head among the arguments
    else:
        kernels = ("decode_attention", "kv_row_write", "moe_grouped_matmul")
        in_place = {"custom-call"}
        assert _plans_outside_the_layer_loop(text)
        # the donated cache is the result's buffers: arguments 15 and 16
        # (after the 15 leaves of the weights), outputs 0 and 1
        alias = text[text.index("input_output_alias="):
                     text.index("entry_computation_layout=")]
        assert re.findall(r"{(\d)}: \((\d+),", alias) == \
            [("0", "15"), ("1", "16")], alias
        # 128 lines x 4 rows x 8 picks: 32 rows an expert here too; and the
        # forward a commit rides (PR 61), 8 rows a line: 64 an expert, in
        # tiles of 128, one tile and one fetch of its weights an expert
        assert _grouped_matmul_rows(text) == {(4096 // 64 + 128) * 64,
                                              (8192 // 128 + 128) * 128}
        # a row a line goes through the head: no product of all 512 rows
        # (nor of the wide forward's 1,024), and the 128 rows' logits are
        # no 0.29 GiB of temporaries: what there is (0.14 GiB) is the wide
        # forward's rows in tiles, 24,576 x 2,048 (96 MiB), and its products
        assert "f32[128,151936]" in text and "f32[512,151936]" not in text
        assert "f32[1024,151936]" not in text
        assert mem.temp_size_in_bytes < 160 << 20
        assert 10.3 < total < 10.6
    for name in kernels:
        assert f'"{name}"' in text or f"%{name}." in text, name
    big = bench.big_shapes(cfg)
    assert _opcodes_with_shape(text, big["kv"]) <= carried | in_place
    for shape in ("kv_layer", "kv_line", "experts_layer_up",
                  "experts_layer_down", "head_f32"):
        assert _opcodes_with_shape(text, big[shape]) == set(), shape
    for shape in ("experts_up", "experts_down", "wq", "embed", "head"):
        assert _opcodes_with_shape(text, big[shape]) <= \
            carried | {"fusion", "custom-call", "dynamic-slice"}, shape


# DeepSeek-V2's dense layer and seven routed ones at the published widths,
# one group of 20 experts, at the shapes of its serving cell (16 slots x
# 16,384): the programs of llm/deepseek_serving.py as the cell compiles them.
@pytest.mark.parametrize("program", ["prefill_chunk(512)", "decode_burst(8)",
                                     "mixed_burst(8)"])
def test_deepseek_programs_copy_no_cache_nor_stacked_leaf_and_fit_the_chip(
        mosaic, program):
    """The latent cache rides both layer loops as carry, and every stacked
    leaf is indexed where it is used. What the first compile of PR 45 got
    wrong, kept from coming back: at 128 heads XLA folded the split of the
    queries into heads into their product and copied the whole stacked
    ``wq_b`` (bf16[8,1536,24576], 0.56 GiB) transposed at the top of every
    decode program, and the stacked ``wkv_b`` (0.25 GiB) head-major beside
    it; the product is kept an array (``mla_project(keep_product=True)``)
    and ``wkv_b`` is stored a head at a time. Arguments and temporaries fit
    the chip's 15.75 GiB with room for the float32 reference's check. The
    burst whose steps carry a chunk (PR 53) holds both loops' bodies: the
    chunk's rows by an update in place and the lines' by the row kernel on
    the one stack, 528 rows through the experts in the chunk's tiles."""
    from devbench import deepseek_bench as bench

    cfg = bench.config()
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.experts_held,
            cfg.num_heads, cfg.latent_row) == (8, 1, 20, 128, 640)
    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    params, cache = bench.shapes(cfg, placed)
    lat = cache["latent"]
    assert lat.shape == (8, bench.SLOTS, bench.MAX_SEQ, 640)
    assert lat.size * lat.dtype.itemsize == 2.5 * 2 ** 30
    compiled = bench.lowerings(cfg, params, cache, arg)[program]().compile()
    text = compiled.as_text()
    carried = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30
    if program.startswith("prefill"):
        # The chunk's rows go in by an update in place; the chunk kernel
        # reads the stack where it lies.
        kernels = ("latent_prefill_attention", "moe_grouped_matmul")
        in_place = {"dynamic-update-slice", "custom-call"}
        # 512 x 6 picks over 160 outputs are 19 rows an expert: tiles of 64
        assert _grouped_matmul_rows(text) == {(3072 // 64 + 20) * 64}
        assert mem.temp_size_in_bytes < 1 << 26
    elif program.startswith("mixed"):
        kernels = ("latent_prefill_attention", "latent_decode_attention",
                   "latent_row_write", "moe_grouped_matmul")
        in_place = {"dynamic-update-slice", "custom-call"}
        # the riding steps' 528 x 6 picks in tiles of 64 (19.8 rows an
        # expert), the steps after them as ``decode_burst``'s
        assert _grouped_matmul_rows(text) == {
            (3168 // 64 + 20) * 64, (96 // 16 + 20) * 16}
        assert mem.temp_size_in_bytes < 1 << 28
    else:
        kernels = ("latent_decode_attention", "latent_row_write",
                   "moe_grouped_matmul")
        in_place = {"custom-call"}
        # 16 x 6 picks: 0.6 rows an expert, tiles of 16
        assert _grouped_matmul_rows(text) == {(96 // 16 + 20) * 16}
        assert mem.temp_size_in_bytes < 1 << 27
    assert 12.0 < total < 12.4
    # A block-visit's float32 scores (128 heads x 512 queries x 512 rows,
    # 134 MB) stay in the chunk kernel's VMEM: no operation writes them out.
    assert not _opcodes_with_shape(text, "f32[128,512,512]")
    for name in kernels:
        assert f'"{name}"' in text or f"%{name}." in text, name
    big = bench.big_shapes(cfg)
    assert _opcodes_with_shape(text, big["cache"]) <= carried | in_place
    for shape in ("cache_layer", "cache_slot"):
        assert _opcodes_with_shape(text, big[shape]) == set(), shape
    for shape in ("we_in", "we_down", "ws_in", "ws_down", "wq_b", "wkv_b",
                  "wo"):
        assert _opcodes_with_shape(text, big[shape]) <= \
            carried | {"custom-call"}, shape


def _step_calls(text: str, state: str,
                kernel: str = "gated_delta_step") -> int:
    """``gated_delta_step`` calls (or ``kernel``'s) of a compiled program,
    each a Mosaic call whose result holds a leaf of shape ``state``."""
    calls = [line for line in text.splitlines()
             if " custom-call(" in line
             and line.split(" = ", 1)[0].split()[-1].startswith(
                 "%" + kernel)]
    assert all(MOSAIC in line and state in line.split(" custom-call(")[0]
               for line in calls), calls[:1]
    return len(calls)


def _rematerialised(text: str, shape: str) -> list[str]:
    """The instructions of ``shape`` that the compiler computes a second
    time (``.remat`` in their names)."""
    return [line for line in text.splitlines()
            if ".remat" in line.split(" = ", 1)[0]
            and shape in line.split("(", 1)[0]]


# Qwen3-Next's first 16 layers (12 Gated DeltaNet, 4 gated attentions) at the
# published widths with 64 of 512 experts, at the shapes of its serving cell
# (16 slots x 32,768): the programs of llm/qwen3_next_serving.py as the cell
# compiles them.
@pytest.mark.parametrize("program", ["prefill_chunk(512)", "decode_burst(8)"])
def test_qwen3_next_programs_copy_no_state_nor_expert_stack_and_fit_the_chip(
        mosaic, program):
    """Three kinds of cache leaf ride every loop as carry: the attention
    lines (keys and values at heads of 256), the gated delta rule's state
    (float32, 2 MiB a slot and layer: 0.375 GiB in all) and the
    convolutions' windows. None, nor a stacked leaf of the experts or of the
    operators, is the result of anything but a parameter, a loop's tuple, a
    kernel's in-place operand or an update in place (the finding of PR 27: a
    stacked leaf indexed by a loop's counter is copied whole unless it is
    indexed where it is used). A prefill chunk writes a slot's state by a
    ``dynamic-update-slice`` into the leaf; a decode step hands the leaf and
    the line to ``gated_delta_step``, whose kernel reads a line's states
    once and writes them once in place (PR 59: no line is sliced out of the
    leaf, no fusion reads it, nothing writes one back), one call a linear
    layer of the scanned group. Arguments and temporaries are what
    benchmark/configs/qwen3-next-80b-a3b.json states under ``reduced``."""
    from devbench import qwen3_next_bench as bench

    cfg = bench.config()
    assert (cfg.num_layers, cfg.linear_lines, cfg.attention_lines,
            cfg.experts_held, cfg.router_rule.outputs, cfg.head_dim) == \
        (16, 12, 4, 64, 512, 256)
    mem, text, _ = bench.compile_programs(cfg, only=program)[program]
    carried = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30
    assert 11.6 < mem.argument_size_in_bytes / 2 ** 30 < 11.7
    assert total < 15.75 - 0.7
    if program.startswith("prefill"):
        kernels = ("prefill_attention", "moe_grouped_matmul",
                   "gated_delta_chunk")
        # 512 x 10 picks over 512 outputs are 10 rows an expert: tiles of 32
        assert _grouped_matmul_rows(text) == {(5120 // 32 + 64) * 32}
        assert mem.temp_size_in_bytes < 1 << 27
        # The chunked rule's system, its inverse and what the walk reads
        # (``decay`` and ``a``; ``u``, ``w``, ``within``, ``q_in``, ``k_out``
        # of 32 heads x 8 sub-chunks) stay in the kernel's VMEM: the jnp
        # body wrote each to HBM in float32, heads first.
        for shape in ("f32[32,8,64,128]", "f32[8,32,64,128]",
                      "f32[32,8,64,64]"):
            assert not _opcodes_with_shape(text, shape), shape
    else:
        kernels = ("decode_attention", "kv_row_write", "moe_grouped_matmul")
        assert _plans_outside_the_layer_loop(text)
        # 16 x 10 picks: 0.3 rows an expert, tiles of 16
        assert _grouped_matmul_rows(text) == {(160 // 16 + 64) * 16}
        assert mem.temp_size_in_bytes < 1 << 25
    for name in kernels:
        assert f'"{name}"' in text or f"%{name}." in text, name
    big = bench.big_shapes(cfg)
    # float32, as the configuration's departures.state_dtype states it: the
    # benchmark's comparison cannot tell a bfloat16 state from a sound run
    # (PERF.md section 7), so the compiled program is held to it here
    assert big["state"] == "f32[12,16,32,128,128]"
    assert "parameter" in _opcodes_with_shape(text, big["state"])
    # and the router to its router_dtype: float32 weights, the product
    # float32 at true float32 (a TPU's default float32 product is one bfloat16 pass)
    assert "parameter" in _opcodes_with_shape(
        text, f"f32[{cfg.num_layers},{cfg.hidden_size},512]")
    routes = [line for line in text.splitlines()
              if "moe_route/dot_general" in line
              and re.search(r" (convolution|dot)\(", line)]
    assert routes and all(
        re.search(r"= f32\[\d+,512\]", line)
        and "operand_precision={highest,highest}" in line
        for line in routes), routes[:1]
    assert _opcodes_with_shape(text, big["lines"]) <= \
        carried | {"dynamic-update-slice", "custom-call"}
    if program.startswith("prefill"):
        # the state: a slot's read (a fusion's parameter), written in place
        # (an update alone or with the sum fused into it)
        assert _opcodes_with_shape(text, big["state"]) <= \
            carried | {"dynamic-update-slice", "fusion"}
        for line in text.splitlines():
            head = line.split(" = ", 1)
            if len(head) == 2 and big["state"] in head[1].split("(", 1)[0] \
                    and " fusion(" in head[1]:
                assert "dynamic-update-slice_fusion" in head[0], line[:200]
    else:
        # the state: the step kernel's in-place operand and nothing else,
        # a call a linear layer of the scanned group of four
        assert _opcodes_with_shape(text, big["state"]) <= \
            carried | {"custom-call"}
        assert _step_calls(text, big["state"]) == 3
    assert not _rematerialised(text, big["state"])
    for shape in ("we_in", "we_down", "in_qkvz", "wq"):
        assert _opcodes_with_shape(text, big[shape]) <= \
            carried | {"custom-call"}, shape


@pytest.mark.parametrize("decay", ["a_head", "a_channel"])
@pytest.mark.parametrize("batched", [False, True], ids=["a_chunk", "vmap"])
def test_gated_delta_chunk_compiles_alone_and_under_vmap(mosaic, batched,
                                                         decay):
    """The chunked gated delta rule's kernels at their cells' shapes (512
    rows; a decay a head with 16 key heads for 32 value heads of 128,
    Qwen3-Next's; a decay a key channel with 32 heads of 128, Ling's, under
    the gate's floor of -5), as the prefill programs call them and as the
    models' ``forward`` does, under ``jax.vmap``: no cell's path, but it
    must still compile on a TPU (``pallas_call``'s batching rule makes the
    batch a grid axis)."""
    from ray_tpu.ops.gated_delta import gated_delta_chunk

    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())
    lead = (2,) if batched else ()

    def sds(*shape):
        return jax.ShapeDtypeStruct(lead + shape, jnp.float32, sharding=dev)

    if decay == "a_channel":
        rule = partial(gated_delta_chunk, g_floor=-5.0)
        keys, g = sds(512, 32, 128), sds(512, 32, 128)
    else:
        rule, keys, g = gated_delta_chunk, sds(512, 16, 128), sds(512, 32)
    fn = jax.vmap(rule) if batched else rule
    text = jax.jit(fn).lower(
        keys, keys, sds(512, 32, 128), g, sds(512, 32),
        sds(32, 128, 128)).compile().as_text()
    assert text.count(MOSAIC) == 1
    assert "gated_delta_chunk" in text


def test_gated_delta_chunk_bwd_compiles_alone_at_the_cell_s_shapes(mosaic):
    """The trained rule's backward at ``olmo-hybrid-train-8k``'s shapes (2
    sequences of 8,192, 30 heads, keys of 96 and values of 192: 64 folded
    heads of 128 x 256, sixteen chunks of eight sub-chunks): one Mosaic
    call, named for the trace, whose 13 MiB of a chunk's states, inverses
    and corrections fit the VMEM it asks for."""
    from ray_tpu.ops import gated_delta as gd

    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=dev)

    b, t, h, dk, dv = 2, 8192, 30, 96, 192
    saved = (sds(b, t, h, dk), sds(b, t, h, dk), sds(b, t, h, dv),
             sds(b, t, h), sds(b, t, h),
             sds(t // gd.TRAIN_CHUNK, b, h, dk, dv))
    text = jax.jit(gd._batch_rule_bwd).lower(
        saved, (sds(b, t, h, dv), sds(b, h, dk, dv))).compile().as_text()
    assert text.count(MOSAIC) == 1
    assert "gated_delta_chunk_bwd" in text


@pytest.mark.parametrize("cell", ["ling_96_slots_a_channel",
                                  "qwen3_next_16_slots_a_head"])
def test_gated_delta_step_compiles_alone_at_the_cells_shapes(mosaic, cell):
    """The step's kernel at its two cells' shapes (32 heads of 128 x 128:
    Ling's 96 slots on a leaf of one line with a decay a key channel,
    Qwen3-Next's 16 slots on a leaf of 12 lines with a decay a head, the
    line a traced index): one Mosaic call named for the trace, the donated
    leaf its in-place operand (the result's buffer is the argument's, no
    copy of a leaf and no temporary of a line's size)."""
    from ray_tpu.ops.gated_delta import gated_delta_step

    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    lines, slots, channel = (1, 96, True) if cell.startswith("ling") \
        else (12, 16, False)
    leaf = sds(lines, slots, 32, 128, 128)
    compiled = jax.jit(gated_delta_step, donate_argnums=5).lower(
        sds(slots, 32, 128), sds(slots, 32, 128), sds(slots, 32, 128),
        sds(slots, 32, 128) if channel else sds(slots, 32), sds(slots, 32),
        leaf, sds(dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count(MOSAIC) == 1
    state = f"f32[{lines},{slots},32,128,128]"
    assert _step_calls(text, state) == 1
    assert _opcodes_with_shape(text, state) <= {
        "parameter", "get-tuple-element", "tuple", "bitcast", "custom-call"}
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= lines * slots * 32 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 1 << 20


# Phi-4-mini-flash whole (32 layers at the published widths, the whole
# vocabulary) at the shapes of its serving cell (64 slots x 12,288): the
# programs of llm/phi4flash_serving.py as the cell compiles them.
@pytest.mark.parametrize("program", ["prefill_chunk(512)", "decode_burst(8)"])
def test_phi4flash_programs_copy_no_state_ring_nor_line_and_fit_the_chip(
        mosaic, program):
    """Five kinds of cache leaf ride every loop as carry: the one full line
    (a packed pair a head of 128), 8 rings of 512 positions, the scan's
    states (float32, 320 KiB a slot and layer) and the convolutions'
    windows; 14 layers keep nothing. None, nor the embedding that is also
    the head, nor a stacked weight leaf, is the result of anything but a
    parameter, a loop's tuple, a kernel's in-place operand or an update in
    place: a chunk writes its rows of the line and its turn of a ring by a
    ``dynamic-update-slice`` into the leaf, a decode step a line of the
    state with the recurrence fused into the update. Arguments and
    temporaries are what benchmark/configs/phi-4-mini-flash-reasoning.json
    states under ``memory``."""
    from devbench import phi4flash_bench as bench

    cfg = bench.config()
    assert (cfg.num_layers, cfg.ssm_lines, cfg.window_lines, cfg.cross_lines,
            cfg.vocab_size, cfg.pair_dim) == (32, 9, 8, 7, 200064, 128)
    mem, text, _ = bench.compile_programs(cfg, only=program)[program]
    carried = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30
    assert 12.3 < mem.argument_size_in_bytes / 2 ** 30 < 12.45
    assert total < 15.75 - 0.5
    if program.startswith("prefill"):
        kernels = ("prefill_attention", "selective_scan_chunk")
        assert mem.temp_size_in_bytes < 1 << 27
    else:
        kernels = ("decode_attention", "kv_row_write")
        assert _plans_outside_the_layer_loop(text)
        assert mem.temp_size_in_bytes < 1 << 28
    for name in kernels:
        assert f'"{name}"' in text or f"%{name}." in text, name
    big = bench.big_shapes(cfg)
    # float32, as the configuration's departures.state_dtype states it: the
    # benchmark's comparison cannot tell a bfloat16 state from a sound run
    # (PERF.md section 7), so the compiled program is held to it here
    assert big["state"] == "f32[9,64,16,5120]"
    assert "parameter" in _opcodes_with_shape(text, big["state"])
    # a ring is 512 positions whatever the line's length
    assert big["ring"] == "bf16[8,64,10,512,128]"
    assert big["ring"] == bench.big_shapes(cfg, max_seq=4096)["ring"]
    assert big["line"] == "bf16[1,64,10,12288,128]"
    in_place = carried | {"dynamic-update-slice", "custom-call", "fusion"}
    for leaf in ("line", "ring", "state"):
        assert "copy" not in _opcodes_with_shape(text, big[leaf])
        assert _opcodes_with_shape(text, big[leaf]) <= in_place, leaf
    # a fusion that gives a leaf is an update in place, nothing else
    for line in text.splitlines():
        head = line.split(" = ", 1)
        if len(head) == 2 and " fusion(" in head[1] and any(
                big[k] in head[1].split("(", 1)[0]
                for k in ("line", "ring", "state")):
            assert "dynamic-update-slice_fusion" in head[0], line[:200]
    # the embedding is gathered from and multiplied by where it lies
    assert _opcodes_with_shape(text, big["embed"]) <= \
        carried | {"fusion"}
    for line in text.splitlines():
        head = line.split(" = ", 1)
        if len(head) == 2 and " fusion(" in head[1] \
                and big["embed"] in head[1].split("(", 1)[0]:
            assert "bitcast_fusion" in line, line[:200]
    assert _opcodes_with_shape(text, big["w_gate"]) <= carried


def test_selective_scan_chunk_compiles_at_the_cell_s_shapes(mosaic):
    """The chunk form's kernel on 512 rows x 5,120 channels x 16 states, as
    the prefill program calls it, and on a tail bucket of 16 rows."""
    from ray_tpu.ops.selective_scan import selective_scan_chunk

    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=dev)

    for rows in (512, 16):
        text = jax.jit(selective_scan_chunk).lower(
            sds(rows, 5120), sds(rows, 5120), sds(16, 5120), sds(rows, 16),
            sds(rows, 16), sds(5120), sds(16, 5120)).compile().as_text()
        assert text.count(MOSAIC) == 1
        assert "selective_scan_chunk" in text


# ISSUE 54: MiMo-V2.5's serving programs at the shapes of
# ``mimo-v2.5-serve-mixed-32k`` (7 layers at the published widths, 16 of 256
# experts held, an eighth of the vocabulary, 24 slots x 32,768): the two
# programs of llm/mimo_serving.py as the cell compiles them.
@pytest.mark.parametrize("program", ["prefill_chunk(512)", "decode_burst(8)"])
def test_mimo_programs_copy_no_line_ring_nor_stacked_leaf_and_fit_the_chip(
        mosaic, program):
    """Two geometries of cache leaf: two full lines of 4 KV heads that grow
    with the line's length and five rings of 8 KV heads x 128 positions
    that do not, a row of either a key of 192 beside a value of 128 in 384
    lanes. Neither, nor a stacked weight leaf, is the result of anything
    but a parameter, a loop's tuple, a kernel's in-place operand or an
    update in place: a chunk writes its rows of a full line by a
    ``dynamic-update-slice`` into the leaf and the slot's five turned rings
    by one more, after the layers (carried through the layers' loops the
    ring leaf was re-laid out whole, twice a chunk: PERF.md, PR 54); a step
    writes a row of each through ``kv_row_write``. The router's weights and
    the sinks are float32 as the configuration's departures state.
    Arguments and temporaries are what benchmark/configs/mimo-v2.5.json
    states under ``memory``."""
    from devbench import mimo_bench as bench

    cfg = bench.config()
    assert (cfg.num_layers, cfg.full_lines, cfg.window_lines,
            cfg.experts_held, cfg.vocab_size, cfg.kv_row) == (7, 2, 5, 16,
                                                              19072, 384)
    mem, text, _ = bench.compile_programs(cfg, only=program)[program]
    carried = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30
    assert 10.9 < mem.argument_size_in_bytes / 2 ** 30 < 11.05
    # the float32 reference of the check wants room beside the weights
    assert total < 15.75 - 4.0
    assert mem.temp_size_in_bytes < 1 << 27
    if program.startswith("prefill"):
        kernels = ("prefill_attention", "moe_grouped_matmul")
    else:
        kernels = ("decode_attention", "kv_row_write", "moe_grouped_matmul")
    for name in kernels:
        assert f'"{name}"' in text or f"%{name}." in text, name
    big = bench.big_shapes(cfg)
    assert big["kv"] == "bf16[2,24,4,32768,384]"
    # a ring is 128 positions of 8 KV heads whatever the line's length
    assert big["ring"] == "bf16[5,24,8,128,384]"
    assert big["ring"] == bench.big_shapes(cfg, max_seq=4096)["ring"]
    in_place = carried | {"dynamic-update-slice", "custom-call", "fusion"}
    for leaf in ("kv", "ring"):
        assert "parameter" in _opcodes_with_shape(text, big[leaf])
        assert "copy" not in _opcodes_with_shape(text, big[leaf])
        assert _opcodes_with_shape(text, big[leaf]) <= in_place, leaf
    # a fusion that gives a leaf is an update in place, nothing else
    for line in text.splitlines():
        head = line.split(" = ", 1)
        if len(head) == 2 and " fusion(" in head[1] and any(
                big[k] in head[1].split("(", 1)[0] for k in ("kv", "ring")):
            assert "dynamic-update-slice_fusion" in head[0], line[:200]
    # a stacked weight is indexed where it is used: a kernel's operand (the
    # experts), a fusion's parameter (a layer's slice into its product)
    for leaf in ("wqkv_window", "wqkv_full", "wo", "we_gate", "we_down",
                 "embed"):
        assert _opcodes_with_shape(text, big[leaf]) <= \
            carried | {"fusion", "custom-call", "dynamic-slice"}, leaf
    # float32 where the configuration's departures say so
    assert "f32[6,4096,256]" in text and "f32[5,64]" in text
    assert "bf16[6,4096,256]" not in text and "bf16[5,64]" not in text


# ISSUE 58: Ling-3.0-flash-VL's serving programs at the shapes of
# ``ling3-flash-serve-rollout-8k`` (12 layers at the published widths: 10
# Kimi Delta Attention and 2 gated latent attentions, 64 of 512 experts held,
# an eighth of the vocabulary, 96 slots x 8,192): the two programs of
# llm/ling_serving.py as the cell compiles them.
@pytest.mark.parametrize("program", ["prefill_chunk(512)", "decode_burst(8)"])
def test_ling_programs_copy_no_state_line_nor_stacked_leaf_and_fit_the_chip(
        mosaic, program):
    """The cache's leaves ride every loop as carry: the latent lines, the
    convolutions' windows and the delta rule's state (float32, 2 MiB a slot
    and layer: 1.875 GiB in all, at this depth a leaf of 0.19 GiB a KDA
    layer: llm/ling_serving._state_leaves). None, nor a stacked leaf of the
    experts or of the mixers, is the result of anything but a parameter, a
    loop's tuple, a kernel's in-place operand or an update in place: a
    prefill chunk writes a slot's state into its leaf by an update in
    place; a decode step hands a leaf and the line to ``gated_delta_step``,
    whose kernel reads a layer's states once and writes them once over
    themselves (PR 59: no fusion reads a leaf, nothing writes one back; a
    copy would show in the temporaries, held under 0.7 GiB), one call a KDA
    layer, and no update is computed twice. The state leaves and the
    router (weights, bias and product) are float32 as the configuration's
    departures state. Arguments and temporaries are what
    benchmark/configs/ling-3.0-flash-vl.json states under ``reduced``."""
    from devbench import ling_bench as bench

    cfg = bench.config()
    assert (cfg.num_layers, cfg.linear_lines, cfg.latent_lines,
            cfg.num_dense_layers, cfg.experts_held, cfg.router_rule.outputs,
            cfg.vocab_size) == (12, 10, 2, 2, 64, 512, 19648)
    mem, text, _ = bench.compile_programs(cfg, only=program)[program]
    carried = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30
    assert 12.6 < mem.argument_size_in_bytes / 2 ** 30 < 12.7
    # the float32 reference of the check wants room beside the weights
    assert total < 15.75 - 2.3
    if program.startswith("prefill"):
        kernels = ("latent_prefill_attention", "moe_grouped_matmul",
                   "gated_delta_chunk")
        # 512 x 8 picks over 512 outputs are 8 rows an expert: tiles of 16
        assert _grouped_matmul_rows(text) == {(4096 // 16 + 64) * 16}
        assert mem.temp_size_in_bytes < 1 << 28
    else:
        kernels = ("latent_decode_attention", "latent_row_write",
                   "moe_grouped_matmul")
        # 96 x 8 picks: 1.5 rows an expert, tiles of 16
        assert _grouped_matmul_rows(text) == {(768 // 16 + 64) * 16}
        assert mem.temp_size_in_bytes < 0.7 * 2 ** 30
    for name in kernels:
        assert f'"{name}"' in text or f"%{name}." in text, name
    big = bench.big_shapes(cfg)
    # float32, as the configuration's departures.state_dtype states it
    assert big["state"] == "f32[1,96,32,128,128]"
    assert big["latent"] == "bf16[2,96,8192,640]"
    assert "parameter" in _opcodes_with_shape(text, big["state"])
    # No update of a state leaf is rematerialised: under one leaf over all
    # ten layers the compiler, short of memory by its own count, computed a
    # layer's ``decay * S + k d^T`` a second time from the buffer the first
    # had written in place (the next layer's read and the next update both
    # used it), and a burst's tokens left the reference by 2 to 5 on the chip
    # where a single step's were sound (PR 58). Since PR 59 a step's update
    # is a kernel's in-place operand, which the compiler cannot compute
    # twice; the chunk's is still XLA's.
    assert not _rematerialised(text, big["state"])
    # and the router to its router_dtype: float32 weights and bias, the
    # product float32 at true float32
    for shape in ("f32[10,2560,512]", "f32[10,512]"):
        assert "parameter" in _opcodes_with_shape(text, shape), shape
    assert "bf16[10,2560,512]" not in text
    routes = [line for line in text.splitlines()
              if "moe_route/dot_general" in line
              and re.search(r" (convolution|dot)\(", line)]
    assert routes and all(
        re.search(r"= f32\[\d+,512\]", line)
        and "operand_precision={highest,highest}" in line
        for line in routes), routes[:1]
    assert _opcodes_with_shape(text, big["latent"]) <= \
        carried | {"dynamic-update-slice", "custom-call"}
    if program.startswith("prefill"):
        # the state: written over itself by a chunk's update of a slot's
        # row, never copied
        assert _opcodes_with_shape(text, big["state"]) <= carried | {
            "dynamic-update-slice", "fusion"}
    else:
        # the state: the step kernel's in-place operand and nothing else, a
        # call a KDA layer
        assert _opcodes_with_shape(text, big["state"]) <= \
            carried | {"custom-call"}
        assert _step_calls(text, big["state"]) == cfg.linear_lines
    # no stacked leaf is copied: not the decay's projection either, which
    # XLA copied whole and transposed at the top of a decode program until
    # its product was kept an array of its own (models/ling.kda_inputs)
    # The compiler may fetch a leaf of a layer written out (the first
    # group's, indexed statically) ahead into fast memory a layer at a time:
    # a ``slice-start`` of one layer whose tuple names the stack, joined by
    # a ``ConcatBitcast`` call; that moves no stack in HBM. (``wq``'s shape
    # is the dense SwiGLUs' ``w_gate``'s and ``w_up``'s too.)
    for shape in ("we_in", "we_down", "in_qkvz", "in_f", "wq", "wkv_b"):
        assert _opcodes_with_shape(text, big[shape]) <= \
            carried | {"custom-call", "slice-start", "slice-done"}, shape


# The programs of the three served models that share the modules ISSUE 58
# opened (ops/gated_delta.py: Qwen3-Next; models/mla.py and models/routed.py:
# DeepSeek-V2 and LongCat), at their tiny sizes with the Mosaic kernels in:
# sha256 of the jaxpr (the kernels' bodies in it, no source line), as
# devbench/lowered_programs.py prints them, taken on PR 57's tree. A PR that
# means to change one of these programs runs that script and pins anew; one
# that does not has changed it all the same when this fails.
LOWERED = {
    # ISSUE 62 opened ops/gated_delta.py again (the step's call is shared
    # with ops/ssd.py) and llm/linear_state.py: Ling's, taken on PR 61's tree
    "ling": {"prefill_chunk": "20b2414b9c301e57",
             "decode_step": "f76d902fceda6f3d",
             "decode_burst": "e4688d90bb46b4c2"},
    "qwen3next": {"prefill_chunk": "62b7e07bbe773b70",
                  "decode_step": "faef59afe4f9ed22",
                  "decode_burst": "18c799a1c09027f8"},
    "deepseekv2": {"prefill_chunk": "0230fdb3ce4f44a3",
                   "decode_step": "1d6ff99e6a1fb74b",
                   "decode_burst": "d84d66289132b474",
                   "mixed_burst": "512060dc33c529ad"},
    "longcat": {"prefill_chunk": "ef7a22e047e0c149",
                "decode_step": "f85b2315a011e456",
                "decode_burst": "27e806083760d96a"},
}


@pytest.mark.parametrize("model", sorted(LOWERED))
def test_the_models_that_share_ling_s_modules_lower_to_what_they_were(
        v5e_2x2, model):
    """A decay a key channel in ``ops/gated_delta.py``, a query without a
    low-rank pair and a rotary by halves in ``models/mla.py``, a grouped
    rule with a bias in ``models/routed.py``: none reaches the programs of
    the models that had these modules before."""
    from devbench import lowered_programs

    got = lowered_programs.run("mosaic", [model])
    assert {prog: got[f"{model}.{prog}.mosaic.jaxpr"]
            for prog in LOWERED[model]} == LOWERED[model]
    assert len([k for k in got if k.endswith(".mosaic.jaxpr")]) \
        == len(LOWERED[model])


# ISSUE 62: granite-4.0-h-small's serving programs at the shapes of
# ``granite4-h-small-serve-support-2k`` (10 layers at the published widths:
# nine Mamba-2 and one attention without positions, 36 of 72 experts held,
# half of the vocabulary, 96 slots x 2,048): the two programs of
# llm/granite_serving.py as the cell compiles them.
@pytest.mark.parametrize("program", ["prefill_chunk(512)", "decode_burst(8)"])
def test_granite_programs_copy_no_state_line_nor_stacked_leaf_and_fit_the_chip(
        mosaic, program):
    """The cache's leaves ride every loop as carry: Mamba-2's state (float32,
    4 MiB a slot and layer, two heads to a lane row: 3.375 GiB in one leaf),
    the convolutions' windows and the attention's line. None, nor a stacked
    leaf of the experts, of the mixers or the tied embedding, is the result
    of anything but a parameter, a loop's tuple, a kernel's in-place operand
    or an update in place: a prefill chunk writes a slot's state into the
    leaf by an update in place (the chunk form hands its state in and out
    as arrays of their own: without that XLA carried the ``[N, heads x P]``
    matrix's layout back through the slot's slice and copied the whole leaf,
    3.4 GiB over the chip's memory); a decode step hands the leaf and the
    line to ``ssd_step``, whose kernel reads a layer's states once and
    writes them once over themselves, one call a Mamba layer, and no update
    of the state is computed twice (ROADMAP R5 (g)). The state leaf and the
    router (weights and product) are float32 as the configuration's
    departures state; the head reads the embedding as it lies. Arguments
    and temporaries are what benchmark/configs/granite-4.0-h-small.json
    states under ``reduced``."""
    from devbench import granite_bench as bench

    cfg = bench.config()
    assert (cfg.num_layers, cfg.linear_lines, cfg.attention_lines,
            cfg.experts_held, cfg.router_rule.outputs, cfg.vocab_size) == (
                10, 9, 1, 36, 72, 50176)
    mem, text, _ = bench.compile_programs(cfg, only=program)[program]
    carried = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30
    assert 13.0 < mem.argument_size_in_bytes / 2 ** 30 < 13.1
    # the float32 reference of the check wants room beside the weights, and
    # ISSUE 62 keeps 96 slots only while a program leaves 1.0 GiB or more
    assert total < 15.75 - 2.3
    if program.startswith("prefill"):
        kernels = ("prefill_attention", "moe_grouped_matmul")
        # 512 x 10 picks over 72 outputs are 71 rows an expert: tiles of 128
        assert _grouped_matmul_rows(text) == {(5120 // 128 + 36) * 128}
        # 0.28 GiB: the chunk's activations and the slot's nine states
        assert mem.temp_size_in_bytes < 0.4 * 2 ** 30
    else:
        kernels = ("decode_attention", "kv_row_write", "moe_grouped_matmul",
                   "ssd_step")
        # 96 x 10 picks: 13 rows an expert, tiles of 32
        assert _grouped_matmul_rows(text) == {(960 // 32 + 36) * 32}
        assert mem.temp_size_in_bytes < 1 << 26
    for name in kernels:
        assert f'"{name}"' in text or f"%{name}." in text, name
    big = bench.big_shapes(cfg)
    # float32, as the configuration's departures.state_dtype states it, two
    # heads of 64 to a lane row
    assert big["state"] == "f32[9,96,64,128,128]"
    assert big["k"] == "bf16[1,96,8,2048,128]"
    assert "parameter" in _opcodes_with_shape(text, big["state"])
    assert not _rematerialised(text, big["state"])
    # the router to its router_dtype: float32 weights, the product float32
    # at true float32
    assert "parameter" in _opcodes_with_shape(text, "f32[10,4096,72]")
    assert "bf16[10,4096,72]" not in text
    routes = [line for line in text.splitlines()
              if "moe_route/dot_general" in line
              and re.search(r" (convolution|dot)\(", line)]
    assert routes and all(
        re.search(r"= f32\[\d+,72\]", line)
        and "operand_precision={highest,highest}" in line
        for line in routes), routes[:1]
    assert _opcodes_with_shape(text, big["k"]) <= \
        carried | {"dynamic-update-slice", "custom-call"}
    if program.startswith("prefill"):
        # the state: written over itself by a chunk's update of a slot's
        # row, never copied
        assert _opcodes_with_shape(text, big["state"]) <= carried | {
            "dynamic-update-slice", "fusion"}
        assert not [line for line in text.splitlines()
                    if " copy(" in line and big["state"] in line]
    else:
        # the state: the step kernel's in-place operand and nothing else, a
        # call a Mamba layer
        assert _opcodes_with_shape(text, big["state"]) <= \
            carried | {"custom-call"}
        assert _step_calls(text, big["state"], "ssd_step") == \
            cfg.linear_lines
    # no stacked leaf is copied, and the tied embedding is no one's result
    # but a bitcast inside the head's product
    for shape in ("we_in", "we_down", "in_xbcz", "out_proj"):
        assert _opcodes_with_shape(text, big[shape]) <= \
            carried | {"custom-call", "slice-start", "slice-done"}, shape
    assert not [line for line in text.splitlines()
                if re.search(r" (copy|transpose)\(", line)
                and big["embed"] in line.split(" = ", 1)[-1].split("(")[0]]


@pytest.mark.parametrize("form", ["chunk", "step"])
def test_ssd_chunk_and_step_compile_alone_at_the_cell_s_shapes(mosaic, form):
    """Mamba-2's rule at the cell's shapes (128 heads of 64 on a state of
    128, stored 64 groups of 128 x 128): the chunk form on 512 rows (plain
    products: no Mosaic call yet, ROADMAP R5) and the step on 96 slots, a
    line of the leaf of 9 lines by a traced index: one Mosaic call named for
    the trace, the donated leaf its in-place operand (the result's buffer is
    the argument's, no copy of the leaf and no temporary of a line's
    size)."""
    from ray_tpu.ops import ssd

    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    if form == "chunk":
        compiled = jax.jit(ssd.ssd_chunk).lower(
            sds(512, 128, 64), sds(512, 128), sds(128), sds(512, 128),
            sds(512, 128), sds(64, 128, 128)).compile()
        assert compiled.as_text().count(MOSAIC) == 0
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28
        return
    lines, slots = 9, 96
    compiled = jax.jit(ssd.ssd_step, donate_argnums=5).lower(
        sds(slots, 128, 64), sds(slots, 128), sds(128), sds(slots, 128),
        sds(slots, 128), sds(lines, slots, 64, 128, 128),
        sds(dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count(MOSAIC) == 1
    state = f"f32[{lines},{slots},64,128,128]"
    assert _step_calls(text, state, "ssd_step") == 1
    assert _opcodes_with_shape(text, state) <= {
        "parameter", "get-tuple-element", "tuple", "bitcast", "custom-call"}
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= lines * slots * 64 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 1 << 24


# ISSUE 64: Keye-VL-2.0's first 12 layers at the published widths with 16 of
# 128 experts, at the shapes of ``keye-vl2-serve-longctx-48k`` (8 slots x
# 49,152): the two programs of llm/keye_serving.py as the cell compiles them.
@pytest.mark.parametrize("program", ["prefill_chunk(512)", "decode_burst(8)"])
def test_keye_programs_copy_no_cache_leaf_and_fit_the_chip(mosaic, program):
    """The cache's three leaves ride every loop as carry: keys and values
    (4.5 GiB each) and the index keys, ``[12, 8, 1, 64, 49152]``, the
    positions last: stored a position a row of 64 values XLA laid the
    parameter out positions-minor by itself and copied the leaf in and out
    of every program around the kernels (two copies of 0.5625 GiB: the
    first compile's 1.128 GiB of temporaries). None is the result of
    anything but a parameter, a loop's tuple, a kernel's in-place operand
    or an update in place; a chunk's ``[512, 49152]`` float32 scores are the
    temporaries (0.095 GiB), a decode step's fit fast memory. The three
    kernels of ops/sparse_attention.py are in both programs under the names
    the trace is read by, and the index key's write is a kernel in a step
    and an update in place in a chunk. Arguments and temporaries are what
    benchmark/configs/keye-vl-2.0-30b-a3b.json states under ``reduced``."""
    from devbench import keye_bench as bench

    cfg = bench.config()
    assert (cfg.num_layers, cfg.experts_held, cfg.router_rule.outputs,
            cfg.vocab_size, cfg.index_topk, cfg.index_heads) == (
                12, 16, 128, 18992, 2048, 16)
    mem, text, _ = bench.compile_programs(cfg, only=program)[program]
    carried = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
    assert 11.8 < mem.argument_size_in_bytes / 2 ** 30 < 11.9
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30
    # the float32 reference of the check wants room beside the weights
    assert total < 15.75 - 3.5
    kernels = ["index_scores", "index_select", "moe_grouped_matmul"]
    if program.startswith("prefill"):
        kernels += ["sparse_prefill_attention"]
        assert mem.temp_size_in_bytes < 0.15 * 2 ** 30
        # 512 x 8 picks over 128 outputs are 32 rows an expert: tiles of 64
        assert _grouped_matmul_rows(text) == {(4096 // 64 + 16) * 64}
    else:
        kernels += ["kv_row_write", "index_rows_write",
                    "sparse_decode_attention"]
        assert mem.temp_size_in_bytes < 1 << 26
        # 8 x 8 picks: half a row an expert, tiles of 16
        assert _grouped_matmul_rows(text) == {(64 // 16 + 16) * 16}
    for name in kernels:
        assert f'"{name}"' in text or f"%{name}." in text, name
    # ISSUE 67 (ISSUE 65 pinned the other way round: one result of
    # ``f32[8,16,49152]`` was left, ``index_scores``' tile of 16 rows a
    # line): a step's scores are a row a line from the kernel that makes
    # them to the kernel that attends under them, 1.5 MB a layer and step
    # for 25, relaid once for the selection's tile of 8 rows; and in both
    # programs the one ``index_scores`` call takes its grid's last bound at
    # run time (the operand before the scalars that are prefetched)
    calls = [line for line in text.splitlines()
             if re.search(r"%index_scores\.\d+ = .* custom-call\(", line)]
    assert len(calls) == 1
    assert "operand_layout_constraints={s32[], s32[1]{0}, " in calls[0]
    if program.startswith("decode"):
        assert _opcodes_with_shape(text, "f32[8,16,49152]") == set()
        assert re.search(r"%index_scores\.\d+ = f32\[8,1,49152\]", calls[0])
        assert _opcodes_with_shape(text, "f32[8,1,49152]") == {
            "custom-call", "parameter", "copy"}
        assert len(re.findall(r"= f32\[8,1,49152\]\S* custom-call\(",
                              text)) == 1
        assert mem.temp_size_in_bytes < 1 << 22
    else:
        assert re.search(r"%index_scores\.\d+ = f32\[1,512,49152\]",
                         calls[0])
    # the program's own attention kernels are not on its path
    assert "%decode_attention." not in text
    assert "%prefill_attention." not in text
    big = bench.big_shapes(cfg)
    assert big["lines"] == "bf16[12,8,4,49152,128]"
    assert big["index_k"] == "bf16[12,8,1,64,49152]"
    for leaf in ("lines", "index_k"):
        assert "parameter" in _opcodes_with_shape(text, big[leaf])
        assert _opcodes_with_shape(text, big[leaf]) <= \
            carried | {"dynamic-update-slice", "custom-call"}, leaf
        assert not _rematerialised(text, big[leaf])
    # the router's weights and its product float32 at true float32
    assert "parameter" in _opcodes_with_shape(text, "f32[12,2048,128]")
    routes = [line for line in text.splitlines()
              if "moe_route/dot_general" in line
              and re.search(r" (convolution|dot)\(", line)]
    assert routes and all(
        re.search(r"= f32\[\d+,128\]", line)
        and "operand_precision={highest,highest}" in line
        for line in routes), routes[:1]
    for shape in ("we_in", "we_down", "wq"):
        assert _opcodes_with_shape(text, big[shape]) <= \
            carried | {"custom-call", "slice-start", "slice-done"}, shape


@pytest.mark.parametrize("form", ["chunk", "step"])
def test_the_sparse_attention_s_kernels_compile_alone_at_the_cell_s_shapes(
        mosaic, form):
    """The three ops at the cell's shapes, a chunk's 512 rows of one line
    and a step's row of each of 8: one Mosaic call each, the scores the
    only array of a line's size that any of them makes."""
    from ray_tpu.ops import sparse_attention as sa

    dev = NamedSharding(build_mesh(MeshSpec(), mosaic[:1]), P())

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    n, c = (1, 512) if form == "chunk" else (8, 1)
    s, i32 = 49152, jnp.int32
    lines = sds(n, dtype=i32)
    scores = sds(n, c, s, dtype=jnp.float32)
    compiled = [
        jax.jit(sa.index_scores).lower(
            sds(n, 16, c, 64), sds(n, 16, c, dtype=jnp.float32),
            sds(12, 8, 1, 64, s), sds(dtype=i32), lines, lines,
            lines).compile(),
        jax.jit(sa.topk_threshold, static_argnums=1).lower(
            sds(n * c, s, dtype=jnp.float32), 2048,
            sds(n * c, dtype=i32)).compile(),
        jax.jit(sa.sparse_attention).lower(
            sds(n, 32, c, 128), sds(12, 8, 4, s, 128), sds(12, 8, 4, s, 128),
            scores, sds(n, c, dtype=jnp.float32), sds(n, c, dtype=i32),
            sds(dtype=i32), lines, lines, lines).compile()]
    for program in compiled:
        assert program.as_text().count(MOSAIC) == 1
        # (a step's scores go in as they are, a row a line: ISSUE 65)
        assert program.memory_analysis().temp_size_in_bytes < 1 << 26
    # and come out so: a step's tile is its row's index heads (ISSUE 67)
    scored = compiled[0].as_text()
    assert f"-> f32[{n},{c},{s}]" in scored.replace("{2,1,0}", "")
    assert "f32[8,16,49152]" not in scored
    assert compiled[0].memory_analysis().temp_size_in_bytes < 1 << 20


def test_sdar_s_programs_lower_to_what_they_were(v5e_2x2):
    """models/keye.py builds its stack with models/sdar.py's ``layer``,
    ``run_layers`` and ``lm_head`` and opens none of them: SDAR's programs
    are what they were on PR 63's tree (``LOWERED``'s way)."""
    from devbench import lowered_programs

    got = lowered_programs.run("mosaic", ["sdar"])
    assert {k: v for k, v in got.items() if k.endswith(".mosaic.jaxpr")} == {
        "sdar.prefill_chunk.mosaic.jaxpr": "0d7d9d0919e51ad2",
        "sdar.decode_burst.mosaic.jaxpr": "d52414571d8bc9f2"}


# ISSUE 68: Olmo-Hybrid's train step (``make_olmo_hybrid_train_step``) at a
# reduced size that keeps what the cell's step has: one period of three Gated
# DeltaNet layers and a full attention under full remat, heads of 128 for the
# flash kernels, keys and values of two widths, the traffic file's optimizer.
def test_olmo_hybrid_step_compiles_with_the_rule_named_on_all_three_passes(
        v5e_2x2):
    """The step compiles for one v5e chip; every operation with a path lies
    under a part (none ``unnamed``); the rule's scope is on the forward,
    on the forward that ``jax.checkpoint`` runs again and on the backward
    (``xplane_meta.pass_of``), and so are the scopes around it; the flash
    kernels and the norms are Mosaic calls, and so is the rule on every
    pass: the forward's kernel on ``fwd`` and ``remat`` and the backward's
    on ``bwd``, one a linear layer of the scan's body (three of a period)."""
    from devbench import olmo_hybrid_bench as bench

    config, traffic = bench.cell_files()
    cfg = bench.model(config, traffic, hidden_size=256, intermediate_size=512,
                      num_heads=2, num_kv_heads=2, linear_num_key_heads=3,
                      linear_num_value_heads=3, linear_key_head_dim=32,
                      linear_value_head_dim=64, vocab_size=512)
    mem, text, _ = bench.compile_step(cfg, traffic, 2, 1024)
    scopes = bench.scopes_by_pass(text)
    assert "unnamed" not in scopes, scopes["unnamed"]
    for scope in ("delta_rule", "linear_attn", "conv", "attn", "mlp"):
        assert set(scopes[scope]) == {"fwd", "bwd", "remat"}, scope
    assert set(scopes["optim"]) == {"fwd"}
    assert {"loss", "head", "embed"} <= set(scopes)
    assert bench.rule_kernels_by_pass(text) == {
        "gated_delta_chunk": {"fwd": 3, "remat": 3},
        "gated_delta_chunk_bwd": {"bwd": 3}}
    # flash forward twice (once recomputed) and backward once; the norms
    assert text.count(MOSAIC) >= 3
    assert mem.temp_size_in_bytes > 0
