"""``tracing.part``: the parts of the jitted programs, one vocabulary for
every model.

A part is a ``jax.named_scope`` from ``tracing.PARTS``; its name lands on
the name stack of every operation traced under it, which a device trace
carries as ``tf_op`` (benchmark/rtbench/xplane_meta.py reads it back).
Here, on the CPU: the vocabulary, and for every model and program that the
parts it must have are in the lowered program's locations.
"""

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.served import served_model
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.longcat import LongcatConfig
from ray_tpu.models.mixtral import MixtralConfig
from ray_tpu.models.ouro import OuroConfig
from ray_tpu.util import tracing
# The tiny served models and the programs' arguments, as the engine's own
# contract test builds them.
from test_served_model import (MAX_SEQ, SLOTS, _arguments, _deepseek,
                               _granite, _keye, _lfm2, _ling, _llama,
                               _longcat, _mimo, _ouro, _phi4flash,
                               _qwen3_next)

# What JAX itself puts on a name stack besides primitives' names.
WRAPPERS = {"transpose", "jvp", "vmap", "pmap", "jit", "pjit", "while",
            "body", "cond", "scan", "closed_call", "core_call", "checkpoint",
            "remat", "remat2", "rematted_computation", "custom_jvp_call",
            "custom_vjp_call", "custom_vjp_call_jaxpr", "shard_map",
            "branch_0_fun", "branch_1_fun", "pallas_call", "named"}


def test_part_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="atn"):
        tracing.part("atn")
    with pytest.raises(ValueError):
        tracing.part("longcat.mla")


def test_the_finer_names_are_a_vocabulary_of_their_own():
    """``SUBPARTS`` are opened inside a part and never stand for one: the
    benchmark's partition (``xplane_meta.PARTS == tracing.PARTS``) does not
    know them and books their operations to the part around them."""
    assert tracing.SUBPARTS == ("conv", "conv_state", "moe_shared",
                                "latent_prefill", "linear_attn",
                                "delta_rule", "linear_state", "kda_rule",
                                "kda_gate", "ssd", "ssm", "ssm_scan",
                                "ssm_state",
                                "window_attn", "cross_attn", "gmu",
                                "indexer", "index_select", "sparse_attn")
    assert not set(tracing.SUBPARTS) & set(tracing.PARTS)
    assert all(re.fullmatch(r"[a-z_]+", p) for p in tracing.SUBPARTS)
    for name in tracing.SUBPARTS:
        with tracing.part(name):
            pass


def test_a_kind_of_step_is_a_third_vocabulary_opened_around_the_parts():
    """``STEP_KINDS`` name a whole step of a program whose steps are not all
    alike: ``tracing.part`` takes one as it takes a part, the three tuples
    share no name, and the parts opened under a kind lie after it on the
    path, so a reader that walks a path for the names it knows finds the
    part it found."""
    assert tracing.STEP_KINDS == ("mixed_step",)
    vocabularies = (tracing.PARTS, tracing.SUBPARTS, tracing.STEP_KINDS)
    names = [n for v in vocabularies for n in v]
    assert len(set(names)) == len(names)
    assert all(re.fullmatch(r"[a-z_]+", k) for k in tracing.STEP_KINDS)
    with pytest.raises(ValueError, match="mixed_step"):
        tracing.part("mixed")      # the message lists what may be opened

    def f(x):
        with tracing.part("stack"), tracing.part("mixed_step"):
            with tracing.part("attn"):
                return x * 2

    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "stack/mixed_step/attn/mul" in text


def test_part_is_a_named_scope_and_nothing_else():
    def f(x):
        with tracing.part("mlp"):
            return x * 2

    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "mlp/mul" in text


def test_no_part_is_a_jax_primitive_or_wrapper():
    import jax.extend.core

    primitives = {
        v.name for m in list(sys.modules.values())
        if getattr(m, "__name__", "").startswith("jax")
        for v in list(vars(m).values())
        if isinstance(v, jax.extend.core.Primitive)}
    assert len(primitives) > 100 and "dot_general" in primitives
    assert not set(tracing.PARTS) & (primitives | WRAPPERS)
    assert not set(tracing.SUBPARTS) & (primitives | WRAPPERS)
    assert not set(tracing.STEP_KINDS) & (primitives | WRAPPERS)
    assert len(set(tracing.PARTS)) == len(tracing.PARTS)
    assert all(re.fullmatch(r"[a-z_]+", p) for p in tracing.PARTS)


def parts_in(lowered) -> tuple[set, str]:
    """The parts on the name stacks of a lowered program's operations,
    from the locations of its text: every ``/`` segment, unwrapped of the
    transformations' names, that is in the vocabulary."""
    found = set()
    text = lowered.as_text(debug_info=True)
    for loc in re.findall(r'loc\("([^"]*)"', text):
        for seg in loc.split("/"):
            while seg.startswith(("transpose(", "jvp(", "vmap(")):
                seg = seg[seg.index("(") + 1:]
            if "(" not in seg and seg.rstrip(")") in tracing.PARTS:
                found.add(seg.rstrip(")"))
    return found, text


DENSE = {"embed", "attn", "cache", "mlp", "head", "stack"}
ROUTED = {"moe_route", "moe_dispatch", "moe_experts", "moe_combine"}
SERVED = {
    "llama": (_llama, DENSE),
    "longcat": (_longcat, DENSE | ROUTED),
    "ouro": (_ouro, DENSE | {"loop"}),
    "lfm2": (_lfm2, DENSE | ROUTED),
    "deepseek": (_deepseek, DENSE | ROUTED),
    "qwen3_next": (_qwen3_next, DENSE | ROUTED),
    "phi4flash": (_phi4flash, DENSE),
    "mimo": (_mimo, DENSE | ROUTED),
    "ling": (_ling, DENSE | ROUTED),
    "granite": (_granite, DENSE | ROUTED),
    # every layer's feed-forward is routed: no ``mlp``
    "keye": (_keye, (DENSE - {"mlp"}) | ROUTED),
}
LONGCAT_GONE = ("longcat.mla", "longcat.moe", "longcat.moe.experts")


@pytest.mark.parametrize("program", ["prefill_chunk", "decode_burst",
                                     "decode_step"])
@pytest.mark.parametrize("model", sorted(SERVED))
def test_a_serving_program_opens_its_parts(model, program):
    make, must = SERVED[model]
    module, cfg = make()
    served = served_model(cfg)
    params = jax.eval_shape(
        lambda: served.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: served.init_cache(cfg, SLOTS, MAX_SEQ))
    rest = _arguments(program, params)
    lowered = getattr(module, program).lower(cfg, rest[0], cache, *rest[1:])
    found, text = parts_in(lowered)
    if program == "decode_burst":
        must = must | {"sample"}
    assert must <= found, sorted(must - found)
    # nothing trains here: no optimizer, no loss
    assert not found & {"optim", "loss"}
    for gone in LONGCAT_GONE:
        assert gone not in text
    if model == "lfm2":
        # The convolution's finer names lie inside ``attn``, the operator's
        # place, on the path of its operations.
        assert re.search(r"attn/conv/dot_general", text)
        assert re.search(r"attn/conv_state/", text)
        assert not re.search(r"[^/\w](conv|conv_state)/", text)
    if model == "deepseek":
        # The shared experts' SwiGLU lies inside ``mlp`` and a chunk's
        # latent attention inside ``attn``, on the path of their operations.
        assert re.search(r"mlp/moe_shared/dot_general", text)
        assert bool(re.search(r"attn/latent_prefill/", text)) == (
            program == "prefill_chunk")
        assert not re.search(r"[^/\w](moe_shared|latent_prefill)/", text)
    if model == "qwen3_next":
        # The linear-attention operator's finer names lie inside ``attn``,
        # the rule alone inside ``linear_attn``; the gated shared expert
        # inside ``mlp``.
        assert re.search(r"attn/linear_attn/dot_general", text)
        assert re.search(r"attn/linear_attn/delta_rule/", text)
        assert re.search(r"attn/linear_state/", text)
        assert re.search(r"mlp/moe_shared/dot_general", text)
        assert not re.search(
            r"[^/\w](linear_attn|delta_rule|linear_state|moe_shared)/", text)
    if model == "phi4flash":
        # The scan operator's finer names lie inside ``attn``, the
        # operator's place, the scan alone inside ``ssm``; a window layer's
        # attention, a cross attention and a gated memory unit each under
        # its own name there. The full layer's attention stays plain
        # ``attn``.
        assert re.search(r"attn/ssm/dot_general", text)
        assert re.search(r"attn/ssm/ssm_scan/", text)
        assert re.search(r"attn/ssm_state/", text)
        assert re.search(r"attn/window_attn/dot_general", text)
        assert re.search(r"attn/window_attn/cache/", text)
        assert re.search(r"attn/cross_attn/dot_general", text)
        assert re.search(r"attn/gmu/dot_general", text)
        assert re.search(r"attn/dot_general", text)
        assert not re.search(
            r"[^/\w](ssm|ssm_scan|ssm_state|window_attn|cross_attn|gmu)/",
            text)
    if model == "ling":
        # KDA's finer names lie inside ``attn``: the rule alone, the
        # decay's gate and the convolution inside ``linear_attn``, the
        # state's reads and writes beside it; a chunk's latent attention
        # inside ``attn`` too; the shared expert inside ``mlp``.
        assert re.search(r"attn/linear_attn/dot_general", text)
        assert re.search(r"attn/linear_attn/kda_rule/", text)
        assert re.search(r"attn/linear_attn/kda_gate/dot_general", text)
        assert re.search(r"attn/linear_attn/kda_gate/logistic", text)
        assert re.search(r"attn/linear_attn/conv/", text)
        assert re.search(r"attn/linear_state/", text)
        assert re.search(r"mlp/moe_shared/dot_general", text)
        assert bool(re.search(r"attn/latent_prefill/", text)) == (
            program == "prefill_chunk")
        assert not re.search(
            r"[^/\w](linear_attn|kda_rule|kda_gate|conv|linear_state"
            r"|moe_shared|latent_prefill)/", text)
    if model == "granite":
        # The Mamba-2 mixer's finer names lie inside ``attn``: the rule
        # alone and the convolution inside ``linear_attn``, the state's and
        # the window's reads and writes beside it; the attention layer's
        # products stay plain ``attn``; the shared SwiGLU inside ``mlp``.
        assert re.search(r"attn/linear_attn/dot_general", text)
        assert re.search(r"attn/linear_attn/ssd/", text)
        assert re.search(r"attn/linear_attn/conv/", text)
        assert re.search(r"attn/linear_state/", text)
        assert re.search(r"attn/dot_general", text)
        assert re.search(r"mlp/moe_shared/dot_general", text)
        assert not re.search(
            r"[^/\w](linear_attn|ssd|conv|linear_state|moe_shared)/", text)
    if model == "keye":
        # The learned sparse attention's three steps lie inside ``attn``,
        # each under its own name: the indexer's projections and scores, the
        # selection, the pass under the mask; the index key's write is
        # ``cache``'s like the keys' and values'; the attention's own
        # projections stay plain ``attn``.
        assert re.search(r"attn/indexer/dot_general", text)
        assert re.search(r"attn/indexer/[^\"]*max", text)      # the ReLU
        assert re.search(r"attn/index_select/", text)
        assert re.search(r"attn/sparse_attn/", text)
        assert re.search(r"attn/cache/", text)
        assert re.search(r"attn/dot_general", text)
        assert not re.search(
            r"[^/\w](indexer|index_select|sparse_attn)/", text)
    if model == "mimo":
        # A window layer's attention proper (the ring's read with the sink,
        # a chunk's banded product) lies under ``window_attn`` inside
        # ``attn``; the projections and the full layers' attention stay
        # plain ``attn``; both geometries' row writes are ``cache``'s.
        if program == "prefill_chunk":     # the banded product, in jnp
            assert re.search(r"attn/window_attn/[^/\"]*/dot_general", text)
            assert re.search(r"attn/window_attn/exp", text)
        assert re.search(r"attn/window_attn/", text)
        assert re.search(r"attn/cache/", text)
        assert re.search(r"attn/dot_general", text)
        assert not re.search(r"attn/window_attn/cache/", text)
        assert not re.search(r"[^/\w]window_attn/", text)


def _train_step(name):
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train import spmd

    mesh = build_mesh(MeshSpec(), jax.devices("cpu")[:1])
    if name == "llama":
        cfg = LlamaConfig.tiny()
        make = spmd.make_llama_train_step
    else:
        cfg = MixtralConfig.tiny()
        make = spmd.make_mixtral_train_step
    step_fn, init_state, shard = make(cfg, mesh, attn_impl="blockwise",
                                      remat=True)
    tokens = shard(np.zeros((2, 16), np.int32))
    return step_fn.lower(jax.eval_shape(init_state), tokens, tokens)


def _loss_grad(name):
    if name == "longcat":
        from ray_tpu.models import longcat as model

        cfg = LongcatConfig.tiny(expert_shards=2, max_seq_len=MAX_SEQ)

        def loss(p, tokens):
            return model.forward(cfg, p, tokens)[0].sum()
    else:
        from ray_tpu.models import ouro as model

        cfg = OuroConfig.tiny(max_seq_len=MAX_SEQ)

        def loss(p, tokens):
            return model.forward(cfg, p, tokens)[0].sum()
    params = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    return jax.jit(jax.grad(loss)).lower(
        params, jnp.zeros((2, 16), jnp.int32))


TRAINED = {
    "llama": (_train_step, {"embed", "attn", "mlp", "head", "loss", "optim",
                            "stack"}),
    "mixtral": (_train_step, {"embed", "attn", "head", "loss", "optim",
                              "stack"} | ROUTED),
    # No train step of their own: the whole-sequence forward under grad.
    "longcat": (_loss_grad, {"embed", "attn", "mlp", "head", "stack"}
                | ROUTED),
    "ouro": (_loss_grad, {"embed", "attn", "mlp", "head", "stack", "loop"}),
}


@pytest.mark.parametrize("model", sorted(TRAINED))
def test_a_loss_and_gradient_step_opens_its_parts(model):
    lower, must = TRAINED[model]
    found, text = parts_in(lower(model))
    assert must <= found, sorted(must - found)
    # a step that reads no cache opens none, and samples nothing
    assert not found & {"cache", "sample"}
    # the backward pass carries the parts through the transformations
    assert re.search(r"transpose\(jvp\((stack|head|loss|embed)\)\)", text)
    for gone in LONGCAT_GONE:
        assert gone not in text


def test_named_scope_is_opened_in_one_place():
    """``jax.named_scope`` is called in util/tracing.py and nowhere else
    under ray_tpu/: a scope outside the vocabulary would be a name no
    reader knows."""
    import pathlib

    import ray_tpu

    root = pathlib.Path(ray_tpu.__file__).parent
    callers = sorted(
        str(p.relative_to(root)) for p in root.rglob("*.py")
        if re.search(r"named_scope\(", p.read_text()))
    assert callers == ["util/tracing.py"]
