"""Logical-axis sharding rules: how tensors map onto the mesh.

TPU-native replacement for the reference's per-framework sharding (reference:
ray.train torch path wraps DDP/FSDP per-parameter at runtime,
train_loop_utils.py:153; vLLM owns TP layout): here sharding is declarative —
params/activations carry *logical* axis names and a rule table maps logical →
mesh axes; XLA inserts the collectives. Swapping dp↔fsdp↔tp strategy is a
rule-table change, not a model change.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.kernels import KernelMesh

# Default rule table for transformer training (MaxText-style conventions):
# logical axis name -> mesh axis (or tuple of mesh axes, or None = replicate).
DEFAULT_RULES: dict[str, object] = {
    # params
    "vocab": "tp",
    "embed": ("fsdp",),          # weight-shard over fsdp
    "mlp": "tp",
    "heads": "tp",
    "kv_heads": "tp",
    "head_dim": None,
    "layers": None,              # stacked-layer leading axis (scan over layers)
    "expert": "ep",
    # activations
    "batch": ("dp", "fsdp"),     # global batch split over both data axes
    "seq": "sp",
    "act_embed": None,
    "act_heads": "tp",
}


@dataclass
class ShardingRules:
    rules: dict[str, object] = field(default_factory=lambda: dict(DEFAULT_RULES))

    def spec(self, *logical_axes: str | None) -> P:
        """PartitionSpec for a tensor whose dims have these logical names."""
        out = []
        used: set[str] = set()
        for ax in logical_axes:
            if ax is None:
                out.append(None)
                continue
            mesh_ax = self.rules.get(ax)
            if mesh_ax is None:
                out.append(None)
            elif isinstance(mesh_ax, tuple):
                fresh = tuple(m for m in mesh_ax if m not in used)
                used.update(fresh)
                out.append(fresh if len(fresh) > 1 else (fresh[0] if fresh else None))
            else:
                if mesh_ax in used:
                    out.append(None)
                else:
                    used.add(mesh_ax)
                    out.append(mesh_ax)
        return P(*out)

    def sharding(self, mesh: Mesh, *logical_axes: str | None) -> NamedSharding:
        return NamedSharding(mesh, self.spec(*logical_axes))

    def override(self, **updates) -> "ShardingRules":
        return ShardingRules({**self.rules, **updates})


def kernel_mesh(mesh: Mesh, rules: ShardingRules | None = None,
                batch: tuple[str, ...] | None = None) -> KernelMesh | None:
    """What the Pallas kernel wrappers need to run per shard under ``mesh``
    (ops/kernels.py): the axes the rule table shards the batch over
    (``batch`` overrides them — the multi-slice step keeps its DCN axes for
    an outer vmap) and the axis of the attention heads. None on one device,
    where the kernels are called directly."""
    if mesh.size == 1:
        return None
    rules = rules or ShardingRules()
    if batch is None:
        batch = batch_axes(rules)
    heads = rules.rules.get("act_heads")
    return KernelMesh(
        mesh, tuple(a for a in batch if a in mesh.axis_names),
        heads if heads in mesh.axis_names else None)


def tree_shardings(mesh: Mesh, logical_tree, rules: ShardingRules | None = None):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    rules = rules or ShardingRules()
    return jax.tree.map(
        lambda axes: rules.sharding(mesh, *axes),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x
        ),
    )


def shard_params(params, mesh: Mesh, logical_tree, rules: ShardingRules | None = None):
    """Device_put a param pytree with shardings derived from logical axes."""
    shardings = tree_shardings(mesh, logical_tree, rules)
    return jax.tree.map(jax.device_put, params, shardings)


# -- cross-replica weight-update sharding (ZeRO-1, arxiv 2004.13336) --------

def batch_axes(rules: ShardingRules | None = None) -> tuple[str, ...]:
    """The mesh axes the global batch shards over — the data-parallel domain
    a ZeRO-1 update can shard optimizer state across."""
    rules = rules or ShardingRules()
    ax = rules.rules.get("batch")
    if ax is None:
        return ()
    return tuple(ax) if isinstance(ax, tuple) else (ax,)


# Logical dims a ZeRO-1 update must NOT shard: "layers" is the scan-stacked
# dim (sharding it would slice the layer loop itself, forcing per-iteration
# resharding inside the backward while-loop), and "vocab" is gather/scatter-
# indexed on the embedding table (a data-dependent-sharded scatter makes the
# partitioner fall back to full gathers of the one-hot activations).
ZERO1_SKIP_LOGICAL = ("layers", "vocab")


def zero1_spec(spec: P, shape: tuple[int, ...], mesh: Mesh,
               axes: tuple[str, ...],
               logical: tuple[str | None, ...] | None = None) -> P:
    """Extend a param leaf's PartitionSpec so one dim is additionally
    sharded over ``axes`` (the data-parallel mesh axes), when divisible.

    This is the ZeRO-1 layout: optimizer moments (and the weight update)
    keyed off this spec live 1/N-sized per data-parallel replica. The dim is
    the largest one divisible by the extra factor whose logical name (when
    ``logical`` is given) isn't in :data:`ZERO1_SKIP_LOGICAL` — matmul-style
    dims lower to clean (reduce-)scatter collectives, scan/index dims don't.
    Axes already used elsewhere in the spec are skipped; leaves with no
    suitable dim keep their original spec (their update stays replicated —
    correct, just not sharded)."""
    spec = P(*spec) if spec is not None else P()
    entries = list(spec) + [None] * (len(shape) - len(spec))
    if not entries or not shape:
        return spec
    used = set()
    for e in entries:
        used.update(e if isinstance(e, tuple) else ((e,) if e else ()))
    extra = tuple(a for a in axes if a not in used and mesh.shape[a] > 1)
    if not extra:
        return spec
    extra_n = math.prod(mesh.shape[a] for a in extra)

    def _entry_axes(e):
        return e if isinstance(e, tuple) else ((e,) if e else ())

    best = None
    for dim, size in enumerate(shape):
        if logical is not None and dim < len(logical) and \
                logical[dim] in ZERO1_SKIP_LOGICAL:
            continue
        factor = extra_n * math.prod(
            mesh.shape[a] for a in _entry_axes(entries[dim]))
        if size % factor:
            continue
        if best is None or size > shape[best]:
            best = dim
    if best is None:
        return spec
    merged = tuple(_entry_axes(entries[best])) + extra  # existing axes major
    entries[best] = merged if len(merged) > 1 else merged[0]
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def zero1_shardings(mesh: Mesh, shapes, shardings, axes: tuple[str, ...],
                    logical_axes=None):
    """Map param-leaf shardings to their ZeRO-1 counterparts: each leaf's
    spec extended over the data-parallel ``axes`` via :func:`zero1_spec`.
    ``shapes`` is any pytree of objects with ``.shape`` matching
    ``shardings``' structure; ``logical_axes`` (the same pytree of
    logical-dim-name tuples the rule table consumes) steers dim choice away
    from scan/index dims."""
    leaves, treedef = jax.tree.flatten(shapes)
    sh_leaves = jax.tree.flatten(shardings)[0]
    if logical_axes is None:
        log_leaves = [None] * len(leaves)
    else:
        # is_leaf must also catch None entries ("no logical names for this
        # leaf") — tree.flatten would otherwise DROP them, misaligning
        # log_leaves against the param leaves.
        log_leaves = jax.tree.flatten(
            logical_axes,
            is_leaf=lambda x: x is None or (
                isinstance(x, tuple) and all(
                    isinstance(a, (str, type(None))) for a in x)))[0]
        if len(log_leaves) != len(leaves):
            raise ValueError(
                f"logical_axes tree has {len(log_leaves)} leaves, params "
                f"have {len(leaves)}")
    out = [
        NamedSharding(mesh, zero1_spec(sh.spec, tuple(leaf.shape), mesh,
                                       axes, logical=log))
        for leaf, sh, log in zip(leaves, sh_leaves, log_leaves)
    ]
    return jax.tree.unflatten(treedef, out)
