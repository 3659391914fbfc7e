"""XLA collective backend: every op is a compiled shard_map program over a
device mesh — the ICI-native replacement for NCCL rings.

Where the reference's NCCLGroup (reference:
python/ray/util/collective/collective_group/nccl_collective_group.py:121)
drives cupy-NCCL kernels on dedicated CUDA streams, this backend builds a
jitted `shard_map` per (op, shape, dtype, axes): XLA lowers `lax.psum` /
`all_gather` / `psum_scatter` / `all_to_all` / `ppermute` to ICI DMA with
compiler-scheduled overlap. Inputs are global jax.Arrays sharded over the
group's mesh (or host arrays, which are device_put first); membership IS the
mesh — no rank bookkeeping, no id exchange, no streams.

Multi-slice groups (``num_slices > 1``) additionally get a hierarchical
allreduce over a 2-level ("dcn" outer, "ici" inner) mesh: reduce-scatter
within the slice over ICI → cross-slice reduction over DCN on shard-sized
payloads → all-gather within the slice (arxiv 2004.13336's decomposition).
The DCN stage can optionally run quantized (bf16, or int8 with per-bucket
scales à la EQuARX, arxiv 2506.17615) so the slice interconnect — orders of
magnitude slower than ICI — carries 2-4x fewer bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import MeshSpec, build_mesh

_REDUCERS = {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin}


# -- EQuARX-style bucketed int8 quantization (shared with train/spmd's
#    quantized-DCN gradient stage) ------------------------------------------

def quantize_int8_bucketed(grouped):
    """The wire-format core, shared by the collective hierarchical allreduce
    and train/spmd's quantized gradient combine so the two EQuARX paths
    cannot drift: ``grouped`` carries buckets on its LAST dim; returns
    ``(int8 values, f32 scales)`` with the scale dim kept."""
    grouped = grouped.astype(jnp.float32)
    scale = jnp.max(jnp.abs(grouped), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale).astype(jnp.float32)
    return jnp.round(grouped / scale).astype(jnp.int8), scale


def quantize_int8_buckets(x, bucket: int = 256):
    """Flatten ``x`` and quantize to int8 with one f32 scale per ``bucket``
    contiguous elements. Returns ``(q [n_buckets, bucket] int8,
    scales [n_buckets, 1] f32)``; the flat length is padded to a bucket
    multiple (callers slice back to the original size after dequantize)."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % bucket
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return quantize_int8_bucketed(flat.reshape(-1, bucket))


def dequantize_int8_buckets(q, scales):
    """Inverse of :func:`quantize_int8_buckets` (still bucket-shaped/padded)."""
    return q.astype(jnp.float32) * scales


class XlaCollectiveGroup:
    """Collectives over one named mesh axis (default: all axes flattened).

    Tensors are sharded along their leading dimension over ``axis`` unless a
    PartitionSpec is given explicitly.
    """

    def __init__(self, group_name: str = "default", mesh: Mesh | None = None,
                 axis: str = "dp", devices: list | None = None,
                 world_size: int | None = None, num_slices: int = 1,
                 hierarchy: tuple[str, str] | None = None,
                 dcn_quant: str | None = None,
                 dcn_quant_bucket: int | None = None):
        """``num_slices > 1`` marks a multi-slice group: members are laid out
        on a 2-level mesh (outer level = slice over DCN, inner level = the
        slice's devices over ICI) and allreduce lowers hierarchically.
        ``hierarchy`` names the two levels, inner first (default
        ``("ici", "dcn")`` — passing it explicitly with ``num_slices == 1``
        is a no-op). ``dcn_quant`` picks the cross-slice wire format for the
        hierarchical sum: ``None``/"none" (f32), "bf16", or "int8"
        (per-bucket scales, ``dcn_quant_bucket`` elements per scale)."""
        if mesh is None:
            n = world_size or len(devices or jax.devices())
            mesh = build_mesh(MeshSpec(dp=n), devices)
        self.mesh = mesh
        self.axis = axis
        self.group_name = group_name
        self._p2p: dict[int, list] = {}  # src_rank -> buffered sends

        from ray_tpu.utils.config import get_config

        cfg = get_config()
        if dcn_quant is None:
            dcn_quant = cfg.collective_dcn_quant
        self.dcn_quant = None if dcn_quant in (None, "", "none") else dcn_quant
        if self.dcn_quant not in (None, "bf16", "int8"):
            raise ValueError(f"unknown dcn_quant {dcn_quant!r}")
        self.dcn_quant_bucket = int(dcn_quant_bucket or
                                    cfg.collective_dcn_quant_bucket)
        self.num_slices = int(num_slices)
        self.hierarchy = tuple(hierarchy) if hierarchy else ("ici", "dcn")
        self.hier_mesh: Mesh | None = None
        if self.num_slices > 1:
            devs = list(self.mesh.devices.reshape(-1))
            if self.mesh.shape[self.axis] != len(devs):
                # hier_mesh re-levels the WHOLE mesh; a group over a
                # mesh-axis subset would silently sum over non-members.
                raise ValueError(
                    "num_slices > 1 requires the group axis to span the "
                    f"whole mesh (axis {self.axis!r} has "
                    f"{self.mesh.shape[self.axis]} members, mesh has "
                    f"{len(devs)} devices)")
            if len(devs) % self.num_slices != 0:
                raise ValueError(
                    f"{len(devs)} devices not divisible into "
                    f"{self.num_slices} slices")
            per_slice = len(devs) // self.num_slices
            inner, outer = self.hierarchy
            # Slice-major device order: consecutive runs share ICI (the same
            # contract hybrid_mesh keeps for the train layer).
            self.hier_mesh = Mesh(
                np.array(devs).reshape(self.num_slices, per_slice),
                axis_names=(outer, inner))

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]

    # -- compiled-op cache -------------------------------------------------
    @functools.lru_cache(maxsize=256)  # noqa: B019 - deliberate per-group cache
    def _compiled(self, op: str, extra=None):
        mesh, axis = self.mesh, self.axis
        shard = P(axis)  # leading-dim sharded
        repl = P()

        if op.startswith("allreduce_"):
            reducer = _REDUCERS[op.split("_")[1]]

            @jax.jit
            def fn(x):
                return shard_map(
                    lambda s: reducer(s, axis), mesh=mesh,
                    in_specs=repl, out_specs=repl, check_vma=False,
                )(x)
            # replicated-in / replicated-out: each member's copy is reduced
            # pointwise. For sharded arrays use spec-aware path below.
            return fn

        if op.startswith("psum_sharded_"):
            reducer = _REDUCERS[op.split("_")[2]]

            @jax.jit
            def fn(x):
                return shard_map(
                    lambda s: reducer(s, axis), mesh=mesh,
                    in_specs=shard, out_specs=shard, check_vma=False,
                )(x)
            return fn

        if op == "allgather":
            @jax.jit
            def fn(x):
                return shard_map(
                    lambda s: lax.all_gather(s, axis, axis=0, tiled=True),
                    mesh=mesh, in_specs=shard, out_specs=repl, check_vma=False,
                )(x)
            return fn

        if op.startswith("reducescatter_"):
            reducer_name = op.split("_")[1]

            @jax.jit
            def fn(x):
                return shard_map(
                    lambda s: lax.psum_scatter(s, axis, scatter_dimension=0,
                                               tiled=True),
                    mesh=mesh, in_specs=repl, out_specs=shard, check_vma=False,
                )(x)
            return fn

        if op == "alltoall":
            @jax.jit
            def fn(x):
                # split leading dim across members, concat received chunks
                return shard_map(
                    lambda s: lax.all_to_all(s, axis, split_axis=0,
                                             concat_axis=0, tiled=True),
                    mesh=mesh, in_specs=shard, out_specs=shard,
                )(x)
            return fn

        if op == "ppermute":
            perm = list(extra)

            @jax.jit
            def fn(x):
                return shard_map(
                    lambda s: lax.ppermute(s, axis, perm=perm),
                    mesh=mesh, in_specs=shard, out_specs=shard,
                )(x)
            return fn

        if op.startswith("reduce_"):
            reducer = _REDUCERS[op.split("_")[1]]
            dst = int(extra)

            @jax.jit
            def fn(x):
                def inner(s):
                    r = reducer(s, axis)
                    keep = lax.axis_index(axis) == dst
                    return jnp.where(keep, r, s)[None]
                # Members differ post-reduce (dst holds the reduction, the
                # rest keep their input), so the global result is the
                # per-member stack [world, ...].
                return shard_map(inner, mesh=mesh, in_specs=repl,
                                 out_specs=P(axis), check_vma=False)(x)
            return fn

        if op == "broadcast":
            src = int(extra)

            @jax.jit
            def fn(x):
                def inner(s):
                    # every member takes src's shard (gather then select —
                    # ppermute can't fan out one source to all)
                    g = lax.all_gather(s, axis, axis=0, tiled=False)
                    return g[src]
                return shard_map(inner, mesh=mesh, in_specs=shard,
                                 out_specs=shard, check_vma=False)(x)
            return fn

        raise ValueError(f"unknown op {op}")

    # -- hierarchical (multi-slice) allreduce ------------------------------
    @functools.lru_cache(maxsize=32)  # noqa: B019 - deliberate per-group cache
    def _compiled_hier_allreduce(self, quant: str | None):
        """Replicated-in/replicated-out sum over ALL members, lowered as
        reduce-scatter(ICI) → cross-slice sum(DCN) on 1/ici_size payloads →
        all-gather(ICI). ``quant`` picks the DCN wire format; int8 rides an
        all-gather of (values, scales) and accumulates dequantized in f32 on
        every member, so only quantized bytes cross slices."""
        mesh = self.hier_mesh
        inner_ax, outer_ax = self.hierarchy
        ici_n = mesh.shape[inner_ax]
        bucket = self.dcn_quant_bucket

        def body(s):
            shape, dt = s.shape, s.dtype
            flat = s.reshape(-1)
            n = flat.size
            pad = (-n) % ici_n
            if pad:
                flat = jnp.pad(flat, (0, pad))
            r = lax.psum_scatter(flat, inner_ax, scatter_dimension=0,
                                 tiled=True)
            if quant == "int8":
                q, sc = quantize_int8_buckets(r, bucket)
                qg = lax.all_gather(q, outer_ax, axis=0, tiled=False)
                sg = lax.all_gather(sc, outer_ax, axis=0, tiled=False)
                r = jnp.sum(dequantize_int8_buckets(qg, sg),
                            axis=0).reshape(-1)[:r.size].astype(dt)
            elif quant == "bf16":
                # all-gather the bf16 shards and sum locally: only bf16
                # crosses the slice boundary, accumulation stays f32 (a
                # bf16 psum would compound rounding per slice).
                bg = lax.all_gather(r.astype(jnp.bfloat16), outer_ax,
                                    axis=0, tiled=False)
                r = jnp.sum(bg.astype(jnp.float32), axis=0).astype(dt)
            else:
                r = lax.psum(r, outer_ax)
            out = lax.all_gather(r, inner_ax, axis=0, tiled=True)
            return out[:n].reshape(shape)

        @jax.jit
        def fn(x):
            return shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(x)
        return fn

    # -- public ops --------------------------------------------------------
    def _device_put_sharded(self, x, spec: P):
        x = jnp.asarray(x)
        sharding = NamedSharding(self.mesh, spec)
        if hasattr(x, "sharding") and x.sharding == sharding:
            return x
        return jax.device_put(x, sharding)

    def allreduce(self, x, op: str = "sum"):
        """Pointwise reduce replicated copies across the axis. For a global
        array sharded on the axis, this is psum of shards (sharded in/out).

        Multi-slice groups (``num_slices > 1``) lower a replicated float sum
        hierarchically (ICI reduce-scatter → DCN sum, optionally quantized →
        ICI all-gather) automatically; other reductions/dtypes and sharded
        inputs keep the flat path."""
        x = jnp.asarray(x)
        if hasattr(x, "sharding") and not x.sharding.is_fully_replicated:
            return self._compiled(f"psum_sharded_{op}")(x)
        if (self.hier_mesh is not None and op == "sum"
                and jnp.issubdtype(x.dtype, jnp.floating)):
            sharding = NamedSharding(self.hier_mesh, P())
            if not (hasattr(x, "sharding") and x.sharding == sharding):
                x = jax.device_put(x, sharding)
            return self._compiled_hier_allreduce(self.dcn_quant)(x)
        x = self._device_put_sharded(x, P())
        return self._compiled(f"allreduce_{op}")(x)

    def allgather(self, x):
        x = self._device_put_sharded(x, P(self.axis))
        return self._compiled("allgather")(x)

    def reducescatter(self, x, op: str = "sum"):
        x = self._device_put_sharded(x, P())
        return self._compiled(f"reducescatter_{op}")(x)

    def alltoall(self, x):
        x = self._device_put_sharded(x, P(self.axis))
        return self._compiled("alltoall")(x)

    def broadcast(self, x, src_rank: int = 0):
        x = self._device_put_sharded(x, P(self.axis))
        return self._compiled("broadcast", src_rank)(x)

    def reduce(self, x, dst_rank: int = 0, op: str = "sum"):
        """Reduce replicated copies to ``dst_rank``. Members diverge after a
        reduce (only dst holds the reduction; the rest keep their input —
        reference: collective.py:356 reduce semantics), so the result is the
        per-member stack ``[world, *x.shape]``: ``out[dst_rank]`` is the
        reduction, ``out[r]`` is member r's original value."""
        x = self._device_put_sharded(jnp.asarray(x), P())
        return self._compiled(f"reduce_{op}", int(dst_rank))(x)

    def ppermute(self, x, perm: list[tuple[int, int]]):
        x = self._device_put_sharded(x, P(self.axis))
        return self._compiled("ppermute", tuple(perm))(x)

    def barrier(self):
        # A zero-byte psum forces a cross-device sync point.
        x = jnp.zeros((self.world_size,), jnp.float32)
        self.allreduce(x).block_until_ready()

    def send(self, x, dst_rank: int, src_rank: int = 0):
        """Point-to-point shard move src→dst, lowered to a one-pair
        ``lax.ppermute`` over ICI (reference: send/recv
        collective.py:576/:639 — NCCL p2p). The group is single-controller
        SPMD, so one call expresses both sides; the moved array is also
        buffered for a matching ``recv``."""
        out = self.ppermute(x, [(int(src_rank), int(dst_rank))])
        buf = self._p2p.setdefault(int(src_rank), [])
        buf.append(out)
        if len(buf) > 64:
            # Dropping entries would silently pair a later recv with the
            # wrong send; fail loudly instead (send-only callers should use
            # ppermute directly).
            buf.clear()
            raise RuntimeError(
                "send(): >64 unmatched sends buffered for rank "
                f"{src_rank}; pair each send with a recv, or use "
                "ppermute() for one-sided transfers")
        return out

    def recv(self, shape, dtype, src_rank: int):
        """Take the oldest buffered ``send`` from ``src_rank`` (matched-pair
        protocol of the two-sided API, collapsed into one process)."""
        buf = self._p2p.get(int(src_rank))
        if not buf:
            raise RuntimeError(
                f"recv: no buffered send from rank {src_rank}; in the "
                "single-controller XLA group send() and recv() form a "
                "matched pair in the same process")
        out = buf.pop(0)
        if tuple(shape) != tuple(out.shape) or jnp.dtype(dtype) != out.dtype:
            raise ValueError(
                f"recv: shape/dtype mismatch: sent {out.shape}/{out.dtype}, "
                f"expected {tuple(shape)}/{jnp.dtype(dtype)}")
        return out

    def destroy(self):
        self._compiled.cache_clear()
        self._compiled_hier_allreduce.cache_clear()
        self._p2p.clear()
