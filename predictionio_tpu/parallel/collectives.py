"""Collective-communication helpers over the framework mesh.

The TPU-native replacement for the reference's driver⇄executor
communication (Spark shuffle/broadcast/collect — SURVEY §2.3): inside a
``shard_map``-ped function these wrap XLA collectives that ride ICI
within a slice and DCN across slices; outside, the sharded-jit pattern
(annotate shardings, let XLA insert collectives) is usually preferable —
these exist for the cases where the schedule must be explicit (Gramian
all-reduce, halo exchanges, sharded top-k merge).
"""

from __future__ import annotations

from typing import Callable, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import MODEL_AXIS

Axis = Union[str, Sequence[str]]


def all_reduce_sum(x: jax.Array, axis: Axis = MODEL_AXIS) -> jax.Array:
    """``lax.psum`` — the Gramian/gradient all-reduce (NCCL allreduce
    role)."""
    return lax.psum(x, axis)


def gramian_allreduce(x: jax.Array, mesh: Mesh) -> jax.Array:
    """``xᵀx`` of a row-sharded ``[n, r]`` table as an EXPLICIT
    per-shard partial + ICI psum, replicated out.

    The all-reduce is a structurally independent node, which frees
    XLA's latency-hiding scheduler to run it on ICI underneath other
    work — the compute/collective overlap ALX builds its sharded
    trainer around (arXiv 2112.02194). No production path calls it
    (ALS training takes the plain einsum, whose collective GSPMD
    derives); it is the example entry of ``analysis/hlo_audit.py`` and
    ``analysis/numerics_audit.py``. Axis names come from the mesh, so
    the same program runs over a ``(data, model)`` training mesh and a
    ``(batch, model)`` serving mesh."""
    axes = tuple(mesh.axis_names)

    def part(t):
        return lax.psum(
            jax.lax.dot_general(t, t, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32),
            axes)

    return jax.shard_map(part, mesh=mesh, in_specs=P(axes),
                         out_specs=P(), check_vma=False)(x)


def all_gather(x: jax.Array, axis: Axis = MODEL_AXIS,
               *, tiled: bool = True) -> jax.Array:
    """Gather shards along the leading dim (NCCL allgather role)."""
    return lax.all_gather(x, axis, tiled=tiled)


def reduce_scatter(x: jax.Array, axis: Axis = MODEL_AXIS) -> jax.Array:
    """Sum across the axis, scattering rows back (NCCL reduce-scatter)."""
    return lax.psum_scatter(x, axis, tiled=True)


def ring_permute(x: jax.Array, axis: Axis = MODEL_AXIS,
                 shift: int = 1) -> jax.Array:
    """Send each shard to its ring neighbor (``lax.ppermute``) — the
    building block for ring-structured algorithms (ring all-reduce,
    ring attention) on ICI."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def axis_index(axis: Axis = MODEL_AXIS) -> jax.Array:
    return lax.axis_index(axis)


def sharded(mesh: Mesh, in_specs, out_specs,
            check_vma: bool = False) -> Callable:
    """Decorator: ``shard_map`` a function over the framework mesh.

        @sharded(mesh, in_specs=P("model"), out_specs=P())
        def global_norm(shard):
            return all_reduce_sum((shard ** 2).sum())
    """

    def deco(fn):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_vma)

    return deco


def sharded_top_k(scores: jax.Array, k: int, mesh: Mesh,
                  axis: str = MODEL_AXIS) -> tuple:
    """Global top-k over a row-sharded score vector.

    Two-phase (the TPU shape of the reference's per-partition
    ``getTopN`` + driver merge): local ``lax.top_k`` per shard, then an
    all-gather of the k·n_shards candidates and a final top-k — the
    cross-device traffic is k·n_shards scalars instead of the full
    vector. Returns (global indices, values).
    """
    n_local = scores.shape[-1] // mesh.shape[axis]

    def local_then_merge(s):
        vals, idx = lax.top_k(s, min(k, s.shape[-1]))
        base = lax.axis_index(axis) * n_local
        idx = idx + base
        all_vals = lax.all_gather(vals, axis, tiled=True)
        all_idx = lax.all_gather(idx, axis, tiled=True)
        mvals, mpos = lax.top_k(all_vals, k)
        return mpos, mvals, all_idx

    fn = jax.shard_map(local_then_merge, mesh=mesh,
                       in_specs=P(axis), out_specs=(P(), P(), P()),
                       check_vma=False)
    mpos, mvals, all_idx = fn(scores)
    return jnp.take(all_idx, mpos), mvals
