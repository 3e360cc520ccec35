"""In-mesh collectives: jitted lax ops over named mesh axes.

The TPU-native replacement for the reference's NCCL group ops
(nccl_collective_group.py): on a `jax.sharding.Mesh`, collectives are
compiler-emitted ICI programs, not library calls. Each helper wraps the
corresponding `jax.lax` primitive in `shard_map` so callers can run a
collective on full (sharded) `jax.Array`s outside any larger jit region —
the same call shape `ray.util.collective.allreduce(tensor, group)` has.

All helpers also work *inside* a jitted/shard_mapped function by passing
`wrap=False` (they reduce to the bare lax op).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _shard_map(fn, mesh: Mesh, in_specs, out_specs):
    # Replication-check off: collective outputs are replicated by
    # construction (psum/all_gather), which shard_map's static checker
    # can't always infer.
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _replicated(mesh):
    return P()


def mesh_allreduce(x: jax.Array, mesh: Mesh, axis: str, op: str = "sum",
                   *, wrap: bool = True):
    """Allreduce over one mesh axis (reference collective.py:258).

    `x` is interpreted as identical-per-axis-member data (replicated input →
    replicated reduced output)."""
    red = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin,
           "mean": lambda v, ax: jax.lax.pmean(v, ax)}[op]

    def body(v):
        return red(v, axis)

    if not wrap:
        return body(x)
    f = _shard_map(body, mesh, in_specs=P(*[None] * x.ndim),
                   out_specs=P(*[None] * x.ndim))
    return jax.jit(f)(x)


def mesh_allgather(x: jax.Array, mesh: Mesh, axis: str, *, tiled_axis: int = 0,
                   wrap: bool = True):
    """Allgather shards along `tiled_axis` (reference collective.py:423)."""

    def body(v):
        return jax.lax.all_gather(v, axis, axis=tiled_axis, tiled=True)

    if not wrap:
        return body(x)
    spec = [None] * x.ndim
    spec[tiled_axis] = axis
    f = _shard_map(body, mesh, in_specs=P(*spec),
                   out_specs=P(*[None] * x.ndim))
    return jax.jit(f)(x)


def mesh_reducescatter(x: jax.Array, mesh: Mesh, axis: str,
                       *, scatter_axis: int = 0, wrap: bool = True):
    """Reduce-scatter (reference collective.py:472): replicated input,
    each member keeps its reduced shard along scatter_axis."""

    def body(v):
        return jax.lax.psum_scatter(v, axis, scatter_dimension=scatter_axis,
                                    tiled=True)

    if not wrap:
        return body(x)
    out = [None] * x.ndim
    out[scatter_axis] = axis
    f = _shard_map(body, mesh, in_specs=P(*[None] * x.ndim),
                   out_specs=P(*out))
    return jax.jit(f)(x)


def mesh_broadcast(x: jax.Array, mesh: Mesh, axis: str, root: int = 0,
                   *, wrap: bool = True):
    """Broadcast root's copy to all axis members (collective.py:373)."""

    def body(v):
        idx = jax.lax.axis_index(axis)
        # select root's value: mask + psum is the standard XLA idiom
        keep = (idx == root).astype(v.dtype)
        return jax.lax.psum(v * keep, axis)

    if not wrap:
        return body(x)
    f = _shard_map(body, mesh, in_specs=P(*[None] * x.ndim),
                   out_specs=P(*[None] * x.ndim))
    return jax.jit(f)(x)


def mesh_ppermute(x: jax.Array, mesh: Mesh, axis: str, shift: int = 1,
                  *, shard_axis: int = 0, wrap: bool = True):
    """Neighbor permute along the axis ring — the ICI primitive ring
    attention is built from (reference has no analog; NCCL send/recv is the
    closest, collective.py:531).

    `x` is sharded over `axis` along dim `shard_axis`; each member's shard
    moves to its ring neighbor `shift` hops away.
    """
    n = mesh.shape[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]

    def body(v):
        return jax.lax.ppermute(v, axis, perm)

    if not wrap:
        return body(x)
    spec = [None] * x.ndim
    spec[shard_axis] = axis
    f = _shard_map(body, mesh, in_specs=P(*spec), out_specs=P(*spec))
    return jax.jit(f)(x)


def mesh_all_to_all(x: jax.Array, mesh: Mesh, axis: str, *,
                    split_axis: int, concat_axis: int, wrap: bool = True):
    """All-to-all (Ulysses-style head/sequence exchange building block)."""

    def body(v):
        return jax.lax.all_to_all(v, axis, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    if not wrap:
        return body(x)
    in_spec = [None] * x.ndim
    in_spec[concat_axis] = axis
    out_spec = [None] * x.ndim
    out_spec[split_axis] = axis
    f = _shard_map(body, mesh, in_specs=P(*in_spec), out_specs=P(*out_spec))
    return jax.jit(f)(x)
