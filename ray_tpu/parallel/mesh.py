"""Device-mesh construction for SPMD execution.

The mesh always carries the full axis set ``(dp, fsdp, ep, sp, tp)`` — axes of
size one are free, and keeping names stable means PartitionSpecs written against
logical rules never need to change when the physical layout does.

Reference contrast: Ray reaches data parallelism through per-framework process
groups (reference: python/ray/train/torch/config.py:69 `_setup_torch_process_group`)
and has no mesh concept; here the mesh *is* the cluster-of-chips abstraction and
XLA compiles the collectives over ICI.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical mesh axis order. dp outermost (pure data parallel, gradients
# all-reduced), pp next (pipeline stages — lowest-bandwidth traffic, one
# activation ppermute per microbatch tick, so it maps to DCN across slices),
# fsdp (data parallel + fully-sharded params, ZeRO-3 analog), ep (expert
# parallel for MoE), sp (sequence/context parallel), tp innermost (tensor
# parallel — highest-traffic axis, so it should map to the fastest/nearest
# ICI neighbors).
AXES = ("dp", "pp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes for each mesh axis. Product must equal the device count."""

    dp: int = 1
    pp: int = 1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.dp, self.pp, self.fsdp, self.ep, self.sp, self.tp)

    @property
    def size(self) -> int:
        return math.prod(self.shape)

    def describe(self) -> str:
        return "x".join(f"{n}={s}" for n, s in zip(AXES, self.shape) if s > 1) or "1chip"


def build_mesh(config: MeshConfig, devices=None) -> Mesh:
    """Build a jax Mesh with the canonical axis names from ``config``.

    Device order: jax.devices() is already sorted so that adjacent ids are
    ICI-adjacent on TPU; tp is the innermost (fastest-varying) axis so tensor
    parallel collectives ride nearest-neighbor links.
    """
    if devices is None:
        devices = jax.devices()
    if config.size != len(devices):
        raise ValueError(
            f"MeshConfig {config.shape} (={config.size}) != {len(devices)} devices"
        )
    arr = np.asarray(devices).reshape(config.shape)
    return Mesh(arr, AXES)


def auto_mesh_config(
    n_devices: int,
    *,
    want_tp: int = 0,
    want_sp: int = 0,
    want_ep: int = 0,
    want_pp: int = 0,
    prefer_fsdp: bool = True,
) -> MeshConfig:
    """Factor ``n_devices`` into a sensible mesh.

    Defaults put everything on fsdp (ZeRO-3-style) which is the robust choice
    for single-slice training; callers can reserve explicit tp/sp/ep/pp
    factors.
    """
    rem = n_devices
    tp = _take_factor(rem, want_tp)
    rem //= tp
    sp = _take_factor(rem, want_sp)
    rem //= sp
    ep = _take_factor(rem, want_ep)
    rem //= ep
    pp = _take_factor(rem, want_pp)
    rem //= pp
    if prefer_fsdp:
        fsdp, dp = rem, 1
    else:
        dp, fsdp = rem, 1
    return MeshConfig(dp=dp, pp=pp, fsdp=fsdp, ep=ep, sp=sp, tp=tp)


def _take_factor(n: int, want: int) -> int:
    """Largest divisor of n that is <= want (want==0 means 1)."""
    if want <= 1:
        return 1
    for f in range(min(n, want), 0, -1):
        if n % f == 0:
            return f
    return 1


def use_mesh(mesh: Mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def local_mesh() -> Mesh:
    """Mesh over all locally-visible devices, everything on fsdp."""
    n = len(jax.devices())
    return build_mesh(auto_mesh_config(n))
