"""Global device mesh management.

The named ``jax.sharding.Mesh`` replaces the reference's ring_id→communicator
registry (platform/collective_helper.h:62 NCCLCommContext) and its
multi-ring/hierarchical NCCL plumbing (nccl_helper.h:185): every parallelism
axis is a *named mesh dimension* (``data``, ``model``, ``pipe``, ``sep``)
and XLA lowers collectives onto ICI/DCN along those axes.

Axis-order convention (outer→inner): ``pipe``, ``data``, ``sharding``,
``sep``, ``expert``, ``model`` — the model axis is innermost so
tensor-parallel collectives (the most latency-sensitive) map onto
directly-wired ICI neighbors; the ``expert`` axis (MoE all-to-alls, see
paddle_tpu/moe) sits next-innermost so dispatch/combine also ride ICI,
while data/pipeline axes can span DCN.  This mirrors the scaling-book
recipe rather than anything in the reference (which has no TP/PP mesh
concept at all).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..framework.errors import InvalidArgumentError

__all__ = [
    "build_mesh",
    "get_mesh",
    "set_mesh",
    "mesh_axis_size",
    "data_axes",
    "PartitionSpec",
    "NamedSharding",
    "Mesh",
]

# canonical axis names, outer→inner
AXIS_ORDER = ("pipe", "data", "sharding", "sep", "expert", "model")

_global_mesh: Optional[Mesh] = None


def build_mesh(
    dp: int = 0,
    mp: int = 1,
    pp: int = 1,
    sep: int = 1,
    sharding: int = 1,
    ep: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
    allow_dcn_inner: bool = False,
) -> Mesh:
    """Construct the hybrid-parallel mesh.  ``dp=0`` means "all remaining
    devices".  Degrees multiply to the device count.

    Multi-host (a ``jax.distributed``-joined gang): the mesh is DCN x ICI
    aware.  Devices are ordered **process-major** so, with the
    outer→inner ``AXIS_ORDER`` reshape, the outer axes (``pipe``,
    ``data``) span process/DCN boundaries while the inner axes
    (``sharding``/``sep``/``expert``/``model`` — the latency-sensitive
    collectives) stay inside a host's directly-wired ICI domain.  An
    inner-axis block that would straddle hosts (inner degrees not fitting
    the per-host device count) is rejected with guidance unless
    ``allow_dcn_inner=True`` — tensor-parallel allreduce over DCN is
    usually a config bug, not a plan.
    """
    if devices is None:
        # process-major, then by id: contiguous ICI blocks per host, DCN
        # on the outer axes.  jax.devices() usually already satisfies
        # this, but the mesh must not depend on backend enumeration luck
        # (on a v5e 2x2 host ids 0..3 sit at (0,0) (1,0) (0,1) (1,1), so
        # the innermost axis pairs ICI neighbours).
        devices = sorted(jax.devices(),
                         key=lambda d: (d.process_index, d.id))
        if jax.process_count() > 1:
            local = len(devices) // jax.process_count()
            inner = mp * ep * sep * sharding
            if local and inner > 1 and local % inner != 0 \
                    and not allow_dcn_inner:
                raise InvalidArgumentError(
                    f"inner (ICI) axes model*expert*sep*sharding={inner} "
                    f"do not fit the {local} devices of one host — a "
                    "tensor/expert-parallel group would cross DCN.  Move "
                    "parallelism to data/pipe, or pass "
                    "allow_dcn_inner=True if cross-host inner collectives "
                    "are intended")
    else:
        devices = list(devices)
    n = len(devices)
    fixed = mp * pp * sep * sharding * ep
    if fixed <= 0:
        raise InvalidArgumentError("parallel degrees must be positive")
    if dp in (0, -1, None):
        if n % fixed != 0:
            raise InvalidArgumentError(
                f"device count {n} not divisible by mp*pp*sep*sharding*ep="
                f"{fixed}"
            )
        dp = n // fixed
    if dp * fixed != n:
        raise InvalidArgumentError(
            f"dp*mp*pp*sep*sharding*ep = {dp * fixed} != device count {n}"
        )
    sizes = {"pipe": pp, "data": dp, "sharding": sharding, "sep": sep,
             "expert": ep, "model": mp}
    shape = [sizes[a] for a in AXIS_ORDER]
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, AXIS_ORDER)


def set_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh
    return mesh


def get_mesh() -> Mesh:
    """The active global mesh; defaults to pure data-parallel over all
    devices (every chip in the ``data`` axis)."""
    global _global_mesh
    if _global_mesh is None:
        _global_mesh = build_mesh()
    return _global_mesh


def mesh_axis_size(axis: str, mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return mesh.shape[axis]


def data_axes(mesh: Optional[Mesh] = None) -> List[str]:
    """Axes a global batch is split over: data + (ZeRO) sharding — the
    sharding axis is data-parallel for the forward pass."""
    mesh = mesh or get_mesh()
    return [a for a in ("data", "sharding") if mesh.shape.get(a, 1) > 1] or ["data"]
