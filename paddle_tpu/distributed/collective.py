"""User-facing collectives.

Parity: python/paddle/distributed/collective.py (broadcast:89, all_reduce:146,
reduce:221, all_gather:304, scatter:377, barrier:449) and the c_* collective
ops (operators/collective/c_allreduce_op.h:109 NCCL dispatch).

TPU-native semantics: there is ONE controller per host, not one process per
chip, so "each rank's tensor" is expressed as a *stacked global array* whose
leading dim indexes ranks along a mesh axis (default ``data``).  Each
collective shard_maps a ``lax`` collective over that axis — XLA lowers it to
an ICI/DCN all-reduce/gather/permute exactly like the reference's NCCL ring
call, but compiler-scheduled and fusable.  After the call, every rank slot
holds the value paddle's per-process API would give that rank.

For *in-graph* use (inside your own ``shard_map``), use the primitives
directly: ``psum``/``pmean``/``pmax``/``ppermute``/``all_to_all`` re-exports.
"""
from __future__ import annotations

import functools
import threading
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from ..framework.errors import InvalidArgumentError, TransientDeviceError
from ..framework.flags import flag as _flag
from .mesh import get_mesh


def shard_map(f, mesh, in_specs, out_specs, axis_names=None):
    # replication check off: collectives like all_gather produce values
    # that ARE replicated over the group axis, but the static checker
    # can't always infer it.  ``axis_names`` restricts which mesh axes the
    # body is manual over.
    kw = {"axis_names": set(axis_names)} if axis_names is not None else {}
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False, **kw)


__all__ = [
    "ReduceOp",
    "all_reduce",
    "all_gather",
    "reduce",
    "broadcast",
    "scatter",
    "alltoall",
    "barrier",
    "psum",
    "pmean",
    "pmax",
    "pmin",
    "ppermute",
    "all_to_all_single",
]

# in-graph primitive re-exports (for custom shard_map code)
psum = lax.psum
pmean = lax.pmean
pmax = lax.pmax
pmin = lax.pmin
ppermute = lax.ppermute


def all_to_all_single(x, axis_name: str, split_axis: int = 0, concat_axis: int = 0):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"


_REDUCERS = {
    ReduceOp.SUM: lax.psum,
    ReduceOp.MAX: lax.pmax,
    ReduceOp.MIN: lax.pmin,
    ReduceOp.PROD: lambda x, a: lax.all_gather(x, a).prod(axis=0),
}


def _group_axis(group) -> str:
    # the one seam every collective passes through — chaos plans inject
    # device/interconnect failures here (site "collective.call")
    from ..resilience.faults import fault_point

    fault_point("collective.call")
    if group is None:
        return "data"
    if isinstance(group, str):
        return group
    return getattr(group, "axis", "data")


def _watchdog(fn):
    """Straggler watchdog: with FLAGS_collective_timeout_s set, the wrapped
    collective runs (through device completion — block_until_ready) in a
    worker thread under a deadline; a wedged interconnect raises
    ``TransientDeviceError`` into the retry/restart path instead of
    hanging the rank forever.  Disabled (the default 0.0) the wrapper is a
    single falsy flag check."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        timeout = _flag("collective_timeout_s")
        if not timeout:
            return fn(*args, **kwargs)
        done = threading.Event()
        box: dict = {}

        def _run():
            try:
                box["value"] = jax.block_until_ready(fn(*args, **kwargs))
            except BaseException as e:  # surfaced in the caller below
                box["error"] = e
            finally:
                done.set()

        # daemon: a wedged device call may never return — the thread must
        # not block interpreter shutdown after the deadline fires
        t = threading.Thread(target=_run, daemon=True,
                             name=f"collective-watchdog-{fn.__name__}")
        t.start()
        if not done.wait(float(timeout)):
            from ..framework import monitor as _monitor
            from ..framework.logging import vlog
            from ..resilience import supervisor as _supervisor

            _monitor.stat_add("collective_watchdog_trips")
            _supervisor.record("watchdog_trips")
            vlog(0, "collective: %s exceeded the %.1fs watchdog deadline "
                    "— raising TransientDeviceError", fn.__name__, timeout)
            raise TransientDeviceError(
                f"collective {fn.__name__} did not complete within "
                f"FLAGS_collective_timeout_s={timeout:g}s — wedged "
                f"interconnect or straggler rank; the call keeps running "
                f"on its watchdog thread but this rank treats it as a "
                f"transient device failure")
        if "error" in box:
            raise box["error"]
        return box["value"]

    return wrapper


def _stacked(tensor, axis: str):
    mesh = get_mesh()
    n = mesh.shape[axis]
    tensor = jnp.asarray(tensor)
    if tensor.shape[0] != n:
        raise InvalidArgumentError(
            f"stacked collective input must have leading dim {n} "
            f"(= size of mesh axis {axis!r}), got {tensor.shape}"
        )
    return mesh, tensor


@functools.partial(jax.jit, static_argnames=("op", "axis", "mesh"))
def _all_reduce_jit(tensor, op, axis, mesh):
    reducer = _REDUCERS[op]

    def f(t):  # t: [1, ...] per rank
        return reducer(t, axis)

    return shard_map(f, mesh=mesh, in_specs=P(axis), out_specs=P(axis))(tensor)


def _all_reduce_impl(tensor, op, axis):
    # the mesh is a static jit key: set_mesh() must never hit a stale cache
    return _all_reduce_jit(tensor, op, axis, get_mesh())


@_watchdog
def all_reduce(tensor, op: str = ReduceOp.SUM, group=None, sync_op: bool = True):
    """Every rank slot ends with the reduction over all rank slots."""
    axis = _group_axis(group)
    _, tensor = _stacked(tensor, axis)
    return _all_reduce_impl(tensor, op, axis)


@_watchdog
def all_gather(tensor_or_list, tensor=None, group=None, sync_op: bool = True) -> List[jax.Array]:
    """Returns the list of per-rank tensors (replicated everywhere).

    Call styles: ``all_gather(stacked)`` or paddle-style
    ``all_gather(out_list, stacked)`` which extends ``out_list``.
    """
    out_list = None
    if tensor is None:
        stacked = tensor_or_list
    else:
        out_list, stacked = tensor_or_list, tensor
    axis = _group_axis(group)
    mesh, stacked = _stacked(stacked, axis)

    def f(t):  # [1, ...] → gather to [n, ...] on every rank
        return lax.all_gather(t, axis, axis=0, tiled=True)

    gathered = shard_map(f, mesh=mesh, in_specs=P(axis), out_specs=P(None))(stacked)
    result = [gathered[i] for i in range(gathered.shape[0])]
    if out_list is not None:
        out_list.extend(result)
    return result


@_watchdog
def reduce(tensor, dst: int = 0, op: str = ReduceOp.SUM, group=None, sync_op: bool = True):
    """Rank ``dst``'s slot gets the reduction; other slots keep their value."""
    axis = _group_axis(group)
    mesh, tensor = _stacked(tensor, axis)
    reducer = _REDUCERS[op]

    def f(t):
        total = reducer(t, axis)
        i = lax.axis_index(axis)
        return jnp.where(i == dst, total, t)

    return shard_map(f, mesh=mesh, in_specs=P(axis), out_specs=P(axis))(tensor)


@_watchdog
def broadcast(tensor, src: int = 0, group=None, sync_op: bool = True):
    """Every rank slot ends with rank ``src``'s value."""
    axis = _group_axis(group)
    mesh, tensor = _stacked(tensor, axis)

    def f(t):
        # mask-and-sum: contributes only src's shard, summed over the axis —
        # lowers to a one-hot all-reduce (XLA folds it into a broadcast)
        i = lax.axis_index(axis)
        contrib = jnp.where(i == src, t, jnp.zeros_like(t))
        return lax.psum(contrib, axis)

    return shard_map(f, mesh=mesh, in_specs=P(axis), out_specs=P(axis))(tensor)


@_watchdog
def scatter(tensor, tensor_list=None, src: int = 0, group=None, sync_op: bool = True):
    """Rank i's slot gets ``tensor_list[i]`` (from rank src).  With the
    stacked representation the rows ARE the per-rank values, so this
    broadcasts src's stacked rows and selects row i for rank i."""
    axis = _group_axis(group)
    if tensor_list is not None:
        tensor = jnp.stack([jnp.asarray(t) for t in tensor_list], axis=0)
    mesh, tensor = _stacked(tensor, axis)
    return tensor  # row i is already rank i's result


@_watchdog
def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op: bool = True):
    """result[i][j] = input[j][i] over the group axis (ragged-free)."""
    axis = _group_axis(group)
    if isinstance(in_tensor_list, (list, tuple)):
        stacked = jnp.stack([jnp.asarray(t) for t in in_tensor_list], axis=0)
    else:
        stacked = jnp.asarray(in_tensor_list)
    mesh, stacked = _stacked(stacked, axis)

    def f(t):  # t: [1, n, ...] per rank — swap rank/slot dims globally
        return lax.all_to_all(t, axis, split_axis=1, concat_axis=0, tiled=False)

    n = mesh.shape[axis]
    if stacked.shape[1] != n:
        raise InvalidArgumentError(
            f"alltoall needs [n, n, ...] stacked input, got {stacked.shape}"
        )
    out = shard_map(f, mesh=mesh, in_specs=P(axis), out_specs=P(axis))(stacked)
    out = out.reshape(stacked.shape)
    result = [out[i] for i in range(n)]
    if out_tensor_list is not None:
        out_tensor_list.extend(result)
    return result


@_watchdog
def barrier(group=None):
    """Block until all prior device work completes (XLA programs are
    compiler-ordered; the host-visible barrier is block_until_ready)."""
    axis = _group_axis(group)
    mesh = get_mesh()
    n = mesh.shape[axis]
    token = jnp.zeros((n,), jnp.int32)
    out = _all_reduce_impl(token, ReduceOp.SUM, axis)
    jax.block_until_ready(out)


# ---------------------------------------------------------------------------
# Overlap schedules: WHERE the decode-path collectives land, as a tunable.
#
# The megatron layers never call collectives directly — they annotate
# (`constrain`) and GSPMD inserts the tensor/expert-parallel all-reduces at
# the annotation points.  GSPMD is semantics-preserving, so moving an
# annotation never changes the value, only WHERE the reduce materializes —
# which decides how much neighboring compute XLA's latency-hiding scheduler
# can overlap the ICI transfer with.  A decode step is latency-bound, so
# the placement is worth real microseconds per layer; instead of
# hand-picking, the dials below are searched by `tuning.plan_space.
# tune_decode_schedule` on REAL decode steps (the `overlap_grad_sync`
# treatment, applied to inference collectives).
#
# Dials (all 0/1, read at TRACE time — retrace after changing them):
#   defer_row_reduce     — RowParallelLinear skips its immediate
#                          output-replication constrain; the all-reduce
#                          slides to the next annotation (after bias/
#                          residual), freeing the scheduler to overlap it
#                          with the adjacent elementwise work.
#   mlp_collective_split — GPTBlock splits the decode residual stream
#                          around the MLP: the MLP's row-parallel reduce is
#                          deferred past the residual add and pinned there,
#                          so it can run concurrently with the add.
_OVERLAP_DIALS = ("defer_row_reduce", "mlp_collective_split")
_overlap_schedule = {k: 0 for k in _OVERLAP_DIALS}
_overlap_lock = threading.Lock()


def get_overlap_schedule() -> dict:
    """The active overlap-schedule dials (a copy)."""
    with _overlap_lock:
        return dict(_overlap_schedule)


def set_overlap_schedule(config: Optional[dict] = None, **dials) -> dict:
    """Set overlap dials (unknown keys rejected; unset dials keep their
    value).  Returns the previous schedule.  Functions traced AFTER the
    call see the new placement; already-compiled executables keep the
    schedule they were traced under."""
    from ..framework.errors import InvalidArgumentError

    merged = dict(config or ())
    merged.update(dials)
    for k in merged:
        if k not in _OVERLAP_DIALS:
            raise InvalidArgumentError(
                f"unknown overlap dial {k!r} (have {_OVERLAP_DIALS})")
    with _overlap_lock:
        prev = dict(_overlap_schedule)
        for k, v in merged.items():
            _overlap_schedule[k] = int(v)
    return prev


class overlap_schedule:
    """Context manager: apply overlap dials for the trace inside, restore
    the previous schedule on exit."""

    def __init__(self, config: Optional[dict] = None, **dials):
        self._new = dict(config or ())
        self._new.update(dials)

    def __enter__(self):
        self._prev = set_overlap_schedule(self._new)
        return get_overlap_schedule()

    def __exit__(self, *exc):
        set_overlap_schedule(self._prev)


def all_reduce_start(x, axis_name: str):
    """Stage an in-graph all-reduce (for explicit ``shard_map`` bodies):
    returns an opaque handle; the reduce itself happens at
    :func:`all_reduce_finish`.  The pair is a SCHEDULING seam, not an
    async runtime: everything the caller computes between start and
    finish is, by data dependence, free to execute while the reduce is
    in flight — XLA's latency-hiding scheduler does the actual overlap
    (the same contract as `overlap_grad_sync` staging for grad syncs).
    """
    return (x, str(axis_name))


def all_reduce_finish(handle):
    """Complete a staged in-graph all-reduce: the ``lax.psum`` over the
    axis captured at :func:`all_reduce_start`."""
    x, axis_name = handle
    return lax.psum(x, axis_name)


__all__ += [
    "all_reduce_start",
    "all_reduce_finish",
    "get_overlap_schedule",
    "set_overlap_schedule",
    "overlap_schedule",
]
