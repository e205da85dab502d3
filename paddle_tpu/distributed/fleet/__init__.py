"""paddle_tpu.distributed.fleet — the distributed-training control plane.

Parity: python/paddle/distributed/fleet/ (Fleet singleton fleet_base.py:62,
init:125, distributed_optimizer:554, minimize:946; meta-optimizer composition
:995-1065).  Usage is the same four lines:

    strategy = fleet.DistributedStrategy(sharding=True)
    fleet.init(is_collective=True, strategy=strategy)
    opt = fleet.distributed_optimizer(paddle_tpu.optimizer.Adam(...))
    model = paddle_tpu.Model(net); model.prepare(opt, loss); model.fit(...)

but where the reference's fleet rewrites the Program through meta-optimizers,
``init`` here builds the hybrid device Mesh and ``distributed_optimizer``
tags the optimizer with a ShardingPlan that Model.prepare lowers to
pjit shardings (see plan.py).
"""
from __future__ import annotations

from typing import Optional

import jax

from ...framework.errors import InvalidArgumentError
from .. import env as _env
from ..mesh import build_mesh, get_mesh, set_mesh
from . import metrics  # noqa: F401
from . import utils  # noqa: F401
from . import data_generator  # noqa: F401
from .utils import LocalFS, HDFSClient  # noqa: F401  (ref fleet/utils)
from .plan import ShardingPlan
from .strategy import DistributedStrategy

__all__ = [
    "DistributedStrategy",
    "ShardingPlan",
    "metrics",
    "init",
    "distributed_optimizer",
    "distributed_model",
    "worker_num",
    "worker_index",
    "is_first_worker",
    "barrier_worker",
    "stop_worker",
    "get_strategy",
    "is_initialized",
]

_strategy: Optional[DistributedStrategy] = None
_initialized = False


def init(role_maker=None, is_collective: bool = True,
         strategy: Optional[DistributedStrategy] = None, devices=None):
    """Build the hybrid mesh from the strategy degrees and mark fleet active.

    ``role_maker`` (the reference's Gloo rendezvous) is accepted for parity
    and ignored — rank wiring comes from init_parallel_env / jax.distributed.
    """
    global _strategy, _initialized
    if not is_collective:
        raise InvalidArgumentError(
            "parameter-server mode is not supported on TPU; capabilities are "
            "covered by sharded arrays (see SURVEY §7 translation table)"
        )
    _env.init_parallel_env()
    strategy = strategy or DistributedStrategy()
    # devices=None reaches build_mesh as None: it owns the default order
    n = len(devices) if devices is not None else jax.device_count()
    fixed = (strategy.mp_degree * strategy.pp_degree * strategy.sep_degree
             * strategy.ep_degree)
    sharding_degree = strategy.sharding_degree
    dp = strategy.dp_degree
    if strategy.sharding and sharding_degree in (0, 1):
        # span the devices an explicit dp_degree doesn't claim
        sharding_degree = n // (fixed * (dp or 1))
        if sharding_degree < 1:
            raise InvalidArgumentError(
                f"mp*pp*sep*dp degrees ({fixed * (dp or 1)}) exceed the "
                f"device count {n}; no devices left for the sharding axis"
            )
    if strategy.sharding and dp in (0, None):
        dp = n // (fixed * sharding_degree)
    mesh = build_mesh(
        dp=dp or 0,
        mp=strategy.mp_degree,
        pp=strategy.pp_degree,
        sep=strategy.sep_degree,
        sharding=max(sharding_degree, 1),
        ep=strategy.ep_degree,
        devices=devices,
    )
    set_mesh(mesh)
    strategy.sharding_degree = max(sharding_degree, 1)
    _strategy = strategy
    _initialized = True
    from ...framework.logging import vlog

    vlog(1, "fleet.init: mesh %s over %d devices", dict(mesh.shape), n)
    return mesh


def is_initialized() -> bool:
    return _initialized


def get_strategy() -> Optional[DistributedStrategy]:
    return _strategy


def distributed_optimizer(optimizer, strategy: Optional[DistributedStrategy] = None):
    """Compose the strategy's optimizer-level features and tag the result
    for distributed execution; Model.prepare builds the ShardingPlan from
    the tag (replaces meta-opt minimize orchestration, fleet_base.py:946,
    and the meta-optimizer composition in strategy_compiler.py:112)."""
    global _strategy
    if not _initialized:
        raise InvalidArgumentError("call fleet.init() before distributed_optimizer")
    if strategy is not None:
        _strategy = strategy
    st = _strategy or DistributedStrategy()

    # honest errors for strategies with no TPU implementation yet — the
    # reference silently composed these as program rewrites; silently
    # ignoring them here would train with a different algorithm than asked
    from ...framework.errors import UnimplementedError

    if (st.localsgd or st.adaptive_localsgd) and st.gradient_merge:
        raise InvalidArgumentError(
            "strategy.localsgd/adaptive_localsgd does not compose with "
            "gradient_merge (the reference meta-optimizers are mutually "
            "exclusive too)")
    if st.localsgd and st.adaptive_localsgd:
        raise InvalidArgumentError(
            "pick ONE of strategy.localsgd / strategy.adaptive_localsgd "
            "(the reference meta-optimizers black-list each other)")
    if st.dgc:
        # reference: DGC meta-optimizer applies only to Momentum
        # (fleet/meta_optimizers/dgc_optimizer.py _can_apply); swap it for
        # DGCMomentum, which compresses inside the DGCPlan shard_map
        from ...optimizer.dgc import DGCMomentum
        from ...optimizer.optimizer import Momentum as _Momentum

        for other in ("localsgd", "adaptive_localsgd", "lamb", "lars",
                      "gradient_merge"):
            if getattr(st, other):
                raise InvalidArgumentError(
                    f"strategy.dgc does not compose with {other} (the "
                    "reference meta-optimizers are mutually exclusive too)")
        if not isinstance(optimizer, (DGCMomentum, _Momentum)):
            raise InvalidArgumentError(
                "strategy.dgc applies to a Momentum optimizer (reference "
                "dgc_optimizer.py _can_apply)")
        if not isinstance(optimizer, DGCMomentum):
            if optimizer._multi_precision:
                raise InvalidArgumentError(
                    "strategy.dgc has no multi_precision support (the u/v "
                    "accumulators are f32 already); construct the Momentum "
                    "with multi_precision=False")
            cfg = st.dgc_configs or {}
            optimizer = DGCMomentum(
                learning_rate=optimizer._learning_rate,
                momentum=optimizer._momentum,
                parameters=optimizer._param_boxes,
                rampup_begin_step=int(cfg.get("rampup_begin_step", 0)),
                rampup_step=int(cfg.get("rampup_step", 1)),
                sparsity=cfg.get("sparsity", [0.999]),
                use_nesterov=optimizer._nesterov,
                # a regularizer object lives in _regularizer with
                # _weight_decay zeroed — forward whichever is active
                weight_decay=(optimizer._regularizer
                              or optimizer._weight_decay),
                grad_clip=optimizer._grad_clip,
            )
    if st.a_sync and int((st.a_sync_configs or {}).get("k_steps", 0)) <= 0:
        raise UnimplementedError(
            "strategy.a_sync with k_steps=0 is PURE parameter-server async "
            "mode (reference: operators/distributed/communicator.h:268); "
            "its stale-tolerance has no counterpart on a synchronous TPU "
            "mesh.  Migrations that carry the capability: "
            "a_sync_configs={'k_steps': N} for Geo-SGD (local steps + "
            "periodic parameter-delta push, geo_sgd_transpiler.py parity), "
            "strategy.localsgd for periodic model averaging, and "
            "paddle.incubate.HostEmbeddingTable for beyond-HBM tables "
            "(the PS role's big-table job)")

    from ...optimizer.optimizer import Lamb, Lars, Momentum

    if st.lamb and not isinstance(optimizer, Lamb):
        # LAMB meta-optimizer replaces an Adam-family inner optimizer
        # (reference: fleet/meta_optimizers/lamb_optimizer.py)
        cfg = st.lamb_configs or {}
        optimizer = Lamb(
            learning_rate=optimizer._learning_rate,
            lamb_weight_decay=cfg.get("lamb_weight_decay", 0.01),
            parameters=optimizer._param_boxes,
            grad_clip=optimizer._grad_clip,
            multi_precision=optimizer._multi_precision,
            exclude_from_weight_decay_fn=cfg.get("exclude_from_weight_decay_fn"),
        )
    if st.lars and not isinstance(optimizer, Lars):
        # reference: fleet/meta_optimizers/lars_optimizer.py (momentum only)
        cfg = st.lars_configs or {}
        momentum = getattr(optimizer, "_momentum", 0.9)
        if not isinstance(optimizer, Momentum):
            raise InvalidArgumentError(
                "strategy.lars applies to a Momentum optimizer (reference "
                "lars_optimizer.py _can_apply)")
        optimizer = Lars(
            learning_rate=optimizer._learning_rate,
            momentum=momentum,
            lars_coeff=cfg.get("lars_coeff", 0.001),
            lars_weight_decay=cfg.get("lars_weight_decay", 0.0005),
            parameters=optimizer._param_boxes,
            grad_clip=optimizer._grad_clip,
            multi_precision=optimizer._multi_precision,
            exclude_from_weight_decay=cfg.get("exclude_from_weight_decay"),
            epsilon=cfg.get("epsilon", 0),
        )
    if st.gradient_merge:
        from ...optimizer.gradient_merge import GradientMergeOptimizer

        cfg = st.gradient_merge_configs or {}
        optimizer = GradientMergeOptimizer(
            optimizer, k_steps=int(cfg.get("k_steps", 1)),
            avg=bool(cfg.get("avg", True)))

    optimizer._fleet_strategy = st
    return optimizer


def distributed_model(model):
    """Place a Layer's parameters onto the mesh per the active strategy
    (replicated + TP annotations).  Returns the same object (no wrapper —
    SPMD needs no grad-hook machinery like dygraph DataParallel,
    fluid/dygraph/parallel.py:335)."""
    from ...hapi.model import Model as _HapiModel
    from ...nn.layer_base import Layer

    net = model.network if isinstance(model, _HapiModel) else model
    if not isinstance(net, Layer):
        raise InvalidArgumentError("distributed_model expects a Layer or Model")
    if _strategy is not None and (_strategy.localsgd
                                  or _strategy.adaptive_localsgd):
        raise InvalidArgumentError(
            "strategy.localsgd only runs through Model.prepare/fit (the "
            "per-replica state and sync schedule live in the Model's plan); "
            "manual training loops would silently skip the averaging")
    plan = ShardingPlan(net, optimizer=None, strategy=_strategy, mesh=get_mesh())
    plan.place_network()
    return model


def worker_num() -> int:
    return jax.process_count()


def worker_index() -> int:
    return jax.process_index()


def is_first_worker() -> bool:
    return jax.process_index() == 0


def barrier_worker():
    from .. import collective

    collective.barrier()


def stop_worker():
    """No persistent worker daemons exist (the reference tears down brpc/gloo
    servers here)."""
