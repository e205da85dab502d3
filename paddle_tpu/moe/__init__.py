"""paddle_tpu.moe — expert-parallel mixture-of-experts layers.

* ``MoELayer`` — GShard-style top-k routed expert FFN, a drop-in for the
  dense ``ParallelMLP`` behind ``GPTConfig.moe_experts`` (layer.py);
* ``DroplessMoE`` — sigmoid-routed experts with no capacity and shared
  experts: sort-by-expert ragged dispatch into one grouped gated-MLP
  kernel (layer.py, ``ops/grouped_matmul.py``);
* ``stats`` — the trace-scoped collector carrying each layer's load-
  balance loss and routed/dropped counters to whoever owns the trace
  (stats.py).

Expert weights shard over the ``expert`` mesh axis
(``distributed.mesh.AXIS_ORDER``); dispatch/combine are static-shape
capacity-bucketed one-hot einsums that GSPMD lowers to all-to-alls.
"""
from . import stats  # noqa: F401
from .layer import DroplessMoE, MoELayer  # noqa: F401
