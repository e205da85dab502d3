"""Tensor-parallel building blocks (megatron-style sharded layers).

The reference at this version has NO tensor parallelism (verified in
SURVEY §2.9: no megatron/model_parallel hits) — these are the new
first-class capability required of the TPU framework.  Naming follows the
later fleet.meta_parallel API so paddle users find what they expect:
ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
ParallelCrossEntropy.

SPMD design: a layer does NOT call collectives.  It annotates its
parameters with a ``partition_spec`` over the ``model`` mesh axis and
constrains its activation sharding; GSPMD inserts the all-gather /
reduce-scatter exactly where the megatron forward would put explicit
NCCL calls.  Column(out-sharded) → Row(in-sharded) pairs therefore fuse
into one all-reduce at the row output, the classic 2-matmul MLP pattern.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework import random as _random
from ..nn import initializer as I
from ..nn.layer_base import Layer, Parameter, current_rng_key
from .mesh import get_mesh

__all__ = [
    "ColumnParallelLinear",
    "RowParallelLinear",
    "VocabParallelEmbedding",
    "constrain",
]

#: weight dtypes the quantized serving path stores (slim.quantize_weights)
_QUANT_DTYPES = ("int8", "float8_e4m3fn")


def _quantized_forward(layer, x):
    """Quantized Linear leg shared by Column/RowParallelLinear: the
    weight arrived int8/fp8 (``slim.quantize_weights`` in place, or a
    quantized tree bound by ``functional_call``), so route through
    ``ops.quantized_matmul`` with the per-channel ``weight_scale``
    buffer and the bias fused into the epilogue.  The dtype branch is
    static under trace — a float weight never pays for this check."""
    from ..ops.quantized_matmul import quantized_linear

    scale = layer._buffers.get("weight_scale")
    if scale is None:
        from ..framework.errors import InvalidArgumentError

        raise InvalidArgumentError(
            f"{type(layer).__name__}: weight is "
            f"{jnp.asarray(layer.weight).dtype} but no weight_scale "
            f"buffer is registered — quantize via slim.quantize_weights "
            f"/ slim.quantize_model_trees, not a bare dtype cast")
    bias = None if layer.bias is None else jnp.asarray(layer.bias)
    return quantized_linear(jnp.asarray(x), jnp.asarray(layer.weight),
                            scale.value, bias)


def _lora_leg(layer, x, y):
    """Batched multi-LoRA delta shared by Column/RowParallelLinear: when
    the layer carries an adapter table (``lora.enable_lora``) AND a
    per-slot id scope is active (the serving step installs one), add the
    ragged grouped delta; rows with id -1 keep the base output bitwise.
    The membership check is the only cost for LoRA-free layers."""
    if "lora_A" not in layer._buffers:
        return y
    from ..lora.batched import apply_lora

    return apply_lora(layer, x, y)


def constrain(x, *spec):
    """Apply a sharding constraint when tracing (no-op eagerly, and on a
    one-device mesh, where a constraint says nothing — but would still
    hand the step's outputs back under a mesh sharding its single-device
    inputs did not have, which costs every training loop and serving
    engine on one chip a second, placement-specialised XLA compile)."""
    if isinstance(x, jax.core.Tracer):
        mesh = get_mesh()
        if mesh.size > 1:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))
    return x


class ColumnParallelLinear(Layer):
    """Linear with the OUTPUT features sharded over the ``model`` axis.

    weight [in, out∥model]; bias [out∥model].  ``gather_output=True``
    replicates the result (ends the TP region)."""

    def __init__(self, in_features: int, out_features: int, weight_attr=None,
                 has_bias: bool = True, gather_output: bool = True, name=None):
        super().__init__()
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight.partition_spec = (None, "model")
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
            self.bias.partition_spec = ("model",)
        else:
            self.bias = None

    def forward(self, x):
        if str(jnp.asarray(self.weight).dtype) in _QUANT_DTYPES:
            y = _quantized_forward(self, x)
        else:
            y = jnp.matmul(jnp.asarray(x), jnp.asarray(self.weight))
            if self.bias is not None:
                y = y + jnp.asarray(self.bias)
        y = _lora_leg(self, x, y)
        if self.gather_output:
            y = constrain(y, *([None] * y.ndim))
        else:
            y = constrain(y, *([None] * (y.ndim - 1) + ["model"]))
        return y


class RowParallelLinear(Layer):
    """Linear with the INPUT features sharded over ``model``.

    weight [in∥model, out]; bias [out] (replicated, added once).  Feeding it
    a ColumnParallelLinear(gather_output=False) output keeps the hidden
    activations sharded end-to-end; the sum over the sharded contraction
    becomes the single all-reduce of the megatron MLP."""

    def __init__(self, in_features: int, out_features: int, weight_attr=None,
                 has_bias: bool = True, input_is_parallel: bool = False, name=None):
        super().__init__()
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight.partition_spec = ("model", None)
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        from .collective import get_overlap_schedule

        x = jnp.asarray(x)
        if self.input_is_parallel:
            x = constrain(x, *([None] * (x.ndim - 1) + ["model"]))
        # overlap dial (trace-time): deferring the output-replication
        # constrain slides the model-axis all-reduce to the NEXT
        # annotation point downstream.  GSPMD is semantics-preserving —
        # the value (bias add included) is unchanged; only the reduce's
        # placement, and thus what the latency-hiding scheduler can
        # overlap it with, moves.  See collective.set_overlap_schedule.
        defer = bool(get_overlap_schedule().get("defer_row_reduce"))
        if str(jnp.asarray(self.weight).dtype) in _QUANT_DTYPES:
            y = _quantized_forward(self, x)
            y = _lora_leg(self, x, y)
            return y if defer else constrain(y, *([None] * y.ndim))
        y = jnp.matmul(x, jnp.asarray(self.weight))
        if not defer:
            y = constrain(y, *([None] * y.ndim))
        if self.bias is not None:
            y = y + jnp.asarray(self.bias)
        return _lora_leg(self, x, y)


class VocabParallelEmbedding(Layer):
    """Embedding with the vocabulary dim sharded over ``model``.

    ``sparse=True``: gradients flow as SelectedRows through sparse-aware
    train steps (framework/selected_rows.py) — the lazy optimizer's row
    gather/scatter is itself partitioned by GSPMD over the vocab shards, so
    the PS property (no O(vocab) work per step) holds on the sharded table
    too."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 weight_attr=None, sparse: bool = False, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.sparse = bool(sparse)
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=I.Normal(std=0.02))
        self.weight.partition_spec = ("model", None)
        self.weight.sparse = self.sparse

    def forward(self, ids):
        if self.sparse:
            from ..framework.selected_rows import tap_lookup

            rows = tap_lookup(self.weight, self.weight.value, ids,
                              self.num_embeddings)
            if rows is not None:
                return constrain(rows, *([None] * rows.ndim))
        # gather from a vocab-sharded table: GSPMD partitions the take along
        # the sharded dim and all-reduces the partial lookups
        out = jnp.take(jnp.asarray(self.weight), jnp.asarray(ids), axis=0)
        return constrain(out, *([None] * out.ndim))
