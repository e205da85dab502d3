"""Layer/Parameter system + the eager↔functional bridge.

TPU-native re-design of the reference's module system:

* ``paddle.nn.Layer`` (reference: python/paddle/fluid/dygraph/layers.py) —
  parameter/buffer/sublayer registration, name scopes, train/eval,
  state_dict.  Reproduced here with the same ergonomics.
* dygraph Tracer + BasicEngine autograd (paddle/fluid/imperative/tracer.cc,
  basic_engine.cc) — NOT reproduced.  Instead ``functional_call`` projects a
  stateful Layer onto a pure function of a parameter pytree, so ``jax.grad``
  / ``jax.jit`` / ``jax.vmap`` provide autodiff and compilation.  This is the
  single-runtime answer to the reference's dual static/dygraph engines: the
  eager API *is* the traceable API.

A ``Parameter`` is a mutable box over a ``jax.Array`` implementing
``__jax_array__``, so ``jnp.matmul(x, layer.weight)`` works directly in
forward() while the optimizer can still rebind values in-place (eager mode)
and ``functional_call`` can substitute tracers (jit mode).
"""
from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import random as _random
from ..framework.errors import InvalidArgumentError, NotFoundError
# amp only imports framework/jax at module level — no cycle back into nn
from ..amp.auto_cast import amp_state as _amp_state
from ..amp.auto_cast import cast_layer_call as _amp_cast_layer_call

__all__ = [
    "Parameter",
    "Buffer",
    "Layer",
    "functional_call",
    "current_rng_key",
    "rng_scope",
]


class Parameter:
    """Trainable tensor box. ``trainable=False`` ≙ paddle's stop_gradient.

    ``partition_spec`` (tuple of mesh axis names / None per dim, or None for
    replicated) is the tensor-parallel placement annotation consumed by
    distributed.fleet.ShardingPlan."""

    __slots__ = ("value", "name", "trainable", "partition_spec", "sparse")

    def __init__(self, value, name: str = "", trainable: bool = True,
                 partition_spec=None):
        self.value = jnp.asarray(value)
        self.name = name
        self.trainable = trainable
        self.partition_spec = partition_spec
        # sparse=True: gradients flow as SelectedRows through sparse-aware
        # train steps (framework/selected_rows.py); set by
        # nn.Embedding(sparse=True)
        self.sparse = False

    # jnp.asarray(param) → the underlying array; makes params usable in ops.
    def __jax_array__(self):
        return self.value

    @property
    def shape(self):
        return tuple(self.value.shape)

    @property
    def dtype(self):
        return self.value.dtype

    @property
    def ndim(self):
        return self.value.ndim

    def numpy(self):
        return np.asarray(self.value)

    def set_value(self, v):
        self.value = jnp.asarray(v, dtype=self.value.dtype)

    def __repr__(self):
        return f"Parameter(name={self.name!r}, shape={self.shape}, dtype={self.dtype}, trainable={self.trainable})"

    # arithmetic conveniences (rarely needed; forward code usually passes
    # the box straight into jnp ops)
    def __mul__(self, o):
        return self.value * o

    def __rmul__(self, o):
        return o * self.value

    def __add__(self, o):
        return self.value + o

    def __radd__(self, o):
        return o + self.value

    def __sub__(self, o):
        return self.value - o

    def __neg__(self):
        return -self.value

    def __getitem__(self, idx):
        return self.value[idx]

    def astype(self, dt):
        return self.value.astype(dt)


class Buffer(Parameter):
    """Non-trainable state (BN running stats). Parity: Layer.register_buffer.
    persistable=False buffers are excluded from state_dict."""

    __slots__ = ("persistable",)

    def __init__(self, value, name: str = "", persistable: bool = True):
        super().__init__(value, name, trainable=False)
        self.persistable = persistable


# ---------------------------------------------------------------------------
# RNG plumbing: eager mode pulls from the global generator; functional mode
# installs a per-call key via rng_scope so traced dropout is pure.
# ---------------------------------------------------------------------------
class _RngState(threading.local):
    def __init__(self):
        self.stack = []


_rng_state = _RngState()


class _RngCtx:
    __slots__ = ("key", "count")

    def __init__(self, key):
        self.key = key
        self.count = 0

    def next(self):
        k = jax.random.fold_in(self.key, self.count)
        self.count += 1
        return k


@contextlib.contextmanager
def rng_scope(key):
    """Install an explicit RNG key for all random layers inside the scope."""
    ctx = _RngCtx(key)
    _rng_state.stack.append(ctx)
    try:
        yield ctx
    finally:
        _rng_state.stack.pop()


def current_rng_key() -> jax.Array:
    """Key for a random op inside a Layer.forward. Deterministic per-call
    inside rng_scope (traced mode); fresh from the global generator otherwise."""
    if _rng_state.stack:
        return _rng_state.stack[-1].next()
    return _random.default_generator().next_key()


# ---------------------------------------------------------------------------
# Layer
# ---------------------------------------------------------------------------
_abstract_init = threading.local()


@contextlib.contextmanager
def abstract_parameters():
    """Build layers without materializing their parameters: inside the
    scope every new :class:`Parameter` box holds a
    ``jax.ShapeDtypeStruct`` (shape and dtype, no memory, no initializer
    run).  For models whose weights arrive from elsewhere and are too
    large to hold twice; the caller fills every box (``p.value = w``)
    before the first forward."""
    prev = getattr(_abstract_init, "on", False)
    _abstract_init.on = True
    try:
        yield
    finally:
        _abstract_init.on = prev


def build_parameter(shape, dtype=None, attr=None, is_bias=False,
                    default_initializer=None) -> "Parameter":
    """Create a Parameter box from ParamAttr semantics — shared by
    Layer.create_parameter and the top-level paddle.create_parameter
    (ref: fluid/layers/tensor.py:75), so initializer precedence, dtype
    defaulting, and the trainable flag cannot drift between the two."""
    from . import initializer as I
    from ..framework import dtype as _dt

    dtype = _dt.convert_dtype(dtype or _dt.get_default_dtype())
    init = None
    name = None
    trainable = True
    if attr is not None and attr is not False:
        init = getattr(attr, "initializer", None)
        name = getattr(attr, "name", None)
        trainable = getattr(attr, "trainable", True)
    if init is None:
        init = default_initializer or (
            I.Constant(0.0) if is_bias else I.XavierNormal())
    if getattr(_abstract_init, "on", False):
        p = Parameter(jnp.zeros((), dtype), name=name or "",
                      trainable=trainable)
        p.value = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
        return p
    value = init(tuple(shape), dtype, key=_random.default_generator().next_key())
    return Parameter(value, name=name or "", trainable=trainable)


class Layer:
    """Parity: paddle.nn.Layer (python/paddle/fluid/dygraph/layers.py).

    Differences by design (TPU-native):
      * no ``.backward()`` — use ``functional_call`` + jax.grad (or the
        hapi ``Model``/fleet APIs which do it for you);
      * buffers mutated in forward (BN stats) are captured functionally by
        ``functional_call(..., return_buffers=True)`` when traced.
    """

    def __init__(self, name_scope: Optional[str] = None, dtype="float32"):
        self._parameters: "OrderedDict[str, Parameter]" = OrderedDict()
        self._buffers: "OrderedDict[str, Buffer]" = OrderedDict()
        self._sub_layers: "OrderedDict[str, Layer]" = OrderedDict()
        self._forward_pre_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._forward_post_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self.training = True
        self._name_scope = name_scope or self.__class__.__name__.lower()
        self._dtype = dtype

    # -- registration --------------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        if isinstance(value, Parameter) and not isinstance(value, Buffer):
            if params is None:
                raise RuntimeError("call super().__init__() before assigning parameters")
            params[name] = value
            self.__dict__.pop(name, None)
            return
        if isinstance(value, Buffer):
            self._buffers[name] = value
            self.__dict__.pop(name, None)
            return
        if isinstance(value, Layer):
            subs = self.__dict__.get("_sub_layers")
            if subs is None:
                raise RuntimeError("call super().__init__() before assigning sublayers")
            subs[name] = value
            self.__dict__.pop(name, None)
            return
        # assigning a plain value (incl. None) over a registered name must
        # evict the registry entry, or state_dict/param_pytree would keep
        # emitting a dead parameter (paddle Layer.__setattr__ does the same)
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
        object.__setattr__(self, name, value)

    def __getattr__(self, name):
        # only called when normal lookup fails
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def add_parameter(self, name: str, param: Optional[Parameter]) -> Optional[Parameter]:
        if param is None:
            self._parameters[name] = None  # type: ignore[assignment]
            return None
        if not isinstance(param, Parameter):
            param = Parameter(param, name=name)
        self._parameters[name] = param
        return param

    def register_buffer(self, name: str, tensor, persistable: bool = True):
        buf = Buffer(tensor, name=name, persistable=persistable)
        self._buffers[name] = buf
        return buf

    def add_sublayer(self, name: str, sublayer: "Layer") -> "Layer":
        self._sub_layers[name] = sublayer
        return sublayer

    def create_parameter(self, shape, dtype=None, attr=None, is_bias=False,
                         default_initializer=None):
        """Parity: Layer.create_parameter (dygraph/layers.py). Uses ParamAttr
        semantics from paddle.ParamAttr."""
        return build_parameter(shape, dtype or self._dtype, attr, is_bias,
                               default_initializer)

    # -- traversal -----------------------------------------------------------
    def named_sublayers(self, prefix: str = "", include_self: bool = False) -> Iterator[Tuple[str, "Layer"]]:
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            p = f"{prefix}.{name}" if prefix else name
            yield p, sub
            yield from sub.named_sublayers(prefix=p)

    def sublayers(self, include_self: bool = False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_parameters(self, prefix: str = "", include_sublayers: bool = True) -> Iterator[Tuple[str, Parameter]]:
        for name, p in self._parameters.items():
            if p is not None:
                dotted = f"{prefix}.{name}" if prefix else name
                if not p.name:
                    # stamp the dotted path as the box's stable identity:
                    # eager optimizer.step() matches jax.grad's name-keyed
                    # grad dicts against box names (a positional zip is
                    # unsound — jax returns dict pytrees in sorted-key
                    # order, not traversal order)
                    p.name = dotted
                yield dotted, p
        if include_sublayers:
            for sname, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sp = f"{prefix}.{sname}" if prefix else sname
                yield from sub.named_parameters(prefix=sp)

    def parameters(self, include_sublayers: bool = True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True) -> Iterator[Tuple[str, Buffer]]:
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for sname, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sp = f"{prefix}.{sname}" if prefix else sname
                yield from sub.named_buffers(prefix=sp)

    def buffers(self, include_sublayers: bool = True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    # -- mode ----------------------------------------------------------------
    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    def apply(self, fn: Callable[["Layer"], None]):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # -- dtype/device --------------------------------------------------------
    def to(self, device=None, dtype=None, blocking=True):
        from ..framework import dtype as _dt

        if dtype is not None:
            nd = _dt.convert_dtype(dtype)
            for p in self.parameters():
                if jnp.issubdtype(p.value.dtype, jnp.floating):
                    p.value = p.value.astype(nd)
            for b in self.buffers():
                if jnp.issubdtype(b.value.dtype, jnp.floating):
                    b.value = b.value.astype(nd)
        if device is not None:
            dev = device.jax_device() if hasattr(device, "jax_device") else device
            for p in self.parameters():
                p.value = jax.device_put(p.value, dev)
            for b in self.buffers():
                b.value = jax.device_put(b.value, dev)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    # -- state ---------------------------------------------------------------
    def state_dict(self, include_sublayers=True, keep_vars=False) -> "OrderedDict[str, Any]":
        out: "OrderedDict[str, Any]" = OrderedDict()
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            out[name] = p if keep_vars else p.value
        for name, b in self.named_buffers(include_sublayers=include_sublayers):
            if getattr(b, "persistable", True):
                out[name] = b if keep_vars else b.value
        return out

    def set_state_dict(self, state_dict: Dict[str, Any], use_structured_name: bool = True):
        """Parity: Layer.set_state_dict / load_dict."""
        own = dict(self.named_parameters())
        own.update(dict(self.named_buffers()))
        missing = []
        for name, value in state_dict.items():
            if name in own:
                tgt = own[name]
                value = jnp.asarray(value)
                if tuple(tgt.value.shape) != tuple(value.shape):
                    raise InvalidArgumentError(
                        f"shape mismatch for {name}: have {tuple(tgt.value.shape)}, "
                        f"loading {tuple(value.shape)}"
                    )
                tgt.value = value.astype(tgt.value.dtype)
            else:
                missing.append(name)
        return missing

    load_dict = set_state_dict

    # -- hooks ---------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        hid = len(self._forward_pre_hooks)
        self._forward_pre_hooks[hid] = hook
        return _HookRemover(self._forward_pre_hooks, hid)

    def register_forward_post_hook(self, hook):
        hid = len(self._forward_post_hooks)
        self._forward_post_hooks[hid] = hook
        return _HookRemover(self._forward_post_hooks, hid)

    # -- call ----------------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        if _amp_state().enabled:
            with _amp_cast_layer_call(self, args, kwargs) as (args, kwargs):
                return self._call_impl(args, kwargs)
        return self._call_impl(args, kwargs)

    def _call_impl(self, args, kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, args)
            if result is not None:
                args = result if isinstance(result, tuple) else (result,)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, args, out)
            if result is not None:
                out = result
        return out

    def __repr__(self):
        lines = [self.__class__.__name__ + "("]
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).replace("\n", "\n  ")
            lines.append(f"  ({name}): {sub_repr}")
        lines.append(")")
        return "\n".join(lines) if len(lines) > 2 else f"{self.__class__.__name__}()"

    # -- functional projection ----------------------------------------------
    def param_pytree(self, trainable_only: bool = False) -> Dict[str, jax.Array]:
        """Flat {dotted_name: value} pytree of parameters."""
        return {
            n: p.value
            for n, p in self.named_parameters()
            if (p.trainable or not trainable_only)
        }

    def buffer_pytree(self) -> Dict[str, jax.Array]:
        return {n: b.value for n, b in self.named_buffers()}


class _HookRemover:
    def __init__(self, store, hid):
        self._store = store
        self._hid = hid

    def remove(self):
        self._store.pop(self._hid, None)


# ---------------------------------------------------------------------------
# functional_call — project a Layer onto a pure function
# ---------------------------------------------------------------------------
def functional_call(
    layer: Layer,
    params: Dict[str, jax.Array],
    *args,
    buffers: Optional[Dict[str, jax.Array]] = None,
    rngs: Optional[jax.Array] = None,
    training: Optional[bool] = None,
    return_buffers: bool = False,
    call: Optional[Callable] = None,
    **kwargs,
):
    """Run ``layer(*args, **kwargs)`` with parameter/buffer values substituted
    from pytrees — pure w.r.t. ``params``/``buffers``/``rngs`` and therefore
    safe under jit/grad/vmap.

    Replaces the reference's static-graph Program construction: instead of
    building an OpDesc graph and calling append_backward
    (python/paddle/fluid/backward.py:1275), we trace the eager forward.

    Returns ``out`` or ``(out, new_buffers)`` when ``return_buffers=True``
    (captures BN running-stat updates made during the call).  With
    ``return_buffers=True`` ALL buffer boxes are restored to their entry
    values afterwards — the updates are returned functionally, never left
    behind (a traced call must not leak tracers into eager state).  Without
    it, in-forward buffer mutation persists (eager paddle semantics).

    ``call`` overrides the invoked callable (still runs with the layer's
    values substituted) — jit.to_static uses it for @to_static-decorated
    bound methods, where calling ``layer(...)`` would re-enter the wrapper.
    """
    boxes: Dict[str, Parameter] = dict(layer.named_parameters())
    buf_boxes: Dict[str, Buffer] = dict(layer.named_buffers())

    saved_vals = {}
    saved_training = None

    try:
        # snapshot EVERY param box, not just the substituted ones: derived
        # params (e.g. the weight_norm cache, nn/utils.py) are rewritten by
        # pre-hooks during the traced call and must not leak tracers into
        # eager state
        for name, box in boxes.items():
            saved_vals[("p", name)] = box.value
        for name, value in params.items():
            box = boxes.get(name)
            if box is None:
                raise NotFoundError(f"no parameter named {name!r} in {type(layer).__name__}")
            box.value = value
        if return_buffers:
            for name, box in buf_boxes.items():
                saved_vals[("b", name)] = box.value
        if buffers:
            for name, value in buffers.items():
                box = buf_boxes.get(name)
                if box is None:
                    raise NotFoundError(f"no buffer named {name!r}")
                saved_vals.setdefault(("b", name), box.value)
                box.value = value
        if training is not None:
            saved_training = [(l, l.training) for l in layer.sublayers(include_self=True)]
            for l, _ in saved_training:
                l.training = training

        ctx = rng_scope(rngs) if rngs is not None else contextlib.nullcontext()
        with ctx:
            out = (layer if call is None else call)(*args, **kwargs)

        if return_buffers:
            new_buffers = {n: b.value for n, b in buf_boxes.items()}
            return out, new_buffers
        return out
    finally:
        for (kind, name), v in saved_vals.items():
            (boxes if kind == "p" else buf_boxes)[name].value = v
        if saved_training is not None:
            for l, t in saved_training:
                l.training = t
