"""paddle.static compatibility surface.

Parity: python/paddle/static/__init__.py.  Three tiers, matching what
each name MEANS without a Program interpreter (jaxpr replaces Program,
SURVEY §7):

* genuinely portable names are implemented (InputSpec, data→InputSpec,
  Print→jax.debug.print, py_func→jax.pure_callback, name_scope,
  cpu_places, create_parameter/create_global_var, the inference
  save/load pair, load_program_state, BuildStrategy/ExecutionStrategy
  config holders);
* Program-machinery names (Program, Executor, append_backward, ...) are
  module-level shims that exist but raise ``UnimplementedError`` (also
  an AttributeError, so feature probes degrade gracefully) *when used*,
  each naming its eager replacement;
* ``static.nn`` is a module of op-builder shims pointing at the eager
  layer/functional equivalents.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import jax
import numpy as np

from ..framework.dtype import convert_dtype

__all__ = [
    "append_backward", "gradients", "Executor", "global_scope",
    "scope_guard", "BuildStrategy", "CompiledProgram", "Print", "py_func",
    "ExecutionStrategy", "name_scope", "ParallelExecutor", "program_guard",
    "WeightNormParamAttr", "default_main_program",
    "default_startup_program", "Program", "data", "InputSpec", "save",
    "load", "save_inference_model", "load_inference_model",
    "load_program_state", "set_program_state", "cpu_places", "cuda_places",
    "Variable", "Scope", "create_parameter", "create_global_var",
    "make_symbols", "nn",
]


class InputSpec:
    """Declarative (shape, dtype, name) signature of a model input.

    ``None`` / ``-1`` dims are dynamic (batch-polymorphic at export).
    """

    def __init__(self, shape: Sequence[Optional[int]], dtype="float32",
                 name: Optional[str] = None):
        # a str dim is a NAMED symbolic size — two specs using the same
        # name share it (e.g. both inputs' batch dim "b"), which is how
        # shapes that must broadcast/match declare it at export time
        self.shape = tuple(
            d if isinstance(d, str)
            else None if d in (None, -1)
            else int(d)
            for d in shape)
        self.dtype = convert_dtype(dtype)
        self.name = name

    def __repr__(self):
        return (f"InputSpec(shape={self.shape}, dtype={self.dtype}, "
                f"name={self.name!r})")

    @classmethod
    def from_tensor(cls, tensor, name: Optional[str] = None) -> "InputSpec":
        t = np.asarray(tensor) if not isinstance(tensor, jax.Array) else tensor
        return cls(t.shape, t.dtype, name)

    def symbol_names(self):
        """One symbol name per dynamic dim: the declared name for str dims,
        an auto-generated unique one for None/-1 dims."""
        out = []
        for i, d in enumerate(self.shape):
            if isinstance(d, str):
                out.append(d)
            elif d is None:
                out.append(f"d_{self.name or 'in'}_{i}".replace("-", "_"))
        return out

    def shape_dtype(self, symbols=None) -> jax.ShapeDtypeStruct:
        """Lower to a ShapeDtypeStruct.  ``symbols`` maps symbol name →
        symbolic dim; ALL dynamic dims of a multi-input export must come
        from ONE ``jax.export.symbolic_shape`` call (one scope) — see
        ``make_symbols``.  Called with ``symbols=None``, a private
        single-scope set is created for this spec alone."""
        if symbols is None:
            symbols = make_symbols([self])
        dims = []
        names = iter(self.symbol_names())
        for d in self.shape:
            dims.append(d if isinstance(d, int) else symbols[next(names)])
        return jax.ShapeDtypeStruct(tuple(dims), self.dtype)


def make_symbols(specs) -> dict:
    """Create every dynamic dim of ``specs`` in one shared symbolic scope
    (jax.export requires all symbols of an export to share a scope; two
    specs reusing a name intentionally share that size)."""
    from jax import export as jexport

    names = []
    for s in specs:
        for n in s.symbol_names():
            if n not in names:
                names.append(n)
    if not names:
        return {}
    dims = jexport.symbolic_shape(", ".join(names))
    return dict(zip(names, dims))


def data(name, shape, dtype="float32", lod_level=0):
    """Declare a feed slot (ref: static/input.py data / fluid/data.py:23).
    In graph mode (enable_static() / an active program_guard): a graph
    Variable in the default Program.  Otherwise: the ``InputSpec`` for
    that slot — the declared-graph-input role for export signatures and
    jit.save."""
    from .graph import data as _gdata, in_program_guard

    if in_program_guard():
        return _gdata(name, shape, dtype or "float32")
    return InputSpec(shape, dtype or "float32", name)


def cpu_places(device_count=None):
    """Host CPU devices (ref: fluid/framework.py cpu_places).  Count
    defaults to the visible CPU device count (the reference uses
    CPU_NUM)."""
    from ..framework.device import CPUPlace

    if device_count is None:
        try:
            device_count = len(jax.devices("cpu"))
        except RuntimeError:
            device_count = 1
    return [CPUPlace() for _ in range(device_count)]


def cuda_places(device_ids=None):
    from ..framework.errors import UnimplementedError

    raise UnimplementedError(
        "cuda_places(): no CUDA devices in the TPU build — use "
        "paddle.set_device('tpu') / jax.devices() (places map to "
        "jax.Device, SURVEY §7)")


@contextlib.contextmanager
def name_scope(prefix=None):
    """Parity: fluid/framework.py:5616 name_scope — a debugging aid that
    prefixed op names in the Program graph.  There is no op graph to
    name here (XLA keeps jaxpr provenance automatically), so this scopes
    nothing; kept so instrumented model code runs unchanged."""
    yield


def Print(input, first_n=-1, message=None, summarize=20, **kwargs):
    """Debug-print a tensor inside compiled code (ref:
    fluid/layers/control_flow.py Print op).  TPU-native: jax.debug.print
    — works under jit, prints when the value resolves; returns the input
    unchanged like the reference op."""
    if isinstance(input, jax.core.Tracer):
        # inside jit: route through the debug-callback channel
        msg = (message or "").replace("{", "{{").replace("}", "}}")
        jax.debug.print((msg + ": {x}") if message else "{x}", x=input)
    else:  # eager: plain host print, works on every backend
        print(f"{message}: {np.asarray(input)}" if message
              else str(np.asarray(input)))
    return input


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Call host Python from compiled code (ref: fluid/layers/nn.py
    py_func over py_func_op).  TPU-native: ``jax.pure_callback`` — ``out``
    declares the result template as InputSpec(s)/ShapeDtypeStruct(s)
    (static shapes; the reference likewise required pre-created out
    vars).  ``backward_func`` is not supported — use jax.custom_vjp for
    differentiable callbacks."""
    from ..framework.errors import UnimplementedError

    if backward_func is not None:
        raise UnimplementedError(
            "py_func(backward_func=...): wrap the op in jax.custom_vjp "
            "instead — host-side backward callbacks don't exist here")
    single = not isinstance(out, (list, tuple))
    specs = [out] if single else list(out)
    shape_dtypes = [
        s.shape_dtype() if isinstance(s, InputSpec)
        else s if isinstance(s, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(np.asarray(s).shape, np.asarray(s).dtype)
        for s in specs
    ]
    xs = x if isinstance(x, (list, tuple)) else [x]

    def host(*args):  # declared template wins: cast host results to it
        res = func(*args)
        rs = [res] if single else list(res)
        rs = [np.asarray(r, sd.dtype) for r, sd in zip(rs, shape_dtypes)]
        return rs[0] if single else tuple(rs)

    if not any(isinstance(a, jax.core.Tracer) for a in xs):
        # eager: nothing is being staged, so call the host function
        # directly
        res = host(*(np.asarray(a) for a in xs))
        import jax.numpy as jnp

        return (jnp.asarray(res) if single
                else tuple(jnp.asarray(r) for r in res))
    result = jax.pure_callback(
        host, shape_dtypes[0] if single else tuple(shape_dtypes), *xs)
    return result


class BuildStrategy:
    """Pass-tuning knob bag (ref: framework/details/build_strategy.h:50).
    XLA owns fusion/memory decisions here, so the knobs are accepted and
    recorded but decide nothing; reads of unwritten knobs return the
    reference defaults (build_strategy.h:71-158) so migration code that
    probes them keeps running."""

    _DEFAULTS = {
        "debug_graphviz_path": "",
        "enable_sequential_execution": False,
        "remove_unnecessary_lock": True,
        "fuse_elewise_add_act_ops": False,
        "fuse_bn_act_ops": False,
        "fuse_relu_depthwise_conv": False,
        "fuse_broadcast_ops": False,
        "fuse_all_optimizer_ops": False,
        "fuse_all_reduce_ops": False,
        "sync_batch_norm": False,
        "memory_optimize": False,
        "enable_inplace": True,
        "cache_runtime_context": False,
        "enable_backward_optimizer_op_deps": True,
        "trainer_id": 0,
        "num_trainers": 1,
        "use_hierarchical_allreduce": False,
        "hierarchical_allreduce_inter_nranks": 0,
        "gradient_scale_strategy": 0,
        "reduce_strategy": 0,
        "build_cinn_pass": False,
    }

    def __init__(self):
        self.__dict__["_opts"] = dict(self._DEFAULTS)

    def __setattr__(self, k, v):
        self._opts[k] = v

    def __getattr__(self, k):
        try:
            return self.__dict__["_opts"][k]
        except KeyError:
            raise AttributeError(k)


class ExecutionStrategy(BuildStrategy):
    """Executor-thread knob bag (ref: details/execution_strategy.h:22) —
    mostly the same accepted-but-inert contract as BuildStrategy, with one
    live knob: ``num_iteration_per_run > 1`` passed via
    ``Executor(strategy=...)`` becomes the default chain length for the
    fused multi-step path (``Executor.run_steps`` with no explicit
    ``iterations=``), matching the reference semantics of running several
    iterations per ``exe.run`` call."""

    _DEFAULTS = {
        "num_threads": 0,
        "use_cuda": False,
        "allow_op_delay": False,
        "num_iteration_per_drop_scope": 100,
        "num_iteration_per_run": 1,
        "use_thread_barrier": False,
    }


def load_program_state(model_path, var_list=None):
    """Read a saved state into {name: numpy} (ref: fluid/io.py:1730
    load_program_state).  Works on this framework's ``paddle.save``
    artifacts AND on reference-Paddle binary checkpoints — per-variable
    persistables directories, combined params + __model__, and 2.x
    pickled .pdparams (framework/paddle_import.py implements the
    reference's binary formats from the in-tree spec)."""
    import os as _os

    if _os.path.isdir(model_path):
        from ..framework.paddle_import import load_reference_state_dict

        state = load_reference_state_dict(model_path)
        return {k: np.asarray(v) for k, v in state.items()
                if var_list is None or k in var_list}
    from ..framework.serialization import load as _load, _MAGIC

    path = model_path
    if not _os.path.isfile(path) and not path.endswith(".pdparams"):
        path = path + ".pdparams"
    # format sniff by header, never by extension: our serializer's artifacts
    # start with the PTPU magic and load with _load; a reference binary
    # (LoDTensor stream starts u32 version 0) or a reference 2.x pickle
    # (b'\x80' marker, no magic) goes to the importer — under ANY filename.
    # Extension-based routing would misparse one of our own ``paddle.save``
    # files stored under e.g. ``ckpt.bin``, or reject a reference pickle
    # named ``ref_ckpt.bin``.  Corruption of OUR files keeps its own error.
    with open(path, "rb") as _f:
        _head = _f.read(len(_MAGIC))
    if _head[:4] == b"\x00\x00\x00\x00" or _head[:1] == b"\x80":
        from ..framework.paddle_import import load_reference_state_dict

        state = load_reference_state_dict(path)
    else:
        state = _load(path)
    return {k: np.asarray(v) for k, v in state.items()
            if var_list is None or k in var_list}


def save_inference_model(path_prefix, feed_vars, fetch_vars=None,
                         executor=None, **kwargs):
    """Ref: fluid/io.py:1164.  Eager form: ``feed_vars`` is the Layer and
    ``fetch_vars`` its InputSpecs (the Program/Executor arguments of the
    reference have no meaning here) — delegates to
    paddle_tpu.inference.save_inference_model (AOT StableHLO export)."""
    from ..inference import save_inference_model as _save

    from ..nn.layer_base import Layer

    if isinstance(feed_vars, Layer):
        return _save(path_prefix, feed_vars, fetch_vars)
    if isinstance(fetch_vars, Layer):  # (specs, layer) order tolerated
        return _save(path_prefix, fetch_vars, feed_vars)
    from ..framework.errors import InvalidArgumentError

    raise InvalidArgumentError(
        "static.save_inference_model(path, layer, input_specs): pass the "
        "eager Layer to export (no Program exists to save — SURVEY §7)")


def load_inference_model(path_prefix, executor=None, **kwargs):
    """Ref: fluid/io.py:1374 — returns the loaded Predictor (the eager
    counterpart of (program, feed_names, fetch_names))."""
    from ..inference import load_inference_model as _load

    return _load(path_prefix)


def save(program, model_path, protocol=4, **configs):
    _program_only("save", "paddle.save(layer.state_dict(), path)")


def load(program, model_path, executor=None, var_list=None):
    _program_only("load", "paddle.load(path) + layer.set_state_dict")


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    """Real eager parameter creation (shared with paddle.create_parameter;
    ref: fluid/layers/tensor.py:75)."""
    import paddle_tpu as _p

    return _p.create_parameter(shape, dtype, name=name, attr=attr,
                               is_bias=is_bias,
                               default_initializer=default_initializer)


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    """Eager mapping (ref: fluid/layers/tensor.py create_global_var): a
    'global variable' is just a named non-trainable Parameter box."""
    from ..nn.layer_base import Parameter

    import jax.numpy as jnp

    return Parameter(jnp.full(tuple(shape), value, convert_dtype(dtype)),
                     name=name or "", trainable=False)


class WeightNormParamAttr:
    """Ref: fluid/param_attr.py WeightNormParamAttr — static-graph weight
    norm via transpiled split params.  The eager equivalent is
    ``paddle.nn.weight_norm(layer, name, dim)`` (nn/utils.py); raising
    here names it rather than silently dropping the reparameterization."""

    def __init__(self, *a, **k):
        from ..framework.errors import UnimplementedError

        raise UnimplementedError(
            "WeightNormParamAttr: apply paddle.nn.weight_norm(layer, "
            "name, dim) to the built layer instead (hook-based weight "
            "norm, nn/utils.py)")


# -- Program-machinery shims: exist, but raise on use --------------------
def _program_only(name, instead):
    from ..framework.errors import UnimplementedError

    class _StaticOnlyError(UnimplementedError, AttributeError):
        """Also an AttributeError so feature probes degrade to 'absent'."""

    raise _StaticOnlyError(
        f"paddle.static.{name} is static-Program API with no counterpart "
        f"in this single-runtime framework (jaxpr replaces Program — "
        f"SURVEY §7); instead: {instead}")


def _make_program_shim(name, instead):
    def shim(*args, **kwargs):
        _program_only(name, instead)

    shim.__name__ = name
    shim.__qualname__ = name
    shim.__doc__ = (f"Static-Program API shim — raises UnimplementedError "
                    f"pointing at: {instead}")
    return shim


# -- the lazy-graph Program/Executor (static/graph.py): the 1.x build/run
#    flow as a recorded DAG jitted into one XLA computation per signature
from .graph import (  # noqa: E402,F401
    Program, Executor, Variable, program_guard, default_main_program,
    default_startup_program, reset_default_programs,
)


class Scope:
    """Param/buffer scope view over a Program (ref: fluid/executor.py
    global_scope — variable store the Executor reads/writes).  Here the
    store IS program.scope; this wrapper serves the find_var/get_tensor
    reading idiom."""

    def __init__(self, program=None):
        self._program = program

    class _Var:
        def __init__(self, value):
            self._value = value

        def get_tensor(self):
            import numpy as _np

            return _np.asarray(self._value)

    def find_var(self, name):
        prog = self._program or default_main_program()
        if name in prog.scope:
            return Scope._Var(prog.scope[name])
        if name in prog.buffers:
            return Scope._Var(prog.buffers[name])
        return None

    def var_names(self):
        prog = self._program or default_main_program()
        return list(prog.scope) + list(prog.buffers)


def global_scope() -> Scope:
    return Scope()


@contextlib.contextmanager
def scope_guard(scope):
    """Accepted for API parity: programs own their scopes here, so the
    guard has nothing to swap — state isolation comes from building under
    separate Programs."""
    yield scope


def CompiledProgram(program, build_strategy=None):
    """ref: compiler.py CompiledProgram — jit compilation is automatic at
    Executor.run here, so the 'compiled' program is the program."""
    return program


ParallelExecutor = _make_program_shim(
    "ParallelExecutor", "distributed.fleet shards the jitted step over a "
                        "device Mesh")
append_backward = _make_program_shim(
    "append_backward", "Executor.run differentiates the recorded graph "
                       "with jax.grad when an optimizer is bound via "
                       "minimize — no backward ops are appended")
gradients = _make_program_shim(
    "gradients", "use paddle.grad_fn (jax.grad) / jax.vjp on a function")


def set_program_state(program, state):
    """ref: io.py set_program_state — load a state dict into the
    program's parameter scope."""
    program.set_state_dict(state)

from . import nn  # noqa: E402,F401  (static.nn op-builder shims)
