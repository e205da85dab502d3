"""The arithmetic a reference runs in.  ``f32`` is the reference proper.
The others are the *controls* of "How correct is decided": the same plain
equations one precision step below what a configuration states, which the
comparison has to tell from a sound run.

* ``bf16``: every weight and activation in bfloat16 (the step below f32).
* ``fp8``: matmul operands rounded to float8_e4m3 with a per-tensor scale,
  straight-through in the backward pass, the rest bfloat16 (the step below
  bfloat16).
"""
import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "fp8")


def static_items(cfg):
    """A configuration's numbers as a hashable static argument of a jitted
    function: weights and inputs stay arguments, sizes become constants."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))
                        and not isinstance(v, bool)))


def compute_dtype(mode):
    return jnp.float32 if mode == "f32" else jnp.bfloat16


def _fp8_round(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), 1e-12)
    scale = 448.0 / amax
    q = (x.astype(jnp.float32) * scale).astype(jnp.float8_e4m3fn)
    q = (q.astype(jnp.float32) / scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(q - x)


def operand(x, mode):
    """A matmul operand as ``mode`` would hold it."""
    if mode == "fp8":
        return _fp8_round(x)
    return x


def matmul(a, b, mode):
    return jnp.matmul(operand(a, mode), operand(b, mode),
                      precision=jax.lax.Precision.HIGHEST)


def einsum(spec, a, b, mode):
    return jnp.einsum(spec, operand(a, mode), operand(b, mode),
                      precision=jax.lax.Precision.HIGHEST)
