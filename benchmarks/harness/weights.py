"""Seeded weights, made on the device in one jitted call, in the type they
are served in.  A spec is an ordered dict ``name -> (shape, kind)`` with
kind ``matrix`` (N(0, 0.02)), ``gain`` (1 + N(0, 0.02)) or ``bias``
(N(0, 0.02)): nothing is exactly zero or one, so a dropped bias or gain
shows in the comparison with the reference."""
import jax
import jax.numpy as jnp


def seed_key(seed):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 16), seed & 0xFFFF)


def make_weights(spec, seed, dtype):
    names = list(spec)

    def gen(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = spec[name]
            w = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
            if kind == "gain":
                w = 1.0 + w
            out[name] = w.astype(dtype)
        return out

    return jax.jit(gen)(seed_key(seed))
