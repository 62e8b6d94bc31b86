"""The benchmark's weight recipe, shared by the plain references (the
templates repeat it in the program's own layout): leaf i of a specification
is `normal(fold_in(key(seed), i)) * std`, or ones, or zeros."""

import jax
import jax.numpy as jnp


def make(seed: int, spec: list) -> dict:
    """All weights on the device in one jitted call from the seed. `spec` is
    a list of (name, shape, std) with std a number, 'ones' or 'zeros'."""

    def build(key):
        out = {}
        for i, (name, shape, std) in enumerate(spec):
            if std == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif std == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32) * std
        return out

    return jax.jit(build)(jax.random.key(seed))
