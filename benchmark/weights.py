"""Initial weights of a run, made on the device from the seed.

One jitted call makes every leaf, in float32 (the type the master
parameters are held in).  The harness hands these to `Module.fit` as
`arg_params`/`aux_params`, and the reference makes the same ones from
the same seed: neither side reads the other's.

He-normal for convolutions and the classifier (gaussian, fan-in,
magnitude 2: what `train_imagenet.py`'s Xavier initializer draws),
gamma 1, beta and biases 0, moving mean 0, moving variance 1.
"""
import math

import jax
import jax.numpy as jnp


def key_of(seed, stream):
    """A key from any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def make(seed, arg_shapes, aux_shapes, sharding=None):
    """({name: array}, {name: array}) for parameters and statistics."""
    names = sorted(arg_shapes)

    def build(key):
        args = {}
        for i, name in enumerate(names):
            shape = arg_shapes[name]
            if name.endswith("_weight"):
                fan_in = math.prod(shape[1:])
                args[name] = math.sqrt(2.0 / fan_in) * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            elif name.endswith("_gamma"):
                args[name] = jnp.ones(shape, jnp.float32)
            else:
                args[name] = jnp.zeros(shape, jnp.float32)
        aux = {name: (jnp.ones if name.endswith("_var") else jnp.zeros)(
            shape, jnp.float32) for name, shape in aux_shapes.items()}
        return args, aux

    return jax.jit(build, out_shardings=sharding)(key_of(seed, 0))
