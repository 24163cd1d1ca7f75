"""Seeded weights, made by the benchmark on the device in one jitted call.

The program defines the layout of its parameter tree (``Model.init_params``
under ``jax.eval_shape``: names and shapes only); every value is drawn here,
from the run's seed, in the type it is served in. The plain reference reads
the same arrays, so neither side takes weights the other made.

Draws, by the leaf's name: projection matrices N(0, 1/fan_in); biases
N(0, 0.02^2) (Qwen2's q/k/v biases are not zero); norm scales
1 + N(0, 0.05^2); the token embedding N(0, 1/d_model). The PRM head (two
layers, float32, as ``repro.core.prm`` lays it out) is drawn the same way.
"""
from __future__ import annotations

import numpy as np


def key_for(seed: int, stream: int):
    """A JAX key from any whole-number seed: numpy hashes all its bits
    (``jax.random.PRNGKey`` keeps only the low 32 of a larger seed)."""
    import jax
    word = np.random.default_rng([int(seed), stream]).integers(2 ** 31)
    return jax.random.PRNGKey(int(word))


def _draw(path: str, shape, dtype, key):
    import jax
    import jax.numpy as jnp
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale":
        x = 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)
    elif leaf.startswith("b"):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif leaf == "embedding":
        x = jax.random.normal(key, shape, jnp.float32) * shape[-1] ** -0.5
    else:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        x = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    return x.astype(dtype)


def _paths(tree):
    import jax
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in flat]
    return names, [leaf for _, leaf in flat], treedef


def draw_like(shapes, seed: int, stream: int):
    """Arrays shaped like the ``ShapeDtypeStruct`` tree ``shapes``, drawn in
    one jitted call."""
    import jax
    names, leaves, treedef = _paths(shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten([
            _draw(n, leaf.shape, leaf.dtype, k)
            for n, leaf, k in zip(names, leaves, keys)])

    return make(key_for(seed, stream))


def prm_head_shapes(d_model: int, hidden: int = 64):
    """The reward head as ``repro.core.prm`` lays it out (float32)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    return {"w1": jax.ShapeDtypeStruct((d_model, hidden), f32),
            "b1": jax.ShapeDtypeStruct((hidden,), f32),
            "w2": jax.ShapeDtypeStruct((hidden,), f32),
            "b2": jax.ShapeDtypeStruct((), f32)}


def make_weights(model, seed: int):
    """(params, prm_head) for ``model`` (a ``repro.models.Model``)."""
    import jax
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    return (draw_like(shapes, seed, 1),
            draw_like(prm_head_shapes(model.cfg.d_model), seed, 2))
