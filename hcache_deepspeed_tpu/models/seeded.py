"""Seeded random weights at a model's real shapes.

Bring-up and measurement need a full-width parameter tree without a
checkpoint. ``model.init`` is the wrong tool at 7B width: it runs the
whole forward (through whatever kernels the platform routes to) and
materializes every leaf in fp32 at once. Here the tree's *shapes* come
from ``jax.eval_shape`` — nothing is lowered or executed — and each
leaf is then drawn on its own, directly in the serving/training dtype,
so the largest transient is one leaf.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, dtype, std):
    return jax.random.normal(key, shape, dtype) * std.astype(dtype)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def seeded_params(model, example_batch, seed: int = 0, dtype=None):
    """``model``'s ``params`` tree with seeded random values.

    Matrices follow the flax defaults the model families use (normal,
    std ``1/sqrt(fan_in)``; an embedding table's fan-in is its width),
    biases are zero and the remaining vectors (norm scales) one.
    ``dtype`` overrides the floating leaves' dtype (``None`` keeps the
    model's own, fp32). Deterministic in ``seed`` and the tree layout.
    """
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), example_batch,
                           train=False))["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def draw(path, leaf, key):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return jnp.zeros(leaf.shape, leaf.dtype)
        out_dtype = jnp.dtype(dtype) if dtype is not None else leaf.dtype
        name = _leaf_name(path)
        if leaf.ndim < 2:
            fill = jnp.zeros if name == "bias" else jnp.ones
            return fill(leaf.shape, out_dtype)
        fan_in = leaf.shape[-1] if name == "embedding" else leaf.shape[-2]
        return _normal(key, leaf.shape, out_dtype,
                       np.float32(1.0 / np.sqrt(fan_in)))

    return jax.tree_util.tree_unflatten(
        treedef, [draw(path, leaf, key)
                  for (path, leaf), key in zip(leaves, keys)])
