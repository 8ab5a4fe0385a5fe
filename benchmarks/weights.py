"""Seeded random weights at a model's real shapes, made on the device.

After ``hcache_deepspeed_tpu/models/seeded.py`` (the same values rule:
matrices normal with std ``1/sqrt(fan_in)``, an embedding's fan-in its
width, biases zero, other vectors one), with two differences the
benchmark needs. The whole tree comes out of ONE jitted call, in the
type it is served in, so set-up pays one program and no per-leaf
dispatch. And every leaf has a key of its own, folded from its path, so
the same call asked for one layer's leaves gives exactly the values the
whole tree has there: the plain reference regenerates a layer at a time
and never holds a second copy of the model.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def _path_names(path):
    return tuple(str(getattr(k, "key", getattr(k, "name", k)))
                 for k in path)


def param_shapes(model, example_batch):
    """The ``params`` tree as ``ShapeDtypeStruct``s: nothing is lowered
    or run."""
    return jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), example_batch,
                           train=False))["params"]


def _draw(names, leaf, root_key, dtype):
    if not jnp.issubdtype(leaf.dtype, jnp.floating):
        return jnp.zeros(leaf.shape, leaf.dtype)
    if leaf.ndim < 2:
        fill = jnp.zeros if names[-1] == "bias" else jnp.ones
        return fill(leaf.shape, dtype)
    fan_in = leaf.shape[-1] if names[-1] == "embedding" else leaf.shape[-2]
    key = jax.random.fold_in(
        root_key, zlib.crc32("/".join(names).encode()) & 0x7FFFFFFF)
    return (jax.random.normal(key, leaf.shape, jnp.float32)
            * np.float32(1.0 / np.sqrt(fan_in))).astype(dtype)


def seeded_tree(shapes, seed, dtype, only=None):
    """The tree of ``shapes`` with seeded values, one jitted call.

    ``only``: keep the top-level entries with these names (a layer of
    the llama tree is ``layers_<i>``); the values are those the whole
    tree has there.
    """
    dtype = jnp.dtype(dtype)
    if only is not None:
        shapes = {k: v for k, v in shapes.items() if k in only}

    @jax.jit
    def make(seed_word):
        root = jax.random.fold_in(jax.random.PRNGKey(0), seed_word)
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: _draw(_path_names(path), leaf, root, dtype),
            shapes)

    # a seed is any whole number up to a little over 2**31: fold it to
    # the 32 bits fold_in takes
    return make(np.uint32(int(seed) & 0xFFFFFFFF))
