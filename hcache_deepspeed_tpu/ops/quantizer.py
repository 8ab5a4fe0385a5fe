"""Group-wise integer quantization.

Reference analog: ``csrc/quantization/`` (2.9k LoC: quantize.cu,
dequantize.cu, quant_reduce.cu, swizzled_quantize.cu) — int8/int4 groupwise
symmetric quantization backing ZeRO++ qwZ/qgZ. Here: a Pallas kernel for
the hot path and a jnp reference; the "fused quantized reduction"
(quant_reduce.cu) maps to quantize → all_to_all → dequant-accumulate in
``runtime/comm`` (EQuARX-style, PAPERS.md).

Symmetric per-group scaling: values in a group share scale = absmax/127.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import note_fallback, register_op
from .partitioning import per_shard


def _pack_groups(x, group_size):
    flat = x.reshape(-1)
    n = flat.shape[0]
    if n % group_size:
        pad = group_size - n % group_size
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, group_size), n


def reference_quantize(x, group_size=256, num_bits=8):
    qmax = 2 ** (num_bits - 1) - 1
    groups, n = _pack_groups(x.astype(jnp.float32), group_size)
    scale = jnp.max(jnp.abs(groups), axis=-1, keepdims=True) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(groups / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q, scale.astype(jnp.float32), x.shape, n


def reference_dequantize(q, scale, orig_shape, orig_n):
    out = (q.astype(jnp.float32) * scale).reshape(-1)[:orig_n]
    return out.reshape(orig_shape)


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax):
    x = x_ref[:].astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    q_ref[:] = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(
        jnp.int8)
    s_ref[:] = scale


def pallas_quantize(x, group_size=256, num_bits=8, interpret=None,
                    block_groups=64):
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    qmax = 2 ** (num_bits - 1) - 1
    groups, n = _pack_groups(x.astype(jnp.float32), group_size)
    G = groups.shape[0]
    block_groups = min(block_groups, G)
    if G % block_groups:
        note_fallback("quantize", "groups_not_block_multiple",
                      f"groups={G} block_groups={block_groups}")
        return reference_quantize(x, group_size, num_bits)
    q, scale = per_shard(pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(G // block_groups,),
        in_specs=[pl.BlockSpec((block_groups, group_size), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_groups, group_size), lambda i: (i, 0)),
            pl.BlockSpec((block_groups, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, group_size), jnp.int8),
            jax.ShapeDtypeStruct((G, 1), jnp.float32),
        ],
        interpret=interpret,
    ), (groups,))
    return q, scale, x.shape, n


def quantize(x, group_size=256, num_bits=8):
    from . import get_op
    return get_op("quantize")(x, group_size=group_size, num_bits=num_bits)


dequantize = reference_dequantize

register_op("quantize", reference_quantize, pallas_quantize)
register_op("dequantize", reference_dequantize)


# ------------------------------------------------------------------ #
# Weight-only quantization container (reference:
# deepspeed/inference/quantization — v1's QuantLinear keeps int8 weights
# and dequantizes in forward; here a pytree node so quantized params
# flow through jit and dequantize inside the compiled program)
# ------------------------------------------------------------------ #
@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """Groupwise-int-quantized weight: (q int8, scale f32) children with
    static (shape, n, dtype) aux — drop-in pytree leaf replacement.

    Two layouts:
    * flat — q ``[G, group]``: one tensor, ``shape``/``n`` describe it.
    * batched — q ``[L, G, group]``: a stack of L per-layer tensors with
      layer-aligned groups, so slicing the leading dim (``lax.scan`` xs,
      ``x[layer]``) yields a valid flat QuantizedTensor of one layer —
      the property the serving models rely on to dequantize per layer
      inside the compiled loop instead of materializing all layers.
      ``shape``/``n`` describe the PER-LAYER tensor.
    """

    def __init__(self, q, scale, shape, n, dtype):
        self.q, self.scale = q, scale
        self.shape, self.n = tuple(shape), int(n)
        self.dtype = dtype

    def tree_flatten(self):
        return (self.q, self.scale), (self.shape, self.n, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    def dequantize(self):
        if self.q.ndim == 3:   # batched [L, G, group]
            L = self.q.shape[0]
            out = (self.q.astype(jnp.float32) * self.scale).reshape(L, -1)
            return out[:, :self.n].reshape((L,) + self.shape).astype(
                self.dtype)
        return dequantize(self.q, self.scale, self.shape,
                          self.n).astype(self.dtype)

    @classmethod
    def make(cls, x, group_size=256, num_bits=8):
        q, scale, shape, n = quantize(x, group_size=group_size,
                                      num_bits=num_bits)
        return cls(q, scale, shape, n, x.dtype)

    @classmethod
    def make_batched(cls, x, group_size=256, num_bits=8):
        """Quantize a stacked ``[L, ...]`` weight with groups that never
        straddle layer boundaries. Returns None when the per-layer size
        is not a group multiple (caller keeps the leaf unquantized).

        Quantizes LAYER BY LAYER: the fp32 cast + group reshape inside
        ``quantize`` is transient per layer instead of for the whole
        stack — a 7B model's stacked MLP leaf is ~1.4e9 elements, whose
        one-shot fp32 group view needs >10 GB of HBM (with sub-lane
        group sizes XLA pads the trailing dim to 128, doubling it
        again); per-layer it is ~180 MB. One compile serves all layers
        (identical shapes), and host (numpy) inputs stream one layer at
        a time instead of landing on device whole."""
        L = x.shape[0]
        per_shape = x.shape[1:]
        n = 1
        for d in per_shape:
            n *= d
        if n % group_size:
            return None
        qs, scales = [], []
        for layer in range(L):
            q, scale, _, _ = quantize(x[layer], group_size=group_size,
                                      num_bits=num_bits)
            qs.append(q)
            scales.append(scale)
        return cls(jnp.stack(qs), jnp.stack(scales), per_shape, n,
                   x.dtype)


def quantize_tree(tree, *, group_size=256, num_bits=8, min_size=4096,
                  skip=lambda path: False,
                  batched=lambda path: False):
    """Replace every large floating matmul-weight leaf (ndim >= 2) with a
    :class:`QuantizedTensor`. ``skip(path)`` exempts leaves (routers,
    norms...); ``batched(path)`` marks stacked ``[L, ...]`` leaves that
    must keep a sliceable leading dim."""
    from .quantized_matmul import MatmulQuantizedTensor

    def one(path, leaf):
        if isinstance(leaf, (QuantizedTensor, MatmulQuantizedTensor)):
            return leaf   # already quantized (e.g. fused-kernel layout)
        # do NOT device-put here: host (numpy) leaves stream to the
        # device layer-by-layer inside make_batched — a 7B stacked
        # weight shipped whole would defeat that
        if (leaf.ndim < 2 or leaf.size < min_size
                or not jnp.issubdtype(leaf.dtype, jnp.floating)
                or skip(path)):
            return leaf
        if batched(path):
            qt = QuantizedTensor.make_batched(leaf, group_size=group_size,
                                              num_bits=num_bits)
            return leaf if qt is None else qt
        return QuantizedTensor.make(leaf, group_size=group_size,
                                    num_bits=num_bits)
    return jax.tree_util.tree_map_with_path(
        one, tree,
        is_leaf=lambda x: isinstance(
            x, (QuantizedTensor, MatmulQuantizedTensor)))


def dequantize_tree(tree):
    """Inverse of :func:`quantize_tree`; no-op on unquantized trees.
    Called at the top of a jitted forward so XLA streams the dequant
    into the consuming matmuls."""
    return jax.tree.map(
        lambda x: x.dequantize() if isinstance(x, QuantizedTensor) else x,
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
