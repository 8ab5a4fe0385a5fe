"""Grouped GEMM — variable-sized per-expert matmuls.

Reference analog: ``deepspeed/inference/v2/kernels/cutlass_ops/moe_gemm/``
(CUTLASS grouped GEMM over expert-sorted token groups) — the kernel
dropless MoE depends on.

TPU-native form: ``jax.lax.ragged_dot`` — XLA's native ragged
(group-sizes-driven) matmul, which Mosaic lowers onto the MXU with one
kernel over all groups; differentiable, so it serves training too. The
reference implementation below (segment-id gather + einsum) is the
numerics oracle and the CPU fallback shape.

:func:`grouped_matmul_stacked` is the serving forward's: the experts of
every layer lie in one stacked leaf ``[L, E, K, M]`` and the layer is an
index. ``ragged_dot`` becomes a custom call on the TPU, and a custom call
wants its operand whole: handed ``stack[layer]`` it has the layer's
experts sliced out of the leaf into a buffer of their own, 403 MB a
product at 128 experts of 2048 x 768, read and written again in every
layer of every forward (the compiled v5e program shows the three
``dynamic-slice`` fusions). So the kernel (``megablox.gmm``, the Pallas
grouped matmul that ships with JAX) is given the whole leaf as ``[L * E,
K, M]`` groups, of which only the layer's have rows: its index map reads
each expert's matrix where it lies, and groups without rows are never
visited.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from . import register_op

#: VMEM the kernel's tiles may claim (two buffers an input, the float32
#: accumulator, the result); the v5e compiler's scoped limit is 16 MiB
_VMEM_BUDGET = 10 * 2**20


def reference_grouped_matmul(x, w, group_sizes):
    """x: [N, K] tokens sorted by group; w: [G, K, M]; group_sizes: [G]
    with sum == N. Returns [N, M] where row i uses its group's matrix."""
    N = x.shape[0]
    seg = jnp.repeat(jnp.arange(w.shape[0]), group_sizes,
                     total_repeat_length=N)
    return jnp.einsum("nk,nkm->nm", x, w[seg])


def ragged_grouped_matmul(x, w, group_sizes):
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32))


def grouped_matmul(x, w, group_sizes):
    from . import get_op
    return get_op("grouped_matmul")(x, w, group_sizes)


register_op("grouped_matmul", reference_grouped_matmul,
            ragged_grouped_matmul)


# ------------------------------------------------------------------ #
# One layer's experts out of a stacked leaf, read in place
# ------------------------------------------------------------------ #
def reference_grouped_matmul_stacked(x, w, layer, group_sizes):
    """x: [N, K] rows sorted by expert; w: [L, E, K, M]; ``layer`` a
    (traced) int32 scalar; group_sizes: [E] with sum <= N. Returns [N, M]:
    row i by its expert's matrix of layer ``layer``."""
    return reference_grouped_matmul(
        x, jax.lax.dynamic_index_in_dim(w, layer, keepdims=False),
        group_sizes)


def stacked_tiles(N, E, K, M, itemsize):
    """``(tm, tk, tn)`` of the kernel: row tiles of 64 where an expert
    sees few rows (a decode dispatch: the product is bound by reading
    each expert's matrix once, and a tile's rows beyond the group's are
    wasted work), 128 else; the whole of K and up to 1024 columns a step
    while the tiles fit ``_VMEM_BUDGET``."""
    tm = 128 if N >= 128 * E else 64
    tk, tn = min(K, 2048), min(M, 1024)

    def claims(tk, tn):
        return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + tm * tn * 4
    while claims(tk, tn) > _VMEM_BUDGET and tn > 128:
        tn //= 2
    while claims(tk, tn) > _VMEM_BUDGET and tk > 128:
        tk //= 2
    return tm, tk, tn


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_grouped_matmul_stacked(x, w, layer, group_sizes,
                                  interpret=False):
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    N, K = x.shape
    L, E, _, M = w.shape
    tm, tk, tn = stacked_tiles(N, E, K, M, x.dtype.itemsize)
    rows = -(-N // tm) * tm
    if rows != N:                   # whole row tiles; the pad is no
        x = jnp.pad(x, ((0, rows - N), (0, 0)))         # group's
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((L * E,), jnp.int32), group_sizes.astype(jnp.int32),
        (jnp.asarray(layer, jnp.int32) * E,))
    # the custom call, and the few operations that lay out its work,
    # carry the kernel's name where a device trace shows it
    with set_xla_metadata(hds_kernel="expert_gemm"):
        out = gmm(x, w.reshape(L * E, K, M), sizes,
                  preferred_element_type=x.dtype, tiling=(tm, tk, tn),
                  interpret=interpret)
    return out[:N]


def grouped_matmul_stacked(x, w, layer, group_sizes):
    """Row i of ``x`` by the matrix of its expert in layer ``layer`` of
    the stacked leaf ``w`` [L, E, K, M], which is read where it lies."""
    from . import get_op
    return get_op("grouped_matmul_stacked")(x, w, layer, group_sizes)


register_op("grouped_matmul_stacked", reference_grouped_matmul_stacked,
            pallas_grouped_matmul_stacked)
