"""Placing a Pallas kernel inside a partitioned program.

Mosaic refuses a kernel wherever XLA would still partition it
("Mosaic kernels cannot be automatically partitioned. Please wrap the
call in a shard_map"): under ``jax.jit`` over more than one device —
every ZeRO stage, tensor and sequence parallelism — and inside a
``shard_map`` that leaves some mesh axes automatic, even axes of size
one. libtpu does not take ``custom_partitioning`` callbacks, so the
kernel has to be told its layout: :func:`per_shard` wraps it in a
``shard_map`` over whatever axes of the mesh in scope are still
automatic, with specs built from the framework's own convention for
activations (``parallel/topology.py``): the batch dimension is split
over ``data``/``zero``/``expert``, the sequence over ``seq``, heads over
``tensor``. Where the convention does not hold for an operand XLA
reshards it to the spec, so the result is right either way and only
the cost changes.

The mesh comes from JAX's own context
(``jax.sharding.get_abstract_mesh()``): a ``shard_map`` body already
has one, with its manual axes marked, and the training engine enters
:func:`kernel_mesh` around the model's forward so that the kernels
under plain ``jit`` find the engine's mesh. With no mesh in scope, on a
single device, and where every axis is manual already, the kernel is
called directly.
"""

import contextlib

import jax
from jax.sharding import PartitionSpec

from ..parallel.topology import (DATA_AXIS, EXPERT_AXIS, SEQ_AXIS,
                                 TENSOR_AXIS, ZERO_AXIS)

#: dimension roles a kernel may name, and the mesh axes each may be
#: split over (MeshTopology.batch_shard_axes / sequence_shard_axes and
#: the Megatron head split)
BATCH, SEQ, HEADS = "batch", "seq", "heads"
_ROLE_AXES = {BATCH: (DATA_AXIS, ZERO_AXIS, EXPERT_AXIS),
              SEQ: (SEQ_AXIS,),
              HEADS: (TENSOR_AXIS,)}


def kernel_mesh(mesh):
    """Context for tracing model code under ``jit``: puts ``mesh`` in
    scope for :func:`per_shard`. A no-op for one device and where a mesh
    is in scope already (a ``shard_map`` body knows its manual axes;
    overriding it would lose them)."""
    if mesh is None or mesh.size == 1 or \
            not jax.sharding.get_abstract_mesh().empty:
        return contextlib.nullcontext()
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def per_shard(kernel, operands, in_roles=None, out_roles=None,
              out_shapes=None):
    """``kernel(*operands)`` where Mosaic accepts it.

    ``in_roles``/``out_roles`` give, per operand and per result, one
    role (``BATCH``, ``SEQ``, ``HEADS`` or ``None``) per dimension. A
    role maps to the same mesh axes in every array that names it, and
    only to axes that divide every such dimension (a GQA kernel's query
    and KV heads are split together or not at all). Without roles
    every array is whole in every shard: right for a kernel that runs
    on the local block of a ``shard_map`` body (the quantized wire),
    a gather anywhere else. ``out_shapes`` are the results' whole shapes
    (``ShapeDtypeStruct``s) where the caller knows them; otherwise the
    kernel is traced once more, on the whole operands, to ask.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return kernel(*operands)
    auto = tuple(a for a in mesh.axis_names if a not in mesh.manual_axes)
    if not auto or (mesh.size == 1 and not mesh.manual_axes):
        return kernel(*operands)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    if out_shapes is None:
        out_shapes = jax.eval_shape(kernel, *operands)
    results, out_tree = jax.tree.flatten(out_shapes)
    if in_roles is None:
        in_roles = tuple((None,) * x.ndim for x in operands)
        out_roles = tuple((None,) * len(r.shape) for r in results)
    elif out_tree.num_leaves == 1 and out_tree.num_nodes == 1:
        out_roles = (out_roles,)      # a bare array result

    dims = {}       # role -> every dimension that carries it
    for arr, roles in list(zip(operands, in_roles)) + \
            list(zip(results, out_roles)):
        for dim, role in zip(arr.shape, roles):
            if role is not None:
                dims.setdefault(role, []).append(dim)
    axes = {}
    for role, sized in dims.items():
        chosen, n = [], 1
        for a in _ROLE_AXES[role]:
            if a in auto and sizes[a] > 1 and \
                    all(d % (n * sizes[a]) == 0 for d in sized):
                chosen.append(a)
                n *= sizes[a]
        axes[role] = tuple(chosen) or None

    def spec(roles):
        return PartitionSpec(*(axes.get(r) for r in roles))

    return jax.shard_map(
        kernel, in_specs=tuple(spec(r) for r in in_roles),
        out_specs=out_tree.unflatten([spec(r) for r in out_roles]),
        axis_names=set(auto), check_vma=False)(*operands)
