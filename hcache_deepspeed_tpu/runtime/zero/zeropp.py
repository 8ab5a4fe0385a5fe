"""ZeRO++ — quantized / hpZ collectives wired into the train step.

Reference analogs:
* ``deepspeed/runtime/engine.py:994-1008`` — the ``zero_quantized_weights``
  (qwZ), ``zero_quantized_gradients`` (qgZ) and ``zero_hpz_partition_size``
  (hpZ) config flags,
* ``deepspeed/runtime/comm/coalesced_collectives.py:81``
  ``all_to_all_quant_reduce`` — the qgZ gradient path,
* ``deepspeed/runtime/zero/partition_parameters.py:770`` ``CUDAQuantizer``
  — the qwZ quantized weight all-gather,
* ``deepspeed/utils/groups.py:650-705`` — the hpZ secondary
  (intra-node) parameter partition groups.

TPU re-design. The engine's default ZeRO path is GSPMD: sharding
constraints make XLA insert the gather/reduce collectives, so their wire
format is not ours to choose. When any ZeRO++ flag is on, the micro
fwd+bwd is instead built as a *partial-manual* ``shard_map`` over the
``data`` axis (tensor/seq/expert stay compiler-managed), with the
parameter gather and gradient reduction written explicitly:

* **qwZ** — parameters are int8 group-quantized (Pallas kernel on TPU)
  before the all-gather; the wire carries int8 + fp32 group scales
  (~4x less than fp32, ~2x less than bf16).
* **qgZ** — the gradient reduction is an all-to-all of int8-quantized
  shard slices followed by a local dequantize-mean, instead of a
  bf16/fp32 reduce-scatter.
* **hpZ** — a secondary bf16 copy of the parameters, partitioned over
  subgroups of ``zero_hpz_partition_size`` consecutive devices (one
  node/slice), is refreshed once per optimizer step; the per-microbatch
  forward/backward gathers read from it with
  ``axis_index_groups`` so they ride intra-group (ICI) links only.
  Gradient reduction still spans the full axis (exactly the reference's
  semantics: hpZ trades memory for inter-node gather traffic).

On the whole-tree path the gather sits *inside* the differentiated
function, so its VJP IS the gradient reduce-scatter — one mechanism,
both directions — with the sharded cotangents coalesced into flat
IPG-style buckets (``reduce_bucket_size``) instead of one collective
per leaf.

Gather granularity and overlap. With a model that exposes a *layered
loss spec* (``models/layered.py``) the micro-step is a hand-written
**software-pipelined** fwd+bwd over the transformer blocks
(:func:`_build_layered`, docs/zero_overlap.md): layer *i*'s (quantized,
hpZ-grouped) parameters gather as one flat bucket per dtype
(``allgather_bucket_size``), prefetched one layer ahead of the block
compute when ``overlap_comm`` is on, and the backward re-gathers and
bucket-reduces layer by layer with the same one-ahead lag — so ICI time
is legally overlappable with compute (verified on the compiled HLO by
``profiling/hlo_audit.py``) and peak gathered parameter memory is
depth+1 layers plus the embedding/head, not the full model. This is the
reference's stage-3 memory contract (live params bounded per-module,
``partitioned_param_coordinator.py:285`` ``max_live_parameters``) plus
its prefetch coordinator, as one loop. Models without a layered spec
(or stages < 3) fall back to the whole-tree gather, whose peak
parameter memory during a micro-step is the full model — fine for
wire-volume experiments, wrong for 7B+ per-chip budgets; set
``zero_optimization.layered_gather`` (default true) to control the
choice explicitly.
"""

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ...comm.comms_logging import get_comms_logger
from ...ops.quantizer import dequantize, quantize
from ...parallel.topology import DATA_AXIS


def _axis_dim(spec: Optional[PartitionSpec], axis: str):
    """Dim index carrying ``axis`` in a PartitionSpec, else None."""
    if spec is None:
        return None
    for i, entry in enumerate(spec):
        if entry == axis or (isinstance(entry, (tuple, list))
                             and axis in entry):
            return i
    return None


def project_spec(spec: Optional[PartitionSpec], axis: str) -> PartitionSpec:
    """Keep only ``axis`` from a spec (shard_map in_spec for a
    partial-manual region over that axis)."""
    dim = _axis_dim(spec, axis)
    if dim is None:
        return PartitionSpec()
    return PartitionSpec(*([None] * dim), axis)


def project_spec_tree(spec_tree, axis):
    return jax.tree.map(
        lambda s: project_spec(s, axis), spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def _log_wire(op, n_int8, n_scale_f32, equiv_bytes):
    """Record quantized wire volume (and the volume it replaced).
    ``equiv_bytes`` is the full-width byte count of the SAME payload in
    the leaf's actual dtype — computed by the caller from the real
    leaves, never assumed (a hard-coded bf16 equivalent under-reported
    fp32 runs 2x)."""
    get_comms_logger().log_quantized(
        op, int(n_int8) + 4 * int(n_scale_f32), int(equiv_bytes),
        (DATA_AXIS,))


def _quantized_all_gather_dim(x, dim, *, group_size, axis_index_groups=None):
    """int8-wire all-gather of ``x`` along named DATA_AXIS into dim
    ``dim``."""
    group_size = min(group_size, x.size)  # avoid pad blowup on small leaves
    q, scale, shape, count = quantize(x, group_size=group_size, num_bits=8)
    q_all = jax.lax.all_gather(q, DATA_AXIS,
                               axis_index_groups=axis_index_groups)
    s_all = jax.lax.all_gather(scale, DATA_AXIS,
                               axis_index_groups=axis_index_groups)
    _log_wire("qwZ_all_gather", q.size, scale.size,
              x.size * x.dtype.itemsize)
    deq = jax.vmap(lambda qi, si: dequantize(qi, si, shape, count))(
        q_all, s_all)
    # [n, ...] -> concatenate along the sharded dim
    parts = jnp.moveaxis(deq, 0, dim)
    new_shape = x.shape[:dim] + (-1,) + x.shape[dim + 1:]
    return parts.reshape(new_shape)


def _quant_reduce_mean_dim(g, dim, *, group_size):
    """qgZ: quantized all-to-all reduce-mean, scattering dim ``dim``.

    Reference: ``coalesced_collectives.py:81 all_to_all_quant_reduce`` +
    ``csrc/quantization/quant_reduce.cu``.
    """
    n = jax.lax.axis_size(DATA_AXIS)
    g = jnp.moveaxis(g, dim, 0)
    parts = g.reshape((n, g.shape[0] // n) + g.shape[1:])
    group_size = min(group_size, int(np.prod(parts.shape[1:])))

    def quant_part(p):
        return quantize(p, group_size=group_size, num_bits=8)[:2]

    qs, scales = jax.vmap(quant_part)(parts)
    qs = jax.lax.all_to_all(qs, DATA_AXIS, 0, 0)
    scales = jax.lax.all_to_all(scales, DATA_AXIS, 0, 0)
    _log_wire("qgZ_all_to_all", qs.size, scales.size,
              g.size * g.dtype.itemsize)
    part_shape = parts.shape[1:]
    part_count = int(np.prod(part_shape))
    deq = jax.vmap(lambda qi, si: dequantize(qi, si, part_shape,
                                             part_count))(qs, scales)
    return jnp.moveaxis(jnp.mean(deq, axis=0), 0, dim)


def _psum_scatter_mean_dim(g, dim):
    n = jax.lax.axis_size(DATA_AXIS)
    _log_plain("zero_reduce_scatter", g.size * g.dtype.itemsize)
    out = jax.lax.psum_scatter(jnp.moveaxis(g, dim, 0), DATA_AXIS,
                               scatter_dimension=0, tiled=True)
    return jnp.moveaxis(out, 0, dim) / n


def _log_plain(op, n_bytes):
    """Byte attribution for the unquantized reduce-scatter / bucketed
    collective sites (the gather/all-reduce sites were already
    attributed; see ``CommsLogger.log_collective``)."""
    logger = get_comms_logger()
    if logger.should_log(op):
        logger.log_collective(op, n_bytes, (DATA_AXIS,))


def bucketed_reduce_scatter_mean(flat, dims, *, bucket_elements, qg,
                                 group_size):
    """Reduce-mean the sharded leaves of ``flat`` (full cotangents) onto
    their data-axis shards — coalesced into flat reduce-scatter buckets
    of at most ``bucket_elements`` elements (the stage-1/2 IPG-bucket
    analog: ``deepspeed/runtime/zero/stage3.py``
    ``__add_grad_to_ipg_bucket``), ONE ``psum_scatter`` per bucket
    instead of one per leaf.

    Leaves with ``dim`` None (replicated wrt data) pass through
    untouched; under qgZ every sharded leaf keeps the per-leaf quantized
    all-to-all (quantization groups are per-leaf — coalescing would
    change the wire format and the math). Buckets are packed in flat
    order, per dtype (a flat buffer cannot mix dtypes), so the layout —
    and therefore the arithmetic — is deterministic: the bucketed
    reduce is bitwise-identical to the per-leaf reduce, element for
    element.
    """
    from .overlap import plan_reduce_buckets
    n = jax.lax.axis_size(DATA_AXIS)
    out = list(flat)
    if qg:
        for i, (g, d) in enumerate(zip(flat, dims)):
            if d is not None:
                out[i] = _quant_reduce_mean_dim(g, d,
                                                group_size=group_size)
        return out
    by_dtype = {}
    for i, (g, d) in enumerate(zip(flat, dims)):
        if d is not None:
            by_dtype.setdefault(jnp.dtype(g.dtype), []).append(i)
    for dtype, indices in sorted(by_dtype.items(), key=lambda kv: kv[0].name):
        marks = set(indices)
        sizes = [int(flat[i].size) if i in marks else None
                 for i in range(len(flat))]
        for bucket in plan_reduce_buckets(sizes, bucket_elements):
            parts, metas = [], []
            for idx in bucket.leaf_indices:
                g, d = flat[idx], dims[idx]
                gm = jnp.moveaxis(g, d, 0)
                lead = gm.shape[0] // n
                parts.append(gm.reshape(n, -1))
                metas.append((idx, (lead,) + gm.shape[1:]))
            wide = parts[0] if len(parts) == 1 \
                else jnp.concatenate(parts, axis=1)
            _log_plain("zero_bucket_reduce_scatter",
                       wide.size * wide.dtype.itemsize)
            red = jax.lax.psum_scatter(wide, DATA_AXIS,
                                       scatter_dimension=0, tiled=True)
            red = red.reshape(-1) / n
            off = 0
            for idx, shard_shape in metas:
                k = int(np.prod(shard_shape))
                seg = red[off:off + k].reshape(shard_shape)
                out[idx] = jnp.moveaxis(seg, 0, dims[idx])
                off += k
    return out


def bucketed_all_gather_start(flat, sec, dims, *, qw, hpz, group_size,
                              bucket_elements, matmul_plan=None):
    """ISSUE half of the layer-granular gather: coalesce the sharded
    leaves of ``flat`` (local shards; the hpZ ``sec`` partition when
    hpz > 1) into flat all-gather payloads of at most
    ``bucket_elements`` elements (the ``allgather_bucket_size`` analog)
    — ONE collective per bucket per dtype (two families under qwZ:
    int8 payloads + fp32 scales) instead of one per leaf.

    Returns ``(payloads, meta)``: ``payloads`` is a flat list of 1-D
    arrays — the gathered wire data, exactly what a prefetch pipeline
    should carry across loop iterations (compressed under qwZ, and 1-D
    so the loop-carry layout is canonical: consuming a carried payload
    compiles to the same kernels as consuming a fresh one, which keeps
    the prefetched and sequential schedules bitwise-identical).
    ``meta`` is the static unpack plan for
    :func:`bucketed_all_gather_finish`.

    Besides amortizing collective launch overhead, coalescing makes
    the overlap audit decidable: a single fused gather either feeds
    this iteration's compute (sequential) or only the carry
    (prefetched); per-leaf gathers always leave intra-layer slack (the
    MLP weights' gather can overlap the attention dots) that would
    make even the serialized fallback audit as partially overlappable.
    Replicated leaves (``dim`` None) ride along unmodified.

    ``matmul_plan`` (qwZ only): ``{leaf index: group_k}`` for 2-D
    matmul-weight leaves that should be quantized in the FUSED-KERNEL
    layout (``quantize_for_matmul``: per-(k-group, n) scales) instead
    of the flat groupwise layout — per-shard quantization tiles the
    contraction dim evenly, so the gathered shards concatenate into a
    valid full-weight ``(q [K, N], scale [G, N])`` pair that
    ``ops/quantized_matmul`` consumes directly
    (:func:`bucketed_all_gather_finish` ``fused=True``). Wire volume
    is identical to the flat layout for the same group size; only the
    scale GEOMETRY changes."""
    from .overlap import plan_reduce_buckets
    n = jax.lax.axis_size(DATA_AXIS)
    if hpz > 1:
        groups = [list(range(g * hpz, (g + 1) * hpz))
                  for g in range(n // hpz)]
        n_g = hpz
        # hpZ reads the intra-group secondary partition, not the
        # primary 1/n shard (wire stays on intra-group links)
        src = [p if d is None else s
               for p, s, d in zip(flat, sec, dims)]
    else:
        groups, n_g = None, n
        src = list(flat)

    def pack(items, log_op):
        # items: [(leaf index, 1-D payload)]; one all-gather per
        # dtype-bucket; payloads flattened to 1-D for the carry
        by_dtype = {}
        for it in items:
            by_dtype.setdefault(jnp.dtype(it[1].dtype), []).append(it)
        payloads, plan = [], []
        for dtype, group in sorted(by_dtype.items(),
                                   key=lambda kv: kv[0].name):
            sizes = [int(it[1].size) for it in group]
            for bucket in plan_reduce_buckets(sizes, bucket_elements):
                sel = [group[j] for j in bucket.leaf_indices]
                payload = sel[0][1] if len(sel) == 1 else jnp.concatenate(
                    [it[1] for it in sel])
                if log_op:
                    _log_plain(log_op,
                               payload.size * payload.dtype.itemsize)
                wide = jax.lax.all_gather(payload, DATA_AXIS,
                                          axis_index_groups=groups)
                payloads.append(wide.reshape(-1))
                plan.append([(it[0], int(it[1].size)) for it in sel])
        return payloads, plan

    meta = {"n_g": n_g, "qw": qw, "n_leaves": len(flat),
            "dims": list(dims),
            "passthrough": [i for i, d in enumerate(dims) if d is None]}
    if qw:
        from ...ops.quantized_matmul import quantize_for_matmul
        matmul_plan = matmul_plan or {}
        qitems, sitems, qmeta = [], [], {}
        for i, (p, d) in enumerate(zip(src, dims)):
            if d is None:
                continue
            if i in matmul_plan:
                group_k = matmul_plan[i]
                q, scale = quantize_for_matmul(p, group_k=group_k)
                qmeta[i] = ("mm", q.shape, scale.shape, group_k, d)
            else:
                gsz = min(group_size, p.size)
                q, scale, shape, count = quantize(p, group_size=gsz,
                                                  num_bits=8)
                qmeta[i] = ("flat", q.shape, scale.shape, shape, count, d)
            qitems.append((i, q.reshape(-1)))
            sitems.append((i, scale.reshape(-1)))
        if qitems:
            _log_wire("qwZ_all_gather",
                      sum(int(q.size) for _, q in qitems),
                      sum(int(s.size) for _, s in sitems),
                      sum(int(flat[i].size) * flat[i].dtype.itemsize
                          for i in qmeta))
        pq, plan_q = pack(qitems, None)
        ps, plan_s = pack(sitems, None)
        meta.update(plan_q=plan_q, plan_s=plan_s, qmeta=qmeta,
                    n_q=len(pq), n_s=len(ps))
        payloads = pq + ps
    else:
        items = [(i, p.reshape(-1))
                 for i, (p, d) in enumerate(zip(src, dims))
                 if d is not None]
        pr, plan_r = pack(items, "zero_bucket_all_gather")
        meta.update(plan_r=plan_r, n_r=len(pr),
                    shapes={i: tuple(src[i].shape) for i, _ in items})
        payloads = pr
    # replicated leaves ride the payload list unchanged (the consumer
    # needs the whole layer, not only its sharded leaves)
    payloads = payloads + [flat[i] for i in meta["passthrough"]]
    return payloads, meta


def bucketed_all_gather_finish(payloads, meta, fused=False):
    """CONSUME half of the layer-granular gather: unpack the 1-D wire
    payloads from :func:`bucketed_all_gather_start` back into full
    (dequantized under qwZ) leaves. This is where the qwZ dequantize
    runs — at consumption, so a prefetch pipeline carries int8 wire
    data, not fp weights.

    ``fused=True`` (matmul-layout leaves only): hand the assembled
    ``(int8, scales)`` pair back as a ``MatmulQuantizedTensor`` instead
    of dequantizing — the consuming block matmul runs
    ``ops/quantized_matmul`` on it and the fp weight never
    materializes. The backward re-gather calls this with
    ``fused=False``: the block VJP needs cotangents against the fp
    weight, so the recompute consumes the dequantized form (same
    linearization point, the dequant value)."""
    n_g = meta["n_g"]
    out = [None] * meta["n_leaves"]

    def unpack(pl, plan):
        got = {}
        for wide_flat, entries in zip(pl, plan):
            wide = wide_flat.reshape(n_g, -1)
            off = 0
            for key, size in entries:
                got[key] = wide[:, off:off + size]
                off += size
        return got

    def assemble(per_dev, local_shape, dim):
        # [n_g, *local] -> concatenate the device axis into ``dim``
        parts = jnp.moveaxis(per_dev.reshape((n_g,) + tuple(local_shape)),
                             0, dim)
        new_shape = (tuple(local_shape[:dim]) + (-1,)
                     + tuple(local_shape[dim + 1:]))
        return parts.reshape(new_shape)

    if meta["qw"]:
        from ...ops.quantized_matmul import MatmulQuantizedTensor
        q_all = unpack(payloads[:meta["n_q"]], meta["plan_q"])
        s_all = unpack(payloads[meta["n_q"]:meta["n_q"] + meta["n_s"]],
                       meta["plan_s"])
        n_buckets = meta["n_q"] + meta["n_s"]
        for i, ent in meta["qmeta"].items():
            if ent[0] == "mm":
                _, qshape, sshape, group_k, d = ent
                qa = q_all[i].reshape((n_g,) + tuple(qshape))
                sa = s_all[i].reshape((n_g,) + tuple(sshape))
                # shards tile the contraction (or n) dim evenly, so
                # concatenating q and scale along the SAME dim yields a
                # consistent full-weight fused-layout pair
                mqt = MatmulQuantizedTensor(
                    assemble(qa.reshape(n_g, -1), qshape, d),
                    assemble(sa.reshape(n_g, -1), sshape, d), group_k)
                out[i] = mqt if fused else mqt.dequantize()
            else:
                _, qshape, sshape, shape, count, d = ent
                qa = q_all[i].reshape((n_g,) + tuple(qshape))
                sa = s_all[i].reshape((n_g,) + tuple(sshape))
                deq = jax.vmap(lambda qi, si: dequantize(
                    qi, si, shape, count))(qa, sa)
                out[i] = assemble(deq.reshape(n_g, -1), shape, d)
    else:
        r_all = unpack(payloads[:meta["n_r"]], meta["plan_r"])
        n_buckets = meta["n_r"]
        for i, wide in r_all.items():
            out[i] = assemble(wide, meta["shapes"][i], meta["dims"][i])
    for j, i in enumerate(meta["passthrough"]):
        out[i] = payloads[n_buckets + j]
    return out


def make_leaf_gather(*, qw: bool, hpz: int, group_size: int = 2048):
    """Per-leaf ``(primary, secondary, dim) -> full`` gather: quantized
    wire under qwZ, intra-group (ICI-only) under hpZ, identity for
    replicated leaves. Must run inside the shard_map region."""

    def _hpz_groups():
        n = jax.lax.axis_size(DATA_AXIS)
        return [list(range(g * hpz, (g + 1) * hpz)) for g in range(n // hpz)]

    def gather_leaf(primary, secondary, dim):
        if dim is None:
            return primary  # replicated wrt data
        if hpz > 1:
            src, groups = secondary, _hpz_groups()
        else:
            src, groups = primary, None
        if qw:
            return _quantized_all_gather_dim(src, dim, group_size=group_size,
                                             axis_index_groups=groups)
        return jax.lax.all_gather(src, DATA_AXIS, axis=dim, tiled=True,
                                  axis_index_groups=groups)

    return gather_leaf


def make_param_gather(param_dims, grad_dims, *, qw: bool, qg: bool, hpz: int,
                      group_size: int = 2048,
                      reduce_bucket_elements: int = 500_000_000):
    """Build ``gather(primary, secondary) -> full params`` with a custom
    VJP that performs the (optionally quantized) gradient reduce-scatter.

    ``param_dims``: flat list (in ``jax.tree.flatten`` order of the param
    tree) of the dim index the ``data`` axis shards, or None for
    replicated leaves. ``secondary`` is a same-order flat list whose
    entries are None unless hpZ (then: the per-device 1/hpz partition,
    refreshed by :func:`build_secondary`). Must be called INSIDE the
    shard_map region.
    """

    _gather_leaf = make_leaf_gather(qw=qw, hpz=hpz, group_size=group_size)

    def _reduce_leaf(g, dim):
        n = jax.lax.axis_size(DATA_AXIS)
        if dim is None:
            return jax.lax.psum(g, DATA_AXIS) / n
        if qg:
            return _quant_reduce_mean_dim(g, dim, group_size=group_size)
        return _psum_scatter_mean_dim(g, dim)

    @jax.custom_vjp
    def gather(primary, secondary):
        flat, treedef = jax.tree.flatten(primary)
        out = [_gather_leaf(p, s, d)
               for p, s, d in zip(flat, secondary, param_dims)]
        return jax.tree.unflatten(treedef, out)

    def gather_fwd(primary, secondary):
        return gather(primary, secondary), None

    def gather_bwd(_, g_full):
        # Only leaves whose *parameter* is data-sharded can take the
        # reduce-scatter inside the VJP (the cotangent must match the
        # primal's local-shard shape). Replicated-param leaves pass
        # through unreduced; reduce_grads() finishes them. Sharded
        # leaves coalesce into flat IPG-style buckets — one
        # reduce-scatter per bucket, not per leaf.
        flat, treedef = jax.tree.flatten(g_full)
        g_primary = jax.tree.unflatten(
            treedef, bucketed_reduce_scatter_mean(
                flat, param_dims, bucket_elements=reduce_bucket_elements,
                qg=qg, group_size=group_size))
        # secondary is a value-copy of primary; its cotangent is defined
        # to be zero (all gradient flows to the primary partition).
        return g_primary, [None] * len(param_dims)

    gather.defvjp(gather_fwd, gather_bwd)

    def reduce_grads(grads):
        """Reduce the leaves the VJP could not: replicated-param leaves
        reduce-mean over the axis onto their *gradient* sharding (the
        stage-2 shape-changing reduce-scatter, or a plain psum-mean for
        fully replicated leaves)."""
        flat, treedef = jax.tree.flatten(grads)
        out = [g if pd is not None else _reduce_leaf(g, gd)
               for g, pd, gd in zip(flat, param_dims, grad_dims)]
        return jax.tree.unflatten(treedef, out)

    return gather, reduce_grads


def build_secondary(params, param_dims, hpz: int):
    """hpZ secondary partition: from the primary 1/n shard, build this
    device's 1/hpz shard (reference: the ZeRO-param secondary groups,
    ``utils/groups.py:650``). Runs INSIDE the shard_map region, once per
    optimizer step. Wire: one full-parameter all-gather over the data
    axis (the amortized refresh the reference does after each step).
    Returns a flat list in ``jax.tree.flatten`` order."""

    def leaf(p, dim):
        if dim is None or hpz <= 1:
            return None
        full = jax.lax.all_gather(p, DATA_AXIS, axis=dim, tiled=True)
        idx = jax.lax.axis_index(DATA_AXIS)
        within = idx % hpz
        # my 1/hpz slice of the sharded dim
        size = full.shape[dim] // hpz
        return jax.lax.dynamic_slice_in_dim(full, within * size, size,
                                            axis=dim)

    flat, _ = jax.tree.flatten(params)
    return [leaf(p, d) for p, d in zip(flat, param_dims)]


def make_layered_split(layered):
    """Generic params split for a layered loss spec: the flat model tree
    → ``(outer, stacked)`` where ``outer`` keeps the spec's
    ``outer_keys`` subtrees and ``stacked`` stacks the n_layer block
    subtrees into a leading layer dim (pure ``jnp.stack`` — its VJP
    unstacks the scan's block cotangents back onto the flat tree)."""
    from ...models._pipe_util import stack_flat_layers

    def split(params):
        stacked = stack_flat_layers(
            params, layered["layer_prefix"], layered["n_layer"],
            required=list(layered["outer_keys"]),
            model_name=layered["model_name"])
        outer = {k: params[k] for k in layered["outer_keys"]}
        return outer, stacked

    return split


def validate_zeropp(zcfg, stage: int, data_size: int):
    """Config-time checks (reference: engine.py:994-1008 asserts)."""
    from ..config import HDSConfigError
    hpz = zcfg.zero_hpz_partition_size
    if zcfg.zero_quantized_weights and stage != 3:
        raise HDSConfigError("zero_quantized_weights (qwZ) requires "
                             "zero stage 3")
    if hpz > 1:
        if stage != 3:
            raise HDSConfigError("zero_hpz_partition_size (hpZ) requires "
                                 "zero stage 3")
        if data_size % hpz != 0:
            raise HDSConfigError(
                f"zero_hpz_partition_size={hpz} must divide the data-"
                f"parallel world size {data_size}")
    if zcfg.zero_quantized_gradients and stage < 2:
        raise HDSConfigError("zero_quantized_gradients (qgZ) requires "
                             "zero stage >= 2 (sharded gradients)")
    from .overlap import validate_quantized_wire
    validate_quantized_wire(
        quantized_reduce_scatter=zcfg.zero_quantized_reduce_scatter,
        error_feedback=zcfg.zero_reduce_scatter_error_feedback,
        bits=zcfg.zero_quantized_reduce_scatter_bits,
        quantized_gradients=zcfg.zero_quantized_gradients,
        fused_matmul=zcfg.zero_quantized_weights_fused_matmul,
        quantized_weights=zcfg.zero_quantized_weights,
        stage=stage)


def build_zeropp_micro_fn(*, adapter_loss, mesh, param_specs, grad_specs,
                          batch_spec_of, gas, grad_accum_dtype,
                          remat_policy, zcfg, layered=None,
                          param_shapes=None):
    """The ZeRO++ micro fwd+bwd: a partial-manual shard_map over ``data``.

    Returns ``(micro_fwd_bwd, prepare_secondary, plan_info)``.
    ``micro_fwd_bwd`` has the engine's GSPMD signature plus an optional
    trailing ``secondary``:
    ``(params, grad_acc, loss_scale, batch, rng, train, secondary=None) ->
    (unscaled loss, new grad_acc)``, with the parameter gather and
    gradient reduction performed explicitly (quantized per the config).
    ``plan_info`` describes the comm/compute overlap plan the program was
    built against (gather pipeline depth, reduce bucket size) for
    telemetry and the HLO audit. ``param_shapes`` (pytree of shaped
    leaves congruent with ``param_specs``) enables build-time rejection
    of nonsensical overlap knobs and the prefetch-depth derivation.
    ``prepare_secondary(params)`` (None unless hpZ) refreshes the hpZ
    secondary partition — call it ONCE per optimizer step and pass the
    result to every micro so the full-axis gather amortizes over the
    gradient-accumulation loop (the reference refreshes its secondary
    partition once per step, not per micro-batch). A micro called without
    ``secondary`` refreshes inline (the unfused forward() path).
    ``batch_spec_of(leaf) -> PartitionSpec`` gives each batch leaf's
    global spec (projected to the data axis here).

    ``layered`` (``models/layered.py`` spec or None) selects the
    software-pipelined scan-over-layers engine (:func:`_build_layered`):
    ``embed → scan(gather-prefetched block body) → head`` with a
    hand-written backward whose gather and reduce lanes are explicitly
    issued against the compute, peak gathered params bounded to
    depth+1 layers + the outer (embedding/head) leaves — the
    reference's ``max_live_parameters`` contract. The whole-tree path
    below is the fallback for models without a spec.
    """
    qw = zcfg.zero_quantized_weights
    qg = zcfg.zero_quantized_gradients
    hpz = zcfg.zero_hpz_partition_size

    if (zcfg.zero_quantized_reduce_scatter
            or zcfg.zero_quantized_weights_fused_matmul) \
            and layered is None:
        # both features live inside the layered pipeline's explicit
        # gather/reduce lanes — the whole-tree fallback's AD-generated
        # reduce cannot thread residual state through a custom_vjp, and
        # its gathered tree feeds an opaque loss with no interception
        # point. Reject loudly instead of silently running full-width.
        from ..config import HDSConfigError
        raise HDSConfigError(
            "zero_quantized_reduce_scatter / "
            "zero_quantized_weights_fused_matmul require the layered "
            "ZeRO-3 step: keep zero_optimization.layered_gather=true "
            "and use a model with a layered spec (models/layered.py)")

    def _flat_specs(tree):
        return jax.tree.flatten(
            tree, is_leaf=lambda x: isinstance(x, PartitionSpec))[0]

    def _dims(tree):
        return [_axis_dim(s, DATA_AXIS) for s in _flat_specs(tree)]

    param_dims = _dims(param_specs)
    grad_dims = _dims(grad_specs)

    if param_shapes is not None:
        # build-time knob sanity against real shapes (no silent clamps)
        from .overlap import validate_overlap_config
        paths_sizes = [
            (jax.tree_util.keystr(path), int(np.prod(leaf.shape)))
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                param_shapes)[0]]
        sharded = [(name, size)
                   for (name, size), d in zip(paths_sizes, param_dims)
                   if d is not None]
        if sharded:
            largest_name, largest = max(sharded, key=lambda t: t[1])
            validate_overlap_config(
                reduce_bucket_elements=zcfg.reduce_bucket_size,
                largest_leaf=largest, largest_leaf_name=largest_name)
    params_proj = project_spec_tree(param_specs, DATA_AXIS)
    grads_proj = project_spec_tree(grad_specs, DATA_AXIS)
    flat_pproj = _flat_specs(params_proj)
    # secondary leaves stay sharded on the same dim as their primary
    # (local size 1/hpz ⇒ the logical global dim is n/hpz times the
    # parameter's, which only ever lives inside the fused step)
    secondary_proj = [s for s in flat_pproj]

    gather, reduce_grads = make_param_gather(
        param_dims, grad_dims, qw=qw, qg=qg, hpz=hpz,
        reduce_bucket_elements=zcfg.reduce_bucket_size)

    if layered is not None:
        return _build_layered(
            layered=layered, mesh=mesh, param_specs=param_specs,
            batch_spec_of=batch_spec_of, gas=gas,
            grad_accum_dtype=grad_accum_dtype, remat_policy=remat_policy,
            qw=qw, qg=qg, hpz=hpz, reduce_grads=reduce_grads,
            params_proj=params_proj, grads_proj=grads_proj,
            zcfg=zcfg, param_shapes=param_shapes)

    prepare_secondary = None
    if hpz > 1:
        def prepare_secondary(params):
            return jax.shard_map(
                lambda p: build_secondary(p, param_dims, hpz),
                mesh=mesh, axis_names={DATA_AXIS},
                in_specs=(params_proj,), out_specs=secondary_proj,
                check_vma=False)(params)

    def micro_fwd_bwd(params, grad_acc, loss_scale, batch, rng, train,
                      secondary=None):
        batch_proj = jax.tree.map(
            lambda leaf: project_spec(batch_spec_of(leaf), DATA_AXIS), batch)
        with_sec = secondary is not None

        def inner(params_local, grad_acc_local, loss_scale, batch_local,
                  rng, *maybe_sec):
            n = jax.lax.axis_size(DATA_AXIS)
            if with_sec:
                sec = list(maybe_sec[0])
            else:
                sec = build_secondary(params_local, param_dims, hpz)

            def raw_loss(p_local):
                full = gather(p_local, sec)
                loss, _aux = adapter_loss(full, batch_local, rng,
                                          train=train)
                return loss

            loss_fn = jax.checkpoint(raw_loss, policy=remat_policy) \
                if remat_policy is not None else raw_loss

            def scaled_loss(p):
                return loss_fn(p) * loss_scale / gas

            loss_s, grads = jax.value_and_grad(scaled_loss)(params_local)
            grads = reduce_grads(grads)
            grads = jax.tree.map(
                lambda g: g.astype(grad_accum_dtype), grads)
            new_acc = jax.tree.map(jnp.add, grad_acc_local, grads)
            loss_avg = jax.lax.psum(loss_s, DATA_AXIS) / n
            return loss_avg * gas / loss_scale, new_acc

        in_specs = [params_proj, grads_proj, PartitionSpec(), batch_proj,
                    PartitionSpec()]
        args = [params, grad_acc, loss_scale, batch, rng]
        if with_sec:
            in_specs.append(secondary_proj)
            args.append(secondary)
        shmapped = jax.shard_map(
            inner, mesh=mesh, axis_names={DATA_AXIS},
            in_specs=tuple(in_specs), out_specs=(PartitionSpec(),
                                                 grads_proj),
            check_vma=False)
        return shmapped(*args)

    plan_info = {
        "mode": "whole-tree", "depth": None,
        "bucket_elements": zcfg.reduce_bucket_size,
        "overlap_comm": zcfg.overlap_comm,
        "quantized_reduce_scatter": False,
    }
    return micro_fwd_bwd, prepare_secondary, plan_info


#: Diagnostic taps for the layered pipeline: when flipped on (module
#: level, before the engine builds its step functions), the layered
#: micro additionally returns {y, y_cot, xs_stack, gfirst, loss} so
#: bitwise divergences between the prefetched and sequential schedules
#: can be localized stage by stage (this is how the loop-carry layout
#: sensitivity of the qwZ gather was found). Never on in production;
#: the extra outputs change micro_fwd_bwd's signature.
_ZO_DEBUG = False


def _build_layered(*, layered, mesh, param_specs, batch_spec_of, gas,
                   grad_accum_dtype, remat_policy, qw, qg, hpz,
                   reduce_grads, params_proj, grads_proj, zcfg,
                   param_shapes=None):
    """Software-pipelined scan-over-layers ZeRO-3 micro step.

    The fwd+bwd over transformer blocks is written by hand (no
    ``jax.value_and_grad`` through the layer loop) so the gather and
    reduce lanes can be *explicitly* scheduled against the compute,
    instead of trusting the compiler's latency-hiding scheduler —
    ``DOMINO_TPU_r4.log`` proved XLA may compile ZERO async collective
    pairs when left to its own devices. Structure, per
    ``derive_prefetch_depth``:

    * **depth 1** (``overlap_comm=True`` and the knobs admit it):
      double-buffered. The forward scan carry holds layer *i*'s gathered
      (qwZ-dequantized, hpZ-grouped) parameters while layer *i+1*'s
      all-gather is issued BEFORE layer *i*'s block compute consumes the
      carry. The backward scan mirrors it with TWO lanes: layer *i+1*'s
      cotangent reduce-scatter buckets and layer *i-1*'s re-gather are
      both issued before layer *i*'s recompute+VJP — neither is an
      ancestor nor a descendant of the block compute, so any scheduler
      may overlap them (and ``profiling/hlo_audit.py`` verifies the
      compiled program keeps that freedom).
    * **depth 0** (``overlap_comm=False`` or vetoed): sequential
      gather→compute→reduce, with the reduce fenced
      (``optimization_barrier``) into the upstream cotangent chain — a
      REAL serialization fallback, not a no-op flag.

    Both depths run identical per-layer math in identical order, so they
    are bitwise-equal on a deterministic backend (asserted in tier-1).
    Peak gathered parameters stay bounded: depth+1 layers + the outer
    (embedding/head) leaves — the ``max_live_parameters`` contract.
    Block cotangents are reduced through
    :func:`bucketed_reduce_scatter_mean` (``reduce_bucket_size``
    elements per flat bucket). ``remat_policy`` does not apply here: the
    manual backward re-gathers and recomputes one block at a time by
    construction.
    """
    from ...comm.overlap import CollectiveIssue
    from ...utils.logging import log_dist
    from .overlap import derive_prefetch_depth, validate_overlap_config
    from .qwire import (plan_wire_residual_widths,
                        quantized_bucket_reduce_scatter_mean)

    split = make_layered_split(layered)
    prefix, n_layer = layered["layer_prefix"], layered["n_layer"]
    outer_keys = list(layered["outer_keys"])
    embed_fn = layered["embed"]
    block_fn = layered["block"]
    head_fn = layered["head"]
    bucket_elems = zcfg.reduce_bucket_size
    ag_bucket = zcfg.allgather_bucket_size
    group_size = 2048
    # quantized gradient wire (bucketed int8 reduce-scatter + error
    # feedback) and fused qwZ weight consumption
    qrs = zcfg.zero_quantized_reduce_scatter
    qrs_ef = zcfg.zero_reduce_scatter_error_feedback
    qrs_bits = zcfg.zero_quantized_reduce_scatter_bits
    fused_mm = zcfg.zero_quantized_weights_fused_matmul
    if (qrs or fused_mm) and param_shapes is None:
        from ..config import HDSConfigError
        raise HDSConfigError(
            "zero_quantized_reduce_scatter / "
            "zero_quantized_weights_fused_matmul need the parameter "
            "shapes at build time (engine passes them; pass "
            "param_shapes to build_zeropp_micro_fn)")
    n_data = int(mesh.shape[DATA_AXIS])

    def _subtree_dims(spec_tree):
        flat = jax.tree.flatten(
            spec_tree, is_leaf=lambda x: isinstance(x, PartitionSpec))[0]
        return [_axis_dim(s, DATA_AXIS) for s in flat]

    block0 = param_specs[f"{prefix}0"]
    for i in range(1, n_layer):
        if _subtree_dims(param_specs[f"{prefix}{i}"]) \
                != _subtree_dims(block0):
            raise ValueError(
                f"layered ZeRO++ gather needs identical shard specs "
                f"across layers; {prefix}{i} differs from {prefix}0")
    block_pdims = _subtree_dims(block0)
    outer_pdims = _subtree_dims({k: param_specs[k] for k in outer_keys})
    # stacked leaves carry the data axis one dim later (leading L dim)
    stacked_pdims = [None if d is None else d + 1 for d in block_pdims]

    # ---- overlap plan (depth from the stage-3 knobs + real shapes) ----
    layer_params = outer_params = 0
    if param_shapes is not None:
        layer_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(
            param_shapes[f"{prefix}0"]))
        outer_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(
            {k: param_shapes[k] for k in outer_keys}))
        largest = max(
            (int(np.prod(l.shape)) for l, d in zip(
                jax.tree.leaves(param_shapes), _subtree_dims(
                    project_spec_tree(param_specs, DATA_AXIS)))
             if d is not None), default=0)
        validate_overlap_config(
            reduce_bucket_elements=bucket_elems,
            largest_leaf=largest,
            max_live_parameters=zcfg.stage3_max_live_parameters,
            layer_params=layer_params, outer_params=outer_params)
        largest_block = max(
            (int(np.prod(l.shape)) for l, d in zip(
                jax.tree.leaves(param_shapes[f"{prefix}0"]),
                block_pdims) if d is not None), default=0)
        validate_overlap_config(
            reduce_bucket_elements=ag_bucket, largest_leaf=largest_block,
            knob="allgather_bucket_size")
    plan = derive_prefetch_depth(
        overlap_comm=zcfg.overlap_comm,
        prefetch_bucket_size=zcfg.stage3_prefetch_bucket_size,
        max_live_parameters=zcfg.stage3_max_live_parameters,
        layer_params=layer_params or 1, outer_params=outer_params)
    depth = plan.depth if n_layer >= 2 else 0
    log_dist(f"zero-overlap: layered gather pipeline depth={depth} "
             f"({plan.reason}); reduce bucket={bucket_elems} elements",
             ranks=[0])

    gather_leaf = make_leaf_gather(qw=qw, hpz=hpz, group_size=group_size)

    # ---- fused qwZ consumption plan: which block leaves gather in the
    # matmul (per-(k-group, n) scale) layout. Dense kernels only — the
    # interceptor consumes exactly those; everything else keeps the
    # flat layout and dequantizes as before.
    matmul_plan = None
    if fused_mm:
        matmul_plan = {}
        n_src = hpz if hpz > 1 else n_data
        block_leaves = jax.tree_util.tree_flatten_with_path(
            param_shapes[f"{prefix}0"])[0]
        for j, ((path, leaf), d) in enumerate(zip(block_leaves,
                                                  block_pdims)):
            if d not in (0, 1) or leaf.ndim != 2:
                continue
            if getattr(path[-1], "key", None) != "kernel":
                continue
            # the per-shard contraction length the group size must tile
            kdim = leaf.shape[0] // n_src if d == 0 else leaf.shape[0]
            group_k = next((gk for gk in (256, 128, 64, 32, 16, 8, 4, 2,
                                          1) if gk <= kdim
                            and kdim % gk == 0), None)
            if group_k is not None:
                matmul_plan[j] = group_k
        log_dist(f"zero-overlap: fused qwZ matmul consumption for "
                 f"{len(matmul_plan)}/{len(block_leaves)} block leaves",
                 ranks=[0])

    # ---- quantized reduce-scatter residual plan (error feedback) ----
    block_res_widths = outer_res_widths = ()
    if qrs:
        block_sizes = [int(np.prod(l.shape)) for l in jax.tree.leaves(
            param_shapes[f"{prefix}0"])]
        outer_sizes = [int(np.prod(l.shape)) for l in jax.tree.leaves(
            {k: param_shapes[k] for k in outer_keys})]
        block_res_widths = plan_wire_residual_widths(
            block_sizes, block_pdims, bucket_elements=bucket_elems,
            n=n_data)
        outer_res_widths = plan_wire_residual_widths(
            outer_sizes, outer_pdims, bucket_elements=bucket_elems,
            n=n_data)

    def wire_error_init():
        """Zero error-feedback residual state, engine-state shaped:
        per bucket, ``[L, n, n, W]`` (block) / ``[n, n, W]`` (outer)
        with the leading stack dim sharded on data — each device
        carries only its own (unsynchronized) ``[n, W]`` residual, the
        1-bit worker-error layout."""
        from jax.sharding import NamedSharding
        block = [jax.device_put(
            jnp.zeros((n_layer, n_data, n_data, w), jnp.float32),
            NamedSharding(mesh, PartitionSpec(None, DATA_AXIS)))
            for w in block_res_widths]
        outer = [jax.device_put(
            jnp.zeros((n_data, n_data, w), jnp.float32),
            NamedSharding(mesh, PartitionSpec(DATA_AXIS)))
            for w in outer_res_widths]
        return {"block": block, "outer": outer}

    def _wire_error_specs():
        return {"block": [PartitionSpec(None, DATA_AXIS)
                          for _ in block_res_widths],
                "outer": [PartitionSpec(DATA_AXIS)
                          for _ in outer_res_widths]}

    def build_layered_secondary(params_local):
        outer_local, stacked_local = split(params_local)
        sec_outer = build_secondary(outer_local, outer_pdims, hpz)
        sec_stacked = build_secondary(
            jax.tree.flatten(stacked_local)[0], stacked_pdims, hpz)
        return sec_outer, sec_stacked

    def _sec_specs():
        outer_proj = [project_spec(s, DATA_AXIS) for s in _flat_specs_of(
            {k: param_specs[k] for k in outer_keys})]
        sec_outer_specs = [
            None if d is None else outer_proj[i]
            for i, d in enumerate(outer_pdims)]
        sec_stacked_specs = [
            None if d is None else PartitionSpec(*([None] * d), DATA_AXIS)
            for d in stacked_pdims]
        return sec_outer_specs, sec_stacked_specs

    def _flat_specs_of(tree):
        return jax.tree.flatten(
            tree, is_leaf=lambda x: isinstance(x, PartitionSpec))[0]

    prepare_secondary = None
    if hpz > 1:
        def prepare_secondary(params):
            return jax.shard_map(
                build_layered_secondary,
                mesh=mesh, axis_names={DATA_AXIS},
                in_specs=(params_proj,), out_specs=_sec_specs(),
                check_vma=False)(params)

    def micro_fwd_bwd(params, grad_acc, loss_scale, batch, rng, train,
                      secondary=None, wire_error=None):
        batch_proj = jax.tree.map(
            lambda leaf: project_spec(batch_spec_of(leaf), DATA_AXIS), batch)
        with_sec = secondary is not None
        if qrs_ef and wire_error is None:
            # unfused forward()/report path: seed zero residuals inline
            wire_error = {
                "block": [jnp.zeros((n_layer, n_data, n_data, w),
                                    jnp.float32)
                          for w in block_res_widths],
                "outer": [jnp.zeros((n_data, n_data, w), jnp.float32)
                          for w in outer_res_widths]}

        def inner(params_local, grad_acc_local, loss_scale, batch_local,
                  rng, *extra):
            n = jax.lax.axis_size(DATA_AXIS)
            extra = list(extra)
            if with_sec:
                sec_outer, sec_stacked = extra.pop(0)
            else:
                sec_outer, sec_stacked = build_layered_secondary(
                    params_local)
            sec_outer, sec_stacked = list(sec_outer), list(sec_stacked)
            if qrs_ef:
                werr = extra.pop(0)
                # engine-state stacked layout -> this device's local
                # [n, W] residuals (leading data-stacked dim is 1 here)
                res_block = [r[:, 0] for r in werr["block"]]
                res_outer = [r[0] for r in werr["outer"]]
            else:
                res_block = res_outer = None

            outer_local, stacked_local = split(params_local)
            outer_flat, outer_def = jax.tree.flatten(outer_local)
            stacked_flat, block_def = jax.tree.flatten(stacked_local)
            keys = jax.random.split(rng, n_layer + 1)

            # Kernel isolation: every lane (gather, reduce, block
            # compute, block VJP) is fenced with optimization_barrier
            # at its boundaries. The barriers are erased after XLA's
            # optimization passes (zero runtime ops in the final
            # module) but stop cross-lane fusion DURING them — so the
            # pipelined and sequential programs compile the same
            # per-layer kernels with the same accumulation order,
            # which is what makes depth-1 vs depth-0 bitwise-equal
            # (the tier-1 parity gate) instead of merely close.
            iso = jax.lax.optimization_barrier

            def gather_outer_flat(flat, sec):
                return list(iso(tuple(
                    gather_leaf(p, s, d)
                    for p, s, d in zip(flat, sec, outer_pdims))))

            # Layer gather, split in two: g_start ISSUES the fused
            # all-gather(s) and returns 1-D wire payloads (int8 +
            # scales under qwZ) — the unit the pipeline carries across
            # loop iterations; g_finish unpacks/dequantizes at the
            # consumption site. Carrying 1-D wire payloads instead of
            # dequantized weights keeps the loop-carry layout canonical
            # (carried-vs-fresh operands compile to the same block
            # kernels -> depth-1 is bitwise-equal to depth-0) and
            # shrinks the carry 4x under qwZ. Lane boundaries are
            # fenced with optimization_barrier so both schedules
            # compile identical per-layer kernels.
            gmeta = {}

            def g_start(flat, sec):
                flat = list(iso(tuple(flat)))
                live = [s for s in sec if s is not None]
                if live:
                    it = iter(iso(tuple(live)))
                    sec = [None if s is None else next(it) for s in sec]
                payloads, meta = bucketed_all_gather_start(
                    flat, sec, block_pdims, qw=qw, hpz=hpz,
                    group_size=group_size, bucket_elements=ag_bucket,
                    matmul_plan=matmul_plan)
                gmeta.setdefault("m", meta)
                return list(iso(tuple(payloads)))

            def g_finish(payloads, fused=False):
                return list(iso(tuple(bucketed_all_gather_finish(
                    list(payloads), gmeta["m"], fused=fused))))

            def g_finish_fwd(payloads):
                # the forward consumer: fused-layout leaves stay
                # (int8, scales) and feed quantized_matmul directly
                return g_finish(payloads, fused=fused_mm)

            def reduce_cots(flat_cots, res=None):
                """Reduce lane: returns ``(reduced leaves, new
                residuals)`` — residuals empty unless the quantized
                reduce-scatter carries error feedback."""
                if qrs:
                    out, nres = quantized_bucket_reduce_scatter_mean(
                        flat_cots, block_pdims,
                        bucket_elements=bucket_elems,
                        group_size=group_size, bits=qrs_bits,
                        residuals=res, error_feedback=qrs_ef)
                else:
                    out = bucketed_reduce_scatter_mean(
                        flat_cots, block_pdims,
                        bucket_elements=bucket_elems,
                        qg=qg, group_size=group_size)
                    nres = []
                out = list(iso(tuple(out)))
                if nres:
                    nres = list(iso(tuple(nres)))
                return out, nres

            def take(idx):
                return ([leaf[idx] for leaf in stacked_flat],
                        [None if s is None else s[idx]
                         for s in sec_stacked])

            def blk(full_flat, x, key):
                full_flat, x = iso((tuple(full_flat), x))
                layer_tree = jax.tree.unflatten(block_def,
                                                list(full_flat))
                if fused_mm:
                    # Dense kernels arrive as (int8, scales); the
                    # interceptor routes them through quantized_matmul
                    # so the fp weight never materializes
                    import flax.linen as fnn
                    from ...ops.quantized_matmul import \
                        fused_dense_interceptor
                    with fnn.intercept_methods(fused_dense_interceptor()):
                        return iso(block_fn(layer_tree, x, batch_local,
                                            key, train))
                return iso(block_fn(layer_tree, x, batch_local, key,
                                    train))

            def blk_vjp(full_flat, x_in, x_cot, key):
                full_flat, x_in, x_cot = iso(
                    (tuple(full_flat), x_in, x_cot))
                _, vjp_t = jax.vjp(
                    lambda f, xx: block_fn(
                        jax.tree.unflatten(block_def, list(f)),
                        xx, batch_local, key, train),
                    full_flat, x_in)
                cot, x_cot_out = vjp_t(x_cot)
                cot, x_cot_out = iso((cot, x_cot_out))
                return list(cot), x_cot_out

            # ---------------- forward ----------------
            outer_full = jax.tree.unflatten(
                outer_def, gather_outer_flat(outer_flat, sec_outer))
            x = iso(embed_fn(outer_full, batch_local, keys[n_layer],
                             train))

            _dbg_gfirst = None
            if depth >= 1:
                # trip-L rolled pipeline: iteration t computes layer t
                # from the carry while issuing layer (t+1) mod L's
                # gather into the carry. The final iteration re-gathers
                # layer 0 (discarded) — one redundant gather per micro
                # buys a uniform loop body that never degenerates to
                # the unrolled form (XLA deletes trip-1 loops, and the
                # prefetch structure only exists inside a loop body).
                cur0 = g_start(*take(0))
                if _ZO_DEBUG:
                    _dbg_gfirst = g_finish(cur0)
                xs_f = ([jnp.roll(leaf, -1, axis=0)
                         for leaf in stacked_flat],
                        [None if s is None else jnp.roll(s, -1, axis=0)
                         for s in sec_stacked],
                        keys[:n_layer])

                def fwd_body(carry, xs_t):
                    x_t, cur = carry
                    nxt_flat, nxt_sec, key = xs_t
                    # gather lane: issue layer t+1's all-gather; nothing
                    # in this iteration consumes it (goes to the carry)
                    nxt = g_start(nxt_flat, nxt_sec)
                    y = blk(g_finish_fwd(cur), x_t, key)
                    return (y, nxt), x_t

                (y, _), xs_stack = jax.lax.scan(
                    fwd_body, (x, cur0), xs_f)
            else:
                if _ZO_DEBUG:
                    _dbg_gfirst = g_finish(g_start(*take(0)))

                def fwd_body0(x_t, xs_t):
                    flat_t, sec_t, key = xs_t
                    full = g_finish_fwd(g_start(flat_t, sec_t))
                    return blk(full, x_t, key), x_t

                y, xs_stack = jax.lax.scan(
                    fwd_body0, x,
                    (stacked_flat, sec_stacked, keys[:n_layer]))

            outer_full_i, y_i = iso((outer_full, y))
            loss, head_vjp = jax.vjp(
                lambda of, yy: head_fn(of, yy, batch_local),
                outer_full_i, y_i)
            seed = (loss_scale / gas).astype(loss.dtype)
            outer_cot_h, y_cot = iso(head_vjp(seed))

            # ---------------- backward ----------------
            if depth >= 1:
                # trip-L rolled dual-lane pipeline, mirror of the
                # forward: iteration t recomputes+VJPs layer t from the
                # carried gathered params while issuing (a) the
                # reduce-scatter buckets of layer t+1's cotangents
                # (carried as ``pending``) and (b) layer t-1's
                # re-gather. Pipeline fill: gather layer L-1 before the
                # loop; ``pending`` seeds with zero cotangents, so the
                # first iteration reduces zeros (discarded) and the
                # last re-gathers layer L-1 (discarded) — one junk
                # reduce + one junk gather per micro-step keep the body
                # uniform (a trip-1 loop would be deleted by XLA and
                # the overlap structure with it).
                g_init = g_start(*take(n_layer - 1))
                # zero cotangent seed, full-leaf shaped (the finish
                # below is consumed only by zeros_like -> DCE'd)
                zero_cot = [jnp.zeros_like(g)
                            for g in g_finish(g_init)]

                # error-feedback residual xs: iteration t reduces layer
                # t+1's cotangents, so it consumes res[t+1]; the junk
                # zero-seed reduce at t=L-1 gets a zero residual (its
                # real res[0] is consumed by the layer-0 reduce below)
                if qrs_ef:
                    res_x = [jnp.concatenate(
                        [r[1:], jnp.zeros_like(r[:1])], axis=0)
                        for r in res_block]
                else:
                    res_x = []
                xs_b = (xs_stack,
                        [jnp.roll(leaf, 1, axis=0)
                         for leaf in stacked_flat],
                        [None if s is None else jnp.roll(s, 1, axis=0)
                         for s in sec_stacked],
                        keys[:n_layer],
                        res_x)

                def bwd_body(carry, xs_t):
                    x_cot_t, pending, cur = carry
                    x_in, prev_f, prev_s, key, res_t = xs_t
                    # reduce lane: layer t+1's cotangent buckets (from
                    # the carry — independent of this body's compute)
                    reduced, res_out = reduce_cots(
                        pending, res_t if qrs_ef else None)
                    # gather lane: layer t-1's params for next iteration
                    nxt = g_start(prev_f, prev_s)
                    cot, x_cot_out = blk_vjp(g_finish(cur), x_in,
                                             x_cot_t, key)
                    return (x_cot_out, cot, nxt), (reduced, res_out)

                (x_cot, pending0, _), (red_stack, res_stack) = \
                    jax.lax.scan(
                        bwd_body, (y_cot, zero_cot, g_init), xs_b,
                        reverse=True)
                red0, res0_out = reduce_cots(
                    pending0,
                    [r[0] for r in res_block] if qrs_ef else None)
                # red_stack[t] = reduced layer t+1 for t <= L-2;
                # red_stack[L-1] is the zero-seed junk — dropped
                stacked_grads = [
                    jnp.concatenate([r0[None], rs[:n_layer - 1]], axis=0)
                    for r0, rs in zip(red0, red_stack)]
                new_res_block = [
                    jnp.concatenate([r0[None], rs[:n_layer - 1]], axis=0)
                    for r0, rs in zip(res0_out, res_stack)] \
                    if qrs_ef else []
            else:
                def bwd_body0(x_cot_t, xs_t):
                    x_in, flat_t, sec_t, key, res_t = xs_t
                    full = g_finish(g_start(flat_t, sec_t))
                    cot, x_cot_out = blk_vjp(full, x_in, x_cot_t, key)
                    reduced, res_out = reduce_cots(
                        cot, res_t if qrs_ef else None)
                    # The REAL serialization here is structural: the
                    # gather is consumed by this body's recompute and
                    # the reduce consumes this body's cotangents, so
                    # both sit on the dependence chain in the final
                    # module (what the audit asserts). The fence only
                    # adds an optimization-time ordering on top (it is
                    # erased after optimization — see
                    # CollectiveIssue.fence).
                    anchors = [r for r, d in zip(reduced, block_pdims)
                               if d is not None]
                    x_cot_out = CollectiveIssue.fence(x_cot_out, *anchors)
                    return x_cot_out, (reduced, res_out)

                x_cot, (red_stack, res_stack) = jax.lax.scan(
                    bwd_body0, y_cot,
                    (xs_stack, stacked_flat, sec_stacked,
                     keys[:n_layer],
                     res_block if qrs_ef else []),
                    reverse=True)
                stacked_grads = list(red_stack)
                new_res_block = list(res_stack) if qrs_ef else []

            _, embed_vjp = jax.vjp(
                lambda of: embed_fn(of, batch_local, keys[n_layer], train),
                outer_full_i)
            (outer_cot_e,) = embed_vjp(iso(x_cot))
            outer_cot_e = iso(outer_cot_e)
            outer_cot = jax.tree.map(jnp.add, outer_cot_h, outer_cot_e)
            new_res_outer = []
            if qrs:
                outer_red, new_res_outer = \
                    quantized_bucket_reduce_scatter_mean(
                        jax.tree.flatten(outer_cot)[0], outer_pdims,
                        bucket_elements=bucket_elems,
                        group_size=group_size, bits=qrs_bits,
                        residuals=res_outer, error_feedback=qrs_ef)
            else:
                outer_red = bucketed_reduce_scatter_mean(
                    jax.tree.flatten(outer_cot)[0], outer_pdims,
                    bucket_elements=bucket_elems, qg=qg,
                    group_size=group_size)

            grads = dict(jax.tree.unflatten(outer_def, outer_red))
            for i in range(n_layer):
                grads[f"{prefix}{i}"] = jax.tree.unflatten(
                    block_def, [g[i] for g in stacked_grads])

            grads = reduce_grads(grads)
            grads = jax.tree.map(
                lambda g: g.astype(grad_accum_dtype), grads)
            new_acc = jax.tree.map(jnp.add, grad_acc_local, grads)
            loss_s = loss * loss_scale / gas
            loss_avg = jax.lax.psum(loss_s, DATA_AXIS) / n
            outs = (loss_avg * gas / loss_scale, new_acc)
            if qrs_ef:
                # back to the engine-state stacked layout ([.., 1, n, W]
                # locally; the jit boundary sees the data-stacked dim)
                outs = outs + ({"block": [r[:, None]
                                          for r in new_res_block],
                                "outer": [r[None]
                                          for r in new_res_outer]},)
            if _ZO_DEBUG:
                taps = {"y": y, "y_cot": y_cot, "xs_stack": xs_stack,
                        "gfirst": _dbg_gfirst, "loss": loss}
                outs = outs + (taps,)
            return outs

        in_specs = [params_proj, grads_proj, PartitionSpec(), batch_proj,
                    PartitionSpec()]
        args = [params, grad_acc, loss_scale, batch, rng]
        if with_sec:
            in_specs.append(_sec_specs())
            args.append(secondary)
        if qrs_ef:
            in_specs.append(_wire_error_specs())
            args.append(wire_error)
        out_specs = (PartitionSpec(), grads_proj)
        if qrs_ef:
            out_specs = out_specs + (_wire_error_specs(),)
        if _ZO_DEBUG:
            P = PartitionSpec
            out_specs = out_specs + ({"y": P(DATA_AXIS), "y_cot": P(DATA_AXIS),
                                      "xs_stack": P(None, DATA_AXIS),
                                      "gfirst": [P() for _ in block_pdims],
                                      "loss": P()},)
        shmapped = jax.shard_map(
            inner, mesh=mesh, axis_names={DATA_AXIS},
            in_specs=tuple(in_specs), out_specs=out_specs,
            check_vma=False)
        return shmapped(*args)

    plan_info = {
        "mode": "layered", "depth": depth, "reason": plan.reason,
        "n_layer": n_layer, "bucket_elements": bucket_elems,
        "overlap_comm": zcfg.overlap_comm,
        "quantized_reduce_scatter": qrs,
        "error_feedback": qrs_ef,
        "wire_bits": qrs_bits if qrs else None,
        "fused_matmul_leaves": len(matmul_plan) if matmul_plan else 0,
        "wire_error_buckets": len(block_res_widths)
        + len(outer_res_widths),
    }
    if qrs_ef:
        # non-JSON engine hook: allocates the error-feedback state
        # (the engine pops it off before logging the plan)
        plan_info["wire_error_init"] = wire_error_init
    return micro_fwd_bwd, prepare_secondary, plan_info
