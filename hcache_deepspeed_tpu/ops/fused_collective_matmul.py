"""Fused computation-collective kernels: gather-matmul and the
quantized reduce-scatter epilogue (ROADMAP item 3).

Reference analogs:
* "Optimizing Distributed ML Communication with Fused
  Computation-Collective Operations" (arXiv 2305.06942) — embed the
  collective's point-to-point steps INSIDE the consuming GEMM kernel so
  chunk k's partial matmul executes while chunk k+1's permute is in
  flight,
* T3 (arXiv 2401.16677) — transparent tracking + hardware triggering of
  the producer->wire handoff; here the software analog: the ring DMA is
  issued by the same kernel that consumes the arrived chunk,
* the PR 6 qwZ fused-dequant matmul (``ops/quantized_matmul.py``) —
  extended to consume the (int8, scales) shards MID-GATHER instead of
  post-``bucketed_all_gather_finish``.

Three execution tiers, one contract:

1. **reference twin** (``reference_fused_gather_matmul``) — gather the
   shards with the flat ring (``comm/ring.py``, pure data movement),
   assemble the full fused-layout pair exactly like
   ``bucketed_all_gather_finish`` does, and consume it through
   ``quantized_matmul``. Integer gathers are exact under every
   transport, so this twin is BITWISE-equal to the unfused
   gather-then-matmul pipeline (the PR 15 transport-swap twin pattern)
   — it is the XLA-CPU path and the cross-engine parity oracle.
2. **streamed schedule** (``streamed_fused_gather_matmul``) — the
   interpreter analog of the fused kernel's timeline expressed in
   stock JAX: one ``ppermute`` per ring step, each arrived chunk
   dequantize-dotted into an fp32 accumulator while the next permute
   is dependence-free in flight. Value-equal (not bitwise: the K-dim
   sum is chunked) to the twin; this is what the in-kernel audit tier
   and the calibration rig measure on CPU.
3. **Pallas kernel** (``pallas_fused_gather_matmul``) — the real
   in-kernel form: double-buffered VMEM chunk slots, per-step
   ``make_async_remote_copy`` to the ring neighbor overlapping the
   MXU dots on the resident slot. Shapes the kernel cannot tile fall
   back to the reference twin, recorded in ``ops.fallback_report()``.

Every in-kernel permute step attributes its bytes through the comms
logger with ``op_kind="fused_permute"`` (never ``collective_permute``
— the wire is inside a kernel, but it is never silent), reconciling
byte-exactly with what the unfused transport would log. All fused
regions are wrapped in ``jax.named_scope`` carrying the
``hds_fused`` marker so ``profiling/hlo_audit.py``'s in-kernel tier
can recognize them in HLO text (custom-calls on TPU, scoped
permute+dot pairs on the CPU twins).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import note_fallback, register_op
from .quantized_matmul import quantized_matmul, reference_quantized_matmul

#: comms-logger op names of the fused wires (matched ``fused_*`` rows)
FUSED_GATHER_MM_OP = "fused_gather_matmul"
FUSED_QRS_OP = "zero_fused_qrs"

#: the named-scope marker the HLO audit's in-kernel tier recognizes
FUSED_SCOPE_GATHER_MM = "hds_fused_gather_matmul"
FUSED_SCOPE_RS = "hds_fused_rs_epilogue"


def _assemble(per_dev, local_shape, dim):
    """[n_g, prod(local)] -> concatenate the device axis into ``dim``
    (the exact ``bucketed_all_gather_finish`` assembly, so assembled
    arrays are bit-identical to the unfused unpack)."""
    n_g = per_dev.shape[0]
    parts = jnp.moveaxis(per_dev.reshape((n_g,) + tuple(local_shape)),
                         0, dim)
    new_shape = (tuple(local_shape[:dim]) + (-1,)
                 + tuple(local_shape[dim + 1:]))
    return parts.reshape(new_shape)


def gather_sharded_pair(q_shard, s_shard, dim, *, axis_name,
                        axis_index_groups=None,
                        op_name=FUSED_GATHER_MM_OP):
    """Ring-gather one (int8, scales) shard pair into the full
    fused-layout ``(q [K, N], scale [G, N])`` arrays — bit-identical to
    the bucketed gather's assembly (integer/fp gathers are pure data
    movement). The permute bytes land as ``fused_permute`` rows."""
    from ..comm.ring import ring_all_gather
    wide_q = ring_all_gather(q_shard.reshape(-1), axis_name,
                             axis_index_groups=axis_index_groups,
                             op_name=op_name, op_kind="fused_permute")
    wide_s = ring_all_gather(s_shard.reshape(-1), axis_name,
                             axis_index_groups=axis_index_groups,
                             op_name=op_name, op_kind="fused_permute")
    return (_assemble(wide_q, q_shard.shape, dim),
            _assemble(wide_s, s_shard.shape, dim))


def reference_fused_gather_matmul(x, q_shard, s_shard, group_k=256, *,
                                  axis_name, shard_dim=0,
                                  axis_index_groups=None):
    """The bitwise transport-swap twin: gather-then-matmul through the
    SAME consumption kernel the unfused pipeline uses
    (``quantized_matmul``), so fused-vs-unfused engine parity is exact.
    ``x: [..., K]``; shards tile dim ``shard_dim`` of the full
    ``(q, scale)`` pair."""
    with jax.named_scope(FUSED_SCOPE_GATHER_MM):
        q_full, s_full = gather_sharded_pair(
            q_shard, s_shard, shard_dim, axis_name=axis_name,
            axis_index_groups=axis_index_groups)
        lead = x.shape[:-1]
        out = quantized_matmul(x.reshape(-1, x.shape[-1]), q_full,
                               s_full, group_k=group_k)
        return out.reshape(*lead, q_full.shape[-1])


def streamed_fused_gather_matmul(x, q_shard, s_shard, group_k=256, *,
                                 axis_name, shard_dim=0,
                                 axis_index_groups=None):
    """The fused kernel's SCHEDULE in stock JAX: ring step ``r``
    permutes chunk ``r+1`` toward this device while chunk ``r`` (source
    rank ``(my_rank + r) % m``) is dequantize-dotted into the fp32
    accumulator — each permute dependence-free of the dot it rides
    beside, which is exactly the in-kernel overlap the Pallas form
    realizes with remote DMA. Value-equal to the reference twin
    (chunked K-sum / column placement; not bitwise). This is the form
    the audit tier scores (scoped permute+dot pairs) and the
    calibration rig times on CPU."""
    from ..comm.ring import _group_layout, _log_permute
    with jax.named_scope(FUSED_SCOPE_GATHER_MM):
        m, my_rank, perm_at = _group_layout(axis_name, axis_index_groups)
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        if m == 1:
            out = quantized_matmul(x2, q_shard, s_shard, group_k=group_k)
            return out.reshape(*lead, q_shard.shape[-1])
        neighbor = perm_at(m - 1)       # rank k -> rank (k - 1) % m
        k_sh, n_sh = q_shard.shape
        if shard_dim == 0:
            acc = jnp.zeros((x2.shape[0], n_sh), jnp.float32)
        else:
            acc = jnp.zeros((x2.shape[0], m * n_sh), jnp.float32)
        cur_q, cur_s = q_shard, s_shard
        nbytes = (q_shard.size * q_shard.dtype.itemsize
                  + s_shard.size * s_shard.dtype.itemsize)
        for r in range(m):
            j = (my_rank + r) % m       # source rank of the resident chunk
            if r < m - 1:
                # in-flight lane: chunk r+1 rides the wire while chunk
                # r feeds the MXU — logged as in-kernel fused bytes
                _log_permute(FUSED_GATHER_MM_OP, nbytes, axis_name,
                             op_kind="fused_permute")
                nxt_q = jax.lax.ppermute(cur_q, axis_name, neighbor)
                nxt_s = jax.lax.ppermute(cur_s, axis_name, neighbor)
            if shard_dim == 0:
                xj = jax.lax.dynamic_slice_in_dim(x2, j * k_sh, k_sh,
                                                  axis=1)
                part = reference_quantized_matmul(xj, cur_q, cur_s,
                                                  group_k=group_k)
                acc = acc + part.astype(jnp.float32)
            else:
                part = reference_quantized_matmul(x2, cur_q, cur_s,
                                                  group_k=group_k)
                acc = jax.lax.dynamic_update_slice_in_dim(
                    acc, part.astype(jnp.float32), j * n_sh, axis=1)
            if r < m - 1:
                cur_q, cur_s = nxt_q, nxt_s
        out_cols = n_sh if shard_dim == 0 else m * n_sh
        return acc.astype(x.dtype).reshape(*lead, out_cols)


# ------------------------------------------------------------------ #
# Pallas kernels
# ------------------------------------------------------------------ #

def _fused_fallback(reason, x, q_shard, s_shard, group_k, **kw):
    note_fallback("fused_gather_matmul", reason,
                  f"M={x.shape[0]} K_sh={q_shard.shape[0]} "
                  f"N={q_shard.shape[1]}")
    return reference_fused_gather_matmul(x, q_shard, s_shard, group_k,
                                         **kw)


def _fused_chunk_dot(x_chunk, q_chunk, s_chunk, acc, *, group_k, gpb):
    """One resident chunk's dequant-dot: raw int8 dot per scale group,
    scaling the [M, N] partial product (the ``_qmm_kernel`` schedule,
    applied to a whole ring chunk)."""
    for j in range(gpb):
        s_row = s_chunk[j:j + 1]
        p = jax.lax.dot(
            x_chunk[:, j * group_k:(j + 1) * group_k],
            q_chunk[j * group_k:(j + 1) * group_k].astype(x_chunk.dtype),
            preferred_element_type=jnp.float32)
        acc[:] += p * s_row
    return acc


def _fused_gm_resident_kernel(x_ref, q_ref, s_ref, o_ref, acc, *,
                              m, group_k, gpb, k_sh):
    """Resident-chunk twin of the ring kernel: the grid walks the m
    chunks in source order (all already in HBM — the transport has been
    swapped out, the COMPUTE schedule is identical to the remote form).
    This is the interpret-mode-testable half of the kernel pair."""
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    x_chunk = x_ref[0]                  # [M, k_sh] (block r of the K dim)
    _fused_chunk_dot(x_chunk, q_ref[0], s_ref[0], acc,
                     group_k=group_k, gpb=gpb)

    @pl.when(r == m - 1)
    def _out():
        o_ref[0] = acc[:].astype(o_ref.dtype)


def _fused_gm_ring_kernel(rank_ref, x_ref, qloc_ref, sloc_ref, o_ref,
                          acc, qbuf, sbuf, send_q, recv_q, send_s,
                          recv_s, *, m, group_k, gpb, k_sh, axis_name):
    """The remote form: double-buffered (q, s) chunk slots; ring step r
    starts the RDMA of the resident slot to the left neighbor's next
    slot, dots the resident chunk (source rank ``(my_rank + r) % m`` —
    its K-offset selects the x columns), then waits the arrival. The
    dots never wait on the wire they overlap: step r's compute reads
    only slot ``r % 2`` while the copy fills slot ``(r+1) % 2``."""
    r = pl.program_id(0)
    my_rank = rank_ref[0]
    slot, nxt = r % 2, (r + 1) % 2

    @pl.when(r == 0)
    def _seed():
        qbuf[0] = qloc_ref[:]
        sbuf[0] = sloc_ref[:]
        # one barrier round so no neighbor's RDMA lands before this
        # device has seeded its slot (the pallas guide ring pattern)
        barrier = pltpu.get_barrier_semaphore()
        left = jax.lax.rem(my_rank + m - 1, m)
        right = jax.lax.rem(my_rank + 1, m)
        pltpu.semaphore_signal(
            barrier, device_id=(left,),
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(
            barrier, device_id=(right,),
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, 2)

    left = jax.lax.rem(my_rank + m - 1, m)
    copy_q = pltpu.make_async_remote_copy(
        qbuf.at[slot], qbuf.at[nxt], send_q, recv_q, device_id=(left,),
        device_id_type=pltpu.DeviceIdType.LOGICAL)
    copy_s = pltpu.make_async_remote_copy(
        sbuf.at[slot], sbuf.at[nxt], send_s, recv_s, device_id=(left,),
        device_id_type=pltpu.DeviceIdType.LOGICAL)

    @pl.when(r < m - 1)
    def _start():
        copy_q.start()
        copy_s.start()

    # resident chunk: source rank j -> columns [j*k_sh, (j+1)*k_sh) of x
    j = jax.lax.rem(my_rank + r, m)
    x_chunk = x_ref[:, pl.ds(j * k_sh, k_sh)]
    _fused_chunk_dot(x_chunk, qbuf[slot], sbuf[slot], acc,
                     group_k=group_k, gpb=gpb)

    @pl.when(r < m - 1)
    def _wait():
        copy_q.wait()
        copy_s.wait()

    @pl.when(r == m - 1)
    def _out():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def pallas_fused_gather_matmul_resident(x, q_all, s_all, group_k=256,
                                        interpret=None):
    """Resident-chunk kernel entry: ``q_all [m, k_sh, N]`` /
    ``s_all [m, g_sh, N]`` chunks in SOURCE order, ``x [M, m*k_sh]``.
    Runs the exact compute schedule of the ring kernel with the
    transport swapped for resident HBM chunks — the interpret-mode
    numerics oracle for the remote form."""
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    m, k_sh, N = q_all.shape
    M = x.shape[0]
    gpb = k_sh // group_k
    kern = functools.partial(_fused_gm_resident_kernel, m=m,
                             group_k=group_k, gpb=gpb, k_sh=k_sh)
    return pl.pallas_call(
        kern,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, M, k_sh), lambda r: (0, 0, r)),
            pl.BlockSpec((1, k_sh, N), lambda r: (r, 0, 0)),
            pl.BlockSpec((1, gpb, N), lambda r: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, M, N), lambda r: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((M, N), jnp.float32)],
        interpret=interpret,
    )(x[None], q_all, s_all)[0]


def pallas_fused_gather_matmul(x, q_shard, s_shard, group_k=256, *,
                               axis_name, shard_dim=0,
                               axis_index_groups=None, interpret=None):
    """Remote fused kernel entry (must run inside shard_map on a ring
    whose members each hold one K-dim shard). Tiling guards mirror
    ``pallas_quantized_matmul``: shapes the whole-shard blocking cannot
    cover fall back to the reference twin (bitwise-safe), recorded in
    ``ops.fallback_report()``."""
    kw = dict(axis_name=axis_name, shard_dim=shard_dim,
              axis_index_groups=axis_index_groups)
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    if shard_dim != 0 or axis_index_groups is not None:
        # the ring kernel streams K-dim shards over the full axis; the
        # N-sharded and grouped (hpZ) forms ride the reference twin
        return _fused_fallback("unsupported_layout", x, q_shard,
                               s_shard, group_k, **kw)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    M, K = x2.shape
    k_sh, N = q_shard.shape
    m = K // max(1, k_sh)
    if k_sh % group_k or m * k_sh != K:
        return _fused_fallback("shard_misaligned", x, q_shard, s_shard,
                               group_k, **kw)
    gpb = k_sh // group_k
    if not interpret and (M % 8 or N % 128 or k_sh % 128 or gpb % 8):
        return _fused_fallback("tile_misaligned", x, q_shard, s_shard,
                               group_k, **kw)
    vmem = (2 * 2 * k_sh * N              # q slots (int8, double buf)
            + 2 * 2 * gpb * N * 4         # scale slots
            + M * K * x2.dtype.itemsize   # resident x
            + M * N * 4                   # acc
            + M * N * x2.dtype.itemsize)  # out
    if vmem > 64 * 2**20:
        return _fused_fallback("no_tile_fits_vmem", x, q_shard, s_shard,
                               group_k, **kw)
    from ..comm.ring import _log_permute
    nbytes = (q_shard.size * q_shard.dtype.itemsize
              + s_shard.size * s_shard.dtype.itemsize)
    for _ in range(m - 1):
        _log_permute(FUSED_GATHER_MM_OP, nbytes, axis_name,
                     op_kind="fused_permute")
    my_rank = jax.lax.axis_index(axis_name).astype(jnp.int32)
    kern = functools.partial(_fused_gm_ring_kernel, m=m, group_k=group_k,
                             gpb=gpb, k_sh=k_sh, axis_name=axis_name)
    with jax.named_scope(FUSED_SCOPE_GATHER_MM):
        out = pl.pallas_call(
            kern,
            grid=(m,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((M, N), x2.dtype),
            scratch_shapes=[
                pltpu.VMEM((M, N), jnp.float32),
                pltpu.VMEM((2, k_sh, N), jnp.int8),
                pltpu.VMEM((2, gpb, N), jnp.float32),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
            compiler_params=pltpu.CompilerParams(
                collective_id=13, has_side_effects=True),
            interpret=interpret,
        )(my_rank[None], x2, q_shard, s_shard)
    return out.reshape(*lead, N)


def fused_gather_matmul(x, q_shard, s_shard, group_k=256, *, axis_name,
                        shard_dim=0, axis_index_groups=None):
    """Routed entry: the Pallas ring kernel where the platform runs it,
    the bitwise gather-then-matmul twin everywhere else."""
    from . import get_op
    return get_op("fused_gather_matmul")(
        x, q_shard, s_shard, group_k=group_k, axis_name=axis_name,
        shard_dim=shard_dim, axis_index_groups=axis_index_groups)


@jax.tree_util.register_pytree_node_class
class ShardedQuantizedTensor:
    """A MID-GATHER weight: this device's (int8, scales) shard of the
    fused matmul layout plus the static ring coordinates. The layered
    ZeRO-3 forward hands these to the block under
    ``zero_collective_impl: fused`` — the gather has NOT happened yet;
    it happens inside :func:`fused_gather_matmul` when the consuming
    Dense fires (the in-kernel overlap site). ``dim`` is the sharded
    dim of the full pair; ``groups`` the hpZ ``axis_index_groups``
    (tuple-of-tuples, or None)."""

    def __init__(self, q, scale, group_k, dim, axis_name, groups=None):
        self.q, self.scale = q, scale
        self.group_k = int(group_k)
        self.dim = int(dim)
        self.axis_name = axis_name
        self.groups = None if groups is None else tuple(
            tuple(int(r) for r in g) for g in groups)

    def tree_flatten(self):
        return ((self.q, self.scale),
                (self.group_k, self.dim, self.axis_name, self.groups))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    def matmul(self, x):
        glist = None if self.groups is None else [list(g)
                                                  for g in self.groups]
        return fused_gather_matmul(
            x, self.q, self.scale, group_k=self.group_k,
            axis_name=self.axis_name, shard_dim=self.dim,
            axis_index_groups=glist)

    def gather(self):
        """Assemble the full :class:`MatmulQuantizedTensor` (the
        backward-recompute form: the block VJP needs cotangents against
        the fp weight, so the bwd re-gather dequantizes — same bits as
        the unfused bucketed gather)."""
        from .quantized_matmul import MatmulQuantizedTensor
        glist = None if self.groups is None else [list(g)
                                                  for g in self.groups]
        q_full, s_full = gather_sharded_pair(
            self.q, self.scale, self.dim, axis_name=self.axis_name,
            axis_index_groups=glist)
        return MatmulQuantizedTensor(q_full, s_full, self.group_k)


def fused_collective_dense_interceptor():
    """``flax.linen.intercept_methods`` interceptor for the fused
    transport: an ``nn.Dense`` whose bound kernel is a
    :class:`ShardedQuantizedTensor` runs the mid-gather fused
    gather-matmul; a :class:`MatmulQuantizedTensor` (already gathered —
    e.g. the hpZ secondary refresh path) runs the PR 6 fused-dequant
    kernel. Anything else passes through untouched."""
    import flax.linen as nn

    from .quantized_matmul import MatmulQuantizedTensor

    def interceptor(next_fun, args, kwargs, context):
        mod = context.module
        if context.method_name != "__call__" \
                or not isinstance(mod, nn.Dense) or not args:
            return next_fun(*args, **kwargs)
        kernel = mod.get_variable("params", "kernel")
        if not isinstance(kernel, (ShardedQuantizedTensor,
                                   MatmulQuantizedTensor)):
            return next_fun(*args, **kwargs)
        x = args[0]
        y = kernel.matmul(x)
        if mod.use_bias:
            bias = mod.get_variable("params", "bias")
            y = y + jnp.asarray(bias, y.dtype)
        return y

    return interceptor


# ------------------------------------------------------------------ #
# Fused reduce-scatter epilogue (the qwire lagged-reduce lane)
# ------------------------------------------------------------------ #

def fused_qrs_exchange(payload, scale, *, axis_name,
                       axis_index_groups=None):
    """The fused epilogue's transport: the already-quantized cotangent
    bucket rows ride the flat data-axis ring (the axis the fused
    kernel's ring rides in the 3-D factoring) with direct per-distance
    delivery, arriving in SOURCE order — pure data movement, so the
    dequant-accumulate that follows is the same local graph as the
    native ``all_to_all``: bitwise-equal (the depth-parity contract).
    Bytes land as ``fused_permute`` rows under ``zero_fused_qrs``."""
    from ..comm.ring import decomposed_all_to_all_rows
    with jax.named_scope(FUSED_SCOPE_RS):
        payload_t = decomposed_all_to_all_rows(
            payload, axis_name, axis_index_groups=axis_index_groups,
            op_name=FUSED_QRS_OP, op_kind="fused_permute")
        scale_t = decomposed_all_to_all_rows(
            scale, axis_name, axis_index_groups=axis_index_groups,
            op_name=FUSED_QRS_OP, op_kind="fused_permute")
    return payload_t, scale_t


def reference_fused_quant_ef(wide, residual, *, group_size, num_bits=8,
                             interpret=None):
    """Host twin of :func:`pallas_fused_quant_ef`: the exact
    ``error_feedback_step`` around per-row ``quantize`` the unfused
    qwire compress path runs — same functions, so the fused reduce
    lane on a platform without Pallas is bitwise-identical to the
    unfused lane by construction. Returns ``(q [n, G, group] int8,
    scale [n, G] f32, new_residual [n, W] f32)``."""
    del interpret
    from ..runtime.onebit import error_feedback_step
    from .quantizer import dequantize, quantize
    n, W = wide.shape
    if W % group_size:
        raise ValueError(f"W={W} not a whole number of groups "
                         f"(group_size={group_size})")

    def compress(c):
        def row(r):
            q, s, _, _ = quantize(r, group_size=group_size,
                                  num_bits=num_bits)
            return q, s
        q, s = jax.vmap(row)(c)
        deq = jax.vmap(lambda qi, si: dequantize(qi, si, (W,), W))
        return (q, s), deq(q, s)

    (q, s), _, new_res = error_feedback_step(
        wide.astype(jnp.float32), residual, compress)
    return q, s[..., 0], new_res


def _quant_ef_kernel(c_ref, q_ref, s_ref, r_ref, *, qmax):
    """One pass over a [rows, W] block: per-group absmax quantize the
    COMPENSATED value and emit the residual in the same kernel — the
    quantize / dequantize / subtract trio of
    ``error_feedback_step(compress=quantize)`` fused into one HBM
    read. Group layout: W is a whole number of groups, delivered as
    ``[rows, G_blk, group]``."""
    c = c_ref[:].astype(jnp.float32)          # [rows, G_blk, group]
    scale = jnp.max(jnp.abs(c), axis=-1, keepdims=True) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(c / scale), -qmax - 1, qmax).astype(jnp.int8)
    q_ref[:] = q
    s_ref[:] = scale[..., 0]
    r_ref[:] = c - q.astype(jnp.float32) * scale


def pallas_fused_quant_ef(wide, residual, *, group_size, num_bits=8,
                          interpret=None):
    """Fused quantize + error-feedback epilogue over one ``[n, W]``
    cotangent bucket: returns ``(q [n, G, group] int8,
    scale [n, G] f32, new_residual [n, W] f32)`` with the exact
    arithmetic of ``error_feedback_step`` around per-row
    ``quantize`` — one kernel pass instead of three HBM round trips.
    ``W`` must be a whole number of groups (the bucketed wire
    guarantees its group size divides W or clamps to it)."""
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    n, W = wide.shape
    if W % group_size:
        raise ValueError(f"W={W} not a whole number of groups "
                         f"(group_size={group_size})")
    G = W // group_size
    qmax = 2 ** (num_bits - 1) - 1
    comp = (wide.astype(jnp.float32) + residual).reshape(n, G,
                                                         group_size)
    kern = functools.partial(_quant_ef_kernel, qmax=qmax)
    q, s, r = pl.pallas_call(
        kern,
        out_shape=(
            jax.ShapeDtypeStruct((n, G, group_size), jnp.int8),
            jax.ShapeDtypeStruct((n, G), jnp.float32),
            jax.ShapeDtypeStruct((n, G, group_size), jnp.float32),
        ),
        interpret=interpret,
    )(comp)
    return q, s, r.reshape(n, W)


register_op("fused_gather_matmul", reference_fused_gather_matmul,
            pallas_fused_gather_matmul)
register_op("fused_quant_ef", reference_fused_quant_ef,
            pallas_fused_quant_ef)
