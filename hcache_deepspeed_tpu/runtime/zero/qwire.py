"""Quantized gradient wire for the explicit ZeRO-3 step.

Reference analogs:
* ``deepspeed/runtime/comm/coalesced_collectives.py:81``
  ``all_to_all_quant_reduce`` — the qgZ all-to-all quantized reduction
  topology (there per tensor; here promoted to flat IPG-bucket
  granularity so it slots into the lagged reduce lane of the pipelined
  layered loop),
* ``deepspeed/runtime/comm/compressed.py`` — the error-feedback
  residual machinery (shared through
  ``runtime/onebit.py error_feedback_step``),
* EQuARX / the fused computation-collective-ops line (PAPERS.md) — the
  quantize→all_to_all→dequant-accumulate schedule the compiler overlaps.

The bucketed quantized reduce-scatter: the sharded cotangent leaves of
one reduce bucket are packed into a flat ``[n, W]`` buffer (row *j* is
the slice destined for device *j*'s shard — the same deterministic
in-order layout the fp bucketed path uses), each row is int8
group-quantized (optionally nibble-packed to an int4 wire), the
quantized payload + fp32 group scales ride ONE ``all_to_all`` per
bucket, and every device dequantize-accumulate-means its received rows
locally in fp32. Unlike the fp path, buckets MIX dtypes: the wire
format is int8+fp32 whatever the cotangent dtype, so leaves pack in
flat order and each output segment casts back to its own leaf dtype —
which also makes the host-side residual shape plan independent of
trace-time dtype promotion.

With error feedback on, the per-device quantization error
``compensated - dequant(q)`` is carried as residual state (``[n, W]``
fp32 per bucket, per device, deliberately unsynchronized — exactly the
1-bit worker-error contract) and re-injected next micro-step, so the
wire error is compensated rather than compounded.

Wire volume vs the fp bucketed ``psum_scatter``: int8 payload + fp32
scales ≈ ``1/itemsize + 4/group_size`` of full width (~25% of fp32 at
the default group size; ~13% with ``bits=4``). Every site reports
matched ``zero_qrs_all_to_all`` / ``..._unquantized_equiv`` byte pairs
through the comms logger.
"""

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...comm.comms_logging import get_comms_logger
from ...ops.quantizer import dequantize, quantize
from ...parallel.topology import DATA_AXIS
from ..onebit import error_feedback_step

#: the comms-logger op name of the bucketed quantized reduce-scatter
QRS_OP = "zero_qrs_all_to_all"


def pack_int4(q):
    """Pack int8 values in [-8, 7] two-per-byte along the last axis
    (padding an odd last dim): the bits=4 wire format."""
    if q.shape[-1] % 2:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, 1)])
    lo = (q[..., 0::2] + 8).astype(jnp.uint8)
    hi = (q[..., 1::2] + 8).astype(jnp.uint8)
    return lo | (hi << 4)


def unpack_int4(packed, last):
    """Inverse of :func:`pack_int4`; ``last`` is the unpadded last-dim
    size."""
    lo = (packed & 0xF).astype(jnp.int8) - 8
    hi = ((packed >> 4) & 0xF).astype(jnp.int8) - 8
    q = jnp.stack([lo, hi], axis=-1).reshape(packed.shape[:-1] + (-1,))
    return q[..., :last]


def plan_wire_buckets(sizes, dims, bucket_elements):
    """Deterministic bucket walk shared by the traced reduce and the
    host-side residual planner: greedy in-order flat buckets over the
    data-sharded leaves (``dims[i]`` not None), dtype-blind."""
    from .overlap import plan_reduce_buckets
    masked = [s if d is not None else None for s, d in zip(sizes, dims)]
    return plan_reduce_buckets(masked, bucket_elements)


def plan_wire_residual_widths(sizes, dims, *, bucket_elements,
                              n) -> List[int]:
    """Per-bucket residual widths ``W`` (local row length) in execution
    order — the host-side shape plan the engine uses to allocate the
    error-feedback state (``[n, W]`` fp32 per bucket per device)."""
    return [bucket.elements // n
            for bucket in plan_wire_buckets(sizes, dims, bucket_elements)]


def _quantized_wide_reduce(wide, residual, *, group_size, bits,
                           equiv_bytes):
    """One bucket: ``wide`` is the full ``[n, W]`` cotangent buffer
    (row j -> device j). Returns ``(mean [W] fp32,
    new_residual [n, W] fp32)``. ``residual`` None means error
    feedback off (the quantization error is dropped, not carried)."""
    n, W = wide.shape
    gsz = max(1, min(group_size, W))
    num_bits = 4 if bits == 4 else 8

    def quant_rows(c):
        def one(row):
            return quantize(row, group_size=gsz, num_bits=num_bits)[:2]
        return jax.vmap(one)(c)

    def deq_rows(q, s):
        return jax.vmap(
            lambda qi, si: dequantize(qi, si, (W,), W))(q, s)

    def compress(c):
        q, s = quant_rows(c)
        return (q, s), deq_rows(q, s)

    if residual is not None:
        (q, scale), _, new_residual = error_feedback_step(
            wide, residual, compress)
    else:
        q, scale = quant_rows(wide)
        new_residual = None
    payload = pack_int4(q) if bits == 4 else q
    get_comms_logger().log_quantized(
        QRS_OP,
        payload.size * payload.dtype.itemsize + 4 * scale.size,
        equiv_bytes, (DATA_AXIS,))
    payload_t = jax.lax.all_to_all(payload, DATA_AXIS, 0, 0)
    scale_t = jax.lax.all_to_all(scale, DATA_AXIS, 0, 0)
    q_t = unpack_int4(payload_t, q.shape[-1]) if bits == 4 else payload_t
    red = jnp.mean(deq_rows(q_t, scale_t), axis=0)      # [W] fp32
    return red, new_residual


def quantized_bucket_reduce_scatter_mean(flat, dims, *, bucket_elements,
                                         group_size, bits=8,
                                         residuals: Optional[list] = None,
                                         error_feedback=True):
    """Bucketed QUANTIZED reduce-mean of the sharded leaves of ``flat``
    (full cotangents) onto their data-axis shards — the qgZ all-to-all
    topology at IPG-bucket granularity, one collective pair (payload +
    scales) per flat bucket instead of one per leaf.

    Must run inside the shard_map region. Leaves with ``dim`` None pass
    through untouched (``reduce_grads`` finishes them, exactly like the
    fp path). ``residuals`` is the error-feedback state: a flat list of
    ``[n, W]`` fp32 arrays in :func:`plan_wire_residual_widths` order
    (``None`` seeds zeros; ignored when ``error_feedback`` is False).
    Returns ``(out_leaves, new_residuals)`` — ``new_residuals`` is
    ``[]`` when error feedback is off.

    The flat layout is deterministic (in-order packing), so the
    prefetched and sequential schedules quantize identical buffers and
    stay bitwise-equal TO EACH OTHER — quantization changes the math
    vs the fp wire, never between the two schedules (the tier-1 parity
    contract).
    """
    n = jax.lax.axis_size(DATA_AXIS)
    out = list(flat)
    new_res = []
    sizes = [int(g.size) for g in flat]
    for r_i, bucket in enumerate(plan_wire_buckets(sizes, dims,
                                                   bucket_elements)):
        parts, metas = [], []
        equiv_bytes = 0
        for idx in bucket.leaf_indices:
            g, d = flat[idx], dims[idx]
            gm = jnp.moveaxis(g, d, 0)
            lead = gm.shape[0] // n
            parts.append(gm.reshape(n, -1).astype(jnp.float32))
            metas.append((idx, (lead,) + gm.shape[1:]))
            equiv_bytes += g.size * g.dtype.itemsize
        wide = parts[0] if len(parts) == 1 \
            else jnp.concatenate(parts, axis=1)
        res = None
        if error_feedback:
            res = residuals[r_i] if residuals is not None \
                else jnp.zeros(wide.shape, jnp.float32)
        red, nr = _quantized_wide_reduce(
            wide, res, group_size=group_size, bits=bits,
            equiv_bytes=equiv_bytes)
        if error_feedback:
            new_res.append(nr)
        off = 0
        for idx, shard_shape in metas:
            k = int(np.prod(shard_shape))
            seg = red[off:off + k].reshape(shard_shape)
            out[idx] = jnp.moveaxis(seg, 0, dims[idx]).astype(
                flat[idx].dtype)
            off += k
    return out, new_res
