"""FP8 / FP6 group-wise quantization.

Reference analog: ``csrc/fp_quantizer/fp_quantize.cu`` (+
``fp_quantize.cpp`` bindings) — group-wise quantization of bf16/fp16
tensors into FP8 (E4M3), FP6 (E3M2) and FP12 formats with a per-group
scale, plus *selective* dequantization of a row range (used by ZeRO++
weight gathers and weight-only-quantized inference GEMMs).

TPU re-design: FP8 is a native jnp dtype (``float8_e4m3fn`` /
``float8_e5m2``) — quantize = per-group scale + cast, one fused XLA/
Pallas pass, and the wire/storage format really is 1 byte. FP6 (E3M2)
has no hardware type: values are rounded onto the E3M2 grid emulated in
arithmetic and stored one-per-uint8 code (sign·1 | exp·3 | man·2). The
reference bit-packs 4 FP6 values into 3 bytes; we keep byte-aligned
codes (TPU vector memory has no cheap 6-bit addressing) and note the
4/3x density delta here.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import note_fallback, register_op
from .partitioning import per_shard
from .quantizer import _pack_groups, reference_dequantize

_FP8_MAX = {"e4m3": 448.0, "e5m2": 57344.0}
_FP8_DTYPE = {"e4m3": jnp.float8_e4m3fn, "e5m2": jnp.float8_e5m2}

# E3M2: exponent bias 3, exponents -2..4 (0b000 subnormal), 2 mantissa
# bits; max normal = 2^4 * 1.75 = 28
_FP6_MAX = 28.0
_FP6_MIN_EXP = -2


# ------------------------------------------------------------------ #
# FP8
# ------------------------------------------------------------------ #
def reference_quantize_fp8(x, group_size=2048, fmt="e4m3"):
    """→ (q fp8[G, group], scale fp32[G, 1], orig shape, orig count)."""
    groups, n = _pack_groups(x.astype(jnp.float32), group_size)
    scale = jnp.max(jnp.abs(groups), axis=-1, keepdims=True) / _FP8_MAX[fmt]
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (groups / scale).astype(_FP8_DTYPE[fmt])
    return q, scale.astype(jnp.float32), x.shape, n


def _fp8_kernel(x_ref, q_ref, s_ref, *, fmt):
    x = x_ref[:].astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / _FP8_MAX[fmt]
    scale = jnp.where(scale == 0, 1.0, scale)
    q_ref[:] = (x / scale).astype(q_ref.dtype)
    s_ref[:] = scale


def pallas_quantize_fp8(x, group_size=2048, fmt="e4m3", interpret=None,
                        block_groups=8):
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    groups, n = _pack_groups(x.astype(jnp.float32), group_size)
    G = groups.shape[0]
    block_groups = min(block_groups, G)
    if G % block_groups:
        note_fallback("quantize_fp8", "groups_not_block_multiple",
                      f"groups={G} block_groups={block_groups}")
        return reference_quantize_fp8(x, group_size, fmt)
    q, scale = per_shard(pl.pallas_call(
        functools.partial(_fp8_kernel, fmt=fmt),
        grid=(G // block_groups,),
        in_specs=[pl.BlockSpec((block_groups, group_size),
                               lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_groups, group_size), lambda i: (i, 0)),
            pl.BlockSpec((block_groups, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, group_size), _FP8_DTYPE[fmt]),
            jax.ShapeDtypeStruct((G, 1), jnp.float32),
        ],
        interpret=interpret,
    ), (groups,))
    return q, scale, x.shape, n


def dequantize_fp8(q, scale, orig_shape, orig_n):
    return reference_dequantize(q, scale, orig_shape, orig_n)


# ------------------------------------------------------------------ #
# FP6 (E3M2, emulated grid, byte-aligned codes)
# ------------------------------------------------------------------ #
def _fp6_encode(x):
    """x: scaled fp32 in [-28, 28] → uint8 code s|eee|mm."""
    sign = (x < 0).astype(jnp.uint32)
    mag = jnp.clip(jnp.abs(x), 0.0, _FP6_MAX)
    # exponent of the leading bit, clamped to the E3M2 normal range
    e = jnp.floor(jnp.log2(jnp.maximum(mag, 2.0 ** _FP6_MIN_EXP)))
    e = jnp.clip(e, _FP6_MIN_EXP, 4)
    # mantissa in [1, 2) quantized to 2 bits (round to nearest)
    man = jnp.round((mag / 2.0 ** e - 1.0) * 4.0)
    # subnormals: below 2^min_exp encode magnitude directly
    sub = mag < 2.0 ** _FP6_MIN_EXP
    man = jnp.where(sub, jnp.round(mag / 2.0 ** _FP6_MIN_EXP * 4.0), man)
    e_bits = jnp.where(sub, 0, (e - _FP6_MIN_EXP + 1)).astype(jnp.uint32)
    # mantissa rounding to 4 overflows into the next exponent
    carry = man >= 4
    man = jnp.where(carry, 0, man).astype(jnp.uint32)
    e_bits = jnp.where(carry, jnp.minimum(e_bits + 1, 7), e_bits)
    return (sign << 5 | e_bits << 2 | man).astype(jnp.uint8)


def _fp6_decode(code):
    code = code.astype(jnp.uint32)
    sign = jnp.where(code >> 5 & 1, -1.0, 1.0)
    e_bits = (code >> 2) & 7
    man = (code & 3).astype(jnp.float32)
    sub = e_bits == 0
    mag = jnp.where(
        sub,
        man / 4.0 * 2.0 ** _FP6_MIN_EXP,
        (1.0 + man / 4.0) * 2.0 ** (e_bits.astype(jnp.float32) - 1 +
                                    _FP6_MIN_EXP))
    return sign * mag


def reference_quantize_fp6(x, group_size=2048):
    """→ (codes uint8[G, group], scale fp32[G, 1], shape, count)."""
    groups, n = _pack_groups(x.astype(jnp.float32), group_size)
    scale = jnp.max(jnp.abs(groups), axis=-1, keepdims=True) / _FP6_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return _fp6_encode(groups / scale), scale.astype(jnp.float32), \
        x.shape, n


def dequantize_fp6(codes, scale, orig_shape, orig_n):
    out = (_fp6_decode(codes) * scale).reshape(-1)[:orig_n]
    return out.reshape(orig_shape)


# ------------------------------------------------------------------ #
# Selective dequantization (reference: fp_quantize.cpp
# selective_dequantize — dequantize only a row range of the tensor)
# ------------------------------------------------------------------ #
def selective_dequantize(q, scale, orig_shape, orig_n, rows):
    """Dequantize rows ``rows`` (slice or index array on dim 0) of the
    original tensor without touching the rest. Requires the row stride
    be a multiple of the group size (the reference imposes the same
    alignment). The format is inferred from ``q.dtype`` (uint8 = FP6
    codes, float8 = FP8)."""
    row_elems = int(np.prod(orig_shape[1:]))
    group_size = q.shape[-1]
    if row_elems % group_size:
        raise ValueError(
            f"row size {row_elems} not aligned to group {group_size}")
    gpr = row_elems // group_size  # groups per row
    rows = np.arange(orig_shape[0])[rows] if isinstance(rows, slice) \
        else np.asarray(rows)
    gidx = (rows[:, None] * gpr + np.arange(gpr)[None, :]).reshape(-1)
    qs = q[gidx]
    ss = scale[gidx]
    dec = _fp6_decode(qs) if qs.dtype == jnp.uint8 \
        else qs.astype(jnp.float32)
    return (dec * ss).reshape((len(rows),) + tuple(orig_shape[1:]))


def quantize_fp8(x, group_size=2048, fmt="e4m3"):
    from . import get_op
    return get_op("quantize_fp8")(x, group_size=group_size, fmt=fmt)


register_op("quantize_fp8", reference_quantize_fp8, pallas_quantize_fp8)
register_op("quantize_fp6", reference_quantize_fp6)
