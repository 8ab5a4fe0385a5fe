"""Fused int8-weight matmul: ``x @ dequant(q, scale)`` in one kernel.

Reference analog: the weight-only-quantized linear path of the v1
inference kernels (``deepspeed/inference/quantization`` +
``csrc/quantization`` dequant kernels fused into the GEMM consumers).

TPU form: the weight stays int8 in HBM; each grid step streams one
``[block_k, block_n]`` int8 tile into VMEM, dequantizes it there
(int8 -> compute dtype, times its per-group scales) and feeds the MXU —
HBM traffic for weights is half of bf16, and no full-precision copy of
the weight ever exists in HBM.

Scale layout: per-(k-group, n) — ``scale[g, n]`` covers rows
``g*group_k : (g+1)*group_k`` of column ``n`` (the groupwise layout
``QuantizedTensor`` uses is flat; ``quantize_for_matmul`` below produces
this 2D layout instead, which is what a matmul kernel can actually use).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import note_fallback, register_op
from .partitioning import per_shard


def quantize_for_matmul(w, group_k=256, num_bits=8):
    """w: [K, N] (or stacked [L, K, N]) -> (q int8 same shape, scale f32
    [(L,) G, N]). Groups run down the contraction dim so a [block_k, N]
    tile needs only its own scale rows."""
    *lead, K, N = w.shape
    if K % group_k:
        raise ValueError(f"K={K} not divisible by group_k={group_k}")
    qmax = 2 ** (num_bits - 1) - 1
    g = w.astype(jnp.float32).reshape(*lead, K // group_k, group_k, N)
    scale = jnp.max(jnp.abs(g), axis=-2) / qmax         # [*lead, G, N]
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(g / scale[..., None, :]), -qmax - 1,
                 qmax).astype(jnp.int8).reshape(*lead, K, N)
    return q, scale.astype(jnp.float32)


@jax.tree_util.register_pytree_node_class
class MatmulQuantizedTensor:
    """Int8 weight in the fused-kernel layout: q ``[(L,) K, N]`` with
    per-(k-group, n) scales ``[(L,) G, N]``. Slicing the leading dim
    (lax.scan xs) yields a valid per-layer tensor, like
    ``QuantizedTensor``'s batched form. Consumed by ``quantized_matmul``
    — NOT dequantized by ``dequantize_tree`` (that is the point)."""

    def __init__(self, q, scale, group_k):
        self.q, self.scale = q, scale
        self.group_k = int(group_k)

    def tree_flatten(self):
        return (self.q, self.scale), (self.group_k,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @classmethod
    def make(cls, w, group_k=256, num_bits=8):
        q, scale = quantize_for_matmul(w, group_k=group_k,
                                       num_bits=num_bits)
        return cls(q, scale, group_k)

    @classmethod
    def make_batched(cls, w, group_k=256, num_bits=8):
        """Quantize a stacked ``[L, K, N]`` weight LAYER BY LAYER: the
        fp32 group view inside ``quantize_for_matmul`` is transient per
        layer instead of for the whole stack — a 7B stacked MLP leaf's
        one-shot view needs >10 GB of HBM (observed OOM on a 16 GB
        v5e). Host (numpy) inputs additionally stream one ~200 MB layer
        at a time instead of landing on device whole (mirrors
        ``QuantizedTensor.make_batched``)."""
        qs, scales = [], []
        for layer in range(w.shape[0]):
            # one explicit H2D per layer: quantize_for_matmul on a host
            # slice would transfer its fp32 view twice (max, then round)
            q, s = quantize_for_matmul(jnp.asarray(w[layer]),
                                       group_k=group_k,
                                       num_bits=num_bits)
            qs.append(q)
            scales.append(s)
        return cls(jnp.stack(qs), jnp.stack(scales), group_k)

    def matmul(self, x):
        """x: [..., K] -> [..., N] through the fused kernel (per-layer
        2D q only — slice the stack first)."""
        if self.q.ndim != 2:
            raise ValueError("slice the layer stack before matmul")
        lead = x.shape[:-1]
        out = quantized_matmul(x.reshape(-1, x.shape[-1]), self.q,
                               self.scale, group_k=self.group_k)
        return out.reshape(*lead, self.q.shape[-1])

    def dequantize(self, dtype=jnp.float32):
        """Materialize the fp weight ``[(L,) K, N]`` — the comparison
        oracle for the fused path and the backward-recompute form of
        the ZeRO++ fused gather (the VJP needs cotangents against the
        fp weight, not against (q, scale))."""
        *lead, K, N = self.q.shape
        g = self.q.astype(dtype).reshape(
            *lead, K // self.group_k, self.group_k, N)
        w = g * self.scale[..., :, None, :].astype(dtype)
        return w.reshape(*lead, K, N)


def reference_quantized_matmul(x, q, scale, group_k=256):
    """Numerics oracle: dequantize fully, then matmul."""
    K, N = q.shape
    # dequantize straight in the compute dtype: when XLA materializes
    # the dequantized weight (it does at 7B scale) an fp32 intermediate
    # would double the HBM bill; int8 * bf16-scale keeps full int8
    # fidelity (|q| <= 127 is exact in bf16's 8-bit mantissa)
    w = q.astype(x.dtype).reshape(K // group_k, group_k, N) \
        * scale[:, None, :].astype(x.dtype)
    return x @ w.reshape(K, N)


def _divisors_128(N, cap):
    """128-multiple divisors of N, descending, <= cap."""
    out = []
    d = min(N, cap) // 128 * 128
    while d >= 128:
        if N % d == 0:
            out.append(d)
        d -= 128
    return out


def _choose_tiles(M, K, N, group_k, block_m, x_bytes=2):
    """(block_n, groups_per_block) minimizing grid steps under a ~10 MB
    VMEM budget. Grid-step overhead (~1-2 us Mosaic dispatch per step)
    is THE cost driver in both kernel regimes on a v5e:

    - matvec (decode, M<=32): HBM-bound; tiles must be multi-MB or the
      per-step overhead halves effective bandwidth (measured 478 GB/s
      at 32 one-group steps vs 681 GB/s for XLA's dense bf16 matvec).
    - compute (prefill/training M>32): a [256, 256, group_k] blocking
      runs the 7B qkv matmul in 1536 steps of ~43 ns MXU work each —
      pure dispatch overhead (prefill measured 15x off the weight-
      streaming ceiling).

    groups_per_block (gpb) must divide G so every k-block covers whole
    scale groups; when gpb is a multiple of 8 the scale BlockSpec can
    deliver exactly the block's rows ([gpb, bn] — sublane dim >= 8
    lowers fine) and the kernel slices rows STATICALLY; smaller gpb
    falls back to the whole-G tile + mask-sum row select.

    ``x_bytes`` is the activation itemsize: the x and out tiles scale
    with it, so fp32 inputs (4 B) get smaller-but-fitting tiles instead
    of a blocking whose true VMEM footprint is 2x the estimate (and
    fp8 inputs get the larger tiles they can afford)."""
    G = K // group_k
    budget = 10 * 2**20
    best = None
    for gpb in (8, 4, 2, 1):
        if G % gpb:
            continue
        bk = gpb * group_k
        for bn in _divisors_128(N, 8 * 2**20 // (2 * bk) // 128 * 128):
            scale_rows = gpb if gpb % 8 == 0 else G
            vmem = (2 * bk * bn                  # q tile int8, x2 buf
                    + 2 * block_m * bk * x_bytes  # x tile, x2
                    + 2 * scale_rows * bn * 4
                    + block_m * bn * 4           # acc scratch
                    + 2 * block_m * bn * x_bytes)  # out
            if vmem > budget:
                continue
            steps = (M // block_m) * (N // bn) * (K // bk)
            cand = (steps, -bk * bn, bn, gpb)
            if best is None or cand < best:
                best = cand
            break   # divisors descend: first fitting bn is the best bn
    if best is None:
        return None
    return best[2], best[3]


def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, acc, *, group_k, gpb,
                sliced_scale):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    x = x_ref[0]                        # [block_m, gpb*group_k]
    qt = q_ref[0]                       # [gpb*group_k, block_n] int8
    s = s_ref[0]                        # [gpb | G, block_n] f32
    if not sliced_scale:
        # whole-G scale tile: the block's rows are selected by mask-sum
        # (dynamic_slice does not lower in Mosaic TC kernels, and a
        # sub-8 sublane scale tile is unlowerable)
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (s.shape[0], s.shape[1]), 0)
    # one raw int8 dot per scale group, scaling the OUTPUT row-block:
    # scales vary per (group, n), so they cannot fold into x, and
    # scaling the [group_k, bn] weight slice would cost group_k/block_m
    # times more VPU work than scaling the [block_m, bn] partial product
    for j in range(gpb):
        if sliced_scale:
            s_row = s[j:j + 1]                       # static row
        else:
            s_row = jnp.sum(
                jnp.where(rows == ki * gpb + j, s, 0.0), axis=0,
                keepdims=True)
        p = jax.lax.dot(x[:, j * group_k:(j + 1) * group_k],
                        qt[j * group_k:(j + 1) * group_k].astype(x.dtype),
                        preferred_element_type=jnp.float32)
        acc[:] += p * s_row

    @pl.when(ki == nk - 1)
    def _out():
        o_ref[0] = acc[:].astype(o_ref.dtype)


def _reference_fallback(reason, x, q, scale, group_k, block=None):
    note_fallback("quantized_matmul", reason,
                  f"M={x.shape[0]} K={x.shape[1]} N={q.shape[1]} "
                  f"block={block}")
    return reference_quantized_matmul(x, q, scale, group_k=group_k)


def pallas_quantized_matmul(x, q, scale, group_k=256, block_m=None,
                            block_n=None, block_k=None, interpret=None):
    """x: [M, K] (bf16/f32); q: [K, N] int8; scale: [K//group_k, N].

    block_* default to the grid-overhead-minimizing tiles from
    ``_choose_tiles`` (sized for x's actual itemsize); explicit values
    override (tests exercise fixed blockings). ``block_k`` must be a
    whole number of scale groups. Shapes the tiles cannot cover fall
    back to the reference path — recorded in
    ``ops.fallback_report()``."""
    M, K = x.shape
    K2, N = q.shape
    assert K == K2
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    if block_m is None:
        block_m = M if M <= 32 else next(
            (bm for bm in (256, 128, 64, 32, 16, 8) if M % bm == 0), M)
    block_m = min(block_m, M)
    if block_n is None and block_k is None and M % block_m == 0:
        chosen = _choose_tiles(M, K, N, group_k, block_m,
                               x_bytes=x.dtype.itemsize)
        if chosen is None:
            return _reference_fallback("no_tile_fits_vmem", x, q,
                                       scale, group_k)
        block_n, gpb = chosen
        block_k = gpb * group_k
    else:
        block_n = min(block_n or 256, N)
        block_k = block_k or group_k
    if (M % block_m or N % block_n or K % block_k
            or block_k % group_k
            or (not interpret and (block_m % 8 or block_n % 128
                                   or block_k % 128))):
        # block_k is x's lane dim and q's sublane dim — it needs 128
        # alignment on hardware just like the others (a 96-wide tile
        # crashes Mosaic; see the same guard in flash_attention.py)
        return _reference_fallback(
            "tile_misaligned", x, q, scale, group_k,
            block=(block_m, block_n, block_k))
    grid = (M // block_m, N // block_n, K // block_k)
    G = K // group_k
    gpb = block_k // group_k
    # scale tile: exactly the block's rows when the sublane dim (gpb)
    # lowers (>= 8); otherwise the whole group dim with in-kernel
    # mask-sum row selection
    sliced_scale = gpb % 8 == 0
    if sliced_scale:
        s_spec = pl.BlockSpec((1, gpb, block_n),
                              lambda mi, ni, ki: (0, ki, ni))
    else:
        s_spec = pl.BlockSpec((1, G, block_n),
                              lambda mi, ni, ki: (0, 0, ni))
    kern = functools.partial(_qmm_kernel, group_k=group_k, gpb=gpb,
                             sliced_scale=sliced_scale)
    return per_shard(pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda mi, ni, ki: (0, mi, ki)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda mi, ni, ki: (0, ki, ni)),
            s_spec,
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda mi, ni, ki: (0, mi, ni)),
        out_shape=jax.ShapeDtypeStruct((1, M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    ), (x[None], q[None], scale[None]))[0]


def quantized_matmul(x, q, scale, group_k=256):
    from . import get_op
    return get_op("quantized_matmul")(x, q, scale, group_k=group_k)


def fused_dense_interceptor():
    """``flax.linen.intercept_methods`` interceptor: an ``nn.Dense``
    whose bound kernel is a :class:`MatmulQuantizedTensor` computes
    ``x @ dequant(q, scale) + b`` through the fused kernel instead of
    tripping over a non-array param — the consumption half of the
    ZeRO++ fused qwZ gather (``runtime/zero/zeropp.py``): the gathered
    int8 payload feeds the MXU directly and the fp weight never
    materializes in HBM. Output dtype follows ``x`` (the kernel's
    contract); anything that is not a Dense with a quantized kernel
    passes through untouched."""
    import flax.linen as nn

    def interceptor(next_fun, args, kwargs, context):
        mod = context.module
        if context.method_name != "__call__" \
                or not isinstance(mod, nn.Dense) or not args:
            return next_fun(*args, **kwargs)
        kernel = mod.get_variable("params", "kernel")
        if not isinstance(kernel, MatmulQuantizedTensor):
            return next_fun(*args, **kwargs)
        x = args[0]
        y = kernel.matmul(x)
        if mod.use_bias:
            bias = mod.get_variable("params", "bias")
            y = y + jnp.asarray(bias, y.dtype)
        return y

    return interceptor


register_op("quantized_matmul", reference_quantized_matmul,
            pallas_quantized_matmul)
