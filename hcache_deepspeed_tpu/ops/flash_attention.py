"""Flash attention (Pallas TPU) with custom VJP.

Reference analog: the CUDA attention kernel set —
``csrc/transformer/inference/csrc/softmax.cu`` + attention glue and the
inference-v2 ``blocked_flash`` kernels
(``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash``). On TPU the
idiomatic form is an online-softmax blocked kernel that keeps the running
(max, sum, acc) in VMEM scratch while the grid streams K/V blocks from HBM —
MXU does the two matmuls, the VPU the rescaling.

Layout: [batch, seq, heads, head_dim] in, same out. fp32 accumulation
regardless of input dtype. Causal masking built in; blocks strictly above
the diagonal skip their FLOPs (predicated), so causal costs ~half of full.

The backward pass is the standard two-kernel flash backward (dq via
k-streaming, dk/dv via q-streaming) using the saved logsumexp and
delta = rowsum(dout * out).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_name, note_fallback, register_op
from .partitioning import BATCH, HEADS, per_shard

_NEG_INF = -1e30


def _default_scale(head_dim):
    return 1.0 / (head_dim ** 0.5)


def _fit_block(block, seq_len):
    """Largest block <= requested that divides seq_len (stepping down
    through 128-multiples keeps e.g. T=1280 on the kernel at block 256
    instead of silently falling back to the O(T^2)-memory reference
    path)."""
    block = min(block, seq_len)
    while block >= 128 and seq_len % block:
        block -= 128
    return block


# ------------------------------------------------------------------ #
# Reference implementation (always available; CPU/debug path)
# ------------------------------------------------------------------ #
def reference_attention(q, k, v, causal=True, scale=None, **_tiling):
    """[B, T, H, D] in/out, plain jnp (XLA-fused) attention. GQA: k/v may
    carry fewer heads (KV divides H) — they broadcast to the query
    heads. Kernel-tiling kwargs (block_q/block_k) are accepted and
    ignored — there are no blocks here, and the dispatcher forwards them
    unconditionally."""
    B, T, H, D = q.shape
    if k.shape[2] != H:   # GQA/MQA: expand kv heads
        rep = H // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale or _default_scale(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ------------------------------------------------------------------ #
# Pallas forward
# ------------------------------------------------------------------ #
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                scale, causal, block_q, block_k):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        # matmuls stay in the input dtype (bf16 hits the MXU at full
        # rate; an fp32 upcast here would run at ~1/8 peak on v5e) with
        # fp32 accumulation via preferred_element_type
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[:, :1] = corr * l_s[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_s[:, :1] = m_new
        acc[:] = acc[:] * corr + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0, 0],
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _out():
        l = l_s[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[:, :1] + jnp.log(l)


def _fwd_pallas(q, k, v, scale, causal, block_q, block_k, interpret):
    B, T, H, D = q.shape
    KV = k.shape[2]
    rep = H // KV   # GQA: q head h reads kv head h // rep — no repeat,
    #                 the index map shares each kv block across the group
    qt = q.transpose(0, 2, 1, 3)  # [B,H,T,D]
    kt = k.transpose(0, 2, 1, 3)  # [B,KV,T,D]
    vt = v.transpose(0, 2, 1, 3)
    nq, nk = T // block_q, T // block_k
    grid = (B, H, nq, nk)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k)
    out, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // rep, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        **kernel_name("flash_attention_fwd"),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


# ------------------------------------------------------------------ #
# Pallas backward
# ------------------------------------------------------------------ #
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_acc[:] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _out():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k):
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)
        pc = p.astype(do.dtype)
        dv_acc[:] += jax.lax.dot_general(
            pc, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _out():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_pallas(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    B, T, H, D = q.shape
    KV = k.shape[2]
    if KV != H:
        # GQA backward: run the dense-head kernels on expanded k/v, then
        # sum each group's dk/dv back onto its shared kv head (the fwd
        # saves the COMPACT k/v, so residual memory stays KV-sized)
        rep = H // KV
        dq, dk, dv = _bwd_pallas(
            scale, causal, block_q, block_k, interpret,
            (q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
             out, lse), g)
        dk = dk.reshape(B, T, KV, rep, D).sum(axis=3)
        dv = dv.reshape(B, T, KV, rep, D).sum(axis=3)
        return dq, dk, dv
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    dot = g.transpose(0, 2, 1, 3)
    ot = out.transpose(0, 2, 1, 3)
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B,H,T,1]
    nq, nk = T // block_q, T // block_k

    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0))
    r_spec = pl.BlockSpec((1, 1, block_q, 1),
                          lambda b, h, qi, ki: (b, h, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        **kernel_name("flash_attention_bwd_dq"),
    )(qt, kt, vt, dot, lse, delta)

    # dkv grid: (B, H, nk, nq) — note swapped roles of the index maps
    q_spec2 = pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, 0))
    k_spec2 = pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0))
    r_spec2 = pl.BlockSpec((1, 1, block_q, 1),
                           lambda b, h, ki, qi: (b, h, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(B, H, nk, nq),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret,
        **kernel_name("flash_attention_bwd_dkv"),
    )(qt, kt, vt, dot, lse, delta)

    to_bthd = lambda x: x.transpose(0, 2, 1, 3)
    return to_bthd(dq), to_bthd(dk), to_bthd(dv)


# ------------------------------------------------------------------ #
# custom_vjp wrapper
# ------------------------------------------------------------------ #
# batch elements and heads are independent: q/k/v/out [B, T, H, D] and
# lse [B, H, T, 1] keep their batch split and (query and KV heads
# together, GQA groups staying whole) their head split; T and D are
# whole in every shard
_BTHD = (BATCH, None, HEADS, None)
_BHT1 = (BATCH, HEADS, None, None)


def _fwd_placed(q, k, v, scale, causal, block_q, block_k, interpret):
    return per_shard(
        lambda q, k, v: _fwd_pallas(q, k, v, scale, causal, block_q,
                                    block_k, interpret),
        (q, k, v), in_roles=(_BTHD,) * 3, out_roles=(_BTHD, _BHT1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _fwd_placed(q, k, v, scale, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out_bhtd, lse = _fwd_placed(q, k, v, scale, causal, block_q, block_k,
                                interpret)
    return out_bhtd, (q, k, v, out_bhtd, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    return per_shard(
        lambda *a: _bwd_pallas(scale, causal, block_q, block_k, interpret,
                               a[:5], a[5]),
        (*res, g), in_roles=(_BTHD,) * 4 + (_BHT1, _BTHD),
        out_roles=(_BTHD,) * 3)


_flash.defvjp(_flash_fwd, _flash_bwd)


def pallas_attention(q, k, v, causal=True, scale=None, block_q=512,
                     block_k=512, interpret=None):
    B, T, H, D = q.shape
    if H % k.shape[2]:
        raise ValueError(
            f"q heads {H} not divisible by kv heads {k.shape[2]}")
    scale = scale or _default_scale(D)
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    block_q, block_k = _fit_block(block_q, T), _fit_block(block_k, T)
    reason = None
    if block_q < 128 or block_k < 128 or T % block_q or T % block_k:
        reason = "seq_not_block_multiple"
    elif not interpret and (block_q % 8 or block_k % 128):
        # Mosaic tiling: the s=[block_q, block_k] tile needs a (8,128)-
        # aligned layout on real hardware
        reason = "tile_misaligned"
    elif not interpret and D % 128 and D != 64:
        # lane (last-dim) tiling: D must be 128-aligned (64 is the one
        # sublane-packable exception Mosaic handles well); e.g. D=96
        # crashes the compiler
        reason = "head_dim_unsupported"
    if reason:
        note_fallback("flash_attention", reason,
                      f"T={T} D={D} block_q={block_q} block_k={block_k}")
        return reference_attention(q, k, v, causal=causal, scale=scale)
    return _flash(q, k, v, scale, causal, block_q, block_k, interpret)


def attention(q, k, v, causal=True, scale=None, block_q=None,
              block_k=None):
    """Dispatching entry point: Pallas on TPU, reference elsewhere.
    ``block_q``/``block_k`` tune the kernel tiling (ignored on the
    reference path, which has no blocks)."""
    from . import get_op
    kw = {}
    if block_q:
        kw["block_q"] = block_q
    if block_k:
        kw["block_k"] = block_k
    return get_op("flash_attention")(q, k, v, causal=causal, scale=scale,
                                     **kw)


# both paths accept compact GQA k/v (KV heads < q heads) natively —
# wrappers (Ulysses) consult this to skip the dense-head expansion
reference_attention.supports_gqa = True
pallas_attention.supports_gqa = True
attention.supports_gqa = True

register_op("flash_attention", reference_attention, pallas_attention)
