"""Ragged paged attention (Pallas TPU) — the inference engine's hot kernel.

Reference analog: the inference-v2 ragged kernel set —
``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash/`` (flash
attention over a blocked KV cache driven by a block table) and the atom
builder that windows it. On TPU the idiomatic form is a grid over
(sequence, kv-head, cache-block) with the block table in scalar-prefetch
memory so each grid step's ``index_map`` DMAs exactly the cache block the
table names — no ``[B, S_max]`` gather materialization, no GQA
``jnp.repeat``; online softmax accumulates across a sequence's valid
blocks only.

Ragged batching contract (matches ``inference/model.py``):

* ``q``        [B, T, Hq, D] — T=1 rows for a ragged decode batch, or a
  prefill chunk (B=1, T=bucket); padded query rows are dropped by the
  caller.
* ``k_pool``/``v_pool`` [KV, P, D] — the flat block pool, P = NBLK * BS.
  Head-major: each grid step's DMA tile is then ``[BS, D]`` over the
  pool's minor dims — the layout Mosaic can tile (token-major would put
  the singleton kv-head pick in the sublane dim, which is unlowerable).
* ``tables``   [B, NB] int32 — per-sequence block table (0-padded).
* ``start``    [B] first absolute position of the chunk's queries.
* ``kv_len``   [B] valid cache length (= start + t_len).

Cost scales with the *actual* context: trailing table slots clamp to the
last valid block in the ``index_map``, and Pallas skips the DMA when the
block index repeats, so out-of-range blocks cost neither bandwidth nor
(predicated-off) FLOPs.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_name, note_fallback, register_op

_NEG_INF = -1e30


# ------------------------------------------------------------------ #
# Reference implementation (CPU/debug; also the parity oracle)
# ------------------------------------------------------------------ #
def reference_paged_attention(q, k_pool, v_pool, tables, start, kv_len,
                              block_size):
    """Dense-gather oracle. [B,T,Hq,D] out, grouped GQA (no repeat)."""
    B, T, Hq, D = q.shape
    KV = k_pool.shape[0]
    G = Hq // KV
    BS = block_size
    NB = tables.shape[1]
    S = NB * BS
    pos = jnp.arange(S)
    gather = tables[:, pos // BS] * BS + pos % BS            # [B, S]
    k_seq = k_pool[:, gather]                                # [KV,B,S,D]
    v_seq = v_pool[:, gather]
    qg = q.reshape(B, T, KV, G, D)
    scale = 1.0 / np.sqrt(D)
    scores = jnp.einsum("btkgd,kbsd->bkgts", qg, k_seq) * scale
    q_pos = start[:, None] + jnp.arange(T)[None, :]          # [B, T]
    valid = (pos[None, None, :] <= q_pos[:, :, None]) & \
            (pos[None, None, :] < kv_len[:, None, None])     # [B,T,S]
    scores = jnp.where(valid[:, None, None], scores.astype(jnp.float32),
                       _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,kbsd->btkgd", probs, v_seq)
    return out.reshape(B, T, Hq, D)


# ------------------------------------------------------------------ #
# Pallas kernel
# ------------------------------------------------------------------ #
def _kernel(tables_ref, kvlen_ref, start_ref,    # scalar prefetch
            q_ref, k_ref, v_ref,                 # [1,KVT,TQ,D], [KVT,1,BS,D]
            o_ref,                               # [1,KVT,TQ,D]
            acc, m_s, l_s,                       # VMEM scratch
            *, scale, G, BS, TQ):
    b, qt, nb = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nblocks = pl.num_programs(3)

    @pl.when(nb == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    kvlen = kvlen_ref[b]
    start = start_ref[b]
    # a cache block runs for this row tile when it holds valid tokens
    # at or before the tile's last query position (causal frontier)
    last_pos = start + (qt * TQ + TQ - 1) // G
    run = (nb * BS < kvlen) & (nb * BS <= last_pos)

    @pl.when(run)
    def _body():
        # KVT kv heads per grid step: one batched MXU call and one
        # [KVT*BS, D]-sized DMA instead of KVT tiny steps — the grid
        # count (not FLOPs) is what dominates decode-shape cost
        q = q_ref[0]                                         # [KVT,TQ,D]
        k = k_ref[:, 0].astype(q.dtype)                      # [KVT,BS,D]
        # matmuls stay in the input dtype (bf16 MXU rate) with fp32
        # accumulation — an fp32 upcast here runs at ~1/8 peak
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale      # [KVT,TQ,BS]
        rows = qt * TQ + jax.lax.broadcasted_iota(jnp.int32, (TQ, BS), 0)
        cols = nb * BS + jax.lax.broadcasted_iota(jnp.int32, (TQ, BS), 1)
        row_pos = start + rows // G
        ok = (cols <= row_pos) & (cols < kvlen)
        s = jnp.where(ok[None], s, _NEG_INF)
        m_prev = m_s[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[:, :, :1] = corr * l_s[:, :, :1] + \
            jnp.sum(p, axis=2, keepdims=True)
        m_s[:, :, :1] = m_new
        v = v_ref[:, 0]                                      # [KVT,BS,D]
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(nb == nblocks - 1)
    def _out():
        l = l_s[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)


class PagedAttentionBudgetError(ValueError):
    """No (row tile, head tile) of the paged kernel fits its VMEM
    budget for this cache layout."""


#: query rows (tokens x group) per grid step: a prefill dispatch of any
#: length is walked in row tiles of at most this many, so the kernel's
#: VMEM footprint does not grow with the dispatch
_MAX_ROW_TILE = 512
#: per-step VMEM the tiles may claim. The v5e compiler's scoped limit is
#: 16 MiB; the rest is left for Mosaic's own temporaries.
_VMEM_BUDGET = 10 * 2**20


def _step_bytes(rows, D, BS, itemsize):
    """VMEM bytes one kv head claims in one grid step at ``rows`` query
    rows: q/o and k/v blocks (double-buffered by the pipeline), the
    fp32 accumulator and m/l scratch, and the fp32 score/prob tiles."""
    return (2 * 2 * rows * D * itemsize       # q + o
            + 2 * 2 * BS * D * itemsize       # k + v
            + rows * D * 4                    # acc
            + 2 * rows * 128 * 4              # m, l
            + 2 * rows * BS * 4)              # s, p


def pick_tiles(KV, TG, D, BS, itemsize):
    """``(row tile, padded rows, head tile)`` for ``TG`` query rows per
    kv head: rows are tiled at ``_MAX_ROW_TILE`` (8-aligned), then the
    largest divisor of ``KV`` that keeps the step under ``_VMEM_BUDGET``.
    Raises :class:`PagedAttentionBudgetError` when one head at the row
    tile is already over it (block_size x head_dim too large)."""
    TQ = min(-(-TG // 8) * 8, _MAX_ROW_TILE)     # Mosaic sublane alignment
    TGp = -(-TG // TQ) * TQ
    per_head = _step_bytes(TQ, D, BS, itemsize)
    if per_head > _VMEM_BUDGET:
        raise PagedAttentionBudgetError(
            f"paged attention cannot tile this cache layout: one kv head "
            f"at {TQ} query rows, block_size={BS}, head_dim={D} needs "
            f"{per_head} bytes of VMEM per grid step, over the "
            f"{_VMEM_BUDGET}-byte budget; use a smaller "
            f"kv_cache.block_size")
    cap = _VMEM_BUDGET // per_head
    KVT = max(kvt for kvt in range(1, KV + 1)
              if KV % kvt == 0 and kvt <= cap)
    return TQ, TGp, KVT


def pallas_paged_attention(q, k_pool, v_pool, tables, start, kv_len,
                           block_size, interpret=None, head_tile=0):
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    B, T, Hq, D = q.shape
    KV = k_pool.shape[0]
    G = Hq // KV
    BS = block_size
    NB = tables.shape[1]
    NBLK = k_pool.shape[1] // BS

    # [B, KV, T*G, D] query layout: one contiguous row block per kv head
    qg = q.reshape(B, T, KV, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, KV, T * G, D)
    TG = T * G
    TQ, TGp, KVT = pick_tiles(KV, TG, D, BS, q.dtype.itemsize)
    if TGp != TG:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, TGp - TG), (0, 0)))
    KVT = head_tile or KVT
    if KV % KVT:
        # a non-divisor tile would floor-divide the grid and silently
        # leave the uncovered heads' output blocks unwritten
        raise ValueError(f"head_tile={KVT} must divide kv heads ({KV})")

    kp = k_pool.reshape(KV, NBLK, BS, D)
    vp = v_pool.reshape(KV, NBLK, BS, D)
    tables = jnp.asarray(tables, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    start = jnp.asarray(start, jnp.int32)

    def page_index(b, kh, qt, nb, tables_ref, kvlen_ref, start_ref):
        # clamp out-of-range slots to the last valid block: repeated block
        # index ⇒ Pallas skips the DMA, so dead slots cost nothing
        last = jnp.maximum(kvlen_ref[b] - 1, 0) // BS
        return (kh, tables_ref[b, jnp.minimum(nb, last)], 0, 0)

    def row_index(b, kh, qt, nb, *refs):
        return (b, kh, qt, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV // KVT, TGp // TQ, NB),
        in_specs=[
            pl.BlockSpec((1, KVT, TQ, D), row_index),
            pl.BlockSpec((KVT, 1, BS, D), page_index),
            pl.BlockSpec((KVT, 1, BS, D), page_index),
        ],
        out_specs=pl.BlockSpec((1, KVT, TQ, D), row_index),
        scratch_shapes=[
            pltpu.VMEM((KVT, TQ, D), jnp.float32),
            pltpu.VMEM((KVT, TQ, 128), jnp.float32),
            pltpu.VMEM((KVT, TQ, 128), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, scale=1.0 / np.sqrt(D), G=G, BS=BS,
                             TQ=TQ)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, TGp, D), q.dtype),
        interpret=interpret,
        **kernel_name("paged_attention"),
    )(tables, kv_len, start, qg, kp, vp)
    out = out[:, :, :TG].reshape(B, KV, T, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, Hq, D)


def _dispatch_paged_attention(q, k_pool, v_pool, tables, start, kv_len,
                              block_size):
    B, T, Hq, D = q.shape
    KV = k_pool.shape[0]
    if Hq % KV:
        raise ValueError(
            f"query heads ({Hq}) must be a multiple of kv heads ({KV})")
    # alignment guards: the kernel needs whole, sublane-aligned blocks
    if k_pool.shape[1] % block_size or block_size % 8:
        note_fallback("paged_attention", "block_misaligned",
                      f"pool={k_pool.shape[1]} block_size={block_size}")
        return reference_paged_attention(q, k_pool, v_pool, tables, start,
                                         kv_len, block_size)
    return pallas_paged_attention(q, k_pool, v_pool, tables, start, kv_len,
                                  block_size)


def paged_attention(q, k_pool, v_pool, tables, start, kv_len, block_size):
    from . import get_op
    return get_op("paged_attention")(q, k_pool, v_pool, tables, start,
                                     kv_len, block_size)


register_op("paged_attention", reference_paged_attention,
            _dispatch_paged_attention)
