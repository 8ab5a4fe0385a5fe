"""Paged attention over a pool of compressed KV rows (Pallas TPU): the
absorbed form of multi-head latent attention.

A layer of latent attention caches one row a position, ``[c | r]``: the
normed compressed KV ``c`` (``C`` wide) and the rotary key ``r`` that all
heads share. With the up-projection ``W_kvb,h = [W_uk,h ; W_uv,h]``
absorbed into the query (``q~_h = W_uk,h^T q_nope,h``) and into the
output (``o_h = W_uv,h u_h``), a head's scores are ``q~_h . c_s + q_rope,h
. r_s`` and its result ``u_h = sum_s p_s c_s``: the one cached row is key
and value of every head, and what the kernel computes is multi-query
attention whose values are the first ``C`` channels of its keys.

Contract (after ``ops/paged_attention.py``, whose walk this is):

* ``q``       [B, T, H, C + R] — ``[q~ | q_rope]``, the rotary part
  padded with zeros to the ``R`` channels of the ``r`` pool.
* ``c_pool``  [L, 1, P, C] and ``r_pool`` [L, 1, P, R] — the whole pools
  of every layer (``P = NBLK * BS`` slots), read at the traced ``layer``
  by the DMA's source index. Two pools, each a whole number of 128-lane
  tiles wide: Mosaic takes a manual DMA window only in whole lane tiles,
  and 576 is four and a half.
* ``tables`` [B, NB], ``start`` [B], ``kv_len`` [B] as the paged kernel's.
* returns ``u`` [B, T, H, C], float32 accumulation, causal.

A grid step is a (lane, row tile) of the ``T * H`` query rows, token
major (row ``t * H + h``: no transpose on the way in or out); the loop
inside walks the lane's own blocks up to the row tile's causal frontier,
several blocks an iteration, each block of ``c`` and of ``r`` fetched
once by DMA into one of two buffers (the next group in flight under this
group's products) and the ``c`` tile used twice: in the scores and as the
values. Decode lanes (``T = 1``: ``H`` rows) and prompt slices (``T =
512``: twenty row tiles) go through the same code; the tiles are sized
from the shapes handed in (:func:`pick_tiles`).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_name, note_fallback, register_op

_NEG_INF = -1e30
#: query rows (tokens x heads) a grid step, cache positions a loop
#: iteration, and the VMEM both may claim (the v5e compiler's scoped
#: limit is 16 MiB): as ``ops/paged_attention.py``'s
_MAX_ROW_TILE = 512
_MAX_COL_TILE = 512
_VMEM_BUDGET = 10 * 2**20


# ------------------------------------------------------------------ #
# Reference implementation (CPU/debug; also the parity oracle)
# ------------------------------------------------------------------ #
def reference_latent_attention(q, c_pool, r_pool, layer, tables, start,
                               kv_len, block_size, scale):
    """Dense-gather oracle: ``[B, T, H, C]``."""
    B, T, H, _ = q.shape
    C = c_pool.shape[-1]
    BS = block_size
    S = tables.shape[1] * BS
    pos = jnp.arange(S)
    gather = jnp.asarray(tables)[:, pos // BS] * BS + pos % BS   # [B, S]
    c_seq = c_pool[layer, 0, gather]                          # [B, S, C]
    k_seq = jnp.concatenate([c_seq, r_pool[layer, 0, gather]], axis=-1)
    scores = jnp.einsum("bthd,bsd->bhts", q, k_seq,
                        preferred_element_type=jnp.float32) * scale
    q_pos = start[:, None] + jnp.arange(T)[None, :]              # [B, T]
    valid = (pos[None, None, :] <= q_pos[:, :, None]) & \
            (pos[None, None, :] < kv_len[:, None, None])         # [B,T,S]
    scores = jnp.where(valid[:, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bsc->bthc", probs, c_seq,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# ------------------------------------------------------------------ #
# Pallas kernel
# ------------------------------------------------------------------ #
def _kernel(tables_ref, kvlen_ref, start_ref, layer_ref,  # scalar prefetch
            q_ref,                               # [1, TQ, C + R]
            c_hbm, r_hbm,                        # [L, NBLK, BS, C / R], HBM
            o_ref,                               # [1, TQ, C]
            c_buf, r_buf, sems,                  # [2, P*BS, C / R], [2, 2]
            acc, m_s, l_s,                       # VMEM scratch
            *, scale, H, BS, TQ, P):
    b, qt = pl.program_id(0), pl.program_id(1)
    C = c_buf.shape[2]
    kvlen = kvlen_ref[b]
    start = start_ref[b]
    layer = layer_ref[0]
    # the lane's own walk, cut at this row tile's causal frontier; none
    # for a padded lane. ``lax.div``: nothing here is negative, and ``//``
    # is traced afresh at every use
    last_pos = start + jax.lax.div(qt * TQ + TQ - 1, H)
    n = jnp.minimum(jax.lax.div(kvlen + BS - 1, BS),
                    jax.lax.div(last_pos, BS) + 1)
    groups = jax.lax.div(n + P - 1, P)

    acc[:] = jnp.zeros_like(acc)
    m_s[:] = jnp.full_like(m_s, _NEG_INF)
    l_s[:] = jnp.zeros_like(l_s)

    def held(g):
        return jnp.clip(n - g * P, 0, P)

    def copies(g, slot, act):
        """``act`` (start or wait) on the two DMAs of each block of
        group ``g`` that the lane holds: a loop, traced once whatever
        P."""
        def one(p, carry):
            block = tables_ref[b, g * P + p]
            rows = pl.ds(pl.multiple_of(p * BS, BS), BS)
            for j, (pool, buf) in enumerate(((c_hbm, c_buf),
                                             (r_hbm, r_buf))):
                act(pltpu.make_async_copy(
                    pool.at[layer, block], buf.at[slot, rows],
                    sems.at[j, slot]))
            return carry
        jax.lax.fori_loop(0, held(g), one, 0)

    copies(0, 0, lambda dma: dma.start())

    def group(g, carry):
        slot = jax.lax.rem(g, 2)
        copies(g + 1, 1 - slot, lambda dma: dma.start())
        copies(g, slot, lambda dma: dma.wait())

        def blank(p, carry):
            # a short last group: the rows past it are masked out of the
            # scores, but as values 0 x NaN is NaN
            c_buf[slot, pl.ds(pl.multiple_of(p * BS, BS), BS)] = \
                jnp.zeros((BS, C), c_buf.dtype)
            return carry
        jax.lax.fori_loop(held(g), P, blank, 0)

        q = q_ref[0]                                         # [TQ, C + R]
        c = c_buf[slot]                                      # [P*BS, C]
        nt = (((1,), (1,)), ((), ()))
        # one fetched tile of c, used here and as the values below; the
        # products stay in the input dtype with float32 accumulation
        s = (jax.lax.dot_general(q[:, :C], c.astype(q.dtype), nt,
                                 preferred_element_type=jnp.float32) +
             jax.lax.dot_general(q[:, C:], r_buf[slot].astype(q.dtype), nt,
                                 preferred_element_type=jnp.float32)
             ) * scale                                       # [TQ, P*BS]
        shape = (TQ, P * BS)
        rows = qt * TQ + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = g * (P * BS) + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        ok = (cols <= start + jax.lax.div(rows, H)) & (cols < kvlen)
        s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[:, :1] = corr * l_s[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_s[:, :1] = m_new
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)
    l = l_s[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc[:] / l).astype(o_ref.dtype)


class LatentAttentionBudgetError(ValueError):
    """No row tile of the latent kernel fits its VMEM budget for this
    pool layout."""


def _step_bytes(rows, C, R, cols, itemsize):
    """VMEM bytes a grid step claims at ``rows`` query rows and ``cols``
    cache positions an iteration: q and o (double-buffered by the
    pipeline), c and r (two buffers each), the float32 accumulator, m/l
    and the float32 score and probability tiles."""
    return (2 * rows * (2 * C + R) * itemsize     # q + o
            + 2 * cols * (C + R) * itemsize       # c + r
            + rows * C * 4                        # acc
            + 2 * rows * 128 * 4                  # m, l
            + 2 * rows * cols * 4)                # s, p


def pick_tiles(TG, C, R, BS, NB, itemsize):
    """``(row tile, padded rows, blocks an iteration)`` for ``TG`` query
    rows (tokens x heads) a lane: rows tiled at ``_MAX_ROW_TILE``
    (8-aligned), then the most blocks (a power of two within the table's
    ``NB`` slots and ``_MAX_COL_TILE`` positions) that keep the step
    under ``_VMEM_BUDGET``."""
    TQ = min(-(-TG // 8) * 8, _MAX_ROW_TILE)
    TGp = -(-TG // TQ) * TQ
    if _step_bytes(TQ, C, R, BS, itemsize) > _VMEM_BUDGET:
        raise LatentAttentionBudgetError(
            f"latent attention cannot tile this pool layout: {TQ} query "
            f"rows, block_size={BS}, row widths {C}+{R} need "
            f"{_step_bytes(TQ, C, R, BS, itemsize)} bytes of VMEM a grid "
            f"step, over the {_VMEM_BUDGET}-byte budget; use a smaller "
            f"kv_cache.block_size")
    P = 1
    while (2 * P <= NB and 2 * P * BS <= _MAX_COL_TILE and
           _step_bytes(TQ, C, R, 2 * P * BS, itemsize) <= _VMEM_BUDGET):
        P *= 2
    return TQ, TGp, P


def pallas_latent_attention(q, c_pool, r_pool, layer, tables, start,
                            kv_len, block_size, scale, interpret=None):
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    B, T, H, W = q.shape
    L, _, slots, C = c_pool.shape
    R = r_pool.shape[-1]
    BS = block_size
    NBLK = slots // BS
    TG = T * H
    TQ, TGp, P = pick_tiles(TG, C, R, BS, tables.shape[1],
                            q.dtype.itemsize)
    qg = q.reshape(B, TG, W)                     # token major: a bitcast
    if TGp != TG:
        qg = jnp.pad(qg, ((0, 0), (0, TGp - TG), (0, 0)))

    def row_index(b, qt, *refs):
        return (b, qt, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, TGp // TQ),
        in_specs=[
            pl.BlockSpec((1, TQ, W), row_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, TQ, C), row_index),
        scratch_shapes=[
            pltpu.VMEM((2, P * BS, C), c_pool.dtype),
            pltpu.VMEM((2, P * BS, R), r_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((TQ, C), jnp.float32),
            pltpu.VMEM((TQ, 128), jnp.float32),
            pltpu.VMEM((TQ, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, H=H, BS=BS, TQ=TQ, P=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, TGp, C), q.dtype),
        interpret=interpret,
        **kernel_name("latent_attention"),
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(kv_len, jnp.int32),
      jnp.asarray(start, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg,
      # bitcasts: BS is whole sublane tiles of the pool's dtype
      c_pool.reshape(L, NBLK, BS, C), r_pool.reshape(L, NBLK, BS, R))
    return out[:, :TG].reshape(B, T, H, C)


def _dispatch_latent_attention(q, c_pool, r_pool, layer, tables, start,
                               kv_len, block_size, scale):
    C, R = c_pool.shape[-1], r_pool.shape[-1]
    reason = None
    if c_pool.shape[2] % block_size or block_size % 8:
        reason = "block_misaligned"
    elif C % 128 or R % 128:
        reason = "row_width_misaligned"
    if reason:
        note_fallback("latent_attention", reason,
                      f"pool={c_pool.shape[2]} block_size={block_size} "
                      f"row widths={C}+{R}")
        return reference_latent_attention(q, c_pool, r_pool, layer, tables,
                                          start, kv_len, block_size, scale)
    return pallas_latent_attention(q, c_pool, r_pool, layer, tables, start,
                                   kv_len, block_size, scale)


def latent_attention(q, c_pool, r_pool, layer, tables, start, kv_len,
                     block_size, scale):
    from . import get_op
    return get_op("latent_attention")(q, c_pool, r_pool, layer, tables,
                                      start, kv_len, block_size, scale)


register_op("latent_attention", reference_latent_attention,
            _dispatch_latent_attention)
