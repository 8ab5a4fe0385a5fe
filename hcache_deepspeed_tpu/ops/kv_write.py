"""Writing a forward's new K and V into the blocked pools (Pallas TPU).

A lane of a serving forward carries ``T`` consecutive positions from
``start``; its rows belong in the pool's slots ``table[p // BS] * BS +
p % BS``. Two granularities of the same write:

* :func:`write_rows` — one ``[D]`` row at ``(layer, head, slot)`` an
  update, an XLA scatter. Right for a decode program (``T = 1``: every
  lane in another block), and the jnp reference of the run path. On the
  TPU an update costs some 73 ns whatever the pool's size (a bf16 row
  shares a packed sublane pair with its neighbour, so each is a partial
  tile write): 4.5 ms of every 512-token slice program.
* :func:`kv_write` — a lane's ``T`` positions are at most
  ``(T - 2) // BS + 2`` runs of consecutive slots, one a block. XLA lays
  the new rows out in block frames (``_frames``: row ``r`` of frame
  ``j`` is position ``(start // BS + j) * BS + r``) and the kernel
  copies each frame that a lane fills whole into its block by one DMA a
  pool, all KV heads at once. A frame the lane fills in part (a slice
  that starts inside a block, the last block of a prompt) is read into
  VMEM, takes the new rows under a row mask and goes back whole, so
  every other slot keeps its bits. K's rows and V's may differ in width
  (a latent pool: ``c`` rows beside narrower ``r`` rows). The pools are
  operands in place
  (``input_output_aliases``): nothing pool-sized or layer-sized is
  formed, and a lane with nothing to write costs no DMA.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_name, note_fallback, register_op

#: VMEM the frames (double-buffered by the pipeline) and the two merge
#: buffers may claim; the v5e compiler's scoped limit is 16 MiB
_VMEM_BUDGET = 10 * 2**20


def write_rows(k_pool, v_pool, layer, k, v, flat_idx):
    """k_pool/v_pool: the whole [L, KV, P, D] pools; k/v: [B, T, KV, D]
    of ``layer``; flat_idx: [B, T] slots (out of bounds ⇒ dropped: padded
    positions use an index past the pool's end)."""
    KV = k.shape[2]
    kt = k.reshape(-1, KV, k.shape[-1]).swapaxes(0, 1)   # [KV, N, D]
    vt = v.reshape(-1, KV, v.shape[-1]).swapaxes(0, 1)
    # single [D] rows at (layer, head, slot), not [KV, D] windows at
    # (layer, :, slot): for windows the TPU compiler keeps the carried
    # pool token-major and transposes it whole to the kernel's
    # head-major layout and back in every layer
    heads = jnp.arange(KV)[:, None]
    idx = flat_idx.reshape(1, -1)
    k_pool = k_pool.at[layer, heads, idx].set(kt.astype(k_pool.dtype),
                                              mode="drop")
    v_pool = v_pool.at[layer, heads, idx].set(vt.astype(v_pool.dtype),
                                              mode="drop")
    return k_pool, v_pool


def flat_slots(tables, start, t_len, T, block_size, pool_slots):
    """[B, T] pool slots of positions ``start + [0, T)`` by the block
    tables; the padding from ``t_len`` on gets ``pool_slots`` (past the
    pool's end: dropped by :func:`write_rows`)."""
    offs = jnp.arange(T)
    positions = start[:, None] + offs[None, :]
    lanes = jnp.arange(start.shape[0])[:, None]
    flat_idx = tables[lanes, positions // block_size] * block_size + \
        positions % block_size
    return jnp.where(offs[None, :] < t_len[:, None], flat_idx, pool_slots)


def reference_kv_write(k_pool, v_pool, k, v, layer, tables, start, kv_len,
                       block_size):
    """The row write of the same slots (CPU, and the parity oracle)."""
    flat_idx = flat_slots(tables, start, kv_len - start, k.shape[1],
                          block_size, k_pool.shape[2])
    return write_rows(k_pool, v_pool, layer, k, v, flat_idx)


# ------------------------------------------------------------------ #
# Pallas kernel
# ------------------------------------------------------------------ #
def n_frames(T, block_size):
    """Blocks that ``T`` consecutive positions can touch."""
    return (T + block_size - 2) // block_size + 1


def _frames(x, start, NJ, BS):
    """x [B, T, KV, D] → [B, NJ, KV, BS, D]: each lane's rows shifted to
    its offset inside its first block, head-major a frame. Rows outside
    the lane's run are blank; the kernel never writes them."""
    B, T, KV, D = x.shape
    off = jax.lax.rem(start, BS)
    # the shift while a row is still all heads wide, so its cost follows
    # the slice's bytes (15 us a pool at 30 heads, 3 at 8). Over
    # [.., KV, D] the compiler is free to lay the heads outside the rows
    # for the transpose that follows, and did at 30 heads: the shift then
    # runs inside each head's tiles, 58 us a pool (at 8 it kept a token a
    # tile and took 0.5)
    x = x.reshape(B, T, KV * D)
    framed = jnp.zeros((B, NJ * BS, KV * D), x.dtype)
    for b in range(B):
        framed = jax.lax.dynamic_update_slice(framed, x[b:b + 1],
                                              (b, off[b], 0))
    return framed.reshape(B, NJ, BS, KV, D).transpose(0, 1, 3, 2, 4)


def _kernel(tables_ref, start_ref, kvlen_ref, layer_ref,   # scalar prefetch
            kf_ref, vf_ref,                 # frames [1, 1, KVT, BS, D], VMEM
            k_in, v_in,                     # the pools [L, KV, P, D], HBM
            k_out, v_out,                   # the same buffers
            k_buf, v_buf, sems,             # [KVT, BS, D] x2, two DMA sems
            *, BS, KVT, NB):
    b, j, h = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    start = start_ref[b]
    first = jax.lax.div(start, BS)
    frame0 = (first + j) * BS               # position of the frame's row 0
    lo = jnp.maximum(start - frame0, 0)
    hi = jnp.minimum(kvlen_ref[b] - frame0, BS)
    block = tables_ref[b, jnp.minimum(first + j, NB - 1)]
    window = (layer_ref[0], pl.ds(h * KVT, KVT),
              pl.ds(pl.multiple_of(block * BS, BS), BS))

    def copy(srcs, dsts):
        dmas = [pltpu.make_async_copy(src, dst, sems.at[i])
                for i, (src, dst) in enumerate(zip(srcs, dsts))]
        for dma in dmas:
            dma.start()
        for dma in dmas:
            dma.wait()

    @pl.when((lo == 0) & (hi == BS))
    def _whole():
        copy((kf_ref.at[0, 0], vf_ref.at[0, 0]),
             (k_out.at[window], v_out.at[window]))

    @pl.when((hi > lo) & ((lo > 0) | (hi < BS)))
    def _ragged():
        copy((k_in.at[window], v_in.at[window]), (k_buf, v_buf))
        # the select in 32 bits: exact there and back for every narrower
        # dtype, and no packed mask for the compiler to lay out
        wide = jnp.float32 if k_buf.dtype.itemsize < 4 else k_buf.dtype
        masks = {}      # a row mask a buffer shape: one, or K's and V's
        for frame, buf in ((kf_ref, k_buf), (vf_ref, v_buf)):
            if buf.shape not in masks:
                row = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1)
                masks[buf.shape] = (row >= lo) & (row < hi)
            buf[...] = jnp.where(masks[buf.shape], frame[0, 0].astype(wide),
                                 buf[...].astype(wide)).astype(buf.dtype)
        copy((k_buf, v_buf), (k_out.at[window], v_out.at[window]))


def head_tile(KV, BS, D, itemsize):
    """KV heads a grid step: the largest divisor of ``KV`` whose two
    double-buffered frames and two merge buffers fit ``_VMEM_BUDGET``."""
    cap = max(1, _VMEM_BUDGET // (6 * BS * D * itemsize))
    return max(t for t in range(1, KV + 1) if KV % t == 0 and t <= cap)


def pallas_kv_write(k_pool, v_pool, k, v, layer, tables, start, kv_len,
                    block_size, interpret=None):
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    B, T, KV, D = k.shape
    Dv = v.shape[-1]
    BS = block_size
    NJ = n_frames(T, BS)
    KVT = head_tile(KV, BS, max(D, Dv), k_pool.dtype.itemsize)
    start = jnp.asarray(start, jnp.int32)
    kf = _frames(k.astype(k_pool.dtype), start, NJ, BS)
    vf = _frames(v.astype(v_pool.dtype), start, NJ, BS)

    def frame(b, j, h, *refs):
        return (b, j, h, 0, 0)

    in_place = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(B, NJ, KV // KVT),
        in_specs=[pl.BlockSpec((1, 1, KVT, BS, D), frame),
                  pl.BlockSpec((1, 1, KVT, BS, Dv), frame),
                  in_place, in_place],
        out_specs=[in_place, in_place],
        scratch_shapes=[pltpu.VMEM((KVT, BS, D), k_pool.dtype),
                        pltpu.VMEM((KVT, BS, Dv), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        functools.partial(_kernel, BS=BS, KVT=KVT, NB=tables.shape[1]),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={6: 0, 7: 1},       # both pools, in place
        interpret=interpret,
        **kernel_name("kv_write"),
    )(jnp.asarray(tables, jnp.int32), start,
      jnp.asarray(kv_len, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), kf, vf, k_pool, v_pool)


def _dispatch_kv_write(k_pool, v_pool, k, v, layer, tables, start, kv_len,
                       block_size):
    # a block is whole tiles of the pool's dtype (16 rows of bf16, 8 of
    # float32): a DMA window then starts and ends on a tile's edge
    tile = 32 // k_pool.dtype.itemsize
    if k_pool.shape[2] % block_size or block_size % tile:
        note_fallback("kv_write", "block_misaligned",
                      f"pool={k_pool.shape[2]} block_size={block_size} "
                      f"dtype={k_pool.dtype.name}")
        return reference_kv_write(k_pool, v_pool, k, v, layer, tables,
                                  start, kv_len, block_size)
    return pallas_kv_write(k_pool, v_pool, k, v, layer, tables, start,
                           kv_len, block_size)


def kv_write(k_pool, v_pool, k, v, layer, tables, start, kv_len,
             block_size):
    """Write ``k``/``v`` [B, T, KV, D] of ``layer`` into the pools
    [L, KV, P, D] at positions ``[start, kv_len)`` of each lane (at most
    ``T`` of them) by ``tables`` [B, NB]: a block run at a time where the
    platform has the kernel. Returns ``(k_pool', v_pool')``."""
    from . import get_op
    return get_op("kv_write")(k_pool, v_pool, k, v, layer, tables, start,
                              kv_len, block_size)


register_op("kv_write", reference_kv_write, _dispatch_kv_write)
