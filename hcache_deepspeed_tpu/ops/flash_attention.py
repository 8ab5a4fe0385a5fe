"""Flash attention (Pallas TPU) with custom VJP.

Reference analog: the CUDA attention kernel set —
``csrc/transformer/inference/csrc/softmax.cu`` + attention glue and the
inference-v2 ``blocked_flash`` kernels
(``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash``). On TPU the
idiomatic form is an online-softmax blocked kernel that keeps the running
(max, sum, acc) in VMEM scratch while the grid streams K/V blocks from HBM —
MXU does the two matmuls, the VPU the rescaling.

Layout: [batch, seq, heads, head_dim] in, same out; the kernels work on
``[B, H, T, D]`` views. Products in the input dtype (bf16 feeds the MXU
at full rate), float32 accumulation and softmax statistics. Three
kernels: the forward, which also writes the rows' logsumexp, and the
standard two-kernel backward (dq streaming K and V along a row of score
tiles, dk/dv streaming q and dout down a column) from the saved
logsumexp and delta = rowsum(dout * out).

They do only what a causal, grouped-query attention asks for:

* **The grid holds no tile above the diagonal** (:func:`_walk`). Its
  last two axes are ``(slots, steps)``; under a causal mask a slot walks
  line ``s`` and then line ``n - 1 - s``, a short one beside a long one,
  so every slot has the same number of steps and each stands on a tile
  the mask leaves. Spare steps (an odd middle line, block sizes that do
  not divide each other) stay on the slot's last tile: the pipeline
  fetches nothing for them and nothing runs. Without a mask a slot is a
  line: the same walk, unpaired.
* **K and V are read by KV head** ``h // rep`` in all three kernels
  (``rep`` 1 is multi-head through the same index map); nothing is
  repeated. dk and dv come out per query head and each group is summed
  onto its KV head after.
* **The statistics stay in whole vregs.** The forward's running max is
  replicated over 128 lanes and its running sum spread over them, summed
  across lanes once a row; lse and delta travel between the kernels as
  dense ``[B, H, 1, T]`` rows; dkv works on the transposed ``[block_k,
  block_q]`` tile, so ``p.T @ dout`` and ``ds.T @ q`` are plain products.
* **The tile follows the input** (:func:`_tiles`): 512 x 512, and
  1024 x 512 forward with 1024 x 1024 backward where the sequence divides
  and a row of the tile is 256 bytes or less; a size the caller names
  holds for all three kernels.

The walk is a few ``jax.lax`` primitives on the grid position, the same
closed form in every index map and in the kernel bodies, and the two
launchers are jitted at module level: a model's layers, and every retrace
of its step, share one jaxpr and one lowered function of each kernel
(PERF.md, PR 33: a start-up that traced them twelve times in ``jnp``
floor divisions took 12 s longer).
"""

import functools
import operator

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
# Which tiles a call visits
# ------------------------------------------------------------------ #
# The walk is integer arithmetic on a grid position. Every block spec's
# index map evaluates it, and each kernel body once, on traced indices;
# the grid is sized from it on Python ints. On a traced index it is
# written in jax.lax primitives alone: jnp's ``//`` lowers through two
# ``sign``s and a ``rem``, each of which Pallas lowers by tracing a
# helper afresh, and that was 2.5 s of a start-up (PERF.md, PR 33).
# ``lax.div`` truncates where ``//`` floors. They agree here because no
# operand can be negative: grid positions, block counts and block sizes
# are all >= 0, and every dividend is a sum or product of those.
def _ints(prim, plain):
    return lambda a, b: plain(a, b) \
        if isinstance(a, int) and isinstance(b, int) else prim(a, b)


_add = _ints(jax.lax.add, operator.add)
_sub = _ints(jax.lax.sub, operator.sub)
_mul = _ints(jax.lax.mul, operator.mul)
_div = _ints(jax.lax.div, operator.floordiv)
_both, _either, _pick = (jax.lax.bitwise_and, jax.lax.bitwise_or,
                         jax.lax.select)


def _walk(n_lines, count, paired):
    """The last two grid axes, ``(slots, steps)``, and the function from
    a grid position to the tile it stands on.

    A *line* is a row of score tiles (forward, dq) or a column (dkv);
    ``count(line)`` is how many of its tiles the mask leaves, counted
    from the line's live end. Unpaired (no mask), a slot is a line.
    Paired (causal), slot ``s`` walks line ``s`` and then line
    ``n_lines - 1 - s``: a short line beside a long one, so that every
    slot has the same number of steps when one block size divides the
    other, and no step stands on a tile above the diagonal. Where the
    sums differ (the middle line of an odd number, block sizes that do
    not divide) the spare steps stay on the slot's last tile: nothing
    is fetched for them and nothing runs.

    ``locate(slot, step) -> (line, pos, live, first, last)``: the tile
    is the ``pos``-th of ``line``, to be computed where ``live``, and
    ``first`` or ``last`` where it opens or completes its line."""
    slots = (n_lines + 1) // 2 if paired else n_lines

    def partner(lo):
        return _sub(n_lines - 1, lo) if paired else lo

    steps = max(count(lo) + (count(partner(lo)) if partner(lo) != lo else 0)
                for lo in range(slots))

    def locate(slot, step):
        lo, hi = slot, partner(slot)
        n_lo = count(lo)
        on_lo = jax.lax.lt(step, n_lo)
        line = _pick(on_lo, lo, hi)
        pos = _pick(on_lo, step, _sub(step, n_lo))
        end = _sub(_pick(on_lo, n_lo, count(hi)), 1)
        live = _either(on_lo, _both(jax.lax.ne(hi, lo),
                                    jax.lax.le(pos, end)))
        return (line, _pick(live, pos, end), live,
                _both(live, jax.lax.eq(pos, 0)),
                _both(live, jax.lax.eq(pos, end)))

    return (slots, steps), locate


def _row_walk(T, block_q, block_k, causal):
    """Forward and dq: a row of tiles from its left end to the last
    tile the diagonal touches. ``tile(slot, step) -> (row, col, live,
    first, last)``."""
    nq, nk = T // block_q, T // block_k

    def count(row):
        if not causal:
            return nk
        return _add(_div(_add(_mul(row, block_q), block_q - 1), block_k), 1)

    return _walk(nq, count, causal)


def _col_walk(T, block_q, block_k, causal):
    """dkv: a column of tiles from the first tile the diagonal touches
    down to the bottom. Same ``tile`` as :func:`_row_walk`."""
    nq, nk = T // block_q, T // block_k

    def top(col):
        return _div(_mul(col, block_k), block_q) if causal else 0

    dims, locate = _walk(nk, lambda col: _sub(nq, top(col)), causal)

    def tile(slot, step):
        col, pos, *flags = locate(slot, step)
        return (_add(top(col), pos), col, *flags)

    return dims, tile


def _on_tile(live, row, col, block_q, block_k, causal, body,
             transposed=False):
    """``body(mask)`` on a live tile: ``mask`` is ``rows >= cols`` over
    the ``[block_q, block_k]`` tile (``transposed``: ``[block_k,
    block_q]``), None in a call without one."""
    @pl.when(live)
    def _live():
        if not causal:
            return body(None)
        shape = (block_k, block_q) if transposed else (block_q, block_k)
        rows = row * block_q + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 if transposed else 0)
        cols = col * block_k + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0 if transposed else 1)
        body(rows >= cols)


def _scores(a, b, scale, mask):
    """``a @ b.T * scale`` in float32, masked. The products stay in the
    input dtype: bf16 feeds the MXU at full rate, an fp32 upcast would
    run at about an eighth of it on v5e."""
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return s if mask is None else jnp.where(mask, s, _NEG_INF)


def _lanes(x, n):
    """A lane-replicated ``[rows, 128]`` statistic at width ``n``."""
    if n == 128:
        return x
    if n % 128 == 0:
        return pltpu.repeat(x, n // 128, 1)
    return x[:, :n] if n < 128 else jnp.broadcast_to(
        x[:, :1], (x.shape[0], n))


def _lane_sums(p):
    """The row sums of ``p`` left spread over 128 lanes, whole vregs
    added to whole vregs: the lanes are summed once, when the row is
    complete, and no step reduces across them."""
    rows, n = p.shape
    if n % 128:     # interpret mode's odd tiles: the whole sum in lane 0
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
        return jnp.where(lane == 0, jnp.sum(p, axis=1, keepdims=True), 0.0)
    return sum(p[:, i:i + 128] for i in range(0, n, 128))


def _as_row(x):
    """``[1, rows]`` of a lane-replicated ``[rows, 128]``: the per-row
    statistics travel between the kernels as dense rows of ``[B, H, 1,
    T]`` (a ``[B, H, T, 1]`` column is padded to 128 lanes in HBM: 67 MB
    a layer at the 7B shape for 0.5 MB of numbers)."""
    return x.T[:1]


def _as_lanes(row):
    """The reverse: a ``[1, rows]`` row as lane-replicated ``[rows,
    128]``."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T


def _bhtd(x):
    return x.transpose(0, 2, 1, 3)


def _specs(tile, rep, block_q, block_k, D):
    """The block specs of a walk: ``(rows, kv, stats, cols)``. ``rows``
    is a ``[block_q, D]`` block of a ``[B, H, T, D]`` array at the
    tile's row (q, out, dout, dq); ``kv`` a ``[block_k, D]`` block of K
    or V at its column, KV head ``h // rep`` (GQA: the index map shares
    each block across the group, nothing is repeated); ``stats`` a
    ``[1, block_q]`` block of a ``[B, H, 1, T]`` row (lse, delta);
    ``cols`` a ``[block_k, D]`` block per query head (dk, dv)."""
    def spec(shape, index):
        return pl.BlockSpec(shape, lambda b, h, s, j: index(
            b, h, *tile(s, j)[:2]))
    return (spec((1, 1, block_q, D), lambda b, h, r, c: (b, h, r, 0)),
            spec((1, 1, block_k, D),
                 lambda b, h, r, c: (b, _div(h, rep), c, 0)),
            spec((1, 1, 1, block_q), lambda b, h, r, c: (b, h, 0, r)),
            spec((1, 1, block_k, D), lambda b, h, r, c: (b, h, c, 0)))


# ------------------------------------------------------------------ #
# Pallas forward
# ------------------------------------------------------------------ #
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                tile, scale, causal, block_q, block_k):
    row, col, live, first, last = tile(pl.program_id(2), pl.program_id(3))

    @pl.when(first)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def body(mask):
        # the running max stays replicated over its 128 lanes and the
        # running sum spread over them: no step slices one lane out,
        # broadcasts it back or sums across lanes
        v = v_ref[0, 0]
        s = _scores(q_ref[0, 0], k_ref[0, 0], scale, mask)
        m_prev = m_s[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))
        corr = jnp.exp(m_prev - m_new)
        l_s[:] = corr * l_s[:] + _lane_sums(p)
        m_s[:] = m_new
        acc[:] = acc[:] * _lanes(corr, acc.shape[1]) + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    _on_tile(live, row, col, block_q, block_k, causal, body)

    @pl.when(last)
    def _out():
        l = jnp.sum(l_s[:], axis=1, keepdims=True)
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _as_row(m_s[:] + jnp.log(l))


# The launchers are jitted with everything but the arrays static, at
# module level, so the cache lives as long as the process: every layer
# of a model and each retrace of its step then share one jaxpr of each
# kind, and a program holds one lowered function that its layers call
# (``per_shard`` is told the results' shapes below, so it does not trace
# them once more on the whole batch to ask). XLA inlines the calls, so
# the compiled step is what it would be without (PERF.md, PR 33).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _fwd_pallas(q, k, v, scale, causal, block_q, block_k, interpret):
    B, T, H, D = q.shape
    dims, tile = _row_walk(T, block_q, block_k, causal)
    rows, kv, stats, _ = _specs(tile, H // k.shape[2], block_q, block_k, D)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, tile=tile, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=(B, H, *dims),
        in_specs=[rows, kv, kv],
        out_specs=[rows, stats],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        **kernel_name("flash_attention_fwd"),
    )(_bhtd(q), _bhtd(k), _bhtd(v))
    return _bhtd(out), lse


# ------------------------------------------------------------------ #
# Pallas backward
# ------------------------------------------------------------------ #
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, lse_s, delta_s, *, tile, scale, causal, block_q,
                   block_k):
    row, col, live, first, last = tile(pl.program_id(2), pl.program_id(3))

    @pl.when(first)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        lse_s[:] = _as_lanes(lse_ref[0, 0])
        delta_s[:] = _as_lanes(delta_ref[0, 0])

    def body(mask):
        k = k_ref[0, 0]
        p = jnp.exp(_scores(q_ref[0, 0], k, scale, mask)
                    - _lanes(lse_s[:], block_k))
        dp = jax.lax.dot_general(do_ref[0, 0], v_ref[0, 0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - _lanes(delta_s[:], block_k)) * scale).astype(k.dtype)
        dq_acc[:] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    _on_tile(live, row, col, block_q, block_k, causal, body)

    @pl.when(last)
    def _out():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, tile, scale, causal,
                    block_q, block_k):
    row, col, live, first, last = tile(pl.program_id(2), pl.program_id(3))

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def body(mask):
        # the tile transposed, [block_k, block_q], lse and delta the
        # rows they arrive as: p.T @ do and ds.T @ q are then plain
        # products, and no [block_q, block_k] tile is turned round for
        # the MXU
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        p = jnp.exp(_scores(k_ref[0, 0], q, scale, mask) - lse_ref[0, 0])
        dv_acc[:] += jax.lax.dot(p.astype(do.dtype), do,
                                 preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[0, 0], do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0]) * scale).astype(q.dtype)
        dk_acc[:] += jax.lax.dot(ds, q, preferred_element_type=jnp.float32)

    _on_tile(live, row, col, block_q, block_k, causal, body,
             transposed=True)

    @pl.when(last)
    def _out():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _bwd_pallas(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    B, T, H, D = q.shape
    KV = k.shape[2]
    rep = H // KV   # GQA: dk and dv come out per query head and each
    #                 group is summed onto its kv head after
    qt, kt, vt, dot, ot = (_bhtd(x) for x in (q, k, v, g, out))
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)[:, :, None]    # [B,H,1,T], as lse

    dims, tile = _row_walk(T, block_q, block_k, causal)
    rows, kv, stats, _ = _specs(tile, rep, block_q, block_k, D)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, tile=tile, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=(B, H, *dims),
        in_specs=[rows, kv, kv, rows, stats, stats],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        interpret=interpret,
        **kernel_name("flash_attention_bwd_dq"),
    )(qt, kt, vt, dot, lse, delta)

    dims, tile = _col_walk(T, block_q, block_k, causal)
    rows, kv, stats, cols = _specs(tile, rep, block_q, block_k, D)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, tile=tile, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=(B, H, *dims),
        in_specs=[rows, kv, kv, rows, stats, stats],
        out_specs=[cols, cols],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, D), q.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret,
        **kernel_name("flash_attention_bwd_dkv"),
    )(qt, kt, vt, dot, lse, delta)

    def to_kv_bthd(x):   # [B,H,T,D] per query head -> [B,T,KV,D]
        x = _bhtd(x)
        return x if rep == 1 else x.reshape(B, T, KV, rep, D).sum(axis=3)

    return _bhtd(dq), to_kv_bthd(dk), to_kv_bthd(dv)


# ------------------------------------------------------------------ #
# custom_vjp wrapper
# ------------------------------------------------------------------ #
# batch elements and heads are independent: q/k/v/out [B, T, H, D] and
# lse [B, H, 1, T] keep their batch split and (query and KV heads
# together, GQA groups staying whole) their head split; T and D are
# whole in every shard
_BTHD = (BATCH, None, HEADS, None)
_BH1T = (BATCH, HEADS, None, None)


def _like(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def _fwd_placed(q, k, v, scale, causal, tiles, interpret):
    B, T, H, _ = q.shape
    return per_shard(
        lambda q, k, v: _fwd_pallas(q, k, v, scale, causal, *tiles[0],
                                    interpret),
        (q, k, v), in_roles=(_BTHD,) * 3, out_roles=(_BTHD, _BH1T),
        out_shapes=(_like(q),
                    jax.ShapeDtypeStruct((B, H, 1, T), jnp.float32)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, tiles, interpret):
    out, _ = _fwd_placed(q, k, v, scale, causal, tiles, interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, tiles, interpret):
    out_bhtd, lse = _fwd_placed(q, k, v, scale, causal, tiles, interpret)
    return out_bhtd, (q, k, v, out_bhtd, lse)


def _flash_bwd(scale, causal, tiles, interpret, res, g):
    return per_shard(
        lambda *a: _bwd_pallas(scale, causal, *tiles[1], interpret,
                               a[:5], a[5]),
        (*res, g), in_roles=(_BTHD,) * 4 + (_BH1T, _BTHD),
        out_roles=(_BTHD,) * 3, out_shapes=tuple(map(_like, res[:3])))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _tiles(T, D, dtype, block_q, block_k):
    """``((block_q, block_k) of the forward, of the backward)``. A size
    the caller names holds for all three kernels. Left to the kernels:
    512 x 512, and where the sequence divides and a tile's operands are
    256 bytes a row or less (bf16 at ``D`` 128 or 64) the forward takes
    1024 x 512 and the backward 1024 x 1024, which measured 1%, 5% and 3%
    faster for the forward, dq and dkv at ``T`` 4096 on a v5e (PERF.md,
    PR 33, kernels alone); 2048 or 256 either way measured slower."""
    fwd = _fit_block(block_q or 512, T), _fit_block(block_k or 512, T)
    if block_q or block_k or T % 1024 or \
            D * jnp.dtype(dtype).itemsize > 256:
        return fwd, fwd
    return (1024, 512), (1024, 1024)


def pallas_attention(q, k, v, causal=True, scale=None, block_q=None,
                     block_k=None, interpret=None):
    B, T, H, D = q.shape
    if H % k.shape[2]:
        raise ValueError(
            f"q heads {H} not divisible by kv heads {k.shape[2]}")
    scale = scale or _default_scale(D)
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    tiles = _tiles(T, D, q.dtype, block_q, block_k)
    block_q, block_k = tiles[0]
    reason = None
    if block_q < 128 or block_k < 128 or T % block_q or T % block_k:
        reason = "seq_not_block_multiple"
    elif not interpret and (block_q % 128 or block_k % 128):
        # Mosaic tiling: the s=[block_q, block_k] tile and its transpose
        # need a (8,128)-aligned layout on real hardware
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
    return _flash(q, k, v, scale, causal, tiles, interpret)


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
