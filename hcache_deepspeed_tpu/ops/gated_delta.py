"""Gated delta rule (Pallas TPU): the recurrent mixer of a hybrid trunk.

A linear-attention layer of the Gated DeltaNet kind keeps, per sequence
and head, one ``[d_k, d_v]`` float32 state instead of a KV cache. Per
token ``t`` (``g_t <= 0`` the log-decay, ``beta_t`` in ``[0, 2]``)::

    S   <- exp(g_t) * S
    u_t  = beta_t * (v_t - S^T k_t)
    S   <- S + k_t u_t^T
    o_t  = S^T q_t / sqrt(d_k)

Serving contract (matches ``inference/model_hybrid.py``):

* ``q``/``k`` ``[B, T, H, d_k]``, ``v`` ``[B, T, H, d_v]`` float32, q and
  k already normalised per head; ``g``/``beta`` ``[B, T, H]``.
* ``state_pool`` ``[L, S + 1, H, d_k, d_v]`` float32: every recurrent
  layer's slot pool, ``layer`` a traced int32 scalar, ``slots`` ``[B]``
  the slot of each lane. The kernels pick ``[layer, slot]`` in their
  ``index_map`` and write the new state back through
  ``input_output_aliases``: no ``pool[layer]`` and no gathered copy of
  the lanes' states is ever formed, the pool is updated in place. Blank
  lanes of a bucket name the spare slot ``S``.
* ``start`` ``[B]``: a lane whose slice starts at position 0 starts from
  the zero state, whatever its slot held (zeroed inside the kernel, so a
  slot freed and taken again needs no clearing pass). ``t_len`` ``[B]``:
  tokens past it are pads and leave the state untouched (their ``g`` and
  ``beta`` are set to 0 here: decay 1, update 0).

Two kernels. ``gated_delta_chunk`` walks a prompt slice in chunks of 64
tokens: inside a chunk the rule is the unit-lower-triangular system
``(I + A) U = beta * (V - diag(e^gamma) K S_0)`` with ``A[t, i] = beta_t
e^(gamma_t - gamma_i) k_t.k_i`` (``gamma`` the running sum of ``g`` in
the chunk), solved by forward substitution on the 64x64 inverse, then
three matmuls; the state rides in VMEM from chunk to chunk. Heads are on
the grid. ``gated_delta_step`` is the rule at ``T = 1`` for decode
lanes: it reads and writes each lane's state once and is bound by that
traffic. The jnp fallback is the recurrence itself, token by token.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_name, note_fallback, register_op

#: tokens per chunk of the chunked rule
CHUNK = 64
#: per-step VMEM the head tile may claim (v5e's scoped limit is 16 MiB)
_VMEM_BUDGET = 10 * 2**20
_HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ #
# Reference implementation (CPU/debug; also the parity oracle)
# ------------------------------------------------------------------ #
def reference_gated_delta(q, k, v, g, beta, state_pool, layer, slots,
                          start):
    """The recurrence, token by token, over the lanes' gathered states;
    ``(o [B, T, H, d_v], state_pool')``. Pads carry ``g = beta = 0``."""
    d_k = q.shape[-1]
    s0 = state_pool[layer, slots]                       # [B, H, dk, dv]
    s0 = jnp.where((start == 0)[:, None, None, None], 0.0, s0)

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs                    # [B, H, ...]
        s = s * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=_HIGHEST))
        s = s + k_t[..., :, None] * u[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HIGHEST)
        return s, o

    def time_major(x):
        return jnp.moveaxis(x, 1, 0)

    s, o = jax.lax.scan(token, s0, tuple(
        time_major(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1) / np.sqrt(d_k).astype(np.float32)
    return o, state_pool.at[layer, slots].set(s)


# ------------------------------------------------------------------ #
# Pallas kernels
# ------------------------------------------------------------------ #
def _bmm(a, b):
    """[Hb, m, k] @ [Hb, k, n] in float32 at full precision."""
    return jax.lax.dot_general(
        a, b, (((2,), (1,)), ((0,), (0,))), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def _to_col(row, n):
    """``[Hb, 1, n]`` -> ``[Hb, n, 1]`` without a transpose: the row
    under an identity mask, summed over lanes."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where((r == c)[None], row, 0.0), axis=2,
                   keepdims=True)


def _chunk_kernel(slots_ref, start_ref, layer_ref,      # scalar prefetch
                  q_ref, k_ref, kt_ref, v_ref, gb_ref, pool_ref,
                  o_ref, pool_out_ref, s_ref, *, C, scale):
    b, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _load():
        fresh = start_ref[b] == 0
        s_ref[...] = jnp.where(fresh, 0.0, pool_ref[0, 0])

    q, k, v = q_ref[0], k_ref[0], v_ref[0]       # [Hb, C, dk|dv]
    kt = kt_ref[0, :, 0]                         # [Hb, dk, C]
    gam_row = gb_ref[0, :, 0, 0:1, :]            # [Hb, 1, C]
    beta_row = gb_ref[0, :, 0, 1:2, :]
    gam_col = _to_col(gam_row, C)                # [Hb, C, 1]
    beta_col = _to_col(beta_row, C)
    s0 = s_ref[...]                              # [Hb, dk, dv]

    r = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)[None]
    cc = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)[None]
    diff = gam_col - gam_row                     # [t, i]: gamma_t - gamma_i
    decay = jnp.where(r >= cc, jnp.exp(jnp.where(r >= cc, diff, 0.0)), 0.0)
    kk = _bmm(k, kt)                             # [Hb, C, C], symmetric
    # N = -A (strictly lower) and its transpose M, built side by side
    n_mat = jnp.where(r > cc, -(beta_col * decay * kk), 0.0)
    m_mat = jnp.where(
        r < cc,
        -(beta_row * jnp.exp(jnp.where(r < cc, -diff, 0.0)) * kk), 0.0)
    # forward substitution: row i of T' = (I - N)^-1 - I is
    # N[i] + sum_j N[i, j] T'[j], the rows above already final
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)[None]
    t_mat = n_mat
    for i in range(1, C):
        col = m_mat[:, :, i:i + 1]               # N[i, :] as a column
        row = n_mat[:, i:i + 1, :] + jnp.sum(col * t_mat, axis=1,
                                             keepdims=True)
        t_mat = jnp.where(rows == i, row, t_mat)
    t_mat = t_mat + jnp.where(r == cc, 1.0, 0.0)

    e_col = jnp.exp(gam_col)
    rhs = beta_col * (v - e_col * _bmm(k, s0))
    u = _bmm(t_mat, rhs)                         # [Hb, C, dv]
    o = e_col * _bmm(q, s0) + _bmm(_bmm(q, kt) * decay, u)
    o_ref[0] = (o * scale).astype(o_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, C), 2)
    gam_last = jnp.sum(jnp.where(lane == C - 1, gam_row, 0.0), axis=2,
                       keepdims=True)            # [Hb, 1, 1]
    s_ref[...] = jnp.exp(gam_last) * s0 + \
        _bmm(kt * jnp.exp(gam_last - gam_row), u)

    @pl.when(c == pl.num_programs(2) - 1)
    def _store():
        pool_out_ref[0, 0] = s_ref[...]


def _step_kernel(slots_ref, start_ref, layer_ref,
                 q_ref, k_ref, v_ref, g_ref, beta_ref, pool_ref,
                 o_ref, pool_out_ref, *, dk, scale):
    b = pl.program_id(0)
    s = jnp.where(start_ref[b] == 0, 0.0, pool_ref[0, 0])   # [Hb, dk, dv]
    k_col = _to_col(k_ref[0], dk)                # [Hb, dk, 1]
    q_col = _to_col(q_ref[0], dk)
    s = s * jnp.exp(g_ref[0])
    u = beta_ref[0] * (v_ref[0] - jnp.sum(k_col * s, axis=1,
                                          keepdims=True))
    s = s + k_col * u
    o_ref[0] = (jnp.sum(q_col * s, axis=1, keepdims=True)
                * scale).astype(o_ref.dtype)
    pool_out_ref[0, 0] = s


def _pad(n, to):
    return -(-n // to) * to


def head_tile(H, dk, dv, C):
    """Heads per grid step: the largest divisor of ``H`` whose blocks,
    carried state and temporaries stay under ``_VMEM_BUDGET``."""
    lanes_k, lanes_v, lanes_c = _pad(dk, 128), _pad(dv, 128), _pad(C, 128)
    per_head = 4 * (
        5 * _pad(dk, 8) * lanes_v               # pool in/out x2, scratch
        + 2 * 2 * C * lanes_k                   # q, k
        + 2 * _pad(dk, 8) * lanes_c             # kT
        + 2 * 2 * C * lanes_v                   # v, o
        + 10 * C * lanes_c + 5 * C * lanes_v)   # temporaries
    cap = max(1, _VMEM_BUDGET // per_head)
    return max(t for t in range(1, H + 1) if H % t == 0 and t <= cap)


def _prefetch(slots, start, layer):
    return (jnp.asarray(slots, jnp.int32), jnp.asarray(start, jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1))


def pallas_gated_delta_chunk(q, k, v, g, beta, state_pool, layer, slots,
                             start, interpret=None):
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(CHUNK, T)
    if T % C:
        raise ValueError(f"slice length {T} is not whole chunks of {C}")
    NC = T // C
    Hb = head_tile(H, dk, dv, C)
    f32 = jnp.float32
    qh, kh, vh = (x.astype(f32).transpose(0, 2, 1, 3) for x in (q, k, v))
    kt = kh.reshape(B, H, NC, C, dk).swapaxes(3, 4)        # [B,H,NC,dk,C]
    gam = jnp.cumsum(g.astype(f32).reshape(B, NC, C, H), axis=2)
    gb = jnp.stack([gam, beta.astype(f32).reshape(B, NC, C, H)], axis=3)
    gb = gb.transpose(0, 4, 1, 3, 2)                       # [B,H,NC,2,C]

    def tok(b, h, c, *refs):
        return (b, h, c, 0)

    def per_chunk(b, h, c, *refs):
        return (b, h, c, 0, 0)

    def slot(b, h, c, slots_ref, start_ref, layer_ref):
        return (layer_ref[0], slots_ref[b], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, H // Hb, NC),
        in_specs=[
            pl.BlockSpec((1, Hb, C, dk), tok),
            pl.BlockSpec((1, Hb, C, dk), tok),
            pl.BlockSpec((1, Hb, 1, dk, C), per_chunk),
            pl.BlockSpec((1, Hb, C, dv), tok),
            pl.BlockSpec((1, Hb, 1, 2, C), per_chunk),
            pl.BlockSpec((1, 1, Hb, dk, dv), slot),
        ],
        out_specs=[
            pl.BlockSpec((1, Hb, C, dv), tok),
            pl.BlockSpec((1, 1, Hb, dk, dv), slot),
        ],
        scratch_shapes=[pltpu.VMEM((Hb, dk, dv), f32)])
    o, pool = pl.pallas_call(
        functools.partial(_chunk_kernel, C=C,
                          scale=float(1.0 / np.sqrt(dk))),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), f32),
                   jax.ShapeDtypeStruct(state_pool.shape, f32)],
        input_output_aliases={8: 1},             # the pool, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        **kernel_name("gated_delta_chunk"),
    )(*_prefetch(slots, start, layer), qh, kh, kt, vh, gb, state_pool)
    return o.transpose(0, 2, 1, 3), pool


def pallas_gated_delta_step(q, k, v, g, beta, state_pool, layer, slots,
                            start, interpret=None):
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    if T != 1:
        raise ValueError(f"the step kernel takes one token a lane, got {T}")
    Hb = head_tile(H, dk, dv, 8)
    f32 = jnp.float32
    qh, kh, vh = (x.astype(f32).transpose(0, 2, 1, 3) for x in (q, k, v))
    gh, bh = (x.astype(f32).transpose(0, 2, 1)[..., None]
              for x in (g, beta))                          # [B, H, 1, 1]

    def lane(b, h, *refs):
        return (b, h, 0, 0)

    def slot(b, h, slots_ref, start_ref, layer_ref):
        return (layer_ref[0], slots_ref[b], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, H // Hb),
        in_specs=[
            pl.BlockSpec((1, Hb, 1, dk), lane),
            pl.BlockSpec((1, Hb, 1, dk), lane),
            pl.BlockSpec((1, Hb, 1, dv), lane),
            pl.BlockSpec((1, Hb, 1, 1), lane),
            pl.BlockSpec((1, Hb, 1, 1), lane),
            pl.BlockSpec((1, 1, Hb, dk, dv), slot),
        ],
        out_specs=[
            pl.BlockSpec((1, Hb, 1, dv), lane),
            pl.BlockSpec((1, 1, Hb, dk, dv), slot),
        ])
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, dk=dk,
                          scale=float(1.0 / np.sqrt(dk))),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, dv), f32),
                   jax.ShapeDtypeStruct(state_pool.shape, f32)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        **kernel_name("gated_delta_step"),
    )(*_prefetch(slots, start, layer), qh, kh, vh, gh, bh, state_pool)
    return o.transpose(0, 2, 1, 3), pool


def _dispatch_chunk(q, k, v, g, beta, state_pool, layer, slots, start):
    T = q.shape[1]
    if T % min(CHUNK, T) or min(CHUNK, T) % 8:
        note_fallback("gated_delta_chunk", "slice_misaligned", f"T={T}")
        return reference_gated_delta(q, k, v, g, beta, state_pool, layer,
                                     slots, start)
    return pallas_gated_delta_chunk(q, k, v, g, beta, state_pool, layer,
                                    slots, start)


def gated_delta_rule(q, k, v, g, beta, state_pool, layer, slots, start,
                     t_len):
    """The rule over a ragged ``[B, T]`` program: the step kernel at
    ``T = 1``, the chunked one otherwise. Returns ``(o, state_pool')``."""
    from . import get_op
    valid = jnp.arange(q.shape[1])[None, :] < t_len[:, None]
    g = jnp.where(valid[..., None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    op = "gated_delta_step" if q.shape[1] == 1 else "gated_delta_chunk"
    return get_op(op)(q, k, v, g, beta, state_pool, layer, slots, start)


register_op("gated_delta_chunk", reference_gated_delta, _dispatch_chunk)
register_op("gated_delta_step", reference_gated_delta,
            pallas_gated_delta_step)
