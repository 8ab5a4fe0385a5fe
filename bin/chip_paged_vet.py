#!/usr/bin/env python3
"""On-chip vet for the head-tiled paged-attention kernel: Mosaic
lowering, parity vs the dense-gather oracle, and timing vs the
single-head grid (head_tile=1 reproduces the old kernel's schedule).

Timing method: scan-stretch SLOPE — (t_256 - t_32)/224, medians of
interleaved draws. A single timed dispatch carries a fixed cost that at
32 iterations reads as phantom kernel time.

Emits JSON lines, each naming the device; needs the chip (exits non-zero
without one, and when any variant emitted an error row):
    python bin/chip_paged_vet.py
"""
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hcache_deepspeed_tpu.ops.paged_attention import (
        pallas_paged_attention, reference_paged_attention)
    from hcache_deepspeed_tpu.platform import require_chip

    device = require_chip("chip_paged_vet")
    failed = []     # variants that emitted an error row: exit non-zero

    def emit(row):
        if "error" in row or row.get("ok") is False:
            failed.append(row["phase"])
        print(json.dumps(dict(row, **device)), flush=True)

    # 1B decode shape: 8 lanes, 32 heads, D=64, context 512
    rng = np.random.default_rng(0)
    B, T, Hq, KV, D, BS, NBLK, NB = 8, 1, 32, 32, 64, 64, 72, 8
    q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((KV, NBLK * BS, D)),
                     jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((KV, NBLK * BS, D)),
                     jnp.bfloat16)
    tables = rng.permutation(NBLK)[:B * NB].reshape(B, NB).astype(np.int32)
    start = jnp.asarray([511, 300, 128, 64, 511, 17, 480, 2], jnp.int32)
    kvl = start + 1

    ref = np.asarray(reference_paged_attention(
        q, kp, vp, tables, start, kvl, BS), np.float32)

    import functools

    def slope_ms(stretch, *operands, reps=5):
        """Per-iteration device time from interleaved 32/256-length
        stretch samples: median(t_256) - median(t_32) over 224 — the
        dispatch's fixed cost swamps any single /n reading.
        Returns None (not a negative 'floor') when unresolvable."""
        for n in (32, 256):
            float(stretch(*operands, n))      # warm both programs
        lo, hi = [], []
        for _ in range(reps):
            for n, acc in ((32, lo), (256, hi)):
                t0 = time.perf_counter()
                float(stretch(*operands, n))
                acc.append(time.perf_counter() - t0)
        lo.sort()
        hi.sort()
        s = (hi[reps // 2] - lo[reps // 2]) / 224 * 1000
        return round(s, 4) if s > 0 else None

    for tile in (1, 8, 32):
        try:
            fn = jax.jit(lambda q, kp, vp, t=tile: pallas_paged_attention(
                q, kp, vp, tables, start, kvl, BS, interpret=False,
                head_tile=t))
            out = np.asarray(fn(q, kp, vp), np.float32)
            err = float(np.max(np.abs(out - ref)))

            # device time: N kernel iterations inside ONE dispatch (a
            # dispatch-per-call chain is enqueue-bound and reads the
            # same for every variant). Loop-carried q
            # perturbation keeps LICM from hoisting the kernel.
            @functools.partial(jax.jit, static_argnums=(3,))
            def stretch(q, kp, vp, n, t=tile):
                def step(c, _):
                    qq = q + (c * 1e-12).astype(q.dtype)
                    o = pallas_paged_attention(
                        qq, kp, vp, tables, start, kvl, BS,
                        interpret=False, head_tile=t)
                    return c + jnp.abs(o).sum().astype(jnp.float32), ()
                c, _ = jax.lax.scan(step, jnp.float32(0), None, length=n)
                return c

            ms = slope_ms(stretch, q, kp, vp)
            emit({"phase": "paged-vet", "head_tile": tile,
                  "max_abs_err": round(err, 5),
                  "ok": err < 0.05, "device_ms_per_iter": ms})
        except Exception as e:
            emit({"phase": "paged-vet", "head_tile": tile,
                  "error": str(e)[:300]})

    # ---- experimental: block-major pool layout [NBLK, KV, BS, D].
    # Hypothesis: the head-major pool makes every (head-tile, block) DMA
    # KVT strided 16 KB segments; block-major makes it ONE contiguous
    # KVT*BS*D segment — if this wins big, the engine layout flips.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from hcache_deepspeed_tpu.ops.paged_attention import _NEG_INF

    def block_major_attention(q, kp_bm, vp_bm, tables, start, kvl, BS,
                              head_tile):
        B, T, Hq, D = q.shape
        NBLK, KV = kp_bm.shape[0], kp_bm.shape[1]
        G = Hq // KV
        NB = tables.shape[1]
        KVT = head_tile
        qg = q.reshape(B, T, KV, G, D).transpose(0, 2, 1, 3, 4).reshape(
            B, KV, T * G, D)
        TG = T * G
        TGp = max(8, -(-TG // 8) * 8)
        if TGp != TG:
            qg = jnp.pad(qg, ((0, 0), (0, 0), (0, TGp - TG), (0, 0)))

        def page_index(b, kh, nb, tables_ref, kvlen_ref, start_ref):
            last = jnp.maximum(kvlen_ref[b] - 1, 0) // BS
            return (tables_ref[b, jnp.minimum(nb, last)], kh, 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, KV // KVT, NB),
            in_specs=[
                pl.BlockSpec((1, KVT, TGp, D),
                             lambda b, kh, nb, *refs: (b, kh, 0, 0)),
                pl.BlockSpec((1, KVT, BS, D), page_index),
                pl.BlockSpec((1, KVT, BS, D), page_index),
            ],
            out_specs=pl.BlockSpec((1, KVT, TGp, D),
                                   lambda b, kh, nb, *refs: (b, kh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KVT, TGp, D), jnp.float32),
                pltpu.VMEM((KVT, TGp, 128), jnp.float32),
                pltpu.VMEM((KVT, TGp, 128), jnp.float32),
            ],
        )

        def kern(tables_ref, kvlen_ref, start_ref, q_ref, k_ref, v_ref,
                 o_ref, acc, m_s, l_s):
            # same online softmax as _kernel, block-major tile indexing
            b, nb = pl.program_id(0), pl.program_id(2)
            nblocks = pl.num_programs(2)

            @pl.when(nb == 0)
            def _init():
                acc[:] = jnp.zeros_like(acc)
                m_s[:] = jnp.full_like(m_s, _NEG_INF)
                l_s[:] = jnp.zeros_like(l_s)

            kvlen = kvlen_ref[b]
            st = start_ref[b]
            run = nb * BS < kvlen

            @pl.when(run)
            def _body():
                qq = q_ref[0]
                k = k_ref[0].astype(qq.dtype)
                s = jax.lax.dot_general(
                    qq, k, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32) / np.sqrt(D)
                rows = jax.lax.broadcasted_iota(jnp.int32, (TGp, BS), 0)
                cols = nb * BS + jax.lax.broadcasted_iota(
                    jnp.int32, (TGp, BS), 1)
                ok = (cols <= st + rows // G) & (cols < kvlen)
                s = jnp.where(ok[None], s, _NEG_INF)
                m_prev = m_s[:, :, :1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=2, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_s[:, :, :1] = corr * l_s[:, :, :1] + \
                    jnp.sum(p, axis=2, keepdims=True)
                m_s[:, :, :1] = m_new
                v = v_ref[0]
                acc[:] = acc[:] * corr + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)

            @pl.when(nb == nblocks - 1)
            def _out():
                l = l_s[:, :, :1]
                l = jnp.where(l == 0.0, 1.0, l)
                o_ref[0] = (acc[:] / l).astype(o_ref.dtype)

        out = pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, KV, TGp, D), q.dtype),
        )(tables, kvl, start, qg, kp_bm, vp_bm)
        out = out[:, :, :TG].reshape(B, KV, T, G, D).transpose(
            0, 2, 1, 3, 4)
        return out.reshape(B, T, Hq, D)

    kp_bm = jnp.asarray(np.asarray(kp).reshape(KV, NBLK, BS, D)
                        .transpose(1, 0, 2, 3))
    vp_bm = jnp.asarray(np.asarray(vp).reshape(KV, NBLK, BS, D)
                        .transpose(1, 0, 2, 3))
    for tile in (8, 32):
        try:
            fn = jax.jit(lambda q, kp_bm, vp_bm, t=tile:
                         block_major_attention(q, kp_bm, vp_bm, tables,
                                               start, kvl, BS, t))
            out = np.asarray(fn(q, kp_bm, vp_bm), np.float32)
            err = float(np.max(np.abs(out - ref)))

            @functools.partial(jax.jit, static_argnums=(3,))
            def stretch(q, kp_bm, vp_bm, n, t=tile):
                def step(c, _):
                    qq = q + (c * 1e-12).astype(q.dtype)
                    o = block_major_attention(qq, kp_bm, vp_bm, tables,
                                              start, kvl, BS, t)
                    return c + jnp.abs(o).sum().astype(jnp.float32), ()
                c, _ = jax.lax.scan(step, jnp.float32(0), None, length=n)
                return c

            ms = slope_ms(stretch, q, kp_bm, vp_bm)
            emit({"phase": "paged-vet-blockmajor", "head_tile": tile,
                  "max_abs_err": round(err, 5),
                  "ok": err < 0.05, "device_ms_per_iter": ms})
        except Exception as e:
            emit({"phase": "paged-vet-blockmajor", "head_tile": tile,
                  "error": str(e)[:300]})
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
