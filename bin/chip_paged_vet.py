#!/usr/bin/env python3
"""On-chip vet for the paged-attention kernel, program alone: Mosaic
lowering, parity vs the dense-gather oracle, and device time a call at
the shapes the benchmark's serve cells give it (lanes, heads, table
width and contexts as their traced runs report them); the Mistral
decode shape also at ``head_tile=1``, one head a grid step (0: the
kernel's own pick). Heads of 128 only: the kernel's DMA out of the pool
wants whole lane tiles, and the dispatcher hands narrower heads to the
reference (``head_dim_misaligned``).

Timing method: scan-stretch SLOPE — (t_256 - t_32)/224, medians of
interleaved draws. A single timed dispatch carries a fixed cost that at
32 iterations reads as phantom kernel time.

Emits JSON lines, each naming the device; needs the chip (exits non-zero
without one, and when any variant emitted an error row). To read a
parent beside a change, run this file from both checkouts in one call:
    python bin/chip_paged_vet.py
Arguments: prefixes of the shapes to run (default: all), as in
``python bin/chip_paged_vet.py cmda`` for the windowed cell's table: the
kernel under its window and without it at contexts of 2k, 8k, 16k and
24k, decode and slice shapes.
"""
import functools
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _spread(live, lo, hi):
    """``live`` context lengths spread evenly over ``lo..hi``."""
    return [lo + (hi - lo) * i // max(live - 1, 1) for i in range(live)]


def _lanes(contexts, bucket, T=1):
    """``(start, kv_len)`` of a bucket of ``bucket`` lanes of ``T``
    positions: a live lane a context (its ``T`` positions end it, on a
    multiple of ``T``), the rest padding (no context, as the engine pads
    a bucket)."""
    ends = [c // T * T for c in contexts]
    pad = [0] * (bucket - len(ends))
    return [e - T for e in ends] + pad, ends + pad


#: name: (B, T, Hq, KV, D, BS, NBLK, NB, mask_block, (start, kv_len),
#: head tiles[, window]). The cells' shapes: PERF.md sections 5 and 6
#: (PR 43; the ``cmda-`` ones PR 56).
SHAPES = {
    # sdar-serve-block-denoise: 64 lanes of a block of 4, 48 live at
    # 650-900 tokens and one near 2,000; and its prompt slice
    "sdar-block": (64, 4, 32, 4, 128, 64, 4096, 36, 4,
                   _lanes(_spread(47, 650, 900) + [2000], 64, 4), (0,)),
    "sdar-slice": (1, 512, 32, 4, 128, 64, 4096, 36, 4,
                   ([512], [1024]), (0,)),
    # m7b-serve-chat-steady: 8 decode lanes, 2 live at 400-500 tokens
    "m7b-decode": (8, 1, 32, 8, 128, 64, 2560, 32, 1,
                   _lanes([420, 480], 8), (1, 0)),
    "m7b-slice": (1, 512, 32, 8, 128, 64, 2560, 32, 1,
                  ([0], [512]), (0,)),
    # olmoh-serve-long-prompt: 8 decode lanes, 7 live at 3-4k tokens;
    # and a 512-token slice at position 2048
    "olmoh-decode": (8, 1, 30, 30, 128, 64, 1536, 128, 1,
                     _lanes(_spread(7, 3000, 4000), 8), (0,)),
    "olmoh-slice": (1, 512, 30, 30, 128, 64, 1536, 128, 1,
                    ([2048], [2560]), (0,)),
}
# cmdaplus-serve-mixed-length: 128 query heads over 8 KV heads of 128, a
# table of 512 slots; 16 decode lanes, 4 live at each context, and a
# 512-token slice ending at it; under the window (the window layers'
# pool, 2,336 blocks) and without (the global layer's, 6,144)
for _ctx in (2048, 8192, 16384, 24576):
    for _window, _blocks in ((4096, 2336), (None, 6144)):
        _tag = f"{_ctx // 1024}k-" + ("window" if _window else "global")
        SHAPES[f"cmda-decode-{_tag}"] = (
            16, 1, 128, 8, 128, 64, _blocks, 512, 1,
            _lanes([_ctx - 40 * i for i in range(4)], 16), (0,), _window)
        SHAPES[f"cmda-slice-{_tag}"] = (
            1, 512, 128, 8, 128, 64, _blocks, 512, 1,
            ([_ctx - 512], [_ctx]), (0,), _window)


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
            failed.append(row["shape"])
        print(json.dumps(dict(row, **device)), flush=True)

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

    wanted = tuple(sys.argv[1:])
    for name, (B, T, Hq, KV, D, BS, NBLK, NB, MB, (start, kvl), tiles,
               *window) in SHAPES.items():
        if wanted and not name.startswith(wanted):
            continue
        (window,) = window or (None,)
        rng = np.random.default_rng(0)
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(keys[0], (B, T, Hq, D), jnp.bfloat16)
        # a two-layer [L, KV, P, D] pool, read at layer 1
        kp = jax.random.normal(keys[1], (2, KV, NBLK * BS, D), jnp.bfloat16)
        vp = jax.random.normal(keys[2], (2, KV, NBLK * BS, D), jnp.bfloat16)
        blocks = -(-np.asarray(kvl) // BS)
        # a lane's blocks anywhere in the pool; under a window the pool
        # holds a window a lane and the table's entries behind it are
        # never read
        held = blocks if window is None else np.minimum(
            blocks, -(-(window + T) // BS) + 1)
        tables = np.zeros((B, NB), np.int32)
        free = list(rng.permutation(NBLK))
        for b in range(B):
            for slot in range(blocks[b] - held[b], blocks[b]):
                tables[b, slot] = free.pop()
        # past a lane's own blocks a table is zero-padded, as the engine
        # packs it
        start = jnp.asarray(start, jnp.int32)
        kvl = jnp.asarray(kvl, jnp.int32)
        shape_row = {
            "phase": "paged-vet", "shape": name, "lanes": B, "rows": T,
            "table_slots": B * NB, "blocks_walked": int(held.sum()),
            # K and V of the lanes' exact contexts (under a window: the
            # positions their rows see), once a call
            "kv_mb": round(int(np.minimum(
                np.asarray(kvl), np.asarray(kvl) if window is None
                else window + T - 1).sum()) * 2 * KV * D * 2 / 1e6, 3)}
        # a padded lane is zeros from the kernel and a mean of V from
        # the oracle's softmax over nothing: the live lanes are compared
        live = np.asarray(kvl) > 0
        extra = {} if window is None else {"window": window}
        # the dense oracle where its scores fit (a slice of 128 heads
        # over a 32k table is 8.6 GB of them)
        ref = None
        if B * T * Hq * NB * BS * 4 < 2 << 30:
            ref = np.asarray(jax.jit(
                lambda q, kp, vp: reference_paged_attention(
                    q, kp, vp, 1, tables, start, kvl, BS, MB, **extra))(
                        q, kp, vp), np.float32)[live]
        for tile in tiles:
            try:
                call = functools.partial(
                    pallas_paged_attention, layer=1, tables=tables,
                    start=start, kv_len=kvl, block_size=BS,
                    interpret=False, head_tile=tile, mask_block=MB,
                    **extra)
                out = np.asarray(jax.jit(call)(q, kp, vp), np.float32)
                err = None if ref is None else \
                    float(np.max(np.abs(out[live] - ref)))

                # device time: N kernel iterations inside ONE dispatch (a
                # dispatch-per-call chain is enqueue-bound and reads the
                # same for every variant). Loop-carried q
                # perturbation keeps LICM from hoisting the kernel.
                @functools.partial(jax.jit, static_argnums=(3,))
                def stretch(q, kp, vp, n, call=call):
                    def step(c, _):
                        qq = q + (c * 1e-12).astype(q.dtype)
                        o = call(qq, kp, vp)
                        return c + jnp.abs(o).sum().astype(jnp.float32), ()
                    c, _ = jax.lax.scan(step, jnp.float32(0), None,
                                        length=n)
                    return c

                ms = slope_ms(stretch, q, kp, vp)
                emit(dict(shape_row, head_tile=tile, window=window,
                          max_abs_err=None if err is None
                          else round(err, 5),
                          ok=bool(np.isfinite(out[live]).all()) and
                          (err is None or err < 0.05),
                          device_ms_per_iter=ms))
            except Exception as e:
                emit(dict(shape_row, head_tile=tile, error=str(e)[:300]))
        del q, kp, vp
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
