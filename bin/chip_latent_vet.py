#!/usr/bin/env python3
"""On-chip vet for the latent-attention kernel, program alone: Mosaic
lowering, parity vs the dense-gather oracle, and device time a call of
one layer at the shapes the cell ``glm47f-serve-long-doc`` gives it (20
heads over rows of 512 + 128, blocks of 64, a table of 512 slots): a
decode dispatch of 16 lanes at 4-28k tokens, and a 512-row prompt slice
that ends at 2k, 8k, 16k and 32k; the slice also with the absorb and
un-absorb products around the kernel (``W_uk``, ``W_uv``), which is what
a layer pays for the absorbed form.

Timing method: ``bin/chip_paged_vet.py``'s (slope of 256 against 32
calls in one program). Emits JSON lines, each naming the device; needs
the chip (exits non-zero without one, and when a row is not ok).
    python bin/chip_latent_vet.py
"""
import functools
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

H, C, R, ROPE, NOPE, V = 20, 512, 128, 64, 192, 256
BS, NBLK, NB = 64, 8192, 512
#: name: (lanes, rows, (start, kv_len))
SHAPES = {
    "decode-16": (16, 1, ([4000 + 1600 * i for i in range(16)],
                          [4001 + 1600 * i for i in range(16)])),
    "slice-2k": (1, 512, ([2048 - 512], [2048])),
    "slice-8k": (1, 512, ([8192 - 512], [8192])),
    "slice-16k": (1, 512, ([16384 - 512], [16384])),
    "slice-32k": (1, 512, ([32768 - 512], [32768])),
}


def slope_ms(stretch, *operands, reps=5, lengths=(32, 256)):
    """Per-iteration device time from interleaved short and long stretch
    samples: the difference of the medians over the difference of the
    lengths (the dispatch's fixed cost swamps any single /n reading).
    ``None`` when unresolvable."""
    short, long = lengths
    for n in lengths:
        float(stretch(*operands, n))      # warm both programs
    lo, hi = [], []
    for _ in range(reps):
        for n, acc in ((short, lo), (long, hi)):
            t0 = time.perf_counter()
            float(stretch(*operands, n))
            acc.append(time.perf_counter() - t0)
    lo.sort()
    hi.sort()
    s = (hi[reps // 2] - lo[reps // 2]) / (long - short) * 1000
    return round(s, 4) if s > 0 else None


def stretch_of(call):
    """``call(q, *rest)`` run ``n`` times inside one dispatch; a
    loop-carried perturbation of ``q`` keeps the call inside the loop."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(2,))
    def stretch(q, rest, n):
        def step(c, _):
            o = call(q + (c * 1e-12).astype(q.dtype), *rest)
            return c + jnp.abs(o).sum().astype(jnp.float32), ()
        c, _ = jax.lax.scan(step, jnp.float32(0), None, length=n)
        return c
    return stretch


def lanes_and_tables(B, start, kvl, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    tables = rng.permutation(NBLK)[:B * NB].reshape(B, NB).astype(np.int32)
    blocks = -(-np.asarray(kvl) // BS)
    tables[np.arange(NB)[None, :] >= blocks[:, None]] = 0
    return tables, blocks


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hcache_deepspeed_tpu.ops.latent_attention import (
        pallas_latent_attention, reference_latent_attention)
    from hcache_deepspeed_tpu.platform import require_chip

    device = require_chip("chip_latent_vet")
    failed = []

    def emit(row):
        if "error" in row or row.get("ok") is False:
            failed.append(row["shape"])
        print(json.dumps(dict(row, **device)), flush=True)

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    cp = jax.random.normal(keys[1], (2, 1, NBLK * BS, C), jnp.bfloat16)
    rp = jax.random.normal(keys[2], (2, 1, NBLK * BS, R), jnp.bfloat16)
    w_uk = jax.random.normal(keys[3], (H, NOPE, C), jnp.bfloat16) / 14
    w_uv = jax.random.normal(keys[4], (H, C, V), jnp.bfloat16) / 22
    scale = 1.0 / np.sqrt(NOPE + ROPE)
    for name, (B, T, (start, kvl)) in SHAPES.items():
        tables, blocks = lanes_and_tables(B, start, kvl)
        start = jnp.asarray(start, jnp.int32)
        kvl = jnp.asarray(kvl, jnp.int32)
        q = jax.random.normal(keys[0], (B, T, H, C + R), jnp.bfloat16)
        q = q.at[..., C + ROPE:].set(0)
        pairs = sum(T * int(k) - T * (T - 1) // 2 for k in np.asarray(kvl))
        row = {"phase": "latent-vet", "shape": name, "lanes": B, "rows": T,
               "blocks_walked": int(blocks.sum()),
               "rows_mb": round(int(np.asarray(kvl).sum()) * (C + R) * 2
                                / 1e6, 3),
               "gflop": round(H * pairs * 2 * (2 * C + ROPE) / 1e9, 3)}
        try:
            call = functools.partial(
                pallas_latent_attention, layer=1, tables=tables,
                start=start, kv_len=kvl, block_size=BS, scale=scale,
                interpret=False)
            out = np.asarray(jax.jit(call)(q, cp, rp), np.float32)
            ref = np.asarray(jax.jit(functools.partial(
                reference_latent_attention, layer=1, tables=tables,
                start=start, kv_len=kvl, block_size=BS,
                scale=scale))(q, cp, rp), np.float32) \
                if kvl.max() <= 8192 else None
            err = float(np.max(np.abs(out - ref))) if ref is not None \
                else None
            ms = slope_ms(stretch_of(call), q, (cp, rp))
            emit(dict(row, form="kernel", max_abs_err=err and round(err, 5),
                      ok=err is None or err < 0.05,
                      device_ms_per_iter=ms))
            if T > 1:
                def absorbed(qh, cp, rp):
                    """The layer's absorbed attention from the published
                    query ``[B, T, H, nope + rope]`` to ``o`` [B, T, H,
                    v]."""
                    q_abs = jnp.einsum("bthd,hdc->bthc", qh[..., :NOPE],
                                       w_uk)
                    pad = jnp.zeros(qh.shape[:3] + (R - ROPE,), qh.dtype)
                    u = call(jnp.concatenate(
                        [q_abs, qh[..., NOPE:], pad], -1), cp, rp)
                    return jnp.einsum("bthc,hcd->bthd", u, w_uv)
                qh = jax.random.normal(keys[5], (B, T, H, NOPE + ROPE),
                                       jnp.bfloat16)
                ms = slope_ms(stretch_of(absorbed), qh, (cp, rp))
                emit(dict(row, form="absorbed", ok=True,
                          device_ms_per_iter=ms))
        except Exception as e:                  # noqa: BLE001
            emit(dict(row, error=str(e)[:300]))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
