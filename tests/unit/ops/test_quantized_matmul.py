"""Fused int8-weight matmul (reference: the weight-only quantized linear
path, deepspeed/inference/quantization + csrc/quantization)."""

import jax.numpy as jnp
import numpy as np

from hcache_deepspeed_tpu.ops.quantized_matmul import (
    pallas_quantized_matmul, quantize_for_matmul,
    reference_quantized_matmul)


def _mk(M=64, K=128, N=256, group_k=32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    q, scale = quantize_for_matmul(w, group_k=group_k)
    return x, w, q, scale


def test_quantize_for_matmul_roundtrip():
    _, w, q, scale = _mk()
    K, N = q.shape
    back = (q.astype(jnp.float32).reshape(K // 32, 32, N)
            * np.asarray(scale)[:, None, :]).reshape(K, N)
    err = np.abs(np.asarray(back) - np.asarray(w)).max()
    assert err < np.abs(np.asarray(w)).max() / 100


def test_reference_matches_dense_matmul():
    x, w, q, scale = _mk()
    out = reference_quantized_matmul(x, q, scale, group_k=32)
    dense = np.asarray(x) @ np.asarray(w)
    rel = np.abs(np.asarray(out) - dense).max() / np.abs(dense).max()
    assert rel < 0.02


def test_pallas_interpret_matches_reference():
    x, w, q, scale = _mk()
    ref = reference_quantized_matmul(x, q, scale, group_k=32)
    out = pallas_quantized_matmul(x, q, scale, group_k=32, block_m=32,
                                  block_n=128, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


def test_shape_fallback():
    """block_k follows group_k (one scale row per k-block), so odd K
    that still divides by the group runs the kernel — many k-blocks,
    looser fp32 accumulation-order tolerance — and K NOT divisible by
    the group takes the reference path."""
    x, w, q, scale = _mk(M=32, K=320, N=256, group_k=32, seed=1)
    out = pallas_quantized_matmul(x, q, scale, group_k=32, block_m=32,
                                  block_n=256, interpret=True)
    ref = reference_quantized_matmul(x, q, scale, group_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4)
    # a ragged M that does not divide block_m trips the fallback (the
    # reference path), which must agree exactly
    x2, w2, q2, scale2 = _mk(M=32, K=192, N=256, group_k=64, seed=2)
    out2 = pallas_quantized_matmul(x2[:17], q2, scale2, group_k=64,
                                   block_m=16, interpret=True)
    ref2 = reference_quantized_matmul(x2[:17], q2, scale2, group_k=64)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               atol=1e-5)


def test_tile_chooser_covers_7b_shapes():
    """The chosen (block_n, groups_per_block) must tile every Llama-7B
    matmul at both serving group sizes — a non-dividing tile silently
    drops the shape onto the dequant fallback (observed on chip: qkv
    and gate_up — 74% of the weight bytes — ran dequantized) — and must
    keep the grid small: per-step Mosaic dispatch overhead is the cost
    driver in both regimes (measured 478 GB/s at 32 one-group decode
    steps vs 681 GB/s dense; 7B prefill 15x off the streaming ceiling
    at 1536 steps/matmul)."""
    from hcache_deepspeed_tpu.ops.quantized_matmul import _choose_tiles
    h, ffn = 4096, 11008
    shapes = {"qkv": (h, 3 * h), "o": (h, h),
              "gate_up": (h, 2 * ffn), "down": (ffn, h)}
    for M, bm, step_cap in ((8, 8, 50), (64, 64, 200)):
        for gk in (128, 256):
            for name, (K, N) in shapes.items():
                if K % gk:
                    continue
                got = _choose_tiles(M, K, N, gk, bm)
                assert got is not None, (name, gk, M)
                bn, gpb = got
                assert N % bn == 0 and bn % 128 == 0, (name, gk, bn)
                assert (K // gk) % gpb == 0, (name, gk, gpb)
                steps = (M // bm) * (N // bn) * (K // (gpb * gk))
                assert steps <= step_cap, (name, gk, M, steps)


def test_sliced_scale_path_numeric():
    """Numerics of the gpb%8==0 STATIC scale-row path (the blocking
    every 7B qkv/o matmul takes at serving group sizes) and of the
    default chooser-driven blocking — the tile-arithmetic test above
    cannot catch a wrong sliced BlockSpec index map."""
    import jax

    from hcache_deepspeed_tpu.ops.quantized_matmul import _choose_tiles
    # K=1024, group 128 -> G=8 -> chooser picks gpb=8 (sliced scale)
    x, w, q, scale = _mk(M=8, K=1024, N=256, group_k=128, seed=3)
    bn, gpb = _choose_tiles(8, 1024, 256, 128, 8)
    assert gpb % 8 == 0, "shape no longer drives the sliced-scale path"
    ref = reference_quantized_matmul(x, q, scale, group_k=128)
    out = pallas_quantized_matmul(x, q, scale, group_k=128,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)
    # compute regime (M>32) through the default chooser
    x2, _, q2, scale2 = _mk(M=64, K=1024, N=256, group_k=128, seed=4)
    ref2 = reference_quantized_matmul(x2, q2, scale2, group_k=128)
    out2 = pallas_quantized_matmul(x2, q2, scale2, group_k=128,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               atol=1e-3, rtol=1e-3)


def test_make_batched_matches_one_shot():
    """Per-layer streaming quantization (the 7B OOM fix) must produce
    exactly the one-shot stacked result — including from a host numpy
    leaf, which streams one layer at a time."""
    import numpy as onp

    from hcache_deepspeed_tpu.ops.quantized_matmul import \
        MatmulQuantizedTensor
    rng = onp.random.default_rng(0)
    w = rng.standard_normal((3, 64, 48)).astype(onp.float32)
    one = MatmulQuantizedTensor.make(jnp.asarray(w), group_k=32)
    for leaf in (jnp.asarray(w), w):          # device and host inputs
        bat = MatmulQuantizedTensor.make_batched(leaf, group_k=32)
        onp.testing.assert_array_equal(onp.asarray(bat.q),
                                       onp.asarray(one.q))
        onp.testing.assert_allclose(onp.asarray(bat.scale),
                                    onp.asarray(one.scale), rtol=1e-6)
        assert bat.group_k == one.group_k


def test_choose_tiles_scale_with_activation_bytes():
    """The VMEM estimate must price the x/out tiles at the ACTUAL
    activation itemsize: a 4-byte (fp32) input picks a smaller tile
    than the 2-byte (bf16) default — the bf16 blocking would overflow
    the budget once the tiles are really fp32."""
    from hcache_deepspeed_tpu.ops.quantized_matmul import _choose_tiles
    M, K, N, G, BM = 256, 4096, 4096, 256, 256
    bn2, gpb2 = _choose_tiles(M, K, N, G, BM, x_bytes=2)
    bn4, gpb4 = _choose_tiles(M, K, N, G, BM, x_bytes=4)
    assert (bn4, gpb4) != (bn2, gpb2)

    def vmem(bn, gpb, xb):
        bk = gpb * G
        rows = gpb if gpb % 8 == 0 else K // G
        return (2 * bk * bn + 2 * BM * bk * xb + 2 * rows * bn * 4
                + BM * bn * 4 + 2 * BM * bn * xb)

    budget = 10 * 2**20
    assert vmem(bn4, gpb4, 4) <= budget
    # the bf16 choice priced at fp32 bytes overflows — exactly the
    # miscount the dtype-derived estimate fixes
    assert vmem(bn2, gpb2, 4) > budget


def test_reference_fallback_still_computes_the_reference():
    """A shape the tiles cannot cover returns the reference result (the
    ledger side is pinned in test_fallback_report.py)."""
    from hcache_deepspeed_tpu.ops import quantized_matmul as qmm
    x, w, q, scale = _mk(M=32, K=192, N=256, group_k=64, seed=3)
    # ragged M against an explicit block_m: 17 % 8 != 0
    out = qmm.pallas_quantized_matmul(
        x[:17], q, scale, group_k=64, block_m=8, interpret=True)
    ref = qmm.reference_quantized_matmul(x[:17], q, scale, group_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4)


class TestFusedConsumption:
    """The ZeRO++ fused qwZ consumption contract: a
    ``MatmulQuantizedTensor`` handed to an ``nn.Dense`` through the
    interceptor computes through the fused kernel and is equal to
    dequant-then-matmul within the kernel's documented tile tolerance
    (atol/rtol 1e-3 at fp32, the pallas-vs-reference bound above)."""

    def test_interceptor_matches_dequant_then_matmul(self):
        import flax.linen as nn
        import jax

        from hcache_deepspeed_tpu.ops.quantized_matmul import (
            MatmulQuantizedTensor, fused_dense_interceptor)
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal((128, 256)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((256,)), jnp.float32)
        x = jnp.asarray(rng.standard_normal((4, 9, 128)), jnp.float32)
        mqt = MatmulQuantizedTensor.make(w, group_k=32)
        dense = nn.Dense(256)
        with nn.intercept_methods(fused_dense_interceptor()):
            y = dense.apply({"params": {"kernel": mqt, "bias": b}}, x)
        ref = x @ mqt.dequantize() + b
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-3, rtol=1e-3)
        # a plain fp kernel passes through the interceptor untouched
        with nn.intercept_methods(fused_dense_interceptor()):
            y2 = dense.apply({"params": {"kernel": w, "bias": b}}, x)
        np.testing.assert_allclose(np.asarray(y2),
                                   np.asarray(x @ w + b), atol=1e-4,
                                   rtol=1e-4)

    def test_dequantize_oracle(self):
        from hcache_deepspeed_tpu.ops.quantized_matmul import (
            MatmulQuantizedTensor, reference_quantized_matmul)
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
        x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
        mqt = MatmulQuantizedTensor.make(w, group_k=32)
        ref = reference_quantized_matmul(x, mqt.q, mqt.scale, group_k=32)
        np.testing.assert_allclose(np.asarray(x @ mqt.dequantize()),
                                   np.asarray(ref), atol=1e-4, rtol=1e-4)

    def test_gathered_shard_assembly_matches_whole_weight(self):
        """Per-shard quantize_for_matmul + concat along the contraction
        dim (what the bucketed gather ships) == one valid fused-layout
        weight: group boundaries tile each shard evenly, so the
        assembled (q, scale) dequantizes to the per-shard dequants."""
        from hcache_deepspeed_tpu.ops.quantized_matmul import (
            MatmulQuantizedTensor, quantize_for_matmul)
        rng = np.random.default_rng(2)
        w = jnp.asarray(rng.standard_normal((128, 64)), jnp.float32)
        shards = jnp.split(w, 4, axis=0)           # [32, 64] each
        qs, ss = zip(*[quantize_for_matmul(s, group_k=32)
                       for s in shards])
        assembled = MatmulQuantizedTensor(
            jnp.concatenate(qs, axis=0), jnp.concatenate(ss, axis=0), 32)
        per_shard = jnp.concatenate(
            [MatmulQuantizedTensor(q, s, 32).dequantize()
             for q, s in zip(qs, ss)], axis=0)
        np.testing.assert_array_equal(np.asarray(assembled.dequantize()),
                                      np.asarray(per_shard))
