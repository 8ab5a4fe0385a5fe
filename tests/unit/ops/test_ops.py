"""Kernel numerics vs reference (reference analog: tests/unit/ops/* —
kernel-vs-torch numerics). Pallas kernels run in interpret mode on CPU, so
the same code path that compiles on TPU is validated here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hcache_deepspeed_tpu.ops import (fallback_report, get_op_impl,
                                      op_report, reset_fallback_report)
from hcache_deepspeed_tpu.ops.flash_attention import (pallas_attention,
                                                      reference_attention)
from hcache_deepspeed_tpu.ops.quantizer import (pallas_quantize,
                                                reference_dequantize,
                                                reference_quantize)
from hcache_deepspeed_tpu.ops.rms_norm import (pallas_rms_norm,
                                               reference_rms_norm)
from hcache_deepspeed_tpu.ops.rope import apply_rope, rope_frequencies


def _flash_case(T=512, heads=(4, 1), D=128, causal=True, blocks=(128, 128),
                B=1, falls_back=None, tol=(2e-5, 1e-4)):
    return dict(T=T, heads=heads, D=D, causal=causal, blocks=blocks, B=B,
                falls_back=falls_back, tol=tol)


# T=512 in blocks of 128 is a 4 x 4 grid of tiles: tiles on, below and
# above the diagonal all occur, and under a causal mask two rows (or
# columns) share a grid slot
FLASH_CASES = {
    # GQA (4 query heads on 1 KV head: rep 4) and multi-head (rep 1),
    # causal and not, head sizes 128 and 64
    **{f"{'causal' if causal else 'full'}-rep{H // KV}-D{D}":
       _flash_case(heads=(H, KV), causal=causal, D=D)
       for causal in (True, False) for H, KV in ((4, 1), (2, 2))
       for D in (128, 64)},
    "rep2-two-kv-heads": _flash_case(heads=(4, 2)),
    # a tile that touches the diagonal is not (row == col) here
    "block_q>block_k": _flash_case(blocks=(256, 128)),
    "block_q<block_k": _flash_case(blocks=(128, 256)),
    "full-block_q>block_k": _flash_case(blocks=(256, 128), causal=False),
    # 384 and 256 do not divide each other: a slot has spare steps
    "undivided-blocks": _flash_case(T=768, blocks=(384, 256)),
    # three lines: the middle one has a slot to itself
    "odd-lines": _flash_case(T=384, heads=(2, 1)),
    "one-block": _flash_case(T=128, heads=(2, 1)),
    "one-block-full": _flash_case(T=128, heads=(2, 1), causal=False),
    # the tiles the kernels choose: 1024 x 512 forward (the row sums
    # kept over four lane groups), 1024 x 1024 backward
    "own-tiles": _flash_case(T=1024, heads=(2, 1), blocks=(None, None)),
    "batch-2": _flash_case(T=256, heads=(2, 1), B=2),
    # blocks under 128, or a length no block divides: the reference
    # runs, under the fall-back's name
    "blocks-64-causal": _flash_case(
        T=128, heads=(4, 4), D=64, blocks=(64, 64), B=2,
        falls_back="seq_not_block_multiple", tol=(2e-3, 5e-3)),
    "blocks-64-full": _flash_case(
        T=128, heads=(4, 4), D=64, blocks=(64, 64), B=2, causal=False,
        falls_back="seq_not_block_multiple", tol=(2e-3, 5e-3)),
    "blocks-64-D32": _flash_case(
        T=128, heads=(2, 2), D=32, blocks=(64, 64),
        falls_back="seq_not_block_multiple", tol=(2e-3, 5e-3)),
    "length-100": _flash_case(
        T=100, heads=(4, 4), D=64, blocks=(None, None), B=2,
        falls_back="seq_not_block_multiple", tol=(2e-3, 5e-3)),
}


class TestFlashAttention:
    @pytest.mark.parametrize("case", sorted(FLASH_CASES))
    def test_matches_reference(self, case):
        """Values and all three gradients against
        ``reference_attention``, the kernels in interpret mode."""
        c = FLASH_CASES[case]
        (H, KV), T, D, B = c["heads"], c["T"], c["D"], c["B"]
        ks = jax.random.split(jax.random.PRNGKey(T + H + KV + D), 4)
        q = jax.random.normal(ks[0], (B, T, H, D))
        k = jax.random.normal(ks[1], (B, T, KV, D))
        v = jax.random.normal(ks[2], (B, T, KV, D))
        w = jax.random.normal(ks[3], (B, T, H, D))
        tiling = {name: size for name, size in
                  zip(("block_q", "block_k"), c["blocks"]) if size}

        def kernel(q, k, v):
            return pallas_attention(q, k, v, causal=c["causal"],
                                    interpret=True, **tiling)

        def plain(q, k, v):
            return reference_attention(q, k, v, causal=c["causal"])

        reset_fallback_report()
        fwd_tol, grad_tol = c["tol"]
        np.testing.assert_allclose(
            np.asarray(kernel(q, k, v)), np.asarray(plain(q, k, v)),
            rtol=fwd_tol, atol=fwd_tol)
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * w),
                              argnums=(0, 1, 2))(q, k, v)
                     for f in (kernel, plain))
        for g, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=grad_tol, atol=grad_tol)
        # the kernels ran, or the reference did under its reason's name
        assert list(fallback_report().get("flash_attention", {})) == \
            ([c["falls_back"]] if c["falls_back"] else [])


class TestRMSNorm:
    def test_fwd(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 256))
        w = jax.random.normal(jax.random.PRNGKey(1), (256,)) + 1.0
        ref = reference_rms_norm(x, w)
        got = pallas_rms_norm(x, w, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("rows,block", [(520, 104), (528, 176),
                                            (544, 136), (576, 192),
                                            (512, 256), (8, 8), (300, 256)])
    def test_rows_of_a_fused_step_run_the_kernel(self, rows, block):
        """The rows of a step's decode lanes and prompt slice together
        (8, 16, 32 or 64 lanes and 512 positions) are no multiple of
        256: the kernel takes the most whole sublane tiles that divide
        them, and only rows that nothing divides go to the reference."""
        from hcache_deepspeed_tpu.ops import (fallback_report,
                                              reset_fallback_report)
        from hcache_deepspeed_tpu.ops.rms_norm import _block_rows
        assert _block_rows(rows) == block
        x = jax.random.normal(jax.random.PRNGKey(0), (1, rows, 128))
        w = jax.random.normal(jax.random.PRNGKey(1), (128,)) + 1.0
        reset_fallback_report()
        got = pallas_rms_norm(x, w, interpret=True)
        assert ("rms_norm" in fallback_report()) == bool(rows % block)
        reset_fallback_report()
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(reference_rms_norm(x, w)),
                                   rtol=1e-5, atol=1e-5)

    def test_bwd(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
        w = jax.random.normal(jax.random.PRNGKey(1), (128,)) + 1.0

        ref = jax.grad(lambda x, w: jnp.sum(reference_rms_norm(x, w) ** 2),
                       argnums=(0, 1))(x, w)
        got = jax.grad(
            lambda x, w: jnp.sum(pallas_rms_norm(x, w, interpret=True) ** 2),
            argnums=(0, 1))(x, w)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-4, atol=1e-4)


class TestRope:
    def test_rotation_preserves_norm(self):
        cos, sin = rope_frequencies(64, 128)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 64))
        out = apply_rope(x, cos, sin)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(out), axis=-1),
            np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)

    def test_position_zero_identity(self):
        cos, sin = rope_frequencies(32, 8)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 2, 32))
        out = apply_rope(x, cos, sin, positions=jnp.zeros((1, 1), jnp.int32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)

    def test_relative_property(self):
        # <rope(q,m), rope(k,n)> depends only on m-n
        cos, sin = rope_frequencies(32, 64)
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, 32))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 32))
        def dot_at(m, n):
            qm = apply_rope(q, cos, sin, jnp.full((1, 1), m, jnp.int32))
            kn = apply_rope(k, cos, sin, jnp.full((1, 1), n, jnp.int32))
            return float(jnp.sum(qm * kn))
        assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4


class TestQuantizer:
    @pytest.mark.parametrize("num_bits", [8, 4])
    def test_roundtrip_error_bounded(self, num_bits):
        x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
        q, s, shape, n = reference_quantize(x, group_size=256,
                                            num_bits=num_bits)
        out = reference_dequantize(q, s, shape, n)
        err = np.abs(np.asarray(out) - np.asarray(x)).max()
        step = np.abs(np.asarray(x)).max() / (2 ** (num_bits - 1) - 1)
        assert err <= step

    def test_pallas_matches_reference(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4096,))
        q1, s1, _, _ = reference_quantize(x, group_size=256)
        q2, s2, _, _ = pallas_quantize(x, group_size=256, interpret=True)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


class TestRegistry:
    def test_report(self):
        report = op_report()
        assert "flash_attention" in report

    def test_cpu_uses_reference(self):
        impl = get_op_impl("flash_attention")
        assert not impl.compatible()  # CPU: pallas not native
        assert impl.best() is impl.reference_fn
