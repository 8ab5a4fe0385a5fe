"""Fused gather-matmul / reduce-scatter-epilogue kernels (ISSUE 18).

The contract under test is the transport-swap twin discipline:

- ``reference_fused_gather_matmul`` is BITWISE-equal to the unfused
  gather-then-matmul pipeline (ring gathers are pure data movement,
  the consumption kernel is shared) for both shard layouts;
- the ``streamed`` schedule (the in-flight ring form the Pallas kernel
  realizes) is value-equal — chunked K-summation reorders fp32
  accumulation, never semantics;
- the resident-chunk Pallas kernel (interpret mode) matches the same
  oracle — it runs the ring kernel's exact compute schedule with the
  transport swapped for HBM chunks;
- layout guards fall back to the reference twin LOUDLY
  (``ops.fallback_report``);
- ``fused_qrs_exchange`` is bitwise-equal to the native ``all_to_all``
  it replaces, and the fused quant+EF epilogue matches the host twin
  under jit (the engine always runs jitted).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from hcache_deepspeed_tpu.ops import fallback_report, reset_fallback_report
from hcache_deepspeed_tpu.ops.fused_collective_matmul import (
    ShardedQuantizedTensor, fused_qrs_exchange, pallas_fused_gather_matmul,
    pallas_fused_gather_matmul_resident, reference_fused_gather_matmul,
    streamed_fused_gather_matmul)
from hcache_deepspeed_tpu.ops.quantized_matmul import (
    quantize_for_matmul, quantized_matmul)
from hcache_deepspeed_tpu.parallel.topology import DATA_AXIS


def _shmap(fn, in_specs, out_specs):
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), (DATA_AXIS,))
    return jax.jit(functools.partial(
        jax.shard_map, mesh=mesh, axis_names={DATA_AXIS},
        in_specs=in_specs, out_specs=out_specs, check_vma=False)(fn))


def _mk(K=64, N=16, M=4, group_k=8, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    q, s = quantize_for_matmul(w, group_k)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    return x, q, s


def _unfused(x, q_sh, s_sh, dim, group_k):
    """The unfused pipeline: native gather, assemble, shared matmul."""
    def asm(sh):
        per = jax.lax.all_gather(sh, DATA_AXIS)
        parts = jnp.moveaxis(per, 0, dim)
        shape = sh.shape[:dim] + (-1,) + sh.shape[dim + 1:]
        return parts.reshape(shape)
    return quantized_matmul(x, asm(q_sh), asm(s_sh), group_k=group_k)


class TestGatherMatmulTwins:

    @pytest.mark.parametrize("dim", [0, 1])
    def test_reference_bitwise_vs_unfused(self, eight_devices, dim):
        x, q, s = _mk()

        def fused(q_sh, s_sh):
            return reference_fused_gather_matmul(
                x, q_sh, s_sh, group_k=8, axis_name=DATA_AXIS,
                shard_dim=dim)

        def unfused(q_sh, s_sh):
            return _unfused(x, q_sh, s_sh, dim, 8)

        specs = (P(DATA_AXIS), P(DATA_AXIS)) if dim == 0 else \
            (P(None, DATA_AXIS), P(None, DATA_AXIS))
        a = _shmap(fused, specs, P())(q, s)
        b = _shmap(unfused, specs, P())(q, s)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("dim", [0, 1])
    def test_streamed_value_equal(self, eight_devices, dim):
        x, q, s = _mk()

        def streamed(q_sh, s_sh):
            return streamed_fused_gather_matmul(
                x, q_sh, s_sh, group_k=8, axis_name=DATA_AXIS,
                shard_dim=dim)

        def unfused(q_sh, s_sh):
            return _unfused(x, q_sh, s_sh, dim, 8)

        specs = (P(DATA_AXIS), P(DATA_AXIS)) if dim == 0 else \
            (P(None, DATA_AXIS), P(None, DATA_AXIS))
        a = _shmap(streamed, specs, P())(q, s)
        b = _shmap(unfused, specs, P())(q, s)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)

    def test_resident_kernel_interpret_matches_oracle(self):
        """The interpret-mode-testable half of the kernel pair: chunked
        resident schedule vs the shared whole-matrix kernel."""
        x, q, s = _mk(K=512, N=128, M=16, group_k=32, seed=3)
        m, k_sh = 4, 128
        q_all = q.reshape(m, k_sh, 128)
        s_all = s.reshape(m, k_sh // 32, 128)
        out = pallas_fused_gather_matmul_resident(
            x, q_all, s_all, group_k=32, interpret=True)
        ref = quantized_matmul(x, q, s, group_k=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-3, rtol=1e-3)


class TestFallbacks:

    def test_unsupported_layout_falls_back_loudly(self, eight_devices):
        """N-sharded (shard_dim=1) rides the reference twin — counted,
        reason recorded, result still bitwise vs the unfused pipeline."""
        x, q, s = _mk(seed=4)
        reset_fallback_report()

        def fused(q_sh, s_sh):
            return pallas_fused_gather_matmul(
                x, q_sh, s_sh, group_k=8, axis_name=DATA_AXIS,
                shard_dim=1)

        def unfused(q_sh, s_sh):
            return _unfused(x, q_sh, s_sh, 1, 8)

        specs = (P(None, DATA_AXIS), P(None, DATA_AXIS))
        a = _shmap(fused, specs, P())(q, s)
        b = _shmap(unfused, specs, P())(q, s)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert fallback_report()["fused_gather_matmul"][
            "unsupported_layout"] >= 1


class TestShardedQuantizedTensor:

    def test_pytree_roundtrip_keeps_static_coords(self):
        _, q, s = _mk()
        sqt = ShardedQuantizedTensor(q[:8], s[:1], 8, 0, DATA_AXIS,
                                     groups=[[0, 1], [2, 3]])
        leaves, treedef = jax.tree.flatten(sqt)
        back = jax.tree.unflatten(treedef, leaves)
        assert isinstance(back, ShardedQuantizedTensor)
        assert back.group_k == 8 and back.dim == 0
        assert back.axis_name == DATA_AXIS
        assert back.groups == ((0, 1), (2, 3))
        np.testing.assert_array_equal(np.asarray(back.q),
                                      np.asarray(q[:8]))

    def test_matmul_and_gather_bitwise(self, eight_devices):
        x, q, s = _mk(seed=5)

        def via_tensor(q_sh, s_sh):
            sqt = ShardedQuantizedTensor(q_sh, s_sh, 8, 0, DATA_AXIS)
            full = sqt.gather()
            return sqt.matmul(x), full.q, full.scale

        def unfused(q_sh, s_sh):
            return _unfused(x, q_sh, s_sh, 0, 8)

        specs = (P(DATA_AXIS), P(DATA_AXIS))
        y, qf, sf = _shmap(via_tensor, specs, (P(), P(), P()))(q, s)
        b = _shmap(unfused, specs, P())(q, s)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(b))
        # the backward-recompute gather reassembles the exact bits
        np.testing.assert_array_equal(np.asarray(qf), np.asarray(q))
        np.testing.assert_array_equal(np.asarray(sf), np.asarray(s))


class TestReduceScatterEpilogue:

    def test_qrs_exchange_bitwise_vs_all_to_all(self, eight_devices):
        rng = np.random.default_rng(6)
        pay = jnp.asarray(rng.integers(-127, 128, (8, 8, 6)), jnp.int8)
        sc = jnp.asarray(rng.normal(size=(8, 8, 2)), jnp.float32)

        def fused(p, s):
            return fused_qrs_exchange(p[0], s[0], axis_name=DATA_AXIS)

        def native(p, s):
            return (jax.lax.all_to_all(p[0], DATA_AXIS, 0, 0),
                    jax.lax.all_to_all(s[0], DATA_AXIS, 0, 0))

        specs = (P(DATA_AXIS), P(DATA_AXIS))
        outs = (P(DATA_AXIS), P(DATA_AXIS))
        fp, fs = _shmap(fused, specs, outs)(pay, sc)
        npay, ns = _shmap(native, specs, outs)(pay, sc)
        np.testing.assert_array_equal(np.asarray(fp), np.asarray(npay))
        np.testing.assert_array_equal(np.asarray(fs), np.asarray(ns))

    def test_fused_quant_ef_matches_host_twin_under_jit(self):
        """The engine always runs jitted; under jit the fused Pallas
        epilogue (interpret mode here) is bitwise-equal to the host
        twin — same quantize / dequantize / subtract trio."""
        from hcache_deepspeed_tpu.ops.fused_collective_matmul import (
            pallas_fused_quant_ef, reference_fused_quant_ef)
        rng = np.random.default_rng(7)
        wide = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
        res = jnp.asarray(rng.normal(size=(4, 64)) * 0.01, jnp.float32)
        ref = jax.jit(functools.partial(
            reference_fused_quant_ef, group_size=16))(wide, res)
        out = jax.jit(functools.partial(
            pallas_fused_quant_ef, group_size=16,
            interpret=True))(wide, res)
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
