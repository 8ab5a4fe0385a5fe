"""Wire-byte attribution for the quantized collectives.

Every quantized wire site must report a MATCHED pair through the comms
logger — the actual (int8 + scales) bytes under its op name and the
full-width bytes the same collective would have carried under
``<op>_unquantized_equiv`` — using the leaf's ACTUAL dtype for the
equivalent (the qwZ site used to hard-code bf16, under-reporting fp32
runs 2x). Covered sites: qwZ bucketed/per-leaf gathers, qgZ per-leaf
all-to-all, the bucketed quantized reduce-scatter
(``runtime/zero/qwire.py``), and Domino's opt-in int8 all-reduce.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from hcache_deepspeed_tpu.comm.comms_logging import get_comms_logger
from hcache_deepspeed_tpu.parallel.topology import DATA_AXIS


@pytest.fixture
def comms():
    logger = get_comms_logger()
    logger.configure(enabled=True)
    logger.reset()
    yield logger
    logger.reset()
    logger.configure(enabled=False)


def _mesh():
    return Mesh(np.array(jax.devices()[:8]).reshape(8), (DATA_AXIS,))


def _shmap(fn, in_specs, out_specs):
    return jax.jit(functools.partial(
        jax.shard_map, mesh=_mesh(), axis_names={DATA_AXIS},
        in_specs=in_specs, out_specs=out_specs, check_vma=False)(fn))


def _pair(comms, op):
    """(wire_bytes, unquantized_equiv_bytes) recorded for ``op``."""
    summary = comms.wire_savings_summary()
    assert op in summary, (op, sorted(summary))
    rec = summary[op]
    return rec["wire_bytes"], rec["unquantized_equiv_bytes"]


class TestWireByteAttribution:

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_qwz_gather_pair_uses_actual_dtype(self, eight_devices,
                                               comms, dtype):
        from hcache_deepspeed_tpu.runtime.zero.zeropp import \
            make_leaf_gather
        x = jnp.arange(8 * 16 * 4, dtype=dtype).reshape(8 * 16, 4)

        def gather(x_local):
            return make_leaf_gather(qw=True, hpz=1, group_size=64)(
                x_local, None, 0)

        out = _shmap(gather, (P(DATA_AXIS),), P())(x)
        assert out.shape == x.shape
        wire, equiv = _pair(comms, "qwZ_all_gather")
        # the unquantized equivalent is the per-device shard in the
        # leaf's ACTUAL dtype (8 devices trace as one program)
        shard_elems = x.size // 8
        assert equiv == shard_elems * jnp.dtype(dtype).itemsize
        assert wire < equiv

    def test_qgz_all_to_all_pair(self, eight_devices, comms):
        from hcache_deepspeed_tpu.runtime.zero.zeropp import \
            _quant_reduce_mean_dim
        g = jnp.arange(8 * 32, dtype=jnp.float32).reshape(8 * 32)

        def reduce(g_full):
            return _quant_reduce_mean_dim(g_full, 0, group_size=64)

        # cotangent enters FULL per device (the VJP layout)
        out = _shmap(reduce, (P(),), P(DATA_AXIS))(g)
        assert out.shape == g.shape
        wire, equiv = _pair(comms, "qgZ_all_to_all")
        assert equiv == g.size * 4
        assert wire < equiv

    @pytest.mark.parametrize("bits,max_frac", [(8, 0.30), (4, 0.17)])
    def test_qrs_bucketed_pair_and_fraction(self, eight_devices, comms,
                                            bits, max_frac):
        from hcache_deepspeed_tpu.runtime.zero.qwire import (
            QRS_OP, quantized_bucket_reduce_scatter_mean)
        leaves = [jnp.ones((8 * 256,), jnp.float32),
                  jnp.ones((8 * 128, 2), jnp.float32)]
        dims = [0, 0]

        def reduce(a, b):
            out, _ = quantized_bucket_reduce_scatter_mean(
                [a, b], dims, bucket_elements=10 ** 9, group_size=2048,
                bits=bits, error_feedback=False)
            return tuple(out)

        out = _shmap(reduce, (P(), P()),
                     (P(DATA_AXIS), P(DATA_AXIS)))(*leaves)
        assert out[0].shape == leaves[0].shape
        wire, equiv = _pair(comms, QRS_OP)
        total = sum(x.size for x in leaves)
        assert equiv == total * 4
        assert wire / equiv <= max_frac, (wire, equiv)

    def test_domino_int8_allreduce_pair(self, eight_devices, comms):
        from hcache_deepspeed_tpu.comm.quantized import \
            quantized_allreduce_body
        x = jnp.ones((16, 64), jnp.float32)

        def ar(x_local):
            y, e = quantized_allreduce_body(x_local, jnp.zeros_like(
                x_local), DATA_AXIS, group_size=128)
            return y, e

        y, _ = _shmap(ar, (P(),), (P(), P()))(x)
        np.testing.assert_allclose(np.asarray(y), 8 * np.ones((16, 64)),
                                   rtol=1e-2)
        wire, equiv = _pair(comms, "domino_half_allreduce_int8")
        # both legs (reduce-scatter + gather) counted full-width
        assert equiv == 2 * x.size * 4
        assert wire < equiv


class TestInt4Pack:

    def test_roundtrip(self):
        from hcache_deepspeed_tpu.runtime.zero.qwire import (pack_int4,
                                                             unpack_int4)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.integers(-8, 8, (4, 33)), jnp.int8)
        packed = pack_int4(q)
        assert packed.dtype == jnp.uint8
        assert packed.shape == (4, 17)
        back = unpack_int4(packed, 33)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(q))
