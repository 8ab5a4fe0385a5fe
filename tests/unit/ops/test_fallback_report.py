"""One ledger for every reference substitution.

A dispatcher that gives way to its jnp reference on a shape its kernel
cannot cover — or because ``HDS_DISABLE_PALLAS`` says so on a platform
that has the kernel — calls ``ops.note_fallback`` first. The
substitution is counted per (op, reason), warned ONCE per pair, and
read back through ``ops.fallback_report()``; nothing falls back
silently.
"""

import logging

import jax.numpy as jnp
import numpy as np
import pytest

from hcache_deepspeed_tpu import ops
from hcache_deepspeed_tpu.utils.logging import logger


@pytest.fixture
def ledger():
    """A clean ledger plus the WARNING records emitted meanwhile."""
    records = []

    class Collect(logging.Handler):
        def emit(self, record):
            records.append(record)

    handler = Collect(level=logging.WARNING)
    logger.addHandler(handler)
    ops.reset_fallback_report()
    yield records
    logger.removeHandler(handler)
    ops.reset_fallback_report()


def _flash():
    from hcache_deepspeed_tpu.ops.flash_attention import pallas_attention
    q = jnp.ones((1, 96, 2, 16), jnp.float32)     # 96 < the 128 block
    return pallas_attention(q, q, q, interpret=True)


def _paged(D=16, block=12):                     # block of 12: 12 % 8
    from hcache_deepspeed_tpu.ops.paged_attention import \
        _dispatch_paged_attention
    q = jnp.ones((1, 1, 2, D), jnp.float32)
    pool = jnp.ones((1, 2, 4 * block, D), jnp.float32)
    return _dispatch_paged_attention(
        q, pool, pool, 0, np.zeros((1, 4), np.int32),
        jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32), block)


def _paged_head():
    return _paged(D=64, block=16)               # heads of 64: 64 % 128


def _rms():
    from hcache_deepspeed_tpu.ops.rms_norm import pallas_rms_norm
    return pallas_rms_norm(jnp.ones((300, 32)), jnp.ones((32,)),
                           interpret=True)      # 300 % 256


def _quantize():
    from hcache_deepspeed_tpu.ops.quantizer import pallas_quantize
    return pallas_quantize(jnp.ones((65 * 256,)), interpret=True)


def _quantize_fp8():
    from hcache_deepspeed_tpu.ops.fp_quantizer import pallas_quantize_fp8
    return pallas_quantize_fp8(jnp.ones((9 * 2048,)), interpret=True)


def _qmm():
    from hcache_deepspeed_tpu.ops.quantized_matmul import (
        pallas_quantized_matmul, quantize_for_matmul)
    q, scale = quantize_for_matmul(jnp.ones((192, 256)), 64)
    return pallas_quantized_matmul(jnp.ones((17, 192)), q, scale,
                                   group_k=64, block_m=8, interpret=True)


@pytest.mark.parametrize("call, op, reason", [
    (_flash, "flash_attention", "seq_not_block_multiple"),
    (_paged, "paged_attention", "block_misaligned"),
    (_paged_head, "paged_attention", "head_dim_misaligned"),
    (_rms, "rms_norm", "rows_not_block_multiple"),
    (_quantize, "quantize", "groups_not_block_multiple"),
    (_quantize_fp8, "quantize_fp8", "groups_not_block_multiple"),
    (_qmm, "quantized_matmul", "tile_misaligned"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_reference_fallback_is_counted_and_warned_once(ledger, call, op,
                                                       reason):
    assert ops.fallback_report() == {}
    call()
    call()
    assert ops.fallback_report() == {op: {reason: 2}}
    warned = [r for r in ledger if r.levelno == logging.WARNING]
    assert len(warned) == 1, [r.getMessage() for r in warned]
    message = warned[0].getMessage()
    assert op in message and reason in message
    assert "fallback_report" in message


def test_each_op_reason_pair_warns_on_its_own(ledger):
    ops.note_fallback("some_op", "reason_a", "M=1")
    ops.note_fallback("some_op", "reason_b")
    ops.note_fallback("other_op", "reason_a")
    ops.note_fallback("some_op", "reason_a")
    assert ops.fallback_report() == {
        "other_op": {"reason_a": 1},
        "some_op": {"reason_a": 2, "reason_b": 1}}
    assert len(ledger) == 3


def test_disable_pallas_on_a_chip_platform_is_a_recorded_fallback(
        ledger, monkeypatch):
    """On a platform that has the kernels, ``HDS_DISABLE_PALLAS=1``
    swaps every one for its reference — and the report says so. On the
    CPU platform there is no kernel to hide and nothing is recorded."""
    from hcache_deepspeed_tpu import platform
    from hcache_deepspeed_tpu.ops.rms_norm import reference_rms_norm
    monkeypatch.setenv("HDS_DISABLE_PALLAS", "1")
    try:
        platform.set_platform("cpu")
        assert ops.get_op("rms_norm") is reference_rms_norm
        assert ops.fallback_report() == {}
        platform.set_platform("tpu")
        assert ops.get_op("rms_norm") is reference_rms_norm
        assert ops.fallback_report() == {
            "rms_norm": {"HDS_DISABLE_PALLAS=1": 1}}
        assert "rms_norm" in ops.op_report()
    finally:
        platform._platform = None
