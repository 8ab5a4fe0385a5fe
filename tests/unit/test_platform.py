"""Peaks and limits are known or it is an error: one table keyed by
``device_kind`` with the published figures, no 0 standing for
"unknown", and a KV pool that is never sized from a guess on a TPU.
"""

import pytest

from hcache_deepspeed_tpu import platform
from hcache_deepspeed_tpu.platform import (CPUPlatform, TPUPlatform,
                                           UnknownPeakError)


class _Kind(TPUPlatform):
    """A TPU platform reporting a chosen ``device_kind``."""

    def __init__(self, kind, stats=None):
        self._kind, self._stats = kind, stats

    def device_kind(self):
        return self._kind

    def memory_stats(self, device=None):
        return dict(self._stats)


def test_v5e_peaks_are_the_published_ones():
    v5e = _Kind("TPU v5 lite")
    assert v5e.peak_tflops("bfloat16") == 197.0
    assert v5e.peak_tflops("int8") == 393.0
    assert v5e.peak_hbm_gbps() == 819.0
    assert _Kind("TPU v5e").peak_tflops() == 197.0


def test_unknown_device_kind_raises():
    with pytest.raises(UnknownPeakError, match="TPU v99"):
        _Kind("TPU v99").peak_tflops("bfloat16")
    with pytest.raises(UnknownPeakError, match="TPU v99"):
        _Kind("TPU v99").peak_hbm_gbps()


def test_fp32_has_no_published_peak_and_is_not_half_of_bf16():
    with pytest.raises(UnknownPeakError, match="float32"):
        _Kind("TPU v5 lite").peak_tflops("float32")


def test_host_cpu_has_no_peak():
    with pytest.raises(UnknownPeakError):
        CPUPlatform().peak_tflops("bfloat16")
    with pytest.raises(UnknownPeakError):
        CPUPlatform().peak_hbm_gbps()


def test_step_metrics_refuse_a_zero_peak_and_skip_mfu_without_one():
    from hcache_deepspeed_tpu.telemetry.metrics import StepMetrics
    with pytest.raises(ValueError, match="peak_tflops"):
        StepMetrics(peak_tflops=0.0, flops_per_token=1.0)
    labels = [e[0] for e in StepMetrics(
        peak_tflops=None, flops_per_token=6e6).events(1, 0.5, tokens=100)]
    assert "Train/tokens_per_sec" in labels and "Train/mfu" not in labels
    with_peak = dict((e[0], e[1]) for e in StepMetrics(
        peak_tflops=197.0, flops_per_token=6e9).events(
            1, 1.0, tokens=1000))
    assert with_peak["Train/mfu"] == pytest.approx(6e12 / 1e12 / 197.0)


def test_kv_pool_is_not_sized_from_a_guess_on_a_tpu():
    """A TPU backend that reports no memory limit is an error in reserve
    mode; the 1 GiB stand-in is the CPU test platform's alone."""
    from hcache_deepspeed_tpu.inference.config import KVCacheConfig
    from hcache_deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from hcache_deepspeed_tpu.models.llama import llama_tiny
    none = {"bytes_in_use": 0, "bytes_limit": 0, "peak_bytes_in_use": 0}
    try:
        platform.set_platform(_Kind("TPU v5 lite", none))
        with pytest.raises(RuntimeError, match="no free device memory"):
            InferenceEngineV2._size_cache_blocks(llama_tiny(),
                                                 KVCacheConfig())
        platform.set_platform(_Kind(
            "TPU v5 lite", dict(none, bytes_limit=16 << 30)))
        assert InferenceEngineV2._size_cache_blocks(
            llama_tiny(), KVCacheConfig()) > 16
    finally:
        platform._platform = None


def test_engine_construction_rejects_a_cache_layout_the_kernel_cannot_tile():
    """Where the paged kernel is what will run, a block size x head size
    that no tile fits is a typed error at construction with the rows,
    the bytes and the limit — not a Mosaic crash at the first dispatch."""
    import jax
    import numpy as np

    from hcache_deepspeed_tpu.inference import (InferenceEngineV2,
                                                RaggedInferenceEngineConfig)
    from hcache_deepspeed_tpu.models.llama import (LlamaForCausalLM,
                                                   llama_tiny)
    from hcache_deepspeed_tpu.ops.paged_attention import \
        PagedAttentionBudgetError
    cfg = llama_tiny(max_positions=128, use_flash=False)
    params = LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)},
        train=False)["params"]

    def build(block_size):
        return InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig(
            state_manager={"max_context": 128},
            kv_cache={"block_size": block_size, "num_blocks": 4}))

    try:
        platform.set_platform("tpu")
        with pytest.raises(PagedAttentionBudgetError) as exc:
            build(8192)
        message = str(exc.value)
        assert "512 query rows" in message and "block_size=8192" in message
        assert "bytes" in message and "budget" in message
        assert isinstance(exc.value, ValueError)
        platform.set_platform("cpu")     # the reference runs: no budget
        build(8192)
    finally:
        platform._platform = None
