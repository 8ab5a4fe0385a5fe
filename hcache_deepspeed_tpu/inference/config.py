"""Inference-v2 engine configuration.

Reference analogs: ``deepspeed/inference/v2/config_v2.py``
(``RaggedInferenceEngineConfig``) and
``deepspeed/inference/v2/ragged/manager_configs.py``
(``DSStateManagerConfig``: max_tracked_sequences, max_ragged_batch_size,
max_ragged_sequence_count, memory_config). Same knob names where they still
mean something on TPU.
"""

from typing import Optional

from pydantic import Field

from ..runtime.config_utils import HDSConfigModel


class KVCacheConfig(HDSConfigModel):
    """Reference: ``AllocationMode``/``KVCacheConfig`` in manager_configs —
     'reserve' (fraction of free HBM) or explicit block count."""
    block_size: int = 64              # tokens per KV block (ref: KV_BLOCK)
    num_blocks: Optional[int] = None  # explicit pool size
    #: the memory budget of the window layers' pool of a trunk that has
    #: window layers beside global ones (``ragged/kv_cache.py
    #: WindowedKVCache``), in blocks, as ``num_blocks`` is the global
    #: layers'. None: the worst case, every tracked sequence past the
    #: window with a slice in flight (``ceil((window + slice) /
    #: block_size) + 1`` blocks each, and the scratch block), under
    #: which the window pool never refuses an admission. A deployment
    #: that tracks more sequences than it expects past the window sets
    #: less, and admission waits on whichever pool is short
    #: (``StateManager.has_room``)
    num_window_blocks: Optional[int] = None
    memory_fraction: float = 0.8      # used when num_blocks is None (TPU:
    #                                   sized from platform free-memory)
    cache_dtype: str = "bfloat16"


class StateManagerConfig(HDSConfigModel):
    max_tracked_sequences: int = 2048
    max_ragged_batch_size: int = 768      # max total tokens per forward
    max_ragged_sequence_count: int = 512  # max sequences per forward
    max_context: int = 8192               # max tokens of any one sequence
    #: > 0: prefills longer than this process in chunks of this size
    #: (the FastGen Dynamic-SplitFuse idea) — prompt length is then
    #: bounded by max_context, not by the per-forward token budget,
    #: and long prefills stop monopolizing a forward
    prefill_chunk: int = Field(0, ge=0)
    #: share full KV blocks across sequences with identical prompt
    #: prefixes (system prompts): a new sequence attaches the matching
    #: blocks by reference and prefills only the tail. Requires
    #: hcache.enable_latents=false (shared prefixes produce no latents,
    #: which would break the restore contract). No reference analog —
    #: FastGen lacks prefix caching.
    prefix_caching: bool = False


class HCacheConfig(HDSConfigModel):
    """The fork delta: latent capture + restore_kv (no reference config —
    the fork hard-enables it; here it is a switch)."""
    enable_latents: bool = True
    #: layers replayed per restore dispatch. 0 = auto: group layers so
    #: each chunk's latent slab is ~restore_chunk_bytes (per-layer
    #: dispatch — the reference's literal dual-stream shape — is
    #: latency-bound when the host link is slow; one giant dispatch
    #: can't overlap H2D with compute and caps at available HBM for
    #: million-token contexts; chunking interpolates)
    restore_chunk_layers: int = Field(0, ge=0)
    restore_chunk_bytes: int = 64 * 1024 * 1024
    #: dtype latents are captured/stored/shipped in; "" = the model's
    #: compute dtype (bit-exact restore). Restore is host-link-
    #: bandwidth-bound and latents live in host DRAM per evicted
    #: sequence, so "float8_e4m3fn" halves both the wire time and the
    #: storage bill for ~0.4% K/V error (latents are post-norm, O(1)
    #: scale — comfortably inside e4m3 range); K/V projections replay
    #: in the compute dtype either way
    latent_dtype: str = ""


class QuantizationConfig(HDSConfigModel):
    """Weight-only serving quantization (reference:
    ``deepspeed/inference/quantization`` — v1's int8 QuantLinear / MoQ
    checkpoints). Weights are stored int8 with per-group scales and
    dequantized inside the compiled forward; ~2x HBM capacity for
    weights at a small accuracy cost."""
    enabled: bool = False
    bits: int = 8
    group_size: int = 256
    #: leaves smaller than this stay full precision (norms, biases)
    min_size: int = 4096
    #: route the llama-trunk families' layer matmuls through the fused
    #: int8-weight Pallas kernel (ops/quantized_matmul.py) instead of
    #: dequantize-then-matmul — weights stream int8 from HBM and
    #: dequantize tile-by-tile in VMEM. Default ON: measured 12.8 vs
    #: 81.4 ms/token 7B decode floors (DECODE_DIAG_7B_FLOORS_V2); the
    #: kernel falls back to the dequant path per-matmul for shapes its
    #: tiles cannot cover and on platforms without Pallas, so the flag
    #: is a measurement escape hatch, not a safety knob.
    use_fused_kernel: bool = True


class RaggedInferenceEngineConfig(HDSConfigModel):
    state_manager: StateManagerConfig = Field(
        default_factory=StateManagerConfig)
    kv_cache: KVCacheConfig = Field(default_factory=KVCacheConfig)
    hcache: HCacheConfig = Field(default_factory=HCacheConfig)
    quantization: QuantizationConfig = Field(
        default_factory=QuantizationConfig)
    # tensor_parallel degree for sharding the KV-head dim over the mesh
    tensor_parallel: int = 1
