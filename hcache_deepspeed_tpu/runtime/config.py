"""Training config system.

Reference analog: ``deepspeed/runtime/config.py`` (1,046 LoC
``DeepSpeedConfig``) + ``runtime/constants.py`` + per-subsystem pydantic
models (zero ``runtime/zero/config.py``, monitor, comms, …). The JSON schema
deliberately accepts the reference's keys (``train_batch_size``,
``zero_optimization.stage``, ``bf16.enabled`` …) so existing configs port
over; TPU-specific knobs live under ``mesh`` and new subsections.
"""

import json
from typing import Any, Dict, List, Literal, Optional, Union

from pydantic import Field, model_validator

from ..linear.config import DEFAULT_TARGET_MODS as _DEFAULT_TARGET_MODS
from ..utils.logging import logger
from .config_utils import HDSConfigModel


class HDSConfigError(Exception):
    pass


# ------------------------------------------------------------------ #
# Precision
# ------------------------------------------------------------------ #
class FP16Config(HDSConfigModel):
    """Reference: fp16 dict (runtime/config.py; loss scaler fp16/loss_scaler.py:91)."""
    enabled: bool = False
    loss_scale: float = 0.0  # 0 = dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0


class BF16Config(HDSConfigModel):
    """Reference: bf16 dict → BF16_Optimizer (runtime/bf16_optimizer.py:35).
    On TPU this is the native mode: bf16 params/compute, fp32 master+state."""
    enabled: bool = False
    immediate_grad_update: bool = True


# ------------------------------------------------------------------ #
# ZeRO
# ------------------------------------------------------------------ #
class OffloadConfig(HDSConfigModel):
    """Reference: runtime/zero/offload_config.py."""
    device: str = "none"  # none | cpu (host memory) | nvme
    nvme_path: str = "/tmp/hds_nvme"
    pin_memory: bool = True
    buffer_count: int = 4
    ratio: float = 1.0


class ZeroConfig(HDSConfigModel):
    """Reference: runtime/zero/config.py (361 LoC).

    TPU mapping: stage 1/2/3 become sharding choices over the ``data``
    mesh axis (optimizer state / +gradients / +params).

    On the explicit ZeRO++ step (any of qwZ/qgZ/hpZ on, layered
    gather), the overlap knobs control a REAL software pipeline
    (``runtime/zero/zeropp.py`` + ``runtime/zero/overlap.py`` — see
    docs/zero_overlap.md), not a compiler hint:

    * ``overlap_comm`` — True: double-buffered gather prefetch + lagged
      bucketed reduce-scatter (collectives legally overlap block
      compute, verified on compiled HLO by ``profiling/hlo_audit.py``).
      False: a fenced, genuinely sequential gather→compute→reduce
      schedule — the serialization fallback, not a no-op.
    * ``stage3_prefetch_bucket_size`` — parameters of gather lookahead;
      0 disables prefetch. The pipeline's prefetch quantum is one
      layer, so any value >= 1 requests depth 1, subject to the
      ``stage3_max_live_parameters`` cap (depth+1 layers + the
      embedding/head leaves must fit; too small to fit ONE layer is
      rejected at engine build).
    * ``reduce_bucket_size`` / ``allgather_bucket_size`` — ELEMENTS per
      flat collective bucket: block cotangents (gradients) coalesce
      into one reduce-scatter per bucket, parameter shards into one
      all-gather payload per bucket per dtype. A bucket smaller than
      the largest sharded leaf is rejected at engine build with an
      HDSConfigError (no silent clamping).

    On the GSPMD path (no ZeRO++ flags) XLA inserts and schedules the
    collectives itself and these knobs are accepted for config
    compatibility only.
    """
    stage: int = 0
    reduce_bucket_size: int = Field(500_000_000, gt=0,
                                    alias="reduce_bucket_size")
    allgather_bucket_size: int = Field(500_000_000, gt=0)
    overlap_comm: bool = True
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    offload_optimizer: OffloadConfig = Field(default_factory=OffloadConfig)
    offload_param: OffloadConfig = Field(default_factory=OffloadConfig)
    sub_group_size: int = 1_000_000_000
    stage3_max_live_parameters: int = Field(1_000_000_000, gt=0)
    stage3_max_reuse_distance: int = 1_000_000_000
    stage3_prefetch_bucket_size: int = Field(50_000_000, ge=0)
    stage3_param_persistence_threshold: int = 100_000
    stage3_gather_16bit_weights_on_model_save: bool = False
    zero_hpz_partition_size: int = 1  # ZeRO++ hierarchical partition size
    zero_quantized_weights: bool = False  # ZeRO++ qwZ
    zero_quantized_gradients: bool = False  # ZeRO++ qgZ
    #: Quantized WIRE for the gradient reduce lane of the layered
    #: ZeRO-3 step (``runtime/zero/qwire.py``): cotangent buckets are
    #: int8-quantized (+fp32 group scales), all-to-all'd, and
    #: dequant-accumulate-meaned locally in fp32 — the qgZ topology at
    #: IPG-bucket granularity. Requires stage 3 + a layered model spec;
    #: mutually exclusive with per-leaf qgZ.
    zero_quantized_reduce_scatter: bool = False
    #: Carry the per-device quantization error of the bucketed
    #: quantized reduce-scatter as residual state (1-bit worker-error
    #: machinery) and re-inject it next micro-step. Requires
    #: ``zero_quantized_reduce_scatter``.
    zero_reduce_scatter_error_feedback: bool = False
    #: Wire width of the quantized reduce-scatter payload: 8 (int8) or
    #: 4 (two values nibble-packed per byte). Scales stay fp32.
    zero_quantized_reduce_scatter_bits: int = 8
    #: qwZ forward fusion: block matmuls consume the gathered
    #: ``(int8, scales)`` payload directly through
    #: ``ops/quantized_matmul`` — the fp weight tensor never
    #: materializes for eligible (Dense-kernel) qwZ leaves. Requires
    #: ``zero_quantized_weights``.
    zero_quantized_weights_fused_matmul: bool = False
    #: ZeRO++ stage-3 gather granularity: scan-over-layers (gather one
    #: block at a time inside the micro step) when the model provides a
    #: layered spec (models/layered.py). False forces the whole-tree
    #: gather (peak param memory = full model).
    layered_gather: bool = True
    ignore_unused_parameters: bool = True
    round_robin_gradients: bool = False
    min_shard_size: int = 2 ** 14  # params smaller than this stay replicated
    shard_min_dim: bool = False

    @model_validator(mode="after")
    def _check_quantized_wire(self):
        # typed, parse-time rejection of nonsensical quantized-wire
        # combinations (stage interplay re-checked at engine build,
        # where the topology is known)
        from .zero.overlap import validate_quantized_wire
        validate_quantized_wire(
            quantized_reduce_scatter=self.zero_quantized_reduce_scatter,
            error_feedback=self.zero_reduce_scatter_error_feedback,
            bits=self.zero_quantized_reduce_scatter_bits,
            quantized_gradients=self.zero_quantized_gradients,
            fused_matmul=self.zero_quantized_weights_fused_matmul,
            quantized_weights=self.zero_quantized_weights)
        return self


# ------------------------------------------------------------------ #
# Optimizer / scheduler
# ------------------------------------------------------------------ #
class OptimizerConfig(HDSConfigModel):
    type: str = "Adam"
    params: Dict[str, Any] = Field(default_factory=dict)


class SchedulerConfig(HDSConfigModel):
    type: Optional[str] = None
    params: Dict[str, Any] = Field(default_factory=dict)


# ------------------------------------------------------------------ #
# Mesh / parallelism (TPU-specific; subsumes reference's mpu + elastic bits)
# ------------------------------------------------------------------ #
class MeshConfig(HDSConfigModel):
    pipe: int = 1
    data: int = -1
    expert: int = 1
    seq: int = 1
    tensor: int = 1
    zero: int = 1  # MiCS shard-group size (runtime/zero/mics.py analog)


class PipelineConfig(HDSConfigModel):
    """Reference: PipelineModule kwargs + pipeline dict (pipe/module.py:86)."""
    stages: int = 1
    partition_method: str = "uniform"  # uniform | parameters | type:<regex>
    activation_checkpoint_interval: int = 0
    micro_batches: Optional[int] = None  # default: gradient_accumulation_steps
    schedule: str = "1f1b"  # 1f1b (TrainSchedule) | gpipe


class ActivationCheckpointingConfig(HDSConfigModel):
    """Reference: runtime/activation_checkpointing/config + checkpointing.py.
    TPU mapping: jax.checkpoint policies; partition_activations → offload to
    sequence-sharded storage is native when seq axis exists."""
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native: named remat policy (nothing_saveable, dots_saveable,
    # dots_with_no_batch_dims_saveable, save_anything_but_these_names, ...)
    policy: Optional[str] = None


# ------------------------------------------------------------------ #
# Monitoring / logging
# ------------------------------------------------------------------ #
class TensorBoardConfig(HDSConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "HDSJobName"


class WandbConfig(HDSConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "hds_tpu"


class CometConfig(HDSConfigModel):
    """Reference: monitor/config.py CometConfig. ``mode`` (when set)
    wins over ``online`` — the two reference knobs describe the same
    choice."""
    enabled: bool = False
    project: str = ""
    workspace: str = ""
    api_key: str = ""
    experiment_name: str = ""
    online: bool = True
    mode: Literal["", "online", "offline"] = ""

    @property
    def is_offline(self) -> bool:
        if self.mode:
            return self.mode == "offline"
        return not self.online


class CSVConfig(HDSConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "HDSJobName"


class CommsLoggerConfig(HDSConfigModel):
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    prof_ops: List[str] = Field(default_factory=list)
    debug: bool = False


class FlopsProfilerConfig(HDSConfigModel):
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


# ------------------------------------------------------------------ #
# Elasticity (reference: elasticity/config.py)
# ------------------------------------------------------------------ #
class ElasticityConfig(HDSConfigModel):
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: List[int] = Field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    version: float = 0.2
    ignore_non_elastic_batch_info: bool = False
    prefer_larger_batch: bool = True


# ------------------------------------------------------------------ #
# Data types
# ------------------------------------------------------------------ #
class DataTypesConfig(HDSConfigModel):
    """Reference: ``data_types`` block (runtime/config.py
    get_data_types). ``grad_accum_dtype`` sets the dtype of the
    gradient ACCUMULATOR buffers (memory + accumulation precision
    across micro-steps; default fp32).

    The reference's separate top-level ``communication_data_type`` has
    no equivalent knob here, measured deliberately (see
    tests/unit/runtime/test_comm_dtype.py): XLA's SPMD partitioner
    flows the un-reduced partial gradients through the elementwise
    unscale/cast chain and materializes ONE combined all-reduce at the
    gradient-norm consumer — i.e. the reduction happens once per step
    at the gas boundary (the IPG-boundary behavior the reference
    hand-builds) in fp32, regardless of the accumulator dtype.
    Forcing a bf16 wire would need an explicit shard_map reduction and
    silently halve gradient-sum precision; exactness wins by default.
    The 1-bit/compressed path (``runtime/onebit.py``) is the opt-in
    lossy-wire story."""
    grad_accum_dtype: Optional[str] = None


class CheckpointConfig(HDSConfigModel):
    """Reference: checkpoint dict keys on runtime/config.py."""
    tag_validation: str = "Warn"
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: Dict[str, Any] = Field(default_factory=dict)
    async_save: bool = False


class WeightQuantizationConfig(HDSConfigModel):
    """MoQ quantize-aware training (reference: deepspeed/compression/
    weight_quantization shared_parameters + runtime/quantize.py)."""
    enabled: bool = False
    start_bits: int = 16
    target_bits: int = 8
    quantize_period: int = 100
    schedule_offset: int = 0
    quantize_groups: int = 1


class PLDConfig(HDSConfigModel):
    """Progressive layer drop (reference: progressive_layer_drop.py)."""
    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


class CompressionConfig(HDSConfigModel):
    """``compression_training`` block. Two families share it:

    * MoQ + PLD (flat keys, reference runtime/quantize.py) — typed
      fields below.
    * The structured library (reference deepspeed/compression/config.py:
      nested ``shared_parameters``/``different_groups`` per technique) —
      kept as raw dicts and parsed by ``compression.structured``.

    A nested ``weight_quantization`` block (it contains
    ``shared_parameters``) is routed to the structured library; the flat
    spelling keeps driving MoQ."""
    weight_quantization: WeightQuantizationConfig = Field(
        default_factory=WeightQuantizationConfig)
    progressive_layer_drop: PLDConfig = Field(default_factory=PLDConfig)
    weight_quantization_structured: Dict[str, Any] = Field(
        default_factory=dict)
    sparse_pruning: Dict[str, Any] = Field(default_factory=dict)
    row_pruning: Dict[str, Any] = Field(default_factory=dict)
    head_pruning: Dict[str, Any] = Field(default_factory=dict)
    channel_pruning: Dict[str, Any] = Field(default_factory=dict)
    activation_quantization: Dict[str, Any] = Field(default_factory=dict)
    layer_reduction: Dict[str, Any] = Field(default_factory=dict)

    @model_validator(mode="before")
    @classmethod
    def _route_nested_weight_quantization(cls, values):
        if isinstance(values, dict):
            wq = values.get("weight_quantization")
            if isinstance(wq, dict) and "shared_parameters" in wq:
                values = dict(values)
                values["weight_quantization_structured"] = \
                    values.pop("weight_quantization")
        return values

    def structured_block(self):
        """The raw ``compression_training`` sub-dict for
        ``compression.structured.get_compression_config`` — or ``None``
        when no structured technique is configured as enabled."""
        block = {}
        if self.weight_quantization_structured:
            block["weight_quantization"] = self.weight_quantization_structured
        for key in ("sparse_pruning", "row_pruning", "head_pruning",
                    "channel_pruning", "activation_quantization"):
            v = getattr(self, key)
            if v:
                block[key] = v
        def on(d):
            return bool((d.get("shared_parameters") or {}).get("enabled"))

        # layer_reduction is an init/export-time transform
        # (student_initialization), never applied in the train step —
        # it alone must not activate the engine's structured path
        if not any(on(v) for v in block.values()):
            return None
        if self.layer_reduction:
            block["layer_reduction"] = self.layer_reduction
        return {"compression_training": block}


class CurriculumLearningConfig(HDSConfigModel):
    """Reference: runtime/data_pipeline/curriculum_scheduler.py + the
    legacy ``curriculum_learning`` engine block. ``seqlen`` curricula are
    applied by the engine itself (batch seq truncation); other metrics go
    through ``data_pipeline.CurriculumSampler``."""
    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: Dict[str, Any] = Field(default_factory=dict)


class LoRAQuantizationConfig(HDSConfigModel):
    """Reference: deepspeed/linear/config.py QuantizationConfig."""
    enabled: bool = False
    q_bits: int = 8
    group_size: int = 512
    mantissa_bits: int = 0  # 0 = int groupwise; 2/3 = fp8 e5m2/e4m3


class LoRATrainingConfig(HDSConfigModel):
    """Reference: deepspeed/linear/config.py LoRAConfig — engine-level
    LoRA fine-tuning. The optimizer sees only the adapter factors; base
    weights are frozen (optionally quantized, QLoRA-style) and keep the
    engine's parameter sharding (the ``base_weight_sharding`` analog)."""
    enabled: bool = False
    lora_r: int = 64
    lora_alpha: float = 16.0
    target_mods: List[str] = Field(
        default_factory=lambda: list(_DEFAULT_TARGET_MODS))
    quantization: LoRAQuantizationConfig = Field(
        default_factory=LoRAQuantizationConfig)


class CompileConfig(HDSConfigModel):
    """Reference: DeepCompile (runtime/config.py compile block). On TPU the
    compiler is XLA; these knobs steer jit: donation, remat, combining.
    The persistent compilation cache (the AOT half of DeepCompile's
    value) is not a knob here: ``utils/compile_cache.py`` places it."""
    enabled: bool = True
    donate_params: bool = True
    remat_policy: Optional[str] = None
    collective_combining_mb: int = 0  # 0 = XLA default


# ------------------------------------------------------------------ #
# Top-level
# ------------------------------------------------------------------ #
class HDSConfig(HDSConfigModel):
    # batch trinity (reference: runtime/config.py batch resolution)
    train_batch_size: Optional[int] = None
    train_micro_batch_size_per_gpu: Optional[int] = None
    gradient_accumulation_steps: Optional[int] = None

    steps_per_print: int = 10
    wall_clock_breakdown: bool = False
    dump_state: bool = False
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    gradient_clipping: float = 0.0
    sparse_gradients: bool = False
    memory_breakdown: bool = False

    seed: int = 1234

    optimizer: Optional[OptimizerConfig] = None
    scheduler: Optional[SchedulerConfig] = None

    fp16: FP16Config = Field(default_factory=FP16Config)
    bf16: BF16Config = Field(default_factory=BF16Config)
    data_types: DataTypesConfig = Field(default_factory=DataTypesConfig)

    zero_optimization: ZeroConfig = Field(default_factory=ZeroConfig)
    zero_allow_untested_optimizer: bool = False
    zero_force_ds_cpu_optimizer: bool = True

    mesh: MeshConfig = Field(default_factory=MeshConfig)
    pipeline: PipelineConfig = Field(default_factory=PipelineConfig)
    sequence_parallel_size: int = 1
    tensor_parallel: Dict[str, Any] = Field(default_factory=dict)

    activation_checkpointing: ActivationCheckpointingConfig = Field(
        default_factory=ActivationCheckpointingConfig)
    curriculum_learning: CurriculumLearningConfig = Field(
        default_factory=CurriculumLearningConfig)
    compression_training: CompressionConfig = Field(
        default_factory=CompressionConfig)
    lora: LoRATrainingConfig = Field(default_factory=LoRATrainingConfig)

    tensorboard: TensorBoardConfig = Field(default_factory=TensorBoardConfig)
    wandb: WandbConfig = Field(default_factory=WandbConfig)
    comet: CometConfig = Field(default_factory=CometConfig)
    csv_monitor: CSVConfig = Field(default_factory=CSVConfig)
    comms_logger: CommsLoggerConfig = Field(default_factory=CommsLoggerConfig)
    flops_profiler: FlopsProfilerConfig = Field(
        default_factory=FlopsProfilerConfig)

    elasticity: ElasticityConfig = Field(default_factory=ElasticityConfig)
    checkpoint: CheckpointConfig = Field(default_factory=CheckpointConfig)
    compile: CompileConfig = Field(default_factory=CompileConfig)

    # ------------------------------------------------------------------ #
    def resolve_batch_sizes(self, dp_world_size: int):
        """Batch-size trinity: train = micro * grad_accum * dp_world.

        Reference: DeepSpeedConfig._configure_train_batch_size — any two
        determine the third; all three must stay consistent.
        """
        train, micro, gas = (self.train_batch_size,
                             self.train_micro_batch_size_per_gpu,
                             self.gradient_accumulation_steps)
        if train is not None and micro is not None and gas is not None:
            pass
        elif train is not None and micro is not None:
            gas = train // (micro * dp_world_size)
        elif train is not None and gas is not None:
            micro = train // (gas * dp_world_size)
        elif micro is not None and gas is not None:
            train = micro * gas * dp_world_size
        elif train is not None:
            gas = 1
            micro = train // dp_world_size
        elif micro is not None:
            gas = 1
            train = micro * dp_world_size
        else:
            raise HDSConfigError(
                "need at least train_batch_size or "
                "train_micro_batch_size_per_gpu in config")
        if micro * gas * dp_world_size != train or micro <= 0 or gas <= 0:
            raise HDSConfigError(
                f"batch sizes inconsistent: train_batch_size={train} != "
                f"micro({micro}) * grad_accum({gas}) * dp_world"
                f"({dp_world_size})")
        self.train_batch_size = train
        self.train_micro_batch_size_per_gpu = micro
        self.gradient_accumulation_steps = gas
        return train, micro, gas

    @property
    def compute_dtype(self):
        import jax.numpy as jnp
        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    @classmethod
    def from_any(cls, config: Union[None, str, Dict, "HDSConfig"]) -> "HDSConfig":
        if config is None:
            return cls()
        if isinstance(config, HDSConfig):
            return config
        if isinstance(config, str):
            with open(config) as fh:
                config = json.load(fh)
        if not isinstance(config, dict):
            raise HDSConfigError(f"cannot parse config of type {type(config)}")
        config = _lift_data_efficiency(config)
        return cls.model_validate(config)


def _lift_data_efficiency(config: Dict) -> Dict:
    """Accept the reference's NESTED curriculum location
    (``data_efficiency.data_sampling.curriculum_learning`` with
    per-metric ``curriculum_metrics`` —
    ``runtime/data_pipeline/config.py``) by lifting the first metric
    onto the legacy top-level ``curriculum_learning`` block this config
    models. A top-level block always wins."""
    de = config.get("data_efficiency")
    if not isinstance(de, dict) or "curriculum_learning" in config:
        return config
    ds = de.get("data_sampling") or {}
    # an explicitly-False outer switch disables the whole chain (the
    # reference gates on data_efficiency.enabled and
    # data_sampling.enabled); an absent switch does not veto a
    # deliberately-written inner block
    if de.get("enabled") is False or ds.get("enabled") is False:
        return config
    cl = ds.get("curriculum_learning") or {}
    if not cl.get("enabled"):
        return config
    metrics = cl.get("curriculum_metrics") or {}
    lifted = {"enabled": True}
    if metrics:
        name, m = sorted(metrics.items())[0]
        if len(metrics) > 1:
            from ..utils.logging import logger
            logger.warning(
                "data_efficiency defines %d curriculum metrics; only "
                "%r is lifted (multi-metric clustering is not "
                "implemented)", len(metrics), name)
        lifted.update({
            "curriculum_type": name,
            "min_difficulty": m.get("min_difficulty", 8),
            "max_difficulty": m.get("max_difficulty", 1024),
            "schedule_type": m.get("schedule_type", "fixed_linear"),
            "schedule_config": m.get("schedule_config", {}),
        })
    config = dict(config)
    config["curriculum_learning"] = lifted
    return config


def load_config(config) -> HDSConfig:
    cfg = HDSConfig.from_any(config)
    if cfg.fp16.enabled and cfg.bf16.enabled:
        raise HDSConfigError("fp16 and bf16 cannot both be enabled")
    return cfg
