"""Config-combination smoke matrix.

The reference's config surface is exercised combinatorially by its CI
matrix (zero × precision × offload × features across ~40 pipelines);
here a deterministic sample of valid combinations goes through
initialize + two fused steps each, pinning the interactions (e.g.
fp16 loss scaling under ZeRO-3 with remat, LoRA over quantized base
with curriculum) that single-feature tests never cross.
"""

import numpy as np
import pytest

import hcache_deepspeed_tpu as hds
from hcache_deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_tiny

COMBOS = [
    # (id, config overrides)
    ("z1-fp16-gas2", {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "fp16": {"enabled": True, "initial_scale_power": 8},
        "zero_optimization": {"stage": 1, "min_shard_size": 1}}),
    ("z2-bf16-clip", {
        "bf16": {"enabled": True},
        "gradient_clipping": 0.5,
        "zero_optimization": {"stage": 2, "min_shard_size": 1}}),
    ("z3-remat-sched", {
        "zero_optimization": {"stage": 3, "min_shard_size": 1},
        "compile": {"remat_policy": "dots_with_no_batch_dims_saveable"},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_num_steps": 2}}}),
    ("z3-zeropp", {
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3, "min_shard_size": 1,
                              "zero_quantized_gradients": True,
                              "zero_quantized_weights": True}}),
    ("z2-lion-curriculum", {
        "optimizer": {"type": "Lion", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2, "min_shard_size": 1},
        "curriculum_learning": {"enabled": True,
                                "curriculum_type": "seqlen",
                                "min_difficulty": 8,
                                "max_difficulty": 16,
                                "schedule_type": "fixed_linear",
                                "schedule_config": {
                                    "total_curriculum_step": 4,
                                    "difficulty_step": 8}}}),
    ("z0-lora-pld", {
        # gpt2 module names (llama-style defaults match nothing here)
        "lora": {"enabled": True, "lora_r": 4, "lora_alpha": 8,
                 "target_mods": ["c_attn", "c_proj", "c_fc"]},
        "compression_training": {
            "progressive_layer_drop": {"enabled": True, "theta": 0.6}}}),
    ("z3-offload-cpu", {
        "zero_optimization": {"stage": 3, "min_shard_size": 1,
                              "offload_optimizer": {"device": "cpu"}}}),
]


@pytest.mark.parametrize("name,overrides",
                         COMBOS, ids=[c[0] for c in COMBOS])
def test_config_combo_initializes_and_steps(eight_devices, name,
                                            overrides):
    mcfg = gpt2_tiny()
    config = {
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 10 ** 9,
    }
    for key, val in overrides.items():
        config[key] = val
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, mcfg.vocab_size, (config["train_batch_size"], 16),
        dtype=np.int32)}
    engine, _, _, _ = hds.initialize(model=GPT2LMHeadModel(mcfg),
                                     config=config, example_batch=batch)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(2)]
    assert all(np.isfinite(l) for l in losses), (name, losses)
