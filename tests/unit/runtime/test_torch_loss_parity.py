"""Training loss parity against torch (reference framework semantics).

The BASELINE north star is throughput *at loss parity*; SURVEY §7 calls
out the loss-parity harness (matching init, Adam bias-correction/eps,
loss conventions) as a hard part. This test pins it end-to-end: the SAME
initial weights (via the HF converter), the SAME batches, torch AdamW vs
our engine's AdamW — per-step losses must track within tolerance for
several steps. A divergence in loss shifting, Adam epsilon placement,
bias correction, weight-decay coupling, or learning-rate application
shows up here as a growing per-step gap.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import hcache_deepspeed_tpu as hds  # noqa: E402
from hcache_deepspeed_tpu.checkpoint.hf_loader import (  # noqa: E402
    convert_hf_state_dict, hf_config_to_model)

LR, WD, BETAS, EPS = 1e-3, 0.01, (0.9, 0.999), 1e-8
STEPS, BATCH, SEQ = 5, 8, 16


def _batches():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 256, (BATCH, SEQ), dtype=np.int32)
            for _ in range(STEPS)]


def _torch_losses(hf_model, batches):
    opt = torch.optim.AdamW(hf_model.parameters(), lr=LR, betas=BETAS,
                            eps=EPS, weight_decay=WD)
    losses = []
    for b in batches:
        ids = torch.tensor(b, dtype=torch.long)
        out = hf_model(ids, labels=ids)   # HF shifts internally
        opt.zero_grad()
        out.loss.backward()
        opt.step()
        losses.append(float(out.loss))
    return losses


def _ours_losses(hf_model, batches, model_type="gpt2", replace_cfg=None,
                 **extra):
    import dataclasses
    mcfg, model = hf_config_to_model(hf_model.config)
    overrides = dict(replace_cfg or {})
    if model_type != "gpt2":   # llama family defaults to bf16 + flash
        overrides.setdefault("dtype", "float32")
        overrides.setdefault("use_flash", False)
    if overrides:
        # clone(), not type(model)(mcfg): MoE families build the llama
        # trunk with mlp_cls=MoEMLP, which reconstruction would drop
        model = model.clone(cfg=dataclasses.replace(mcfg, **overrides))
    params = convert_hf_state_dict(hf_model, model_type)
    engine, _, _, _ = hds.initialize(
        model=model, init_params=params,
        config={
            "train_batch_size": BATCH,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": LR, "betas": list(BETAS),
                                     "eps": EPS, "weight_decay": WD}},
            "steps_per_print": 10 ** 9,
            **extra,
        })
    return [float(engine.train_batch(batch={"input_ids": b}))
            for b in batches]


class TestTorchLossParity:
    @pytest.mark.parametrize("extra", [
        {},
        {"zero_optimization": {"stage": 3}},
    ], ids=["dp", "zero3"])
    def test_gpt2_adamw_loss_trajectories_match(self, eight_devices,
                                                extra):
        cfg = transformers.GPT2Config(
            vocab_size=256, n_positions=128, n_embd=64, n_layer=2,
            n_head=4, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
        torch.manual_seed(0)
        hf_model = transformers.GPT2LMHeadModel(cfg).train()
        batches = _batches()
        want = _torch_losses(hf_model, batches)

        torch.manual_seed(0)
        hf_fresh = transformers.GPT2LMHeadModel(cfg)  # same init
        got = _ours_losses(hf_fresh.eval(), batches, **extra)

        # fp32 end to end: the trajectories agree to float tolerance
        # (measured ~2e-7); any loss-shift / bias-correction / eps /
        # weight-decay-coupling mismatch is orders of magnitude larger
        np.testing.assert_allclose(got, want, rtol=1e-4)

    def test_mixtral_adamw_loss_trajectories_match(self, eight_devices):
        # MoE: exact top-k routing + expert gradients vs transformers.
        # HF's default loss is pure CE (router aux only with
        # output_router_logits), so our aux coefficient is zeroed.
        cfg = transformers.MixtralConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            num_local_experts=4, num_experts_per_tok=2,
            attention_dropout=0.0, tie_word_embeddings=False)
        torch.manual_seed(0)
        hf_model = transformers.MixtralForCausalLM(cfg).train()
        batches = _batches()
        want = _torch_losses(hf_model, batches)

        torch.manual_seed(0)
        hf_fresh = transformers.MixtralForCausalLM(cfg)
        got = _ours_losses(hf_fresh.eval(), batches, model_type="mixtral",
                           replace_cfg=dict(dropless=True,
                                            moe_aux_loss_coef=0.0))
        np.testing.assert_allclose(got, want, rtol=2e-4)

    def test_llama_adamw_loss_trajectories_match(self, eight_devices):
        # the llama trunk pins rope / rmsnorm / SwiGLU / GQA *gradients*
        # against transformers, not just the forward
        cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            attention_dropout=0.0, tie_word_embeddings=False)
        torch.manual_seed(0)
        hf_model = transformers.LlamaForCausalLM(cfg).train()
        batches = _batches()
        want = _torch_losses(hf_model, batches)

        torch.manual_seed(0)
        hf_fresh = transformers.LlamaForCausalLM(cfg)
        got = _ours_losses(hf_fresh.eval(), batches, model_type="llama")
        np.testing.assert_allclose(got, want, rtol=1e-4)
