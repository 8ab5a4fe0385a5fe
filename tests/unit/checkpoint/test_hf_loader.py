"""HF checkpoint conversion: logit parity against transformers models.

Reference analog: the v2 checkpoint-loading tests
(``tests/unit/inference/v2/model_implementations``) — but stronger: each
family converts a REAL (randomly initialised) transformers model's
state_dict and must reproduce its logits, which pins down rope/gelu/norm
conventions, not just tensor shapes.
"""

import dataclasses

import jax
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from hcache_deepspeed_tpu.checkpoint.hf_loader import (  # noqa: E402
    convert_hf_state_dict, hf_config_to_model)

TOKENS = np.array([[3, 17, 250, 99, 1, 42, 7, 123]], dtype=np.int32)


def _logits_ours(model, cfg, params):
    out = model.apply({"params": params}, {"input_ids": TOKENS},
                      train=False, return_logits=True)
    return np.asarray(out, np.float32)[0]


def _logits_hf(hf_model):
    with torch.no_grad():
        return hf_model(torch.tensor(TOKENS, dtype=torch.long)) \
            .logits[0].float().numpy()


def _assert_close(got, want, atol=2e-4):
    scale = np.abs(want).max() or 1.0
    np.testing.assert_allclose(got, want, atol=atol * scale, rtol=1e-3)


class TestLlamaParity:
    @pytest.fixture(scope="class")
    def hf_model(self):
        cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            rms_norm_eps=1e-5, tie_word_embeddings=False)
        torch.manual_seed(0)
        return transformers.LlamaForCausalLM(cfg).eval()

    def test_logit_parity(self, hf_model):
        cfg, model = hf_config_to_model(hf_model.config)
        # the family default dtype is bf16 (serving); parity needs f32
        cfg = dataclasses.replace(cfg, use_flash=False, dtype="float32")
        model = type(model)(cfg)
        params = convert_hf_state_dict(hf_model, "llama")
        _assert_close(_logits_ours(model, cfg, params),
                      _logits_hf(hf_model))

    def test_serving_from_converted_weights(self, hf_model):
        from hcache_deepspeed_tpu.inference import (
            RaggedInferenceEngineConfig, build_hf_engine)
        params = jax.tree.map(
            lambda x: np.asarray(x, np.float32),
            convert_hf_state_dict(hf_model, "llama"))
        engine = build_hf_engine(
            {**hf_model.config.to_dict(), "torch_dtype": "float32"}, params,
            engine_config=RaggedInferenceEngineConfig(
                state_manager={"max_tracked_sequences": 4,
                               "max_context": 128},
                kv_cache={"block_size": 16, "num_blocks": 24,
                          "cache_dtype": "float32"}))
        logits, _ = engine.put([1], [list(TOKENS[0])])
        _assert_close(np.asarray(logits[0]), _logits_hf(hf_model)[-1],
                      atol=2e-3)


class TestGPT2Parity:
    @pytest.fixture(scope="class")
    def hf_model(self):
        cfg = transformers.GPT2Config(
            vocab_size=256, n_positions=128, n_embd=64, n_layer=2,
            n_head=4, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
        torch.manual_seed(0)
        return transformers.GPT2LMHeadModel(cfg).eval()

    def test_logit_parity(self, hf_model):
        cfg, model = hf_config_to_model(hf_model.config)
        params = convert_hf_state_dict(hf_model, "gpt2")
        _assert_close(_logits_ours(model, cfg, params),
                      _logits_hf(hf_model))


class TestOPTParity:
    @pytest.fixture(scope="class")
    def hf_model(self):
        cfg = transformers.OPTConfig(
            vocab_size=256, hidden_size=64, ffn_dim=256,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128, word_embed_proj_dim=64,
            do_layer_norm_before=True, dropout=0.0)
        torch.manual_seed(0)
        return transformers.OPTForCausalLM(cfg).eval()

    def test_logit_parity(self, hf_model):
        cfg, model = hf_config_to_model(hf_model.config)
        params = convert_hf_state_dict(hf_model, "opt")
        _assert_close(_logits_ours(model, cfg, params),
                      _logits_hf(hf_model))


class TestQwen2Parity:
    def test_logit_parity_with_biases_and_gqa(self):
        cfg = transformers.Qwen2Config(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            tie_word_embeddings=False)
        torch.manual_seed(0)
        hf_model = transformers.Qwen2ForCausalLM(cfg).eval()
        mcfg, model = hf_config_to_model(hf_model.config)
        assert mcfg.attention_bias  # qwen2 carries qkv biases
        mcfg = dataclasses.replace(mcfg, use_flash=False, dtype="float32")
        model = type(model)(mcfg)
        params = convert_hf_state_dict(hf_model, "qwen2")
        _assert_close(_logits_ours(model, mcfg, params),
                      _logits_hf(hf_model))


class TestFalconParity:
    @pytest.mark.parametrize("kw", [
        dict(multi_query=True, new_decoder_architecture=False),
        dict(multi_query=False, new_decoder_architecture=False),
    ], ids=["mqa-7b", "mha"])
    def test_logit_parity(self, kw):
        cfg = transformers.FalconConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, bias=False, parallel_attn=True,
            alibi=False, attention_dropout=0.0, hidden_dropout=0.0, **kw)
        torch.manual_seed(0)
        hf_model = transformers.FalconForCausalLM(cfg).eval()
        mcfg, model = hf_config_to_model(hf_model.config)
        mcfg = dataclasses.replace(mcfg, dtype="float32")
        model = type(model)(mcfg)
        params = convert_hf_state_dict(hf_model, "falcon")
        _assert_close(_logits_ours(model, mcfg, params),
                      _logits_hf(hf_model))

    def test_dual_ln_rejected(self):
        sd = {"transformer.h.0.ln_attn.weight": np.zeros(4)}
        with pytest.raises(ValueError, match="dual-layernorm"):
            convert_hf_state_dict(sd, "falcon", hf_config={})

    def test_biased_falcon_rejected(self):
        sd = {"transformer.h.0.self_attention.query_key_value.bias":
              np.zeros(4)}
        with pytest.raises(ValueError, match="bias"):
            convert_hf_state_dict(sd, "falcon", hf_config={})

    def test_config_required(self):
        with pytest.raises(ValueError, match="needs hf_config"):
            convert_hf_state_dict({}, "falcon")


class TestPhiParity:
    def test_logit_parity(self):
        cfg = transformers.PhiConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128, partial_rotary_factor=0.5,
            resid_pdrop=0.0, embd_pdrop=0.0, attention_dropout=0.0)
        torch.manual_seed(0)
        hf_model = transformers.PhiForCausalLM(cfg).eval()
        mcfg, model = hf_config_to_model(hf_model.config)
        mcfg = dataclasses.replace(mcfg, dtype="float32")
        model = type(model)(mcfg)
        params = convert_hf_state_dict(hf_model, "phi")
        _assert_close(_logits_ours(model, mcfg, params),
                      _logits_hf(hf_model))


class TestPhi3Parity:
    def test_logit_parity_with_fused_splits(self):
        cfg = transformers.Phi3Config(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            pad_token_id=0, resid_pdrop=0.0, embd_pdrop=0.0,
            attention_dropout=0.0)
        torch.manual_seed(0)
        hf_model = transformers.Phi3ForCausalLM(cfg).eval()
        mcfg, model = hf_config_to_model(hf_model.config)
        mcfg = dataclasses.replace(mcfg, use_flash=False, dtype="float32")
        model = type(model)(mcfg)
        params = convert_hf_state_dict(hf_model, "phi3")
        _assert_close(_logits_ours(model, mcfg, params),
                      _logits_hf(hf_model))

    def test_config_required(self):
        with pytest.raises(ValueError, match="needs hf_config"):
            convert_hf_state_dict({}, "phi3")


class TestMixtralParity:
    def test_logit_parity(self):
        cfg = transformers.MixtralConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            num_local_experts=4, num_experts_per_tok=2,
            tie_word_embeddings=False)
        torch.manual_seed(0)
        hf_model = transformers.MixtralForCausalLM(cfg).eval()
        mcfg, model = hf_config_to_model(hf_model.config)
        # HF computes exact renormalized top-k — that is the dropless
        # path; the default capacity-buffer MoE may drop tokens
        mcfg = dataclasses.replace(mcfg, use_flash=False, dtype="float32",
                                   dropless=True)
        from hcache_deepspeed_tpu.models.mixtral import MixtralForCausalLM
        model = MixtralForCausalLM(mcfg)
        params = convert_hf_state_dict(hf_model, "mixtral")
        _assert_close(_logits_ours(model, mcfg, params),
                      _logits_hf(hf_model), atol=1e-3)


class TestQwen2MoeParity:
    def test_logit_parity_with_shared_expert(self):
        cfg = transformers.Qwen2MoeConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=96,
            shared_expert_intermediate_size=112,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            num_experts=4, num_experts_per_tok=2, norm_topk_prob=False,
            decoder_sparse_step=1, tie_word_embeddings=False)
        torch.manual_seed(0)
        hf_model = transformers.Qwen2MoeForCausalLM(cfg).eval()
        mcfg, model = hf_config_to_model(hf_model.config)
        mcfg = dataclasses.replace(mcfg, use_flash=False, dtype="float32")
        from hcache_deepspeed_tpu.models.mixtral import MixtralForCausalLM
        model = MixtralForCausalLM(mcfg)
        params = convert_hf_state_dict(hf_model, "qwen2_moe")
        _assert_close(_logits_ours(model, mcfg, params),
                      _logits_hf(hf_model), atol=1e-3)


def _tiny_hf(family):
    torch.manual_seed(0)
    if family == "gpt2":
        return transformers.GPT2LMHeadModel(transformers.GPT2Config(
            vocab_size=256, n_positions=128, n_embd=64, n_layer=2,
            n_head=4, resid_pdrop=0.0, embd_pdrop=0.0,
            attn_pdrop=0.0)).eval()
    if family == "opt":
        return transformers.OPTForCausalLM(transformers.OPTConfig(
            vocab_size=256, hidden_size=64, ffn_dim=256,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128, word_embed_proj_dim=64,
            do_layer_norm_before=True, dropout=0.0)).eval()
    if family == "falcon":
        return transformers.FalconForCausalLM(transformers.FalconConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, bias=False, parallel_attn=True,
            alibi=False, multi_query=True,
            new_decoder_architecture=False, attention_dropout=0.0,
            hidden_dropout=0.0)).eval()
    if family == "phi":
        return transformers.PhiForCausalLM(transformers.PhiConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128, partial_rotary_factor=0.5,
            resid_pdrop=0.0, embd_pdrop=0.0,
            attention_dropout=0.0)).eval()
    if family == "mixtral":
        return transformers.MixtralForCausalLM(transformers.MixtralConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            num_local_experts=4, num_experts_per_tok=2,
            tie_word_embeddings=False)).eval()
    raise KeyError(family)


class TestServingEveryConvertedFamily:
    """The full switch path per family: HF weights → converter → paged
    serving engine, prefill logits vs the torch model."""

    @pytest.mark.parametrize("family", ["gpt2", "opt", "falcon", "phi",
                                        "mixtral"])
    def test_prefill_parity(self, family):
        from hcache_deepspeed_tpu.inference import (
            RaggedInferenceEngineConfig, build_hf_engine)
        hf_model = _tiny_hf(family)
        params = jax.tree.map(
            lambda x: np.asarray(x, np.float32),
            convert_hf_state_dict(hf_model, family))
        engine = build_hf_engine(
            {**hf_model.config.to_dict(), "torch_dtype": "float32"},
            params,
            engine_config=RaggedInferenceEngineConfig(
                state_manager={"max_tracked_sequences": 4,
                               "max_context": 128},
                kv_cache={"block_size": 16, "num_blocks": 32,
                          "cache_dtype": "float32"}))
        toks = list(TOKENS[0][:6])
        logits, _ = engine.put([1], [toks])
        _assert_close(np.asarray(logits[0]), _logits_hf(hf_model)[5],
                      atol=3e-3)


class TestErrors:
    def test_unknown_family(self):
        with pytest.raises(ValueError, match="no HF converter"):
            convert_hf_state_dict({}, "t5")
