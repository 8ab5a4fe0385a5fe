"""Engine factory.

Reference analog: ``deepspeed/inference/v2/engine_factory.py:69
build_hf_engine`` — maps a model family name/config to its policy (llama,
mistral, mixtral, opt, falcon, phi, qwen...). Here the family table maps to
our training-model configs whose param trees the paged inference model
consumes directly.
"""

from typing import Any, Dict, Optional

from ..models.llama import LlamaConfig
from .config import RaggedInferenceEngineConfig
from .engine_v2 import InferenceEngineV2


def _llama_like(hf: Dict[str, Any]) -> LlamaConfig:
    # HF Qwen2 carries q/k/v biases; its config spells llama-style keys.
    # (Qwen-v1 does NOT map here — it uses seq_length/layer_norm_epsilon
    # and a fused c_attn, so mapping it would mis-read the config.)
    bias_default = hf.get("model_type") == "qwen2"
    return LlamaConfig(
        attention_bias=hf.get("attention_bias",
                              hf.get("qkv_bias", bias_default)),
        vocab_size=hf.get("vocab_size", 32000),
        hidden_size=hf.get("hidden_size", 4096),
        intermediate_size=hf.get("intermediate_size", 11008),
        n_layer=hf.get("num_hidden_layers", 32),
        n_head=hf.get("num_attention_heads", 32),
        n_kv_head=hf.get("num_key_value_heads",
                         hf.get("num_attention_heads", 32)),
        max_positions=hf.get("max_position_embeddings", 4096),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        rope_theta=hf.get("rope_theta", 10000.0),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        dtype=hf.get("torch_dtype") or "bfloat16",
    )


def _gpt2_like(hf: Dict[str, Any]):
    from ..models.gpt2 import GPT2Config
    return GPT2Config(
        vocab_size=hf.get("vocab_size", 50257),
        n_positions=hf.get("n_positions", hf.get("n_ctx", 1024)),
        n_embd=hf.get("n_embd", 768),
        n_layer=hf.get("n_layer", 12),
        n_head=hf.get("n_head", 12),
        layer_norm_epsilon=hf.get("layer_norm_epsilon", 1e-5),
        dtype=hf.get("torch_dtype") or "float32",
    )


def _opt_like(hf: Dict[str, Any]):
    from ..models.opt import OPTConfig
    return OPTConfig(
        vocab_size=hf.get("vocab_size", 50272),
        hidden_size=hf.get("hidden_size", 768),
        ffn_dim=hf.get("ffn_dim", 3072),
        n_layer=hf.get("num_hidden_layers", 12),
        n_head=hf.get("num_attention_heads", 12),
        max_positions=hf.get("max_position_embeddings", 2048),
        dtype=hf.get("torch_dtype") or "float32",
    )


def _falcon_like(hf: Dict[str, Any]):
    from ..models.falcon import FalconConfig
    n_head = hf.get("num_attention_heads", hf.get("n_head", 71))
    if hf.get("new_decoder_architecture", False):
        kv = hf.get("num_kv_heads", 8)
    else:
        kv = n_head if not hf.get("multi_query", True) else 1
    return FalconConfig(
        vocab_size=hf.get("vocab_size", 65024),
        hidden_size=hf.get("hidden_size", 4544),
        n_layer=hf.get("num_hidden_layers", hf.get("n_layer", 32)),
        n_head=n_head,
        n_kv_head=kv,
        max_positions=hf.get("max_position_embeddings", 2048),
        layer_norm_epsilon=hf.get("layer_norm_epsilon", 1e-5),
        rope_theta=hf.get("rope_theta", 10000.0),
        tie_word_embeddings=hf.get("tie_word_embeddings", True),
        dtype=hf.get("torch_dtype") or "bfloat16",
    )


def _phi_like(hf: Dict[str, Any]):
    from ..models.phi import PhiConfig
    return PhiConfig(
        vocab_size=hf.get("vocab_size", 51200),
        hidden_size=hf.get("hidden_size", 2560),
        intermediate_size=hf.get("intermediate_size", 10240),
        n_layer=hf.get("num_hidden_layers", 32),
        n_head=hf.get("num_attention_heads", 32),
        max_positions=hf.get("max_position_embeddings", 2048),
        layer_norm_epsilon=hf.get("layer_norm_eps", 1e-5),
        rope_theta=hf.get("rope_theta", 10000.0),
        partial_rotary_factor=hf.get("partial_rotary_factor", 0.4),
        dtype=hf.get("torch_dtype") or "float32",
    )


def _mixtral_like(hf: Dict[str, Any]):
    from ..models.mixtral import MixtralConfig
    return MixtralConfig(
        vocab_size=hf.get("vocab_size", 32000),
        hidden_size=hf.get("hidden_size", 4096),
        intermediate_size=hf.get("intermediate_size", 14336),
        n_layer=hf.get("num_hidden_layers", 32),
        n_head=hf.get("num_attention_heads", 32),
        n_kv_head=hf.get("num_key_value_heads", 8),
        max_positions=hf.get("max_position_embeddings", 8192),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        rope_theta=hf.get("rope_theta", 1e6),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        num_experts=hf.get("num_local_experts", hf.get("num_experts", 8)),
        top_k=hf.get("num_experts_per_tok", 2),
        dtype=hf.get("torch_dtype") or "bfloat16",
    )


def _qwen_v1_like(hf: Dict[str, Any]) -> LlamaConfig:
    """Qwen (v1) spells its config in its own keys — ``seq_length`` for the
    context window, ``layer_norm_epsilon`` for the RMSNorm eps, an
    ``intermediate_size`` that is TWICE the SwiGLU branch width (the HF
    module builds w1/w2 at intermediate_size // 2), qkv bias always on, and
    ``rotary_emb_base``. Architecturally it is the llama block layout
    (RMSNorm + rope + SwiGLU, MHA, untied head), so it maps onto our llama
    trunk once those keys are translated."""
    return LlamaConfig(
        attention_bias=True,
        vocab_size=hf.get("vocab_size", 151936),
        hidden_size=hf.get("hidden_size", 4096),
        intermediate_size=hf.get("intermediate_size", 22016) // 2,
        n_layer=hf.get("num_hidden_layers", 32),
        n_head=hf.get("num_attention_heads", 32),
        n_kv_head=hf.get("num_attention_heads", 32),  # MHA: no GQA in v1
        max_positions=hf.get("seq_length", 8192),
        rms_norm_eps=hf.get("layer_norm_epsilon", 1e-6),
        rope_theta=hf.get("rotary_emb_base", 10000.0),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        dtype=hf.get("torch_dtype") or "bfloat16",
    )


def _qwen2_moe_like(hf: Dict[str, Any]):
    from ..models.mixtral import Qwen2MoeConfig
    return Qwen2MoeConfig(
        vocab_size=hf.get("vocab_size", 151936),
        hidden_size=hf.get("hidden_size", 3584),
        # expert FFN width is moe_intermediate_size (the dense
        # intermediate_size key refers to layers qwen2-moe doesn't use)
        intermediate_size=hf.get("moe_intermediate_size", 2560),
        shared_expert_intermediate_size=hf.get(
            "shared_expert_intermediate_size", 20480),
        n_layer=hf.get("num_hidden_layers", 28),
        n_head=hf.get("num_attention_heads", 28),
        n_kv_head=hf.get("num_key_value_heads", 4),
        max_positions=hf.get("max_position_embeddings", 32768),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        rope_theta=hf.get("rope_theta", 1e6),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        num_experts=hf.get("num_experts", 64),
        top_k=hf.get("num_experts_per_tok", 8),
        norm_topk_prob=hf.get("norm_topk_prob", False),
        attention_bias=hf.get("attention_bias",
                              hf.get("qkv_bias", True)),
        dtype=hf.get("torch_dtype") or "bfloat16",
    )


def _sdar_moe_like(hf: Dict[str, Any]):
    """SDAR-MoE: the Qwen3-MoE layer (explicit ``head_dim``, per-head
    q/k norm, every layer sparse, no shared expert) generating by
    diffusion over blocks of ``diffusion_block_length`` positions
    (absent: causal, one token a step). A dense layer among the sparse
    ones is refused by name: the trunk is one stack of one kind."""
    from ..models.sdar_moe import SdarMoeConfig
    if hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers"):
        raise NotImplementedError(
            "sdar_moe with dense layers among the sparse ones "
            f"(decoder_sparse_step={hf.get('decoder_sparse_step')}, "
            f"mlp_only_layers={hf.get('mlp_only_layers')}) is not "
            "supported: the serving trunk stacks layers of one kind")
    if hf.get("use_sliding_window") or hf.get("rope_scaling"):
        raise NotImplementedError(
            "sdar_moe with a sliding window or rope scaling is not "
            "supported")
    n_head = hf.get("num_attention_heads", 32)
    hidden = hf.get("hidden_size", 2048)
    return SdarMoeConfig(
        vocab_size=hf.get("vocab_size", 151936),
        hidden_size=hidden,
        # the experts' width; ``intermediate_size`` is the dense layers',
        # which this family has none of
        intermediate_size=hf.get("moe_intermediate_size", 768),
        n_layer=hf.get("num_hidden_layers", 48),
        n_head=n_head,
        n_kv_head=hf.get("num_key_value_heads", 4),
        head_width=hf.get("head_dim", hidden // n_head),
        max_positions=hf.get("max_position_embeddings", 32768),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        rope_theta=hf.get("rope_theta", 1e6),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        attention_bias=hf.get("attention_bias", False),
        num_experts=hf.get("num_experts", 128),
        top_k=hf.get("num_experts_per_tok", 8),
        norm_topk_prob=hf.get("norm_topk_prob", True),
        diffusion_block_length=hf.get("diffusion_block_length", 1),
        mask_token_id=hf.get("mask_token_id", 151669),
        dtype=hf.get("torch_dtype") or "bfloat16",
    )


def _olmo_hybrid_like(hf: Dict[str, Any]):
    """Olmo-Hybrid: ``layer_types`` names each layer ``linear_attention``
    (gated delta rule) or ``full_attention``; the ``linear_*`` keys are
    the Gated DeltaNet layer's. ``rope_parameters.rope_theta`` is read as
    it stands: ``null`` means no rotary step."""
    from ..models.olmo_hybrid import OlmoHybridConfig
    n_head = hf.get("num_attention_heads", 30)
    return OlmoHybridConfig(
        vocab_size=hf.get("vocab_size", 100352),
        hidden_size=hf.get("hidden_size", 3840),
        intermediate_size=hf.get("intermediate_size", 11008),
        n_layer=hf.get("num_hidden_layers", 32),
        n_head=n_head,
        n_kv_head=hf.get("num_key_value_heads", n_head),
        max_positions=hf.get("max_position_embeddings", 65536),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        rope_theta=(hf.get("rope_parameters") or {}).get("rope_theta"),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        attention_bias=hf.get("attention_bias", False),
        layer_types=tuple(hf["layer_types"]),
        linear_num_key_heads=hf.get("linear_num_key_heads", n_head),
        linear_num_value_heads=hf.get("linear_num_value_heads", n_head),
        linear_key_head_dim=hf.get("linear_key_head_dim", 96),
        linear_value_head_dim=hf.get("linear_value_head_dim", 192),
        linear_conv_kernel_dim=hf.get("linear_conv_kernel_dim", 4),
        linear_allow_neg_eigval=hf.get("linear_allow_neg_eigval", True),
        dtype=hf.get("torch_dtype") or "bfloat16",
    )


def _glm4_moe_lite_like(hf: Dict[str, Any]):
    """GLM-4-MoE-Lite: multi-head latent attention (low-rank q and kv
    projections, a rotary part of ``qk_rope_head_dim`` channels a head
    beside ``qk_nope_head_dim`` without), ``first_k_dense_replace`` dense
    layers before sparse ones with a sigmoid router under a selection
    bias (``noaux_tc``) and an ungated shared expert. What is not built
    is refused by name; the release's multi-token-prediction module
    (``num_nextn_predict_layers``) is no part of the main model's
    logits and is not read."""
    from ..models.glm4_moe_lite import Glm4MoeLiteConfig
    refused = {
        "rope_scaling": hf.get("rope_scaling") is not None,
        "n_group > 1 (group-limited routing)": hf.get("n_group", 1) > 1,
        "topk_group > 1 (group-limited routing)":
            hf.get("topk_group", 1) > 1,
        "attention_bias": bool(hf.get("attention_bias", False)),
        f"topk_method {hf.get('topk_method')!r} (only noaux_tc)":
            hf.get("topk_method", "noaux_tc") != "noaux_tc",
        "partial_rotary_factor != 1":
            hf.get("partial_rotary_factor", 1) != 1,
    }
    for feature, present in refused.items():
        if present:
            raise NotImplementedError(
                f"glm4_moe_lite with {feature} is not supported: the "
                "latent-attention trunk (inference/model_latent.py) "
                "does not build it")
    return Glm4MoeLiteConfig(
        vocab_size=hf.get("vocab_size", 154880),
        hidden_size=hf.get("hidden_size", 2048),
        intermediate_size=hf.get("moe_intermediate_size", 1536),
        dense_intermediate_size=hf.get("intermediate_size", 10240),
        n_layer=hf.get("num_hidden_layers", 47),
        n_head=hf.get("num_attention_heads", 20),
        max_positions=hf.get("max_position_embeddings", 202752),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        rope_theta=hf.get("rope_theta", 1e6),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        num_experts=hf.get("n_routed_experts", 64),
        top_k=hf.get("num_experts_per_tok", 4),
        norm_topk_prob=hf.get("norm_topk_prob", True),
        q_lora_rank=hf.get("q_lora_rank", 768),
        kv_lora_rank=hf.get("kv_lora_rank", 512),
        qk_nope_head_dim=hf.get("qk_nope_head_dim", 192),
        qk_rope_head_dim=hf.get("qk_rope_head_dim", 64),
        v_head_dim=hf.get("v_head_dim", 256),
        first_k_dense_replace=hf.get("first_k_dense_replace", 1),
        n_shared_experts=hf.get("n_shared_experts", 1),
        routed_scaling_factor=hf.get("routed_scaling_factor", 1.8),
        dtype=hf.get("torch_dtype") or "bfloat16",
    )


def _cohere2_moe_like(hf: Dict[str, Any]):
    """Cohere2-MoE (Command A+): a parallel block with one bias-free
    LayerNorm a layer over window (``sliding_attention``: rotary,
    interleaved pairing) and global (``full_attention``: no positional
    step) layers in the period ``layer_types`` gives, sigmoid-routed
    experts beside averaged shared ones, a tied head.
    ``experts_held = [first, count]``: the routed experts this parameter
    tree holds (default all). What is not built is refused by name; the
    vision tower is no part of the language model's ``config`` and is
    not read."""
    from ..models.cohere2_moe import Cohere2MoeConfig
    rope = hf.get("rope_parameters") or {}
    refused = {
        "use_qk_norm": bool(hf.get("use_qk_norm", False)),
        "attention_bias": bool(hf.get("attention_bias", False)),
        "first_k_dense_replace > 0 (leading dense layers)":
            hf.get("first_k_dense_replace", 0) > 0,
        f"rope_type {rope.get('rope_type')!r} (only default)":
            rope.get("rope_type", "default") != "default",
        "rope_scaling": hf.get("rope_scaling") is not None,
        "rotary_pct != 1": hf.get("rotary_pct", 1) != 1,
        "use_parallel_block false (a sequential block)":
            not hf.get("use_parallel_block", True),
        f"position_embedding_type "
        f"{hf.get('position_embedding_type')!r} (only rope_gptj)":
            hf.get("position_embedding_type", "rope_gptj") != "rope_gptj",
        f"expert_selection_fn {hf.get('expert_selection_fn')!r} "
        "(only sigmoid)":
            hf.get("expert_selection_fn", "sigmoid") != "sigmoid",
        f"shared_expert_combination_strategy "
        f"{hf.get('shared_expert_combination_strategy')!r} (only "
        "average)":
            hf.get("shared_expert_combination_strategy",
                   "average") != "average",
        "use_gated_activation false": not hf.get("use_gated_activation",
                                                 True),
        "tie_word_embeddings false (an untied head)":
            not hf.get("tie_word_embeddings", True),
    }
    for feature, present in refused.items():
        if present:
            raise NotImplementedError(
                f"cohere2_moe with {feature} is not supported: the "
                "windowed trunk (inference/model_window.py) does not "
                "build it")
    held = hf.get("experts_held")
    return Cohere2MoeConfig(
        vocab_size=hf.get("vocab_size", 262144),
        hidden_size=hf.get("hidden_size", 4096),
        intermediate_size=hf.get("intermediate_size", 4096),
        n_layer=hf.get("num_hidden_layers", 32),
        n_head=hf.get("num_attention_heads", 128),
        n_kv_head=hf.get("num_key_value_heads", 8),
        head_width=hf.get("head_dim", 128),
        max_positions=hf.get("max_position_embeddings", 200000),
        layer_norm_eps=hf.get("layer_norm_eps", 1e-5),
        rope_theta=hf.get("rope_theta", rope.get("rope_theta", 50000)),
        layer_types=tuple(hf["layer_types"]),
        sliding_window=hf.get("sliding_window", 4096),
        num_experts=hf.get("num_experts", 128),
        top_k=hf.get("num_experts_per_tok", 8),
        norm_topk_prob=hf.get("norm_topk_prob", True),
        num_shared_experts=hf.get("num_shared_experts", 4),
        logit_scale=hf.get("logit_scale", 1.0),
        experts_held=tuple(held) if held else None,
        dtype=hf.get("torch_dtype") or "bfloat16",
    )


#: model_type -> config adapter (reference: the policy map in
#: engine_factory.py:69 — llama/mistral/qwen2/phi3 share the llama block
#: layout; mixtral/qwen2_moe route through the MoE paged model
#: (model_moe.py: dropless grouped GEMM, and for qwen2_moe the shared
#: expert + raw top-k gate mass); gpt2/opt/falcon/phi have their own
#: paged trunks; qwen (v1) translates its idiosyncratic config keys
#: onto the llama trunk (_qwen_v1_like); olmo_hybrid is the hybrid trunk
#: (model_hybrid.py: gated-delta-rule layers beside full attention);
#: sdar_moe is the MoE paged model with a per-head q/k norm, an explicit
#: head width and the block mask of generation by diffusion over blocks;
#: glm4_moe_lite is the latent-attention trunk (model_latent.py: a pool
#: of compressed KV rows, dense layers leading a sparse stack);
#: cohere2_moe is the windowed trunk (model_window.py: window and global
#: layers each with a block pool, a parallel block, an expert layer that
#: may hold a share of its experts).
MODEL_FAMILIES = {
    "llama": _llama_like,
    "mistral": _llama_like,
    "qwen": _qwen_v1_like,
    "qwen2": _llama_like,
    "phi3": _llama_like,
    "gpt2": _gpt2_like,
    "opt": _opt_like,
    "falcon": _falcon_like,
    "phi": _phi_like,
    "mixtral": _mixtral_like,
    "qwen2_moe": _qwen2_moe_like,
    "olmo_hybrid": _olmo_hybrid_like,
    "sdar_moe": _sdar_moe_like,
    "glm4_moe_lite": _glm4_moe_lite_like,
    "cohere2_moe": _cohere2_moe_like,
}


def build_engine(model=None, config=None, *, model_config=None, params=None,
                 engine_config: Optional[RaggedInferenceEngineConfig] = None,
                 topology=None) -> InferenceEngineV2:
    """``hcache_deepspeed_tpu.init_inference`` backend. Accepts either a
    ready ``(model_config, params)`` pair or an HF-style config dict via
    ``model``. ``topology``: a MeshTopology whose ``tensor`` axis shards
    the engine (see :class:`InferenceEngineV2`)."""
    if engine_config is None and isinstance(config, dict):
        engine_config = RaggedInferenceEngineConfig(**config)
    if model_config is None:
        from ..models.falcon import FalconConfig
        from ..models.gpt2 import GPT2Config
        from ..models.opt import OPTConfig
        from ..models.phi import PhiConfig
        if isinstance(model, (LlamaConfig, GPT2Config, OPTConfig,
                              FalconConfig, PhiConfig)):
            model_config = model
        elif isinstance(model, dict):
            family = model.get("model_type", "llama")
            if family not in MODEL_FAMILIES:
                raise ValueError(
                    f"unsupported model family {family!r}; known: "
                    f"{sorted(MODEL_FAMILIES)}")
            model_config = MODEL_FAMILIES[family](model)
        else:
            raise TypeError("build_engine needs model_config+params, a "
                            "model-family config (LlamaConfig/GPT2Config/"
                            "OPTConfig/FalconConfig/PhiConfig/"
                            "MixtralConfig), or an HF config dict")
    if params is None:
        raise ValueError("build_engine requires params (a trained "
                         "LlamaForCausalLM param tree)")
    return InferenceEngineV2(model_config, params,
                             config=engine_config, topology=topology)


def build_hf_engine(hf_config: Dict[str, Any], params,
                    engine_config=None, topology=None) -> InferenceEngineV2:
    return build_engine(model=hf_config, params=params,
                        engine_config=engine_config, topology=topology)
