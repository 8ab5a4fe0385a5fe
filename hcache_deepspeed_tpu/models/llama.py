"""Llama model family (Llama-2 / Llama-3 style) in flax.linen — the
flagship model for the BASELINE north-star config (ZeRO-3 Llama-2-7B).

Reference analog: the inference-v2 llama implementation
(``deepspeed/inference/v2/model_implementations/llama_v2/model.py``) and the
HF-Llama AutoTP sharding policy (``deepspeed/module_inject/auto_tp.py``).
This module is the *training-side* definition, built TPU-first:

* pre-norm RMSNorm (Pallas kernel via ``ops.rms_norm``),
* rotary embeddings (``ops.rope``; XLA fuses into the QKV matmul),
* grouped-query attention (n_kv_heads <= n_heads) through the Pallas flash
  attention kernel (``ops.flash_attention``),
* SwiGLU MLP,
* static shapes, bf16-friendly, remat-able blocks,
* Megatron-style TP rules exposed via ``llama_tp_spec_fn`` (column-split
  q/k/v/gate/up, row-split o/down, vocab-split embed/lm_head) so the same
  module runs pure-DP, ZeRO-sharded, or TP without code changes,
* optional Ulysses sequence parallelism: pass ``attention_fn`` (see
  ``sequence/layer.py``) to swap the core attention for the
  all-to-all-wrapped one.
"""

from dataclasses import dataclass
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..ops.flash_attention import attention as flash_attention
from ..ops.rms_norm import rms_norm
from ..ops.rope import apply_rope, rope_frequencies
from ..parallel.topology import TENSOR_AXIS
from .gpt2 import causal_lm_loss, default_lm_labels


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 32          # < n_head => GQA; == 1 => MQA
    max_positions: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "float32"
    remat: bool = False
    #: jax.checkpoint_policies name for per-block remat (implies remat;
    #: see GPT2Config.remat_policy)
    remat_policy: str = ""
    use_flash: bool = True
    #: flash kernel tile sizes (0 = kernel default; see
    #: GPT2Config.flash_block_q)
    flash_block_q: int = 0
    flash_block_k: int = 0
    #: biases on q/k/v projections (qwen / qwen1.5-style; llama: False)
    attention_bias: bool = False
    #: > 0: chunked LM loss — no full [B, T, V] fp32 logits (see
    #: GPT2Config.loss_chunk)
    loss_chunk: int = 0
    #: width of one attention head where the family publishes it apart
    #: from ``hidden_size // n_head`` (q and o are then ``n_head *
    #: head_width`` wide, not ``hidden_size``); 0: the quotient
    head_width: int = 0
    #: RMSNorm over each head's channels of q and k before the rotary
    #: step (weights ``q_norm``/``k_norm`` of ``[head_dim]``)
    qk_norm: bool = False

    @property
    def head_dim(self):
        return self.head_width or self.hidden_size // self.n_head

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


def llama2_7b(**kw):
    defaults = dict(vocab_size=32000, hidden_size=4096,
                    intermediate_size=11008, n_layer=32, n_head=32,
                    n_kv_head=32, max_positions=4096, dtype="bfloat16",
                    remat=True)
    defaults.update(kw)
    return LlamaConfig(**defaults)


def llama2_13b(**kw):
    defaults = dict(hidden_size=5120, intermediate_size=13824, n_layer=40,
                    n_head=40, n_kv_head=40, dtype="bfloat16", remat=True)
    defaults.update(kw)
    return LlamaConfig(**defaults)


def llama3_8b(**kw):
    defaults = dict(vocab_size=128256, hidden_size=4096,
                    intermediate_size=14336, n_layer=32, n_head=32,
                    n_kv_head=8, max_positions=8192, rope_theta=500000.0,
                    dtype="bfloat16", remat=True)
    defaults.update(kw)
    return LlamaConfig(**defaults)


def llama_tiny(**kw):
    """Test-scale config (reference tests' SimpleModel analog)."""
    defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    n_layer=2, n_head=4, n_kv_head=2, max_positions=128)
    defaults.update(kw)
    return LlamaConfig(**defaults)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig
    attention_fn: Optional[Callable] = None  # Ulysses hook

    @nn.compact
    def __call__(self, x, train: bool):
        cfg = self.cfg
        B, T, C = x.shape
        H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim

        ab = cfg.attention_bias
        q = nn.Dense(H * D, use_bias=ab, dtype=x.dtype, name="q_proj")(x)
        k = nn.Dense(KV * D, use_bias=ab, dtype=x.dtype, name="k_proj")(x)
        v = nn.Dense(KV * D, use_bias=ab, dtype=x.dtype, name="v_proj")(x)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, KV, D)
        v = v.reshape(B, T, KV, D)
        if getattr(cfg, "qk_norm", False):   # family configs may lack it
            q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(k)

        cos, sin = rope_frequencies(D, cfg.max_positions, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        if self.attention_fn is not None:
            if KV < H and not getattr(self.attention_fn, "supports_gqa",
                                      False):
                # fns without GQA support (e.g. ring) take dense heads;
                # Ulysses declares supports_gqa and moves compact k/v
                # through its all-to-alls (H/KV x less wire)
                rep = H // KV
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            y = self.attention_fn(q, k, v, causal=True)
        elif cfg.use_flash:
            # GQA-native: the kernel's index map shares kv blocks across
            # each query-head group — no repeat, KV HBM reads drop H/KV x
            y = flash_attention(
                q, k, v, causal=True,
                # family configs reusing this block (falcon/phi/...)
                # may not declare the tiling knobs
                block_q=getattr(cfg, "flash_block_q", 0),
                block_k=getattr(cfg, "flash_block_k", 0))
        else:
            from ..ops.flash_attention import reference_attention
            y = reference_attention(q, k, v, causal=True)
        y = y.reshape(B, T, H * D)
        return nn.Dense(C, use_bias=False, dtype=x.dtype, name="o_proj")(y)


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = nn.Dense(cfg.intermediate_size, use_bias=False, dtype=x.dtype,
                        name="gate_proj")(x)
        up = nn.Dense(cfg.intermediate_size, use_bias=False, dtype=x.dtype,
                      name="up_proj")(x)
        h = nn.silu(gate) * up
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=x.dtype,
                        name="down_proj")(h)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],),
                       jnp.float32)
        return rms_norm(x, w, eps=self.eps)


class LlamaBlock(nn.Module):
    """Returns ``(x, aux_loss)`` — dense blocks report 0 aux; an MoE
    ``mlp_cls`` (models/mixtral.py) returns its load-balancing loss, which
    the top-level model sums and folds into the training loss (the
    reference collects ``MOELayer.l_aux`` the same way, moe/sharded_moe.py)."""
    cfg: LlamaConfig
    attention_fn: Optional[Callable] = None
    mlp_cls: Any = None  # MoE swap-in point (models/mixtral.py)

    @nn.compact
    def __call__(self, x, train: bool):
        cfg = self.cfg
        x = x + LlamaAttention(cfg, attention_fn=self.attention_fn,
                               name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x), train)
        h = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        if self.mlp_cls is None:
            y = LlamaMLP(cfg, name="mlp")(h)
            aux = jnp.zeros((), jnp.float32)
        else:
            out = self.mlp_cls(cfg, name="mlp")(h, train)
            y, aux = out if isinstance(out, tuple) \
                else (out, jnp.zeros((), jnp.float32))
        return x + y, aux


class _HeadKernel(nn.Module):
    """Declares the LM-head weight at the ``lm_head/kernel`` path (the
    tree nn.Dense would create) while handing the raw kernel back, so the
    chunked loss can stream it without a full-logits GEMM."""
    hidden: int
    vocab: int

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (self.hidden, self.vocab), jnp.float32)


class LlamaForCausalLM(nn.Module):
    """Batch contract matches GPT2LMHeadModel: {"input_ids": [B,T] int32,
    optional "labels" (-100 ignore), optional "attention_mask"}. Returns the
    mean causal-LM loss (fp32 scalar)."""
    cfg: LlamaConfig
    attention_fn: Optional[Callable] = None
    mlp_cls: Any = None

    @nn.compact
    def __call__(self, batch, train: bool = False,
                 return_logits: bool = False):
        cfg = self.cfg
        ids = batch["input_ids"]
        B, T = ids.shape
        dtype = cfg.compute_dtype

        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                         name="embed_tokens")
        x = embed(ids)

        block = LlamaBlock
        if cfg.remat or cfg.remat_policy:
            policy = getattr(jax.checkpoint_policies, cfg.remat_policy) \
                if cfg.remat_policy else None
            block = nn.remat(LlamaBlock, static_argnums=(2,),
                             policy=policy)
        aux_total = jnp.zeros((), jnp.float32)
        for i in range(cfg.n_layer):
            x, aux = block(cfg, attention_fn=self.attention_fn,
                           mlp_cls=self.mlp_cls, name=f"layers_{i}")(x, train)
            aux_total = aux_total + aux
        x = RMSNorm(cfg.rms_norm_eps, name="norm")(x)

        if cfg.tie_word_embeddings:
            head_kernel = embed.embedding.T.astype(dtype)
        else:
            # same param path as nn.Dense(name="lm_head") would declare
            head_kernel = _HeadKernel(cfg.hidden_size, cfg.vocab_size,
                                      name="lm_head")().astype(dtype)

        if return_logits:
            return x @ head_kernel
        labels = batch.get("labels")
        if labels is None:
            labels = default_lm_labels(ids)
        if cfg.loss_chunk and T % cfg.loss_chunk == 0:
            from ..sequence.fpdt import chunked_lm_loss
            loss = chunked_lm_loss(x, head_kernel, labels,
                                   chunk=cfg.loss_chunk)
        else:
            if cfg.loss_chunk:
                from .gpt2 import _warn_loss_chunk_fallback
                _warn_loss_chunk_fallback(T, cfg.loss_chunk)
            loss = causal_lm_loss(x @ head_kernel, labels)
        aux_coef = getattr(cfg, "moe_aux_loss_coef", 0.0)
        if aux_coef:
            loss = loss + aux_coef * aux_total
        return loss


# ------------------------------------------------------------------ #
# Pipeline decomposition (reference: PipelineModule layer specs —
# pipe/module.py; the gpt2 decomposition is the template)
# ------------------------------------------------------------------ #
class LlamaPipeEmbed(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, train: bool = False):
        ids = x["input_ids"] if isinstance(x, dict) else x
        return nn.Embed(self.cfg.vocab_size, self.cfg.hidden_size,
                        dtype=self.cfg.compute_dtype,
                        name="embed_tokens")(ids)


class LlamaPipeBlock(nn.Module):
    """Block with the pipeline body contract ``(x, train) -> x`` (dense
    aux loss is zero and dropped; MoE blocks are not pipeline-decomposed
    here). Honors ``cfg.remat``/``remat_policy`` like the flat model."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, train: bool = False):
        block = LlamaBlock
        if self.cfg.remat or self.cfg.remat_policy:
            policy = getattr(jax.checkpoint_policies,
                             self.cfg.remat_policy) \
                if self.cfg.remat_policy else None
            block = nn.remat(LlamaBlock, static_argnums=(2,),
                             policy=policy)
        out, _aux = block(self.cfg, name="block")(x, train)
        return out


class LlamaPipeFinalNorm(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, train: bool = False):
        return RMSNorm(self.cfg.rms_norm_eps, name="norm")(x)


class LlamaPipeHead(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, train: bool = False):
        kernel = _HeadKernel(self.cfg.hidden_size, self.cfg.vocab_size,
                             name="lm_head")()
        return x @ kernel.astype(x.dtype)


def llama_pipeline_layers(cfg: LlamaConfig):
    """(layers, loss_fn) for ``PipelineModule``: embed, n_layer
    homogeneous blocks, final RMSNorm, untied LM head."""
    if cfg.tie_word_embeddings:
        raise ValueError(
            "llama_pipeline_layers supports untied embeddings only (a "
            "tied head would need a TiedLayerSpec pair like gpt2's)")
    if cfg.loss_chunk:
        from ..utils.logging import logger
        logger.warning(
            "llama_pipeline_layers: cfg.loss_chunk is not applied — the "
            "pipeline loss head computes full logits (the chunked loss "
            "needs the fused head+loss layer of the flat model)")
    from ..runtime.pipe.module import LayerSpec
    from .gpt2 import lm_loss_fn
    layers = [
        LayerSpec(LlamaPipeEmbed, cfg),
        *[LayerSpec(LlamaPipeBlock, cfg) for _ in range(cfg.n_layer)],
        LayerSpec(LlamaPipeFinalNorm, cfg),
        LayerSpec(LlamaPipeHead, cfg),
    ]
    return layers, lm_loss_fn


def llama_zeropp_layered_spec(cfg: LlamaConfig):
    """Layered loss decomposition for the ZeRO++ scan-over-layers gather
    (``runtime/zero/zeropp.py``); see ``gpt2.gpt2_zeropp_layered_spec``
    for the contract. Dense blocks only — MoE/custom-attention models
    fall back to the whole-tree gather (``models/layered.py`` gates)."""
    dtype = cfg.compute_dtype
    outer_keys = ("embed_tokens", "norm") if cfg.tie_word_embeddings \
        else ("embed_tokens", "norm", "lm_head")

    def embed(outer, batch, key, train):
        # root module: params sit at the tree top (no name nesting)
        return nn.Embed(cfg.vocab_size, cfg.hidden_size,
                        dtype=dtype).apply(
            {"params": outer["embed_tokens"]}, batch["input_ids"])

    def block(layer, x, batch, key, train):
        out, _aux = LlamaBlock(cfg).apply({"params": layer}, x, train)
        return out

    def head(outer, x, batch):
        x = RMSNorm(cfg.rms_norm_eps).apply({"params": outer["norm"]}, x)
        if cfg.tie_word_embeddings:
            head_kernel = outer["embed_tokens"]["embedding"].T \
                .astype(dtype)
        else:
            head_kernel = outer["lm_head"]["kernel"].astype(dtype)
        ids = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = default_lm_labels(ids)
        T = ids.shape[1]
        if cfg.loss_chunk and T % cfg.loss_chunk == 0:
            from ..sequence.fpdt import chunked_lm_loss
            return chunked_lm_loss(x, head_kernel, labels,
                                   chunk=cfg.loss_chunk)
        return causal_lm_loss(x @ head_kernel, labels)

    return {
        "model_name": "llama",
        "layer_prefix": "layers_",
        "n_layer": cfg.n_layer,
        "outer_keys": outer_keys,
        "embed": embed,
        "block": block,
        "head": head,
    }


def llama_flat_to_pipeline(params, cfg: LlamaConfig):
    """Flat ``LlamaForCausalLM`` tree (training run or
    ``checkpoint.hf_loader``) → ``PipelineModule`` layout; see
    ``gpt2.gpt2_flat_to_pipeline`` for the contract."""
    from ._pipe_util import stack_flat_layers
    block_tree = stack_flat_layers(
        params, "layers_", cfg.n_layer,
        required=["embed_tokens", "norm", "lm_head"], model_name="llama")
    return {
        "pre": {"layer_0": {"embed_tokens": dict(params["embed_tokens"])}},
        "blocks": {"block": block_tree},
        "post": {"layer_0": {"norm": dict(params["norm"])},
                 "layer_1": {"lm_head": dict(params["lm_head"])}},
    }


def llama_tp_spec_fn(path, leaf):
    """Megatron-style TP rules (reference: AutoTP policy for HF Llama,
    module_inject/auto_tp.py — shard qkv/gate/up column-wise, o/down
    row-wise, vocab dims of embed/lm_head)."""
    names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
    joined = "/".join(str(n) for n in names)
    if leaf.ndim < 2:
        return PartitionSpec()
    if "embed_tokens" in joined or "lm_head" in joined:
        return PartitionSpec(None, TENSOR_AXIS)
    if any(n in joined for n in ("q_proj", "k_proj", "v_proj",
                                 "gate_proj", "up_proj")):
        return PartitionSpec(None, TENSOR_AXIS)  # column parallel
    if any(n in joined for n in ("o_proj", "down_proj")):
        return PartitionSpec(TENSOR_AXIS, None)  # row parallel
    # stacked MoE expert tensors (w1/w2/w3, [E, ...]) belong to
    # mixtral_tp_spec_fn, which handles the expert leading dim
    return PartitionSpec()
