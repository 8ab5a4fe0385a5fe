"""Paged-KV serving for the Falcon family.

Reference analog: the falcon policy in
``deepspeed/inference/v2/engine_factory.py:69`` +
``model_implementations/falcon/``. Reuses the llama paged trunk's
KV plumbing (RoPE + GQA/MQA paged attention); overrides the layer to
Falcon's **parallel** form — one shared LayerNorm feeding both the
attention and GELU-MLP branches — and the final norm to LayerNorm.

Latents (HCache) = the post-input_layernorm hidden states, the same
pre-QKV snapshot the llama model uses, so ``restore_kv`` (QKV-only
replay) works unchanged.

Tensor-parallel serving: GQA configs shard q + kv heads and the MLP
dims (one psum covers the attention and MLP row-parallel partials of
the parallel block); MQA (n_kv_head=1) is rejected — it would need KV
replication, which the cache layout doesn't model.
"""

import jax
import jax.numpy as jnp

from ..models.falcon import FalconConfig
from ..parallel.topology import TENSOR_AXIS
from .model import PagedInferenceModel, stack_layer_params


class PagedFalconModel(PagedInferenceModel):
    def __init__(self, cfg: FalconConfig, params, **kw):
        if not isinstance(cfg, FalconConfig):
            raise TypeError("PagedFalconModel needs a FalconConfig")
        super().__init__(cfg, params, **kw)

    def _validate_tp(self):
        """GQA falcon (40b/180b-style) shards KV heads; MQA (falcon-7b,
        n_kv_head=1) would need KV replication — rejected explicitly."""
        cfg, tp = self.cfg, self.tp
        for name, val in (("n_head", cfg.n_head),
                          ("n_kv_head", cfg.n_kv_head),
                          ("ffn_dim", cfg.ffn_dim),
                          ("vocab_size", cfg.vocab_size)):
            if val % tp:
                raise ValueError(f"{name}={val} not divisible by "
                                 f"tensor parallel degree {tp}")

    _COL_NAMES = ("q_proj", "k_proj", "v_proj", "dense_h_to_4h")
    _ROW_NAMES = ("o_proj", "dense_4h_to_h")

    def load_params(self, params):
        new = {
            "embed": params["embed_tokens"]["embedding"],
            "norm": {k: params["ln_f"][k] for k in ("scale", "bias")},
            "layers": stack_layer_params(params, self.cfg.n_layer),
        }
        if not self.tied:
            new["lm_head"] = params["lm_head"]["kernel"]
        self.params = self._finalize_params(new)

    @staticmethod
    def _ln(x, p, eps):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (out * p["scale"] + p["bias"]).astype(x.dtype)

    def _final_norm(self, params, x):
        return self._ln(x, params["norm"], self.cfg.layer_norm_epsilon)

    def _layer_step(self, x, lp, ck, cv, layer, lanes):
        """Parallel residual (falcon-7b): x + attn(h) + mlp(h) with ONE
        shared input LayerNorm h."""
        cfg = self.cfg
        h = self._ln(x, lp["input_layernorm"], cfg.layer_norm_epsilon)
        latent = h.astype(self.latent_dtype) \
            if self.capture_latents else jnp.zeros(
            (x.shape[0], x.shape[1], 0), h.dtype)
        q, k, v = self._qkv(lp, h, lanes.positions)
        ck, cv = self._scatter_kv(ck, cv, layer, k, v, lanes)
        attn = self._paged_attention(q, ck, cv, layer, lanes)
        attn = self._mm(attn, lp["self_attn"]["o_proj"]["kernel"])
        up = self._mm(h, lp["dense_h_to_4h"]["kernel"])
        mlp = self._mm(jax.nn.gelu(up), lp["dense_4h_to_h"]["kernel"])
        both = attn + mlp
        if self.tp > 1:   # one psum covers both row-parallel partials
            both = jax.lax.psum(both, TENSOR_AXIS)
        x = x + both
        return x.astype(cfg.compute_dtype), ck, cv, latent, {}
