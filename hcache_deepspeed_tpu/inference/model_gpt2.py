"""Paged-KV inference model for the GPT-2 architecture family.

Reference analog: the v1 kernel-injection containers for gpt2/gpt-neo
(``module_inject/containers/gpt2.py``) and the v2 model-implementation
framework's per-arch layer containers — a SECOND architecture served by
the same ragged engine: LayerNorm (not RMSNorm), learned absolute
position embeddings (no RoPE), biased projections, MHA, tied LM head.

Built on :class:`PagedInferenceModel`'s trunk, which supplies the KV
plumbing, TP machinery (vocab-parallel tied embedding, sharded KV,
per-layer psum), quantized serving and HCache restore. The fused HF
``c_attn`` splits into separate q/k/v at load time — the TP-shardable
layout (a column shard of the fused [C, 3C] kernel would mix whole-q
with half-k); biases on the row-parallel projections add once, after
the psum. Latents (HCache) = the post-ln_1 hidden states.
"""

import jax
import jax.numpy as jnp

from ..models.gpt2 import GPT2Config
from ..parallel.topology import TENSOR_AXIS
from .model import PagedInferenceModel, stack_layer_params


class PagedGPT2Model(PagedInferenceModel):
    _COL_NAMES = ("q_proj", "k_proj", "v_proj", "c_fc")
    _ROW_NAMES = ("c_proj",)          # attn and mlp output projections
    _ROW_BIAS_OK = True               # added after the psum below

    def __init__(self, cfg: GPT2Config, params, **kw):
        if not isinstance(cfg, GPT2Config):
            raise TypeError("PagedGPT2Model needs a GPT2Config")
        super().__init__(cfg, params, **kw)

    def _validate_tp(self):
        cfg, tp = self.cfg, self.tp
        for name, val in (("n_head", cfg.n_head),
                          ("n_embd", cfg.n_embd),
                          ("vocab_size", cfg.vocab_size)):
            if val % tp:
                raise ValueError(f"{name}={val} not divisible by "
                                 f"tensor parallel degree {tp}")

    # -------------------------------------------------------------- #
    def load_params(self, params):
        """Training layout -> serving layout: fused c_attn [C, 3C]
        splits into q/k/v [C, C] (+ biases), everything stacked."""
        layers = stack_layer_params(params, self.cfg.n_layer, prefix="h_")
        ca_k = layers["attn"]["c_attn"]["kernel"]      # [L, C, 3C]
        ca_b = layers["attn"]["c_attn"]["bias"]        # [L, 3C]
        qk, kk, vk = jnp.split(ca_k, 3, axis=-1)
        qb, kb, vb = jnp.split(ca_b, 3, axis=-1)
        new = {
            "embed": params["wte"]["embedding"],
            "wpe": params["wpe"]["embedding"],
            "norm": {k: params["ln_f"][k] for k in ("scale", "bias")},
            "layers": {
                "ln_1": layers["ln_1"],
                "ln_2": layers["ln_2"],
                "attn": {
                    "q_proj": {"kernel": qk, "bias": qb},
                    "k_proj": {"kernel": kk, "bias": kb},
                    "v_proj": {"kernel": vk, "bias": vb},
                    "c_proj": layers["attn"]["c_proj"],
                },
                "mlp": layers["mlp"],
            },
        }
        self.params = self._finalize_params(new)

    # -------------------------------------------------------------- #
    def _top_leaf_spec(self, key, path, leaf):
        from jax.sharding import PartitionSpec as P
        if key == "wpe":
            return P()            # positions replicate
        return super()._top_leaf_spec(key, path, leaf)

    def _embed_extra(self, params, positions):
        return params["wpe"][positions].astype(self.cfg.compute_dtype)

    @staticmethod
    def _ln(x, p, eps):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (out * p["scale"] + p["bias"]).astype(x.dtype)

    def _final_norm(self, params, x):
        return self._ln(x, params["norm"], self.cfg.layer_norm_epsilon)

    # -------------------------------------------------------------- #
    def _qkv(self, lp, h, positions):
        """Separate biased projections, no rope."""
        return self._qkv_heads(lp["attn"], h)

    def _attn_out_parts(self, lp, attn):
        p = lp["attn"]["c_proj"]
        return self._mm(attn, p["kernel"]), p["bias"]

    def _mlp_out_parts(self, lp, h2):
        m = lp["mlp"]
        ff = jax.nn.gelu(self._mm(h2, m["c_fc"]["kernel"]) +
                         m["c_fc"]["bias"], approximate=True)
        return self._mm(ff, m["c_proj"]["kernel"]), m["c_proj"]["bias"]

    def _layer_step(self, x, lp, ck, cv, layer, lanes):
        cfg = self.cfg
        eps = cfg.layer_norm_epsilon
        h = self._ln(x, lp["ln_1"], eps)
        latent = h.astype(self.latent_dtype) \
            if self.capture_latents else jnp.zeros(
            (x.shape[0], x.shape[1], 0), h.dtype)
        q, k, v = self._qkv(lp, h, lanes.positions)
        ck, cv = self._scatter_kv(ck, cv, layer, k, v, lanes)
        attn = self._paged_attention(q, ck, cv, layer, lanes)
        ap, ab = self._attn_out_parts(lp, attn)
        if self.tp > 1:
            ap = jax.lax.psum(ap, TENSOR_AXIS)
        x = x + ap + ab           # row bias once, after the psum
        h2 = self._ln(x, lp["ln_2"], eps)
        mp, mb = self._mlp_out_parts(lp, h2)
        if self.tp > 1:
            mp = jax.lax.psum(mp, TENSOR_AXIS)
        x = x + mp + mb
        return x.astype(cfg.compute_dtype), ck, cv, latent, {}
