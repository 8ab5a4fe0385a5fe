"""Paged-KV serving for the Phi family.

Reference analog: the phi policy in
``deepspeed/inference/v2/engine_factory.py:69`` +
``model_implementations/phi/``. Builds on the falcon parallel-block
serving model; adds partial rotary (only ``rotary_dim`` features
rotate), biased q/k/v/dense/fc projections, and the biased untied LM
head.
"""

import jax
import jax.numpy as jnp

from ..models.phi import PhiConfig, partial_rope
from ..ops.rope import rope_frequencies
from ..parallel.topology import TENSOR_AXIS
from .model import stack_layer_params
from .model_falcon import PagedFalconModel


class PagedPhiModel(PagedFalconModel):
    def __init__(self, cfg: PhiConfig, params, **kw):
        if not isinstance(cfg, PhiConfig):
            raise TypeError("PagedPhiModel needs a PhiConfig")
        # skip PagedFalconModel's FalconConfig check
        super(PagedFalconModel, self).__init__(cfg, params, **kw)
        # rope tables over the rotated slice only (must exist before the
        # first jitted call, which __init__ does not trigger)
        self.cos, self.sin = rope_frequencies(cfg.rotary_dim,
                                              cfg.max_positions,
                                              cfg.rope_theta)

    def _validate_tp(self):
        cfg, tp = self.cfg, self.tp
        for name, val in (("n_head", cfg.n_head),
                          ("intermediate_size", cfg.intermediate_size),
                          ("vocab_size", cfg.vocab_size)):
            if val % tp:
                raise ValueError(f"{name}={val} not divisible by "
                                 f"tensor parallel degree {tp}")

    _COL_NAMES = ("q_proj", "k_proj", "v_proj", "fc1")
    _ROW_NAMES = ("dense", "fc2")
    _ROW_BIAS_OK = True   # _layer_step adds row biases after the psum

    def load_params(self, params):
        new = {
            "embed": params["embed_tokens"]["embedding"],
            "norm": {k: params["final_layernorm"][k]
                     for k in ("scale", "bias")},
            "lm_head": {k: params["lm_head"][k]
                        for k in ("kernel", "bias")},
            "layers": stack_layer_params(params, self.cfg.n_layer),
        }

        self.params = self._finalize_params(new)

    def _qkv(self, lp, h, positions):
        cfg = self.cfg
        q, k, v = self._qkv_heads(lp["self_attn"], h)
        q = partial_rope(q, self.cos, self.sin, positions,
                         rotary_dim=cfg.rotary_dim)
        k = partial_rope(k, self.cos, self.sin, positions,
                         rotary_dim=cfg.rotary_dim)
        return q, k, v

    def _layer_step(self, x, lp, ck, cv, layer, lanes):
        cfg = self.cfg
        h = self._ln(x, lp["input_layernorm"], cfg.layer_norm_epsilon)
        latent = h.astype(self.latent_dtype) \
            if self.capture_latents else jnp.zeros(
            (x.shape[0], x.shape[1], 0), h.dtype)
        q, k, v = self._qkv(lp, h, lanes.positions)
        ck, cv = self._scatter_kv(ck, cv, layer, k, v, lanes)
        attn = self._paged_attention(q, ck, cv, layer, lanes)
        d = lp["self_attn"]["dense"]
        attn = self._mm(attn, d["kernel"])
        up = self._mm(h, lp["fc1"]["kernel"]) + lp["fc1"]["bias"]
        mlp = self._mm(jax.nn.gelu(up), lp["fc2"]["kernel"])
        both = attn + mlp
        if self.tp > 1:
            # row-parallel partials psum together; their (replicated)
            # biases add exactly once, after the sum
            both = jax.lax.psum(both, TENSOR_AXIS)
        x = x + both + d["bias"] + lp["fc2"]["bias"]
        return x.astype(cfg.compute_dtype), ck, cv, latent, {}

    def _head_logits(self, params, last):
        head = params["lm_head"]
        return (self._mm(last, head["kernel"])
                + head["bias"]).astype(jnp.float32)
