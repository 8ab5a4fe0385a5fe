"""Olmo-Hybrid family in flax.linen: full-attention layers among
gated-delta-rule (linear attention) layers, in the OLMo 2/3 reordered
norm wrapper.

This module defines the parameter tree the paged hybrid model
(``inference/model_hybrid.py``) serves, and a plain forward over it (the
recurrence token by token through ``ops.gated_delta``'s jnp reference,
dense causal attention): enough to ``init`` the tree, to read shapes off
with ``jax.eval_shape`` and to check the serving path against at toy
width. It is not a training path: the chunked scan has no backward pass
here (ROADMAP R4).

Block, both kinds: ``x <- x + norm(mixer(x))``; ``x <- x + norm(mlp(x))``
(the mixer reads the residual stream itself: no input norm). Full mixer:
q and k RMS-normed over all channels before the split into heads, no
rotary step when ``rope_theta`` is ``None``. Linear mixer: see
``ops/gated_delta.py`` for the rule; q, k and v pass a causal depthwise
convolution and SiLU first, the output a per-head RMSNorm and a SiLU
gate.
"""

from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.gated_delta import reference_gated_delta
from .llama import LlamaConfig, LlamaMLP, RMSNorm

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass(frozen=True)
class OlmoHybridConfig(LlamaConfig):
    rms_norm_eps: float = 1e-6
    rope_theta: Optional[float] = None
    #: one entry per layer, ``linear_attention`` or ``full_attention``
    layer_types: Tuple[str, ...] = ()
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True

    def __post_init__(self):
        if len(self.layer_types) != self.n_layer:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"n_layer is {self.n_layer}")
        bad = set(self.layer_types) - {LINEAR, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise NotImplementedError(
                "linear layers with more value heads than key heads "
                "(grouped value attention) are not implemented")

    @property
    def conv_channels(self) -> int:
        """q | k | v channels of a linear layer's convolution."""
        return self.linear_num_key_heads * self.linear_key_head_dim * 2 \
            + self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern of layer kinds that ``layer_types``
        repeats."""
        n = len(self.layer_types)
        for p in range(1, n + 1):
            if n % p == 0 and \
                    self.layer_types == self.layer_types[:p] * (n // p):
                return self.layer_types[:p]
        return self.layer_types


def olmo_hybrid_tiny(**kw):
    """Test-scale config: two periods of (3 linear, 1 full)."""
    defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    n_layer=8, n_head=4, n_kv_head=4, max_positions=256,
                    layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 2,
                    linear_num_key_heads=4, linear_num_value_heads=4,
                    linear_key_head_dim=8, linear_value_head_dim=16)
    defaults.update(kw)
    return OlmoHybridConfig(**defaults)


def _dense(width, x, name):
    return nn.Dense(width, use_bias=False, dtype=x.dtype, name=name)(x)


def decay_init(lo=0.02, hi=0.98):
    """``A_log`` = 0 and ``dt_bias`` such that a token whose ``a``
    projection is 0 decays its head's state by a factor spread evenly
    over ``(lo, hi)`` across the heads: ``g = -softplus(dt_bias)`` and
    ``exp(g)`` is that factor."""
    def init(key, shape, dtype=jnp.float32):
        factor = jnp.linspace(lo, hi, shape[0], dtype=jnp.float32)
        rate = -jnp.log(factor)                     # softplus(dt_bias)
        return jnp.log(jnp.expm1(rate)).astype(dtype)
    return init


class FullMixer(nn.Module):
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, _ = x.shape
        H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(
            _dense(H * D, x, "q_proj"))
        k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(
            _dense(KV * D, x, "k_proj"))
        v = _dense(KV * D, x, "v_proj")
        if cfg.rope_theta is not None:
            raise NotImplementedError("rotary step of the hybrid trunk")
        q = q.reshape(B, T, KV, H // KV, D)
        k, v = k.reshape(B, T, KV, D), v.reshape(B, T, KV, D)
        s = jnp.einsum("btkgd,bskd->bkgts", q, k) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        y = jnp.einsum("bkgts,bskd->btkgd", jax.nn.softmax(s, axis=-1), v)
        return _dense(cfg.hidden_size, y.reshape(B, T, H * D), "o_proj")


class _ConvKernel(nn.Module):
    """``[K, channels]`` taps of a causal depthwise convolution; tap
    ``K - 1`` multiplies the current token."""
    taps: int
    channels: int

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (self.taps, self.channels))


def causal_conv(x, kernel):
    """x: [B, T, C] from position 0; kernel: [K, C]."""
    K = kernel.shape[0]
    xx = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    T = x.shape[1]
    return sum(xx[:, j:j + T] * kernel[j] for j in range(K))


class LinearMixer(nn.Module):
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, _ = x.shape
        H = cfg.linear_num_key_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        K = cfg.linear_conv_kernel_dim
        f32 = jnp.float32

        def conv_act(name, width):
            pre = _dense(width, x, f"{name}_proj").astype(f32)
            taps = _ConvKernel(K, width, name=f"{name}_conv")()
            return jax.nn.silu(causal_conv(pre, taps.astype(f32)))

        q = conv_act("q", H * dk).reshape(B, T, H, dk)
        k = conv_act("k", H * dk).reshape(B, T, H, dk)
        v = conv_act("v", H * dv).reshape(B, T, H, dv)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        a_log = self.param("A_log", nn.initializers.zeros, (H,))
        dt_bias = self.param("dt_bias", decay_init(), (H,))
        beta = jax.nn.sigmoid(_dense(H, x, "b_proj").astype(f32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            _dense(H, x, "a_proj").astype(f32) + dt_bias.astype(f32))
        pool = jnp.zeros((1, B, H, dk, dv), f32)
        o, _ = reference_gated_delta(
            q, k, v, g, beta, pool, 0, jnp.arange(B),
            jnp.zeros((B,), jnp.int32))
        o = RMSNorm(cfg.rms_norm_eps, name="o_norm")(o)
        gate = jax.nn.silu(_dense(H * dv, x, "g_proj").astype(f32))
        y = (o * gate.reshape(B, T, H, dv)).reshape(B, T, H * dv)
        return _dense(cfg.hidden_size, y.astype(x.dtype), "o_proj")


class OlmoHybridBlock(nn.Module):
    cfg: OlmoHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        if self.kind == FULL:
            y = FullMixer(cfg, name="self_attn")(x)
        else:
            y = LinearMixer(cfg, name="linear_attn")(x)
        x = x + RMSNorm(cfg.rms_norm_eps,
                        name="post_attention_layernorm")(y)
        y = LlamaMLP(cfg, name="mlp")(x)
        return x + RMSNorm(cfg.rms_norm_eps,
                           name="post_feedforward_layernorm")(y)


class OlmoHybridForCausalLM(nn.Module):
    """``layers_<i>`` holds ``self_attn`` or ``linear_attn`` by
    ``cfg.layer_types[i]``; the rest of the tree is the llama tree."""
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, batch, train: bool = False):
        cfg = self.cfg
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                     dtype=cfg.compute_dtype, name="embed_tokens")(ids)
        for i, kind in enumerate(cfg.layer_types):
            x = OlmoHybridBlock(cfg, kind, name=f"layers_{i}")(x)
        x = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
        if cfg.tie_word_embeddings:
            raise NotImplementedError("tied head of the hybrid trunk")
        return _dense(cfg.vocab_size, x, "lm_head").astype(jnp.float32)
