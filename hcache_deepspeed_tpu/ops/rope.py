"""Rotary position embeddings.

Reference analog: ``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu``
and the fused ``linear_blocked_kv_rotary`` v2 kernel (the one the HCache
``restore_kv`` path replays). Pure jnp here — XLA fuses the elementwise
rotation into the surrounding QKV matmul, which is exactly what the CUDA
fusion hand-builds; a Pallas variant adds nothing on TPU.
"""

import jax.numpy as jnp

from . import register_op


def rope_frequencies(head_dim, max_positions, theta=10000.0,
                     dtype=jnp.float32):
    """[max_positions, head_dim//2] cos/sin tables."""
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_positions, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x, cos, sin, positions=None):
    """x: [B, T, H, D]; cos/sin: [P, D//2]; positions: [B, T] (default iota).

    Pairs (x_i, x_{i+D/2}) are rotated (GPT-NeoX / llama convention).
    """
    B, T, H, D = x.shape
    if positions is None:
        c = cos[:T][None, :, None, :]
        s = sin[:T][None, :, None, :]
    else:
        c = cos[positions][:, :, None, :]
        s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def rope_at(x, positions, theta):
    """:func:`apply_rope` with the angles computed from ``positions``
    [B, T] (float32), for a path whose contexts are too long for tables
    built into every program. x: [B, T, H, D]."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [B,T,D/2]
    c, s = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


register_op("rope", apply_rope)
