"""The plain reference of the llama trunk (Mistral-7B-v0.1 as the trunk
runs it: pre-norm RMSNorm, rotary embeddings in the GPT-NeoX pairing,
grouped-query attention, SwiGLU, untied head; no sliding window, which
the trunk does not implement and no benchmark context reaches).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, independent of the code under test. One layer's weights are
upcast at a time (``layer_params(i)`` hands them over), so the reference
fits beside the engine it checks. Attention runs one KV group at a time,
which bounds the score matrix at ``group x T x T`` floats.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _rope(x, theta):
    """x: [T, H, D]; pairs (x_i, x_{i+D/2}) rotate by position."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv_head", "eps",
                                             "theta"))
def _layer(x, lp, *, n_head, n_kv_head, eps, theta):
    f32 = lambda a: a.astype(jnp.float32)
    T, C = x.shape
    D = C // n_head
    group = n_head // n_kv_head
    h = _rms_norm(x, f32(lp["input_layernorm"]["weight"]), eps)
    attn = lp["self_attn"]
    q = (h @ f32(attn["q_proj"]["kernel"])).reshape(T, n_head, D)
    k = (h @ f32(attn["k_proj"]["kernel"])).reshape(T, n_kv_head, D)
    v = (h @ f32(attn["v_proj"]["kernel"])).reshape(T, n_kv_head, D)
    q, k = _rope(q, theta), _rope(k, theta)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def one_group(g):
        qg = jax.lax.dynamic_slice_in_dim(q, g * group, group, axis=1)
        kg, vg = k[:, g], v[:, g]
        s = jnp.einsum("thd,sd->hts", qg, kg) / np.sqrt(D).astype(np.float32)
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("hts,sd->thd", jax.nn.softmax(s, axis=-1), vg)

    y = jax.lax.map(one_group, jnp.arange(n_kv_head))    # [KV, T, group, D]
    y = jnp.transpose(y, (1, 0, 2, 3)).reshape(T, C)
    x = x + y @ f32(attn["o_proj"]["kernel"])
    h = _rms_norm(x, f32(lp["post_attention_layernorm"]["weight"]), eps)
    mlp = lp["mlp"]
    gate = h @ f32(mlp["gate_proj"]["kernel"])
    up = h @ f32(mlp["up_proj"]["kernel"])
    return x + (jax.nn.silu(gate) * up) @ f32(mlp["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_kernel, *, eps):
    x = _rms_norm(x, norm_w.astype(jnp.float32), eps)
    return x @ head_kernel.astype(jnp.float32)


def logits(tokens, arch, outer_params, layer_params):
    """Logits ``[T, vocab]`` of one sequence ``tokens`` (``[T]`` ints).

    ``arch``: the configuration's sizes (``num_hidden_layers``,
    ``num_attention_heads``, ``num_key_value_heads``, ``rms_norm_eps``,
    ``rope_theta``). ``outer_params``: ``embed_tokens``, ``norm`` and
    ``lm_head`` of the tree; ``layer_params(i)``: layer ``i``'s subtree.
    """
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = outer_params["embed_tokens"]["embedding"][tokens] \
            .astype(jnp.float32)
        for i in range(arch["num_hidden_layers"]):
            x = _layer(x, layer_params(i),
                       n_head=arch["num_attention_heads"],
                       n_kv_head=arch["num_key_value_heads"],
                       eps=float(arch["rms_norm_eps"]),
                       theta=float(arch["rope_theta"]))
        return _head(x, outer_params["norm"]["weight"],
                     outer_params["lm_head"]["kernel"],
                     eps=float(arch["rms_norm_eps"]))


def next_token_logits(tokens, n_real, arch, outer_params, layer_params):
    """Row ``n_real - 1`` of :func:`logits`: what follows the first
    ``n_real`` tokens. ``tokens`` may be padded past ``n_real`` (to a
    shape already compiled); causality keeps the padding out of the
    row."""
    return np.asarray(
        logits(tokens, arch, outer_params, layer_params)[n_real - 1],
        np.float32)


def lm_loss(tokens, arch, outer_params, layer_params):
    """Mean next-token cross-entropy of one sequence, every position but
    the last a target (the trunk's ``default_lm_labels``)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lg = logits(tokens, arch, outer_params, layer_params)
    logp = jax.nn.log_softmax(lg[:-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)
    return float(jnp.mean(nll))


def logit_gap(got, ref):
    """Largest |difference| as a share of the reference row's largest
    |logit|."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
