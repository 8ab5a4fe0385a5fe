"""The plain reference of the Olmo-Hybrid trunk: full-attention layers
among gated-delta-rule (Gated DeltaNet) layers, in the OLMo 2/3 reordered
norm wrapper, as ``benchmarks/configs/olmo-hybrid-7b-serve-l8.json`` runs
it.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
pool, no batching, independent of the code under test. One layer's
weights are upcast at a time (``layer_params(i)`` hands them over). The
recurrence runs **token by token** (``lax.scan`` over positions, not in
chunks), so it shares no algebra with ``ops/gated_delta.py``; attention
runs one head at a time, which bounds the score matrix at ``T x T``
floats and lets an 8k context fit.

Three conventions the published ``config.json`` does not state are
*assumed* (the configuration file lists them under ``assumed`` in these
words):

1. the linear layers sit in the same wrapper as the full ones:
   ``x <- x + RMSNorm_post_attn(Mixer(x))``, ``x <- x +
   RMSNorm_post_ff(MLP(x))``, the mixer reading the residual stream
   itself (no input norm);
2. ``rope_parameters.rope_theta`` is ``null`` and is read as it stands:
   no rotary step in the full layers;
3. the linear mixer's output gate and per-head output norm are those of
   the layer's published form (flash-linear-attention's
   ``GatedDeltaNet``): ``out = W_o(RMSNorm_{d_v}(o_t) * silu(x_t
   W_gate))``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

LINEAR, FULL = "linear_attention", "full_attention"


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _f32(a):
    return a.astype(jnp.float32)


def _mlp_block(x, lp, eps):
    """``x <- x + RMSNorm_post_ff(W_down(silu(W_gate x) * W_up x))``."""
    mlp = lp["mlp"]
    y = (jax.nn.silu(x @ _f32(mlp["gate_proj"]["kernel"]))
         * (x @ _f32(mlp["up_proj"]["kernel"]))) \
        @ _f32(mlp["down_proj"]["kernel"])
    return x + _rms_norm(y, _f32(lp["post_feedforward_layernorm"]["weight"]),
                         eps)


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _full_layer(x, lp, *, n_head, eps):
    T, C = x.shape
    D = C // n_head
    attn = lp["self_attn"]
    # q and k are normed over all channels, before the split into heads
    q = _rms_norm(x @ _f32(attn["q_proj"]["kernel"]),
                  _f32(attn["q_norm"]["weight"]), eps).reshape(T, n_head, D)
    k = _rms_norm(x @ _f32(attn["k_proj"]["kernel"]),
                  _f32(attn["k_norm"]["weight"]), eps).reshape(T, n_head, D)
    v = (x @ _f32(attn["v_proj"]["kernel"])).reshape(T, n_head, D)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def one_head(h):
        s = (q[:, h] @ k[:, h].T) / np.sqrt(D).astype(np.float32)
        s = jnp.where(causal, s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v[:, h]

    y = jax.lax.map(one_head, jnp.arange(n_head))        # [H, T, D]
    y = jnp.transpose(y, (1, 0, 2)).reshape(T, C)
    x = x + _rms_norm(y @ _f32(attn["o_proj"]["kernel"]),
                      _f32(lp["post_attention_layernorm"]["weight"]), eps)
    return _mlp_block(x, lp, eps)


def _causal_conv(x, taps):
    """Depthwise, causal, from position 0: ``y_t = sum_j taps[j] *
    x_{t - (K - 1) + j}``; ``taps`` is ``[K, channels]`` (the torch
    ``conv1d`` weight ``[channels, 1, K]`` transposed)."""
    K = taps.shape[0]
    xx = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(xx[j:j + x.shape[0]] * taps[j] for j in range(K))


@functools.partial(jax.jit, static_argnames=(
    "n_head", "d_k", "d_v", "neg_eigval", "eps"))
def _linear_layer(x, lp, *, n_head, d_k, d_v, neg_eigval, eps):
    T, _ = x.shape
    la = lp["linear_attn"]

    def conv_act(name, d):
        pre = x @ _f32(la[f"{name}_proj"]["kernel"])
        return jax.nn.silu(_causal_conv(
            pre, _f32(la[f"{name}_conv"]["kernel"]))).reshape(T, n_head, d)

    q, k, v = conv_act("q", d_k), conv_act("k", d_k), conv_act("v", d_v)
    # q/|q|, k/|k| per head; the 1e-6 under the root is the published
    # layer's (its l2norm), kept so that a zero row stays finite
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(x @ _f32(la["b_proj"]["kernel"]))      # [T, H]
    if neg_eigval:
        beta = 2.0 * beta                 # linear_allow_neg_eigval
    g = -jnp.exp(_f32(la["A_log"])) * jax.nn.softplus(
        x @ _f32(la["a_proj"]["kernel"]) + _f32(la["dt_bias"]))

    def token(s, xs):                     # s: [H, d_k, d_v]
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        o = jnp.einsum("hkv,hk->hv", s, q_t) / np.sqrt(d_k).astype(
            np.float32)
        return s, o

    s0 = jnp.zeros((n_head, d_k, d_v), jnp.float32)   # zero at position 0
    _, o = jax.lax.scan(token, s0, (q, k, v, g, beta))            # [T,H,dv]
    o = _rms_norm(o, _f32(la["o_norm"]["weight"]), eps)
    gate = jax.nn.silu(x @ _f32(la["g_proj"]["kernel"]))
    y = (o * gate.reshape(T, n_head, d_v)).reshape(T, n_head * d_v)
    x = x + _rms_norm(y @ _f32(la["o_proj"]["kernel"]),
                      _f32(lp["post_attention_layernorm"]["weight"]), eps)
    return _mlp_block(x, lp, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_kernel, *, eps):
    return _rms_norm(x, _f32(norm_w), eps) @ _f32(head_kernel)


def logits(tokens, arch, outer_params, layer_params):
    """Logits ``[T, vocab]`` of one sequence ``tokens`` (``[T]`` ints).

    ``arch``: the configuration's published keys. ``outer_params``:
    ``embed_tokens``, ``norm`` and ``lm_head`` of the tree;
    ``layer_params(i)``: layer ``i``'s subtree."""
    if (arch.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise NotImplementedError("the reference has no rotary step: the "
                                  "published rope_theta is null")
    tokens = jnp.asarray(tokens, jnp.int32)
    eps = float(arch["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        x = _f32(outer_params["embed_tokens"]["embedding"][tokens])
        for i, kind in enumerate(arch["layer_types"]):
            if kind == FULL:
                x = _full_layer(x, layer_params(i),
                                n_head=arch["num_attention_heads"], eps=eps)
            else:
                x = _linear_layer(
                    x, layer_params(i),
                    n_head=arch["linear_num_value_heads"],
                    d_k=arch["linear_key_head_dim"],
                    d_v=arch["linear_value_head_dim"],
                    neg_eigval=bool(arch["linear_allow_neg_eigval"]),
                    eps=eps)
        return _head(x, outer_params["norm"]["weight"],
                     outer_params["lm_head"]["kernel"], eps=eps)


def next_token_logits(tokens, n_real, arch, outer_params, layer_params):
    """Row ``n_real - 1`` of :func:`logits`: what follows the first
    ``n_real`` tokens. ``tokens`` may be padded past ``n_real`` (to a
    shape already compiled); causality keeps the padding out of the row
    (the recurrence and the convolution only look back)."""
    return np.asarray(
        logits(tokens, arch, outer_params, layer_params)[n_real - 1],
        np.float32)


def logit_gap(got, ref):
    """Largest |difference| as a share of the reference row's largest
    |logit|."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
