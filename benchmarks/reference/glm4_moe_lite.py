"""The plain reference of GLM-4-MoE-Lite (``model_type``
``glm4_moe_lite``; GLM-4.7-Flash): multi-head latent attention in its
**published (up-projected) form**, a leading dense layer, sparse layers
with a sigmoid router under a selection bias and an ungated shared
expert. The comparison that decides a cell's ``correct`` does not import
the code it checks; the program's CPU tests import this file.

The layer, with ``x`` the normed residual of position ``t``:

* ``q = W_qb RMSNorm(W_qa x)``: heads of ``[q_nope | q_rope]``;
  ``q_rope <- RoPE(q_rope, t)``.
* ``a = W_kva x = [c | r]``; ``c <- RMSNorm(c)``; ``r <- RoPE(r, t)``,
  one ``r`` for all heads.
* ``[k_nope_h | v_h] = W_kvb,h c``; ``k_h = [k_nope_h | r]``; ``o_h =
  softmax_s(q_h . k_h,s / sqrt(qk_nope + qk_rope)) v_h,s`` over ``s <=
  t``; ``y = W_o concat_h o_h``.
* layers before ``first_k_dense_replace``: a dense SwiGLU. The others:
  ``s = sigmoid(W_g x)`` in float32; the ``num_experts_per_tok`` experts
  of largest ``s + b`` (``b`` = ``e_score_correction_bias``); weights
  ``s_e / sum_picked s`` times ``routed_scaling_factor``; ``y = sum_e w_e
  FFN_e(x) + FFN_shared(x)``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, nothing absorbed. So that a 32k context fits beside the engine
it checks, everything is computed in blocks: one layer's weights at a
time (``layer_params(i)``), attention one head at a time and a block of
query positions at a time, the experts over a block of positions at a
time, each expert's rows (the block's rows sorted by expert) in windows,
one expert's matrices upcast at a time. A check may hand the routers of
chosen positions what the checked system's routers read
(``route_from``): those rows' picks are then the same on both sides.

Departures from the published description (the configuration's
``assumed``): the rotary step pairs channel ``i`` with ``i + D/2``
(half-split; the published config does not say); the softmax scale is
``1 / sqrt(qk_nope_head_dim + qk_rope_head_dim)``.

``arch`` is the published ``config.json``'s keys. Keys beside them make
the reference wrong on purpose (``tools/latent_controls.py``):
``rope_r`` false (``r`` kept without its rotary step), ``norm_c`` false
(``c`` kept before its norm), ``softmax_scale_dim``, ``scoring_func``
``softmax``, ``bias_in_weights``, ``stream_dtype``; the scaling factor,
the shared expert and the fourth pick are dropped through their
published keys.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: query positions a step of attention, positions a step of the expert
#: layer, and rows a window of one expert's products
_Q_BLOCK = 1024
_MOE_BLOCK = 4096
_WINDOW = 256
_HEAD_COLUMNS = 1 << 15


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _rope(x, theta):
    """x: [T, ..., D]; pairs (x_i, x_{i+D/2}) rotate by position."""
    T, D = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (D // 2,))
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _swiglu(x, p):
    return (jax.nn.silu(x @ _f32(p["gate_proj"]["kernel"])) *
            (x @ _f32(p["up_proj"]["kernel"]))) @ \
        _f32(p["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=(
    "n_head", "nope", "rope", "v_dim", "eps", "theta", "scale_dim",
    "rope_r", "norm_c"))
def _attention(x, lp, *, n_head, nope, rope, v_dim, eps, theta, scale_dim,
               rope_r=True, norm_c=True):
    """``x + W_o attention(RMSNorm(x))`` over ``x`` [T, d]."""
    T = x.shape[0]
    attn = lp["self_attn"]
    h = _rms_norm(x, _f32(lp["input_layernorm"]["weight"]), eps)
    qa = _rms_norm(h @ _f32(attn["q_a_proj"]["kernel"]),
                   _f32(attn["q_a_layernorm"]["weight"]), eps)
    a = h @ _f32(attn["kv_a_proj_with_mqa"]["kernel"])
    C = a.shape[-1] - rope
    c, r = a[:, :C], a[:, C:]
    if norm_c:
        c = _rms_norm(c, _f32(attn["kv_a_layernorm"]["weight"]), eps)
    if rope_r:
        r = _rope(r, theta)
    w_qb = attn["q_b_proj"]["kernel"].reshape(-1, n_head, nope + rope)
    w_kvb = attn["kv_b_proj"]["kernel"].reshape(C, n_head, nope + v_dim)
    scale = np.float32(1.0 / np.sqrt(scale_dim))
    n_blocks = T // _Q_BLOCK
    cols = jnp.arange(T)

    def one_head(i):
        q = qa @ _f32(w_qb[:, i])                              # [T, D]
        q = jnp.concatenate([q[:, :nope], _rope(q[:, nope:], theta)], -1)
        kv = c @ _f32(w_kvb[:, i])                       # [T, nope + v]
        k = jnp.concatenate([kv[:, :nope], r], axis=-1)
        v = kv[:, nope:]

        def one_block(j):
            rows = j * _Q_BLOCK + jnp.arange(_Q_BLOCK)
            s = (jax.lax.dynamic_slice_in_dim(q, j * _Q_BLOCK, _Q_BLOCK)
                 @ k.T) * scale
            s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v

        return jax.lax.map(one_block, jnp.arange(n_blocks)).reshape(
            T, v_dim)

    o = jax.lax.map(one_head, jnp.arange(n_head))            # [H, T, v]
    o = jnp.transpose(o, (1, 0, 2)).reshape(T, n_head * v_dim)
    return x + o @ _f32(attn["o_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_mlp(x, lp, *, eps):
    r = _rms_norm(x, _f32(lp["post_attention_layernorm"]["weight"]), eps)
    return x + _swiglu(r, lp["mlp"])


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "norm_topk", "scaling", "shared", "scoring",
    "bias_in_weights"))
def _sparse_mlp(x, lp, route_rows, route_in, *, eps, top_k, norm_topk,
                scaling, shared, scoring="sigmoid",
                bias_in_weights=False):
    """``x + experts(RMSNorm(x))`` over ``x`` [T, d], a block of
    ``_MOE_BLOCK`` positions at a time. ``route_rows`` [n] positions
    whose routers read ``route_in`` [n, d] instead of their own input
    (a position past ``T`` changes nothing)."""
    T, d = x.shape
    mlp = lp["mlp"]
    r = _rms_norm(x, _f32(lp["post_attention_layernorm"]["weight"]), eps)
    read = r.at[route_rows].set(_f32(route_in), mode="drop")
    logits = read @ _f32(mlp["gate"]["weight"])
    bias = _f32(mlp["gate"]["e_score_correction_bias"])
    score = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    _, picked = jax.lax.top_k(score + bias, top_k)               # [T, k]
    weight = jnp.take_along_axis(
        score + bias if bias_in_weights else score, picked, axis=-1)
    if norm_topk:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    weight = weight * np.float32(scaling)
    experts = mlp["experts"]
    E = experts["w1"].shape[0]
    rows_b = min(_MOE_BLOCK, T) * top_k

    def one_block(b):
        at = b * min(_MOE_BLOCK, T)
        rb = jax.lax.dynamic_slice_in_dim(r, at, min(_MOE_BLOCK, T))
        pb = jax.lax.dynamic_slice_in_dim(picked, at, min(_MOE_BLOCK, T))
        wb = jax.lax.dynamic_slice_in_dim(weight, at, min(_MOE_BLOCK, T))
        flat = pb.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        token_of = order // top_k
        # the block's rows sorted by expert, a window's room behind them
        xs = jnp.concatenate([rb[token_of],
                              jnp.zeros((_WINDOW, d), jnp.float32)])
        sizes = jnp.bincount(flat, length=E)
        offsets = jnp.cumsum(sizes) - sizes

        def one_expert(e, ys):
            w1, w3, w2 = (_f32(experts[n][e]) for n in ("w1", "w3", "w2"))

            def one_window(w, ys):
                lo = offsets[e] + w * _WINDOW
                rows = jax.lax.dynamic_slice_in_dim(xs, lo, _WINDOW)
                out = (jax.nn.silu(rows @ w1) * (rows @ w3)) @ w2
                mine = (w * _WINDOW + jnp.arange(_WINDOW)) < sizes[e]
                old = jax.lax.dynamic_slice_in_dim(ys, lo, _WINDOW)
                return jax.lax.dynamic_update_slice_in_dim(
                    ys, jnp.where(mine[:, None], out, old), lo, axis=0)

            return jax.lax.fori_loop(
                0, (sizes[e] + _WINDOW - 1) // _WINDOW, one_window, ys)

        ys = jax.lax.fori_loop(
            0, E, one_expert, jnp.zeros((rows_b + _WINDOW, d), jnp.float32))
        gate = wb.reshape(-1)[order]
        return jax.ops.segment_sum(ys[:rows_b] * gate[:, None], token_of,
                                   num_segments=min(_MOE_BLOCK, T))

    out = jax.lax.map(one_block, jnp.arange(T // min(_MOE_BLOCK, T)))
    out = out.reshape(T, d)
    if shared:
        out = out + _swiglu(r, mlp["shared_experts"])
    return x + out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_kernel, *, eps):
    x = _rms_norm(x, _f32(norm_w), eps)
    # the vocabulary in pieces: the whole head upcast is 1.3 GB at
    # 154,880 x 2048, beside an engine that fills most of the chip
    return jnp.concatenate(
        [x @ _f32(head_kernel[:, at:at + _HEAD_COLUMNS])
         for at in range(0, head_kernel.shape[1], _HEAD_COLUMNS)], axis=-1)


def padded_length(n):
    """The positions a context of ``n`` tokens is computed at: whole
    blocks of the attention's and the experts' steps (a few shapes for
    all contexts; the padding lies behind every compared row, where the
    causal mask hides it)."""
    if n <= _Q_BLOCK:
        return _Q_BLOCK
    return -(-n // _MOE_BLOCK) * _MOE_BLOCK


def logits(tokens, arch, outer_params, layer_params, rows,
           route_from=None):
    """Logits ``[len(rows), vocab]`` at positions ``rows`` of one
    sequence ``tokens`` (``[T]`` ints), causal. ``arch``: the published
    ``config.json``'s keys; ``outer_params``: ``embed_tokens``, ``norm``,
    ``lm_head``; ``layer_params(i)``: layer ``i``'s subtree.
    ``route_from``: ``{position: [sparse layers, hidden]}``, what the
    checked system's routers read there."""
    n = len(tokens)
    ids = np.zeros(padded_length(n), np.int32)
    ids[:n] = tokens
    route_from = route_from or {}
    route_rows = jnp.asarray(sorted(route_from) or [len(ids)], jnp.int32)
    n_dense = int(arch["first_k_dense_replace"])
    eps = float(arch["rms_norm_eps"])
    nope, rope = int(arch["qk_nope_head_dim"]), int(arch["qk_rope_head_dim"])
    with jax.default_matmul_precision("highest"):
        x = _f32(outer_params["embed_tokens"]["embedding"][jnp.asarray(ids)])
        for i in range(int(arch["num_hidden_layers"])):
            lp = layer_params(i)
            x = _attention(
                x, lp, n_head=int(arch["num_attention_heads"]), nope=nope,
                rope=rope, v_dim=int(arch["v_head_dim"]), eps=eps,
                theta=float(arch["rope_theta"]),
                scale_dim=int(arch.get("softmax_scale_dim", nope + rope)),
                rope_r=bool(arch.get("rope_r", True)),
                norm_c=bool(arch.get("norm_c", True)))
            if i < n_dense:
                x = _dense_mlp(x, lp, eps=eps)
            else:
                hidden = x.shape[-1]
                route_in = jnp.stack(
                    [jnp.asarray(route_from[p][i - n_dense])
                     for p in sorted(route_from)]) if route_from \
                    else jnp.zeros((1, hidden), jnp.float32)
                x = _sparse_mlp(
                    x, lp, route_rows, route_in, eps=eps,
                    top_k=int(arch["num_experts_per_tok"]),
                    norm_topk=bool(arch["norm_topk_prob"]),
                    scaling=float(arch["routed_scaling_factor"]),
                    shared=int(arch["n_shared_experts"]) > 0,
                    scoring=arch.get("scoring_func", "sigmoid"),
                    bias_in_weights=bool(arch.get("bias_in_weights",
                                                  False)))
            del lp
            if "stream_dtype" in arch:
                x = _f32(x.astype(arch["stream_dtype"]))
        return _head(x[jnp.asarray(rows)], outer_params["norm"]["weight"],
                     outer_params["lm_head"]["kernel"], eps=eps)


def logit_gap(got, ref):
    """Largest |difference| as a share of the reference row's largest
    |logit|."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
