"""The plain reference of Cohere2-MoE (``model_type`` ``cohere2_moe``;
Command A+): a parallel block over window and global attention layers
and an expert layer of sigmoid-routed experts beside averaged shared
ones, as published. The comparison that decides a cell's ``correct``
does not import the code it checks; the program's CPU tests import this
file.

Layer ``l`` on the residual stream ``x`` (one norm a layer)::

    h  = LN(x)      subtract the mean, divide by sqrt(var + eps) in
                    float32, times a weight; no bias
    x' = x + Attn_l(h) + MoE(h)

* ``Attn``: ``q = h Wq`` (``num_attention_heads`` heads of
  ``head_dim``), ``k = h Wk``, ``v = h Wv`` (``num_key_value_heads``),
  scale ``1 / sqrt(head_dim)``. A ``sliding_attention`` layer rotates q
  and k over the whole head at ``rope_theta`` with the **interleaved**
  pairing (``rope_gptj``: channels ``2i`` and ``2i + 1``) and key ``j``
  is visible to query ``i`` iff ``0 <= i - j < sliding_window`` (a dense
  mask here). A ``full_attention`` layer has **no positional step** and
  the causal mask.
* ``MoE``: ``s = sigmoid(h Wr)`` in float32 over ``num_experts``; the
  ``num_experts_per_tok`` largest picked; weights ``s_e / sum of the
  picked s`` (``norm_topk_prob``); ``routed = sum_e w_e W2_e (silu(W1_e
  h) * W3_e h)``; ``num_shared_experts`` **separate** shared experts on
  every token, ``shared = 1/n sum_j S_j(h)``
  (``shared_expert_combination_strategy`` ``average``); ``MoE(h) =
  routed + shared`` (:func:`with_shared`: the one reading the published
  keys do not pin).
* after the last layer the same LayerNorm and ``logit_scale x E^T`` on
  the tied embedding.

``experts_held = [first, count]`` in ``arch``: the expert stacks hold
only experts ``[first, first + count)`` (one chip's share of a layer
spread over several); the router scores all ``num_experts`` and picks
that fall elsewhere add nothing: the same share the served layer
computes.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching. So that a 21k context fits beside the engine it checks,
everything is computed in blocks: one layer's weights at a time
(``layer_params(i)``), attention one head and a block of query positions
at a time, the experts over a block of positions at a time, each
expert's rows in windows, one expert's matrices upcast at a time. A
check may hand the routers of chosen positions what the checked system's
routers read (``route_from``): those rows' picks are then the same on
both sides.

The shared experts lie in the parameter tree as one fused leaf a
projection (``gate_proj``/``up_proj`` ``[d, n f]``, ``down_proj`` ``[n
f, d]``); shared expert ``j`` is columns ``[j f, (j + 1) f)`` and is
computed here on its own.

``arch`` is the published ``config.json``'s keys. Keys beside them, and
published keys changed, make the reference wrong on purpose
(``tools/window_controls.py``): ``sliding_window`` ``None`` or halved,
``rope_on_global``, ``rope_pairing`` ``half_split``, ``use_parallel_block``
false, ``expert_selection_fn`` ``softmax``, ``norm_topk_prob`` false,
``shared_expert_combination_strategy`` ``sum``, ``num_shared_experts``
0, ``experts_held`` moved, ``num_experts_per_tok`` 7, ``stream_dtype``.
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
_HEAD_ROWS = 1 << 15


def _f32(a):
    return a.astype(jnp.float32)


def layer_norm(x, weight, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, theta, pairing="interleaved"):
    """x: [T, D], position = row; ``interleaved``: channels ``2i`` and
    ``2i + 1`` rotate together (``rope_gptj``); ``half_split``: ``i`` and
    ``i + D/2``."""
    T, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    if pairing == "interleaved":
        x1, x2 = x[:, 0::2], x[:, 1::2]
        return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                         axis=-1).reshape(T, D)
    x1, x2 = x[:, :D // 2], x[:, D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def with_shared(routed, shared_mean):
    """How the averaged shared experts' output meets the routed sum:
    added whole (the configuration's ``assumed``)."""
    return routed + shared_mean


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv_head", "head_dim", "theta", "window", "rotary",
    "pairing"))
def _attention(h, attn, *, n_head, n_kv_head, head_dim, theta, window,
               rotary, pairing):
    """``Attn(h)`` over ``h`` [T, d]: ``window`` positions visible
    behind a query (``None``: all of them), ``rotary`` whether q and k
    take the positional step."""
    T, D = h.shape[0], head_dim
    group = n_head // n_kv_head
    wq = attn["q_proj"]["kernel"].reshape(-1, n_head, D)
    wk = attn["k_proj"]["kernel"].reshape(-1, n_kv_head, D)
    wv = attn["v_proj"]["kernel"].reshape(-1, n_kv_head, D)
    scale = np.float32(1.0 / np.sqrt(D))
    cols = jnp.arange(T)

    wo = attn["o_proj"]["kernel"].reshape(n_head, D, -1)

    def one_head(i, out):
        q = h @ _f32(wq[:, i])                                   # [T, D]
        k = h @ _f32(wk[:, i // group])
        v = h @ _f32(wv[:, i // group])
        if rotary:
            q, k = rope(q, theta, pairing), rope(k, theta, pairing)

        def one_block(j):
            rows = j * _Q_BLOCK + jnp.arange(_Q_BLOCK)
            s = (jax.lax.dynamic_slice_in_dim(q, j * _Q_BLOCK, _Q_BLOCK)
                 @ k.T) * scale
            seen = cols[None, :] <= rows[:, None]
            if window is not None:
                seen &= rows[:, None] - cols[None, :] < window
            s = jnp.where(seen, s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v

        o = jax.lax.map(one_block, jnp.arange(T // _Q_BLOCK)).reshape(T, D)
        # this head's rows of Wo: the heads' results are never held
        # side by side (1.6 GB at 128 heads and 24k positions)
        return out + o @ _f32(wo[i])

    return jax.lax.fori_loop(0, n_head, one_head,
                             jnp.zeros((T, wo.shape[-1]), jnp.float32))


@functools.partial(jax.jit, static_argnames=(
    "top_k", "norm_topk", "n_shared", "combination", "scoring", "held"))
def _experts(h, mlp, route_rows, route_in, *, top_k, norm_topk, n_shared,
             combination, scoring, held):
    """``MoE(h)`` over ``h`` [T, d], a block of ``_MOE_BLOCK`` positions
    at a time. ``route_rows`` [n] positions whose routers read
    ``route_in`` [n, d] instead of their own input (a position past
    ``T`` changes nothing). ``held`` ``(first, count)``: the experts the
    stacks hold."""
    T, d = h.shape
    read = h.at[route_rows].set(_f32(route_in), mode="drop")
    logits = read @ _f32(mlp["gate"]["weight"])
    score = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    weight, picked = jax.lax.top_k(score, top_k)                 # [T, k]
    if norm_topk:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    first, count = held
    here = (picked >= first) & (picked < first + count)
    local = jnp.where(here, picked - first, count)     # count: not held
    weight = jnp.where(here, weight, 0.0)
    experts = mlp["experts"]
    block = min(_MOE_BLOCK, T)
    rows_b = block * top_k

    def one_block(b):
        at = b * block
        hb = jax.lax.dynamic_slice_in_dim(h, at, block)
        pb = jax.lax.dynamic_slice_in_dim(local, at, block)
        wb = jax.lax.dynamic_slice_in_dim(weight, at, block)
        flat = pb.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        token_of = order // top_k
        # the block's rows sorted by expert, a window's room behind them
        xs = jnp.concatenate([hb[token_of],
                              jnp.zeros((_WINDOW, d), jnp.float32)])
        sizes = jnp.sum(flat[None, :] == jnp.arange(count)[:, None],
                        axis=1)
        offsets = jnp.cumsum(sizes) - sizes

        def one_expert(e, ys):
            w1, w3, w2 = (experts[n][e] for n in ("w1", "w3", "w2"))

            def one_window(w, ys):
                lo = offsets[e] + w * _WINDOW
                rows = jax.lax.dynamic_slice_in_dim(xs, lo, _WINDOW)
                out = _swiglu(rows, w1, w3, w2)
                mine = (w * _WINDOW + jnp.arange(_WINDOW)) < sizes[e]
                old = jax.lax.dynamic_slice_in_dim(ys, lo, _WINDOW)
                return jax.lax.dynamic_update_slice_in_dim(
                    ys, jnp.where(mine[:, None], out, old), lo, axis=0)

            return jax.lax.fori_loop(
                0, (sizes[e] + _WINDOW - 1) // _WINDOW, one_window, ys)

        ys = jax.lax.fori_loop(
            0, count, one_expert,
            jnp.zeros((rows_b + _WINDOW, d), jnp.float32))
        gate = wb.reshape(-1)[order]
        return jax.ops.segment_sum(ys[:rows_b] * gate[:, None], token_of,
                                   num_segments=block)

    routed = jax.lax.map(one_block, jnp.arange(T // block)).reshape(T, d)
    if not n_shared:
        return routed
    fused = mlp["shared_experts"]
    f = fused["down_proj"]["kernel"].shape[0] // n_shared
    total = sum(_swiglu(
        h, fused["gate_proj"]["kernel"][:, j * f:(j + 1) * f],
        fused["up_proj"]["kernel"][:, j * f:(j + 1) * f],
        fused["down_proj"]["kernel"][j * f:(j + 1) * f])
        for j in range(n_shared))
    return with_shared(routed, total if combination == "sum"
                       else total / np.float32(n_shared))


@functools.partial(jax.jit, static_argnames=("eps", "scale"))
def _head(x, norm_w, embedding, *, eps, scale):
    x = layer_norm(x, _f32(norm_w), eps)
    # the vocabulary in pieces: the whole table upcast beside an engine
    # that fills most of the chip
    out = jnp.concatenate(
        [x @ _f32(embedding[at:at + _HEAD_ROWS]).T
         for at in range(0, embedding.shape[0], _HEAD_ROWS)], axis=-1)
    return out * np.float32(scale)


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
    sequence ``tokens`` (``[T]`` ints). ``arch``: the published
    ``config.json``'s keys (and ``experts_held``); ``outer_params``:
    ``embed_tokens`` and ``norm``; ``layer_params(i)``: layer ``i``'s
    subtree. ``route_from``: ``{position: [layers, hidden]}``, what the
    checked system's routers read there."""
    n = len(tokens)
    ids = np.zeros(padded_length(n), np.int32)
    ids[:n] = tokens
    route_from = route_from or {}
    route_rows = jnp.asarray(sorted(route_from) or [len(ids)], jnp.int32)
    eps = float(arch["layer_norm_eps"])
    held = tuple(arch.get("experts_held") or
                 (0, int(arch["num_experts"])))
    embedding = outer_params["embed_tokens"]["embedding"]
    with jax.default_matmul_precision("highest"):
        x = _f32(embedding[jnp.asarray(ids)])
        for i in range(int(arch["num_hidden_layers"])):
            lp = layer_params(i)
            sliding = arch["layer_types"][i] == "sliding_attention"
            norm_w = _f32(lp["input_layernorm"]["weight"])
            h = layer_norm(x, norm_w, eps)
            attn = _attention(
                h, lp["self_attn"],
                n_head=int(arch["num_attention_heads"]),
                n_kv_head=int(arch["num_key_value_heads"]),
                head_dim=int(arch["head_dim"]),
                theta=float(arch["rope_theta"]),
                window=arch["sliding_window"] if sliding else None,
                rotary=sliding or bool(arch.get("rope_on_global", False)),
                pairing=arch.get("rope_pairing", "interleaved"))
            if not arch.get("use_parallel_block", True):
                h = layer_norm(x + attn, norm_w, eps)
            route_in = jnp.stack(
                [jnp.asarray(route_from[p][i]) for p in sorted(route_from)]
            ) if route_from else jnp.zeros((1, x.shape[-1]), jnp.float32)
            x = x + attn + _experts(
                h, lp["mlp"], route_rows, route_in,
                top_k=int(arch["num_experts_per_tok"]),
                norm_topk=bool(arch["norm_topk_prob"]),
                n_shared=int(arch["num_shared_experts"]),
                combination=arch.get("shared_expert_combination_strategy",
                                     "average"),
                scoring=arch.get("expert_selection_fn", "sigmoid"),
                held=held)
            del lp
            if "stream_dtype" in arch:
                x = _f32(x.astype(arch["stream_dtype"]))
        return _head(x[jnp.asarray(rows)], outer_params["norm"]["weight"],
                     embedding, eps=eps,
                     scale=float(arch.get("logit_scale", 1.0)))


def logit_gap(got, ref):
    """Largest |difference| as a share of the reference row's largest
    |logit|."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
