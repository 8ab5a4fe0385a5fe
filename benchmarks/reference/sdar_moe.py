"""The plain reference of SDAR-MoE (``model_type`` ``sdar_moe``;
SDAR-30B-A3B-Chat): a sparse-expert llama trunk under a block mask, and
generation by diffusion over blocks. The benchmark's copy of the
reference that ``hcache_deepspeed_tpu/models/sdar_moe.py`` holds for the
program's own tests: the comparison that decides a cell's ``correct``
does not import the code it checks.

The layer, with ``u = RMSNorm(x)``: ``q_i = RoPE(RMSNorm_D(W_q u)_i)``,
``k_j = RoPE(RMSNorm_D(W_k u)_j)``, ``v_j = (W_v u)_j`` over heads of
the published width ``D`` (``head_dim``: q and o are ``n_head * D``
wide); ``h = x + W_o softmax(q k^T / sqrt(D) + M) v`` with ``M[t, s] = 0
if s < (t // B + 1) * B else -inf``; ``y = h + sum_{e in top-k(p)} (p_e
/ sum_top-k p) W2_e (silu(W1_e r) * W3_e r)``, ``r = RMSNorm(h)``, ``p =
softmax_f32(W_r r)``. A denoise pass is a forward over the open block
behind the committed blocks; position ``i``'s logits predict position
``i``'s token (no shift).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching. One layer's weights are handed over at a time
(``layer_params(i)``) and one expert's upcast at a time (a plain loop
over the experts: every token through every expert, weighed by its gate
or by zero), so the reference fits beside the engine it checks;
attention runs one KV group at a time. The generate procedure is plain
Python, both remasking rules. A check may hand the block's routers what
the checked system's routers read (``route_from``): the picks are then
the same, and every row can be held to the limit of the precision.

Departures from the published description (the configuration's
``assumed``): the block length and the schedule are not in the published
``config.json``; the per-head norm of q and k is the Qwen3-MoE layer's;
confidence is read off the untempered softmax; ties in confidence go to
the lower position.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

REMASKING = ("static", "dynamic")


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


def block_mask(T, block):
    """``M`` as a boolean ``[T, T]``: row ``t`` sees column ``s`` when
    ``s < (t // block + 1) * block``."""
    t = jnp.arange(T)
    return t[None, :] < ((t // block + 1) * block)[:, None]


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv_head", "head_dim", "eps", "theta", "block", "top_k",
    "norm_topk", "qk_norm", "router_dtype"))
def _layer(x, lp, route=None, *, n_head, n_kv_head, head_dim, eps, theta,
           block, top_k, norm_topk, qk_norm=True, router_dtype="float32"):
    f32 = lambda a: a.astype(jnp.float32)
    T = x.shape[0]
    D, group = head_dim, n_head // n_kv_head
    h = _rms_norm(x, f32(lp["input_layernorm"]["weight"]), eps)
    attn = lp["self_attn"]
    q = (h @ f32(attn["q_proj"]["kernel"])).reshape(T, n_head, D)
    k = (h @ f32(attn["k_proj"]["kernel"])).reshape(T, n_kv_head, D)
    v = (h @ f32(attn["v_proj"]["kernel"])).reshape(T, n_kv_head, D)
    if qk_norm:
        q = _rms_norm(q, f32(attn["q_norm"]["weight"]), eps)
        k = _rms_norm(k, f32(attn["k_norm"]["weight"]), eps)
    q, k = _rope(q, theta), _rope(k, theta)
    seen = block_mask(T, block)

    def one_group(g):
        qg = jax.lax.dynamic_slice_in_dim(q, g * group, group, axis=1)
        s = jnp.einsum("thd,sd->hts", qg, k[:, g]) / \
            np.sqrt(D).astype(np.float32)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hts,sd->thd", jax.nn.softmax(s, axis=-1),
                          v[:, g])

    y = jax.lax.map(one_group, jnp.arange(n_kv_head))    # [KV, T, group, D]
    y = jnp.transpose(y, (1, 0, 2, 3)).reshape(T, n_head * D)
    x = x + y @ f32(attn["o_proj"]["kernel"])

    r = _rms_norm(x, f32(lp["post_attention_layernorm"]["weight"]), eps)
    moe = lp["mlp"]["moe"]
    rd = jnp.dtype(router_dtype)         # float32: the model's own
    routed = r
    if route is not None:       # these positions' router reads this
        at, values = route
        routed = r.at[at].set(f32(values))
    p = jax.nn.softmax(f32(routed.astype(rd) @ moe["wg"].astype(rd)),
                       axis=-1)                                  # [T, E]
    top_p, top_e = jax.lax.top_k(p, top_k)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    experts = moe["experts"]

    def one_expert(e, acc):        # every token through expert e, then
        gate = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)  # weighed
        mid = jax.nn.silu(r @ f32(experts["w1"][e])) * \
            (r @ f32(experts["w3"][e]))
        return acc + gate[:, None] * (mid @ f32(experts["w2"][e]))

    return jax.lax.fori_loop(0, experts["w1"].shape[0], one_expert, x)


_HEAD_COLUMNS = 1 << 15


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_kernel, *, eps):
    x = _rms_norm(x, norm_w.astype(jnp.float32), eps)
    # the vocabulary in pieces: the whole head upcast is 1.2 GB at
    # 151,936 x 2048, beside an engine that fills most of the chip
    return jnp.concatenate(
        [x @ head_kernel[:, at:at + _HEAD_COLUMNS].astype(jnp.float32)
         for at in range(0, head_kernel.shape[1], _HEAD_COLUMNS)], axis=-1)


def logits(tokens, arch, outer_params, layer_params, rows=None,
           route_from=None):
    """Logits of one sequence ``tokens`` (``[T]`` ints, committed context
    and open block together) under the block mask; position ``i``'s row
    predicts position ``i``'s token. ``rows``: the positions wanted
    (default all). ``arch``: the published configuration's keys;
    ``outer_params``: ``embed_tokens``, ``norm``, ``lm_head``;
    ``layer_params(i)``: layer ``i``'s subtree. ``route_from``:
    ``(positions [b], values [L, b, hidden])``: in layer ``l`` the router
    of these positions reads ``values[l]`` in place of the reference's own
    stream (what the checked system's router read there: routing is
    discrete, and a near tie between the eighth and the ninth expert
    falls the other way in a stream of another precision, which is no
    fault; the experts picked so are still applied to the reference's
    own stream, weighed by the reference's router)."""
    return logits_of([(tokens, rows, route_from)], arch, outer_params,
                     layer_params)[0]


def logits_of(sequences, arch, outer_params, layer_params):
    """:func:`logits` of each ``(tokens, rows, route_from)`` in
    ``sequences``, a layer at a time over all of them: ``layer_params(i)``
    is asked for once a layer, where making a layer's weights costs more
    than running it."""
    with jax.default_matmul_precision("highest"):
        xs = [outer_params["embed_tokens"]["embedding"][
            jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
            for tokens, _, _ in sequences]
        for i in range(arch["num_hidden_layers"]):
            lp = layer_params(i)
            for j, (_, _, route_from) in enumerate(sequences):
                route = None if route_from is None else (
                    jnp.asarray(route_from[0]),
                    jnp.asarray(route_from[1][i]))
                x = _layer(xs[j], lp, route,
                           n_head=arch["num_attention_heads"],
                           n_kv_head=arch["num_key_value_heads"],
                           head_dim=arch["head_dim"],
                           eps=float(arch["rms_norm_eps"]),
                           theta=float(arch["rope_theta"]),
                           block=int(arch["diffusion_block_length"]),
                           top_k=int(arch["num_experts_per_tok"]),
                           norm_topk=bool(arch["norm_topk_prob"]),
                           qk_norm=bool(arch.get("qk_norm", True)),
                           router_dtype=arch.get("router_dtype",
                                                 "float32"))
                if "stream_dtype" in arch:
                    x = x.astype(arch["stream_dtype"]).astype(jnp.float32)
                xs[j] = x
            del lp
        return [_head(x if rows is None else x[jnp.asarray(rows)],
                      outer_params["norm"]["weight"],
                      outer_params["lm_head"]["kernel"],
                      eps=float(arch["rms_norm_eps"]))
                for x, (_, rows, _) in zip(xs, sequences)]


def _block_sequence(context, block_tokens, pad_to, route_from):
    n, b = len(context), len(block_tokens)
    ids = np.zeros(max(n + b, pad_to), np.int32)
    ids[:n] = context
    ids[n:n + b] = block_tokens
    at = np.arange(n, n + b)
    return ids, at, None if route_from is None else (at, route_from)


def block_logits(context, block_tokens, arch, outer_params, layer_params,
                 pad_to=0, route_from=None):
    """Logits ``[B, vocab]`` of one pass over ``block_tokens`` (masks
    and all) behind ``context`` (committed tokens, whole blocks).
    ``pad_to``: run at this many positions (a shape already compiled);
    the block mask keeps the padding's blocks out of the rows.
    ``route_from`` ``[L, B, hidden]``: what the block's routers read
    (:func:`logits`)."""
    return blocks_logits([(context, block_tokens, pad_to, route_from)],
                         arch, outer_params, layer_params)[0]


def blocks_logits(passes, arch, outer_params, layer_params):
    """:func:`block_logits` of each ``(context, block_tokens, pad_to,
    route_from)`` in ``passes`` (:func:`logits_of`)."""
    return [np.asarray(rows, np.float32) for rows in logits_of(
        [_block_sequence(*p) for p in passes], arch, outer_params,
        layer_params)]


def choose(rows, mask_id):
    """Greedy choice and its confidence a position: ``(tokens [B],
    confidence [B])`` of logits ``rows`` ``[B, vocab]``. The mask token
    is no prediction: it is left out of the choice and of the softmax."""
    rows = np.array(rows, np.float64)
    rows[:, mask_id] = -np.inf
    tokens = rows.argmax(axis=-1)
    shifted = rows - rows.max(axis=-1, keepdims=True)
    probs = np.exp(shifted) / np.exp(shifted).sum(axis=-1, keepdims=True)
    return tokens.astype(np.int64), \
        probs[np.arange(len(tokens)), tokens]


def unmask(block_tokens, tokens, confidence, mask_id, count,
           remasking="static", threshold=0.9):
    """One denoise pass's remasking rule: ``block_tokens`` with its
    masked positions of highest ``confidence`` filled from ``tokens``:
    ``count`` of them (``static``), or every one over ``threshold`` and
    at least ``count`` (``dynamic``). Ties go to the lower position."""
    if remasking not in REMASKING:
        raise ValueError(f"remasking must be one of {REMASKING}, got "
                         f"{remasking!r}")
    out = list(block_tokens)
    masked = [i for i, t in enumerate(out) if t == mask_id]
    order = sorted(masked, key=lambda i: (-float(confidence[i]), i))
    picked = order[:count]
    if remasking == "dynamic":
        picked += [i for i in order[count:]
                   if float(confidence[i]) > threshold]
    for i in picked:
        out[i] = int(tokens[i])
    return out


def generate(prompt, max_new_tokens, arch, outer_params, layer_params, *,
             denoising_steps=2, remasking="static", threshold=0.9,
             eos_token_id=None, pad_to=0):
    """The family's generate procedure, greedy, with no cache: every
    pass is a full forward of (committed context + open block). Returns
    the generated tokens (at most ``max_new_tokens``, cut after an
    EOS)."""
    B = int(arch["diffusion_block_length"])
    mask_id = int(arch["mask_token_id"])
    count = -(-B // int(denoising_steps))
    whole = len(prompt) // B * B
    context, carried = list(prompt[:whole]), list(prompt[whole:])
    out = []
    while len(out) < max_new_tokens:
        block = carried + [mask_id] * (B - len(carried))
        while mask_id in block:
            tokens, conf = choose(block_logits(
                context, block, arch, outer_params, layer_params, pad_to),
                mask_id)
            block = unmask(block, tokens, conf, mask_id, count, remasking,
                           threshold)
        # the commit pass stores K and V: with no cache, nothing to do
        for tok in block[len(carried):]:
            out.append(int(tok))
            if len(out) >= max_new_tokens or tok == eos_token_id:
                return out
        context, carried = context + block, []
    return out


def row_gaps(got, ref):
    """A row (a position) at a time: largest |difference| as a share of
    the reference row's largest |logit|."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return [float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
            for g, r in zip(got, ref)]


def logit_gap(got, ref):
    """Largest |difference| as a share of the reference rows' largest
    |logit|."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
