"""SDAR-MoE family (``model_type`` ``sdar_moe``): a sparse-expert llama
trunk that generates by diffusion over blocks.

The layer, with ``u = RMSNorm(x)``: ``q_i = RoPE(RMSNorm_D(W_q u)_i)``,
``k_j = RoPE(RMSNorm_D(W_k u)_j)``, ``v_j = (W_v u)_j`` over heads of a
published width ``D`` (q and o are ``n_head * D`` wide, not
``hidden_size``); ``h = x + W_o softmax(q k^T / sqrt(D) + M) v`` under
the **block mask** ``M[t, s] = 0 if s < (t // B + 1) * B else -inf``
(both directions inside a block of ``B`` positions, causal across
blocks); ``y = h + sum_{e in top-k(p)} (p_e / sum_top-k p) W2_e
(silu(W1_e r) * W3_e r)``, ``r = RMSNorm(h)``, ``p = softmax_f32(W_r
r)``.

Generation: the prompt's whole blocks prefill under ``M``; a new block
is ``B`` mask tokens (a partial last block of the prompt stands in it
unmasked); a **denoise pass** is a forward over the block against the
committed blocks, position ``i``'s logits predict position ``i``'s token
(no shift), and the pass unmasks the masked positions of highest
confidence (the softmax probability of the chosen token): a fixed count
a pass (``static``), or every position over a threshold and at least
that count (``dynamic``); when no mask is left a **commit pass** over
the clean block stores its K and V and the block's tokens are final.

This module holds the configuration, the parameter tree (the Mixtral
tree with ``q_norm``/``k_norm``: :func:`SdarMoeForCausalLM`, whose own
flax forward is the causal training one and is used here to ``init`` and
to read shapes only) and the plain reference: float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``, no kernel, no cache,
no batching, the experts a plain loop, and the generate procedure in
plain Python. ``benchmarks/reference/sdar_moe.py`` is the benchmark's
copy of the reference.

Departures from the published description, all under ``assumed`` in the
benchmark's configuration: the block length and the schedule are not in
the published ``config.json`` (``diffusion_block_length`` 4 is the Chat
release's); the per-head norm of q and k is the Qwen3-MoE layer's, from
which the family derives; confidence is read off the untempered
softmax; ties in confidence go to the lower position.
"""

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .mixtral import MixtralConfig, MixtralForCausalLM

REMASKING = ("static", "dynamic")


@dataclass(frozen=True)
class SdarMoeConfig(MixtralConfig):
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    num_experts: int = 128
    top_k: int = 8
    norm_topk_prob: bool = True
    dropless: bool = True
    qk_norm: bool = True
    head_width: int = 128
    #: positions a generation block holds; 1: causal, one token a step
    diffusion_block_length: int = 1
    #: the token a position holds until a denoise pass unmasks it
    mask_token_id: int = 151669

    def __post_init__(self):
        if self.diffusion_block_length < 1:
            raise ValueError("diffusion_block_length must be >= 1, got "
                             f"{self.diffusion_block_length}")


def sdar_moe_tiny(**kw):
    defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                    n_layer=2, n_head=4, n_kv_head=2, head_width=32,
                    max_positions=128, num_experts=8, top_k=2,
                    diffusion_block_length=4, mask_token_id=255)
    defaults.update(kw)
    return SdarMoeConfig(**defaults)


def SdarMoeForCausalLM(cfg: SdarMoeConfig):
    """The parameter tree (``layers_i/{self_attn/{q,k,v,o}_proj,
    self_attn/{q,k}_norm, mlp/moe/{wg, experts/{w1,w2,w3}}}``)."""
    return MixtralForCausalLM(cfg)


def arch_of(cfg: SdarMoeConfig):
    """``cfg`` in the published ``config.json``'s keys: what the plain
    reference reads."""
    return {"num_hidden_layers": cfg.n_layer,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.rms_norm_eps,
            "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.top_k,
            "norm_topk_prob": cfg.norm_topk_prob,
            "diffusion_block_length": cfg.diffusion_block_length,
            "mask_token_id": cfg.mask_token_id}


# ------------------------------------------------------------------ #
# The plain reference
# ------------------------------------------------------------------ #
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
def _layer(x, lp, *, n_head, n_kv_head, head_dim, eps, theta, block,
           top_k, norm_topk, qk_norm=True, router_dtype="float32"):
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
    p = jax.nn.softmax(f32(r.astype(rd) @ moe["wg"].astype(rd)),
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


def logits(tokens, arch, outer_params, layer_params, rows=None):
    """Logits of one sequence ``tokens`` (``[T]`` ints, committed context
    and open block together) under the block mask; position ``i``'s row
    predicts position ``i``'s token. ``rows``: the positions wanted
    (default all). ``arch``: :func:`arch_of`'s keys; ``outer_params``:
    ``embed_tokens``, ``norm``, ``lm_head``; ``layer_params(i)``: layer
    ``i``'s subtree."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = outer_params["embed_tokens"]["embedding"][tokens] \
            .astype(jnp.float32)
        for i in range(arch["num_hidden_layers"]):
            x = _layer(x, layer_params(i),
                       n_head=arch["num_attention_heads"],
                       n_kv_head=arch["num_key_value_heads"],
                       head_dim=arch["head_dim"],
                       eps=float(arch["rms_norm_eps"]),
                       theta=float(arch["rope_theta"]),
                       block=int(arch["diffusion_block_length"]),
                       top_k=int(arch["num_experts_per_tok"]),
                       norm_topk=bool(arch["norm_topk_prob"]),
                       qk_norm=bool(arch.get("qk_norm", True)),
                       router_dtype=arch.get("router_dtype", "float32"))
            if "stream_dtype" in arch:
                x = x.astype(arch["stream_dtype"]).astype(jnp.float32)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return _head(x, outer_params["norm"]["weight"],
                     outer_params["lm_head"]["kernel"],
                     eps=float(arch["rms_norm_eps"]))


def block_logits(context, block_tokens, arch, outer_params, layer_params,
                 pad_to=0):
    """Logits ``[B, vocab]`` of one pass over ``block_tokens`` (masks
    and all) behind ``context`` (committed tokens, whole blocks).
    ``pad_to``: run at this many positions (a shape already compiled);
    the block mask keeps the padding's blocks out of the rows."""
    n, b = len(context), len(block_tokens)
    ids = np.zeros(max(n + b, pad_to), np.int32)
    ids[:n] = context
    ids[n:n + b] = block_tokens
    return np.asarray(logits(ids, arch, outer_params, layer_params,
                             rows=np.arange(n, n + b)), np.float32)


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
