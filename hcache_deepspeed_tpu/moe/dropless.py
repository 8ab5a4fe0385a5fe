"""Dropless MoE: grouped-GEMM expert compute without capacity buffers.

Reference analog: the MoE-GEMM kernel path
(``inference/v2/kernels/cutlass_ops/moe_gemm`` + ``moe_gather`` /
``moe_scatter`` ragged ops) — tokens sorted by expert, one grouped GEMM
over the ragged groups, scattered back. No token is ever dropped (the
megablocks formulation), unlike the capacity-factor path in
``moe/layer.py``.

TPU-native: sort-by-expert is an ``argsort`` (static [N*k] shape), the
grouped GEMMs are ``lax.ragged_dot`` (``ops/grouped_gemm.py``), and the
combine is a ``segment_sum`` — all differentiable, the whole layer jits
as one program. Expert-parallel sharding note: this layer computes all
experts' GEMMs from one token stream, so it composes with tensor/data
sharding; the expert-axis a2a path keeps using the capacity layer.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.grouped_gemm import grouped_matmul, grouped_matmul_stacked


def dropless_route(logits, k, renormalize=True, score="softmax",
                   bias=None, scale=None):
    """Top-k routing without capacity: returns (probs [N,k], experts
    [N,k], aux load-balancing loss) — same aux formula as the capacity
    gate (fraction-mean * prob-mean * E). ``renormalize=False`` keeps
    the raw mass of the selected experts (qwen2-moe's
    norm_topk_prob=False semantics). ``score``: the experts' scores are
    the ``softmax`` of the logits over the experts, or each logit's
    ``sigmoid``. ``bias`` [E] (a selection bias, ``noaux_tc``) is added
    to the scores for the choice of experts alone: the weights are the
    unbiased scores of the chosen. ``scale`` multiplies the weights
    after the renormalisation (``routed_scaling_factor``)."""
    N, E = logits.shape
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router score function {score!r}")
    if bias is None:
        topv, topi = jax.lax.top_k(probs, k)
    else:
        _, topi = jax.lax.top_k(probs + bias, k)
        topv = jnp.take_along_axis(probs, topi, axis=-1)
    if renormalize:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    if scale is not None:
        topv = topv * scale
    # aux loss (reference: sharded_moe.py load-balancing)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * E
    return topv, topi, aux


def dropless_expert_ffn(tokens, wg, w1, w3, w2, k, renormalize=True):
    """:func:`routed_expert_ffn` for the training layer below: ([N, d],
    aux)."""
    return routed_expert_ffn(tokens, wg, w1, w3, w2, k, renormalize)[:2]


def routed_expert_ffn(tokens, wg, w1, w3, w2, k, renormalize=True,
                      layer=None, held=None, **router):
    """The routed grouped-GEMM SwiGLU computation shared by the training
    layer below and the paged serving model (inference/model_moe.py).
    tokens: [N, d]; returns ([N, d], aux, experts picked [N, k]).
    ``layer``: ``w1``/``w3``/``w2`` are every layer's experts stacked
    ``[L, E, ...]`` and this is the layer to compute by; the stack is
    read in place (``ops/grouped_gemm.py grouped_matmul_stacked``).
    ``router``: :func:`dropless_route`'s ``score``, ``bias`` and
    ``scale``, from the configuration's published keys.
    ``held`` ``(first, count)``: the stacks hold only experts ``[first,
    first + count)`` of the ``E`` the router scores (a layer whose
    experts are spread over several chips, this chip's share). Scores,
    picks and weights are over all ``E``; picks that fall elsewhere sort
    into a tail that belongs to no group, so no product is computed for
    them and no weight read, and what those experts would have added is
    left out of the result."""
    N, d = tokens.shape
    E = wg.shape[-1]
    dt = tokens.dtype
    logits = tokens.astype(jnp.float32) @ wg
    probs, experts, aux = dropless_route(logits, k, renormalize, **router)
    flat_e = experts.reshape(-1)                     # [N*k]
    here = None
    if held is not None and tuple(held) != (0, E):
        first, count = held
        here = (flat_e >= first) & (flat_e < first + count)
        flat_e = jnp.where(here, flat_e - first, count)  # the tail: count
        E = count
    order = jnp.argsort(flat_e, stable=True)
    token_of = order // k
    xs = tokens[token_of]
    # the tail's rows lie behind every group's: bincount leaves them out
    group_sizes = jnp.bincount(flat_e, length=E)
    if layer is None:
        def product(x, w):
            return grouped_matmul(x, w.astype(dt), group_sizes)
    else:
        def product(x, w):
            return grouped_matmul_stacked(x, w.astype(dt), layer,
                                          group_sizes)
    h = jax.nn.silu(product(xs, w1)) * product(xs, w3)
    ys = product(h, w2)                                  # [N*k, d]
    gate = probs.reshape(-1)[order].astype(dt)
    ys = ys * gate[:, None]
    if here is not None:
        # a row of no group was never written by the kernel
        ys = jnp.where(here[order][:, None], ys, 0)
    out = jax.ops.segment_sum(ys, token_of, num_segments=N)
    return out, aux, experts


class _ExpertWeights(nn.Module):
    """Declares the stacked [E, ...] expert tensors under the SAME param
    paths as ``SwiGLUExperts`` (``.../experts/{w1,w2,w3}``) so capacity
    and dropless layers share checkpoints and the paged serving model
    consumes either."""
    num_experts: int
    hidden_size: int
    intermediate_size: int

    @nn.compact
    def __call__(self):
        E, d, f = self.num_experts, self.hidden_size, self.intermediate_size
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w1 = self.param("w1", init, (E, d, f), jnp.float32)
        w3 = self.param("w3", init, (E, d, f), jnp.float32)
        w2 = self.param("w2", init, (E, f, d), jnp.float32)
        return w1, w3, w2


class DroplessMOELayer(nn.Module):
    """Drop-in replacement for ``MOELayer`` (same param tree: ``wg`` +
    ``experts/{w1,w2,w3}``) computing with the dropless grouped-GEMM path
    instead of capacity buffers. [B, T, d] -> ([B, T, d], aux).

    ``shared_expert_size > 0`` adds the qwen2-moe shared expert: a dense
    SwiGLU every token passes through, gated per token by
    ``sigmoid(x @ shared_expert_gate)`` and added to the routed output
    (HF Qwen2MoeSparseMoeBlock)."""
    num_experts: int
    hidden_size: int
    intermediate_size: int
    k: int = 2
    renormalize: bool = True
    shared_expert_size: int = 0

    @nn.compact
    def __call__(self, x, train: bool = True):
        B, T, d = x.shape
        wg = self.param("wg", nn.initializers.lecun_normal(),
                        (d, self.num_experts), jnp.float32)
        w1, w3, w2 = _ExpertWeights(
            self.num_experts, self.hidden_size, self.intermediate_size,
            name="experts")()
        out, aux = dropless_expert_ffn(x.reshape(B * T, d), wg, w1, w3, w2,
                                       self.k, self.renormalize)
        out = out.reshape(B, T, d)
        if self.shared_expert_size:
            gate = nn.Dense(self.shared_expert_size, use_bias=False,
                            dtype=x.dtype, name="shared_gate_proj")(x)
            up = nn.Dense(self.shared_expert_size, use_bias=False,
                          dtype=x.dtype, name="shared_up_proj")(x)
            shared = nn.Dense(d, use_bias=False, dtype=x.dtype,
                              name="shared_down_proj")(
                nn.silu(gate) * up)
            sg = nn.Dense(1, use_bias=False, dtype=x.dtype,
                          name="shared_expert_gate")(x)
            out = out + jax.nn.sigmoid(sg) * shared
        return out.astype(x.dtype), aux.astype(jnp.float32)


#: back-compat alias — the one dropless module (param tree ``wg`` +
#: ``experts/{w1,w2,w3}``, shared with the capacity MOELayer)
DroplessMoEMLP = DroplessMOELayer
