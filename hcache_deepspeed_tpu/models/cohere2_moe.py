"""Cohere2-MoE family (``model_type`` ``cohere2_moe``, Command A+): a
parallel block over window and global attention layers and an expert
layer of routed experts beside averaged shared ones.

Layer ``l``, residual stream ``x`` (one norm a layer, ``use_parallel_block``)::

    h  = LN(x)                  Cohere's bias-free LayerNorm: subtract the
                                mean, divide by sqrt(var + layer_norm_eps)
                                in float32, times a weight
    x' = x + Attn_l(h) + MoE(h)

* ``Attn``: ``q = h Wq`` (``n_head`` heads of ``head_dim``), ``k = h Wk``,
  ``v = h Wv`` (``n_kv_head`` heads), no bias, no q/k norm, scale ``1 /
  sqrt(head_dim)``. ``layer_types`` has a period (``layer_switch``): the
  ``sliding_attention`` layers rotate q and k over the whole head
  (``rotary_pct`` 1) at ``rope_theta`` with the interleaved pairing
  (``rope_gptj``: channels ``2i`` and ``2i + 1``) and see key ``j`` from
  query ``i`` iff ``0 <= i - j < sliding_window``; the ``full_attention``
  layers have no positional step at all and the causal mask.
* ``MoE``, on the same ``h``: ``s = sigmoid(h Wr)`` in float32 over
  ``num_experts``; the ``top_k`` largest picked; weights ``s_e / sum of
  the picked s`` (``norm_topk_prob``); ``routed = sum_e w_e W2_e
  (silu(W1_e h) * W3_e h)``; ``num_shared_experts`` shared experts of the
  same shape on every token, combined as
  ``shared_expert_combination_strategy`` ``"average"`` says; ``MoE(h) =
  routed + mean_j S_j(h)``.
* after the last layer the same LayerNorm; logits ``= logit_scale x
  E^T`` on the tied embedding.

**An expert layer that holds a share** (``experts_held = (first,
count)``): a deployment that spreads a layer's routed experts over
several chips gives each chip a run of them. The router still scores all
``num_experts``; the three expert stacks hold only the ``count`` experts
from ``first``; positions routed elsewhere add nothing here
(``moe/dropless.py routed_expert_ffn(held=...)``).

**The shared experts are held as one SwiGLU** of width
``num_shared_experts * intermediate_size``: the sum of the shared
experts' SwiGLUs on one input is one SwiGLU of their weights
concatenated (``gate_proj``/``up_proj`` along the columns, ``down_proj``
along the rows), and the mean is that times ``1 / num_shared_experts``.
Shared expert ``j`` is columns ``[j f, (j + 1) f)``
(:func:`shared_expert`); ``tests/unit/inference/test_window_model.py``
holds the one equal to the four.

This module holds the configuration and the parameter tree with the
checkpoint's leaves (:func:`param_shapes`; :func:`seeded_params` draws
them). The plain reference is the benchmark's
(``benchmarks/reference/cohere2_moe.py``); the served trunk is
``inference/model_window.py``.
"""

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .mixtral import MixtralConfig

WINDOW = "sliding_attention"
GLOBAL = "full_attention"


@dataclass(frozen=True)
class Cohere2MoeConfig(MixtralConfig):
    """``intermediate_size`` is one expert's width (routed and shared
    alike); ``head_width`` the published ``head_dim``."""
    rope_theta: float = 50000.0
    tie_word_embeddings: bool = True
    dropless: bool = True
    layer_norm_eps: float = 1e-5
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, GLOBAL)
    sliding_window: int = 4096
    num_shared_experts: int = 4
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    logit_scale: float = 1.0
    #: ``(first, count)``: the routed experts this parameter tree holds;
    #: ``None``: all of them
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        kinds = set(self.layer_types)
        if len(self.layer_types) != self.n_layer or \
                not kinds <= {WINDOW, GLOBAL}:
            raise ValueError(
                f"layer_types must name {self.n_layer} layers of "
                f"{WINDOW!r} or {GLOBAL!r}, got {self.layer_types}")
        period = self.period
        if self.n_layer % len(period) or \
                self.layer_types != period * (self.n_layer // len(period)):
            raise ValueError(
                f"layer_types must repeat one period, got "
                f"{self.layer_types}")
        first, count = self.held
        if not (0 <= first and count >= 1 and
                first + count <= self.num_experts):
            raise ValueError(
                f"experts_held={self.experts_held} is no run of the "
                f"{self.num_experts} experts")

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern ``layer_types`` repeats."""
        types = self.layer_types
        for n in range(1, len(types) + 1):
            if len(types) % n == 0 and types == types[:n] * (len(types) // n):
                return types[:n]
        return types

    @property
    def held(self) -> Tuple[int, int]:
        """``(first, count)`` of the routed experts held."""
        return self.experts_held or (0, self.num_experts)


def cohere2_moe_tiny(**kw):
    defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                    n_layer=4, n_head=8, n_kv_head=2, head_width=16,
                    max_positions=512, num_experts=8, top_k=2,
                    num_shared_experts=2, sliding_window=32)
    defaults.update(kw)
    if "layer_types" not in kw:
        defaults["layer_types"] = (WINDOW, WINDOW, WINDOW, GLOBAL) * \
            (defaults["n_layer"] // 4)
    return Cohere2MoeConfig(**defaults)


def _s(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def layer_shapes(cfg: Cohere2MoeConfig):
    """The leaves of one ``layers_<i>`` (kernels ``[in, out]``): every
    layer has the same."""
    d, D = cfg.hidden_size, cfg.head_dim
    f, shared = cfg.intermediate_size, cfg.num_shared_experts
    count = cfg.held[1]
    return {
        "input_layernorm": {"weight": _s(d)},
        "self_attn": {
            "q_proj": {"kernel": _s(d, cfg.n_head * D)},
            "k_proj": {"kernel": _s(d, cfg.n_kv_head * D)},
            "v_proj": {"kernel": _s(d, cfg.n_kv_head * D)},
            "o_proj": {"kernel": _s(cfg.n_head * D, d)}},
        "mlp": {
            "gate": {"weight": _s(d, cfg.num_experts)},
            "experts": {"w1": _s(count, d, f), "w3": _s(count, d, f),
                        "w2": _s(count, f, d)},
            "shared_experts": {
                "gate_proj": {"kernel": _s(d, shared * f)},
                "up_proj": {"kernel": _s(d, shared * f)},
                "down_proj": {"kernel": _s(shared * f, d)}}}}


def param_shapes(cfg: Cohere2MoeConfig):
    """The parameter tree as ``ShapeDtypeStruct``s: ``embed_tokens``
    (tied: the head too), ``norm`` and ``layers_<i>``."""
    tree = {"embed_tokens": {"embedding": _s(cfg.vocab_size,
                                             cfg.hidden_size)},
            "norm": {"weight": _s(cfg.hidden_size)}}
    for i in range(cfg.n_layer):
        tree[f"layers_{i}"] = layer_shapes(cfg)
    return tree


def shared_expert(shared, j, width):
    """Shared expert ``j``'s three kernels out of the fused leaves
    ``shared`` (``gate_proj``/``up_proj`` ``[d, n f]``, ``down_proj``
    ``[n f, d]``)."""
    cut = slice(j * width, (j + 1) * width)
    return {"gate_proj": {"kernel": shared["gate_proj"]["kernel"][:, cut]},
            "up_proj": {"kernel": shared["up_proj"]["kernel"][:, cut]},
            "down_proj": {"kernel": shared["down_proj"]["kernel"][cut]}}


def seeded_params(cfg: Cohere2MoeConfig, seed: int = 0, dtype=None):
    """:func:`param_shapes` with seeded values, after
    ``models/seeded.py``'s rule (matrices normal with std ``1 /
    sqrt(fan_in)``, norm scales one)."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out_dtype = jnp.dtype(dtype) if dtype is not None else jnp.float32

    def draw(path, leaf, key):
        if leaf.ndim < 2:
            return jnp.ones(leaf.shape, out_dtype)
        name = str(getattr(path[-1], "key", path[-1]))
        fan_in = leaf.shape[-1] if name == "embedding" else leaf.shape[-2]
        return (jax.random.normal(key, leaf.shape, jnp.float32)
                / np.sqrt(fan_in)).astype(out_dtype)

    return jax.tree_util.tree_unflatten(
        treedef, [draw(path, leaf, key)
                  for (path, leaf), key in zip(leaves, keys)])


def held_share(params, cfg: Cohere2MoeConfig, first: int, count: int):
    """``params`` (every expert held) cut to the share ``(first,
    count)``: the same leaves, each layer's expert stacks sliced."""
    out = dict(params)
    for i in range(cfg.n_layer):
        layer = dict(params[f"layers_{i}"])
        mlp = dict(layer["mlp"])
        mlp["experts"] = {k: v[first:first + count]
                          for k, v in mlp["experts"].items()}
        layer["mlp"] = mlp
        out[f"layers_{i}"] = layer
    return out
