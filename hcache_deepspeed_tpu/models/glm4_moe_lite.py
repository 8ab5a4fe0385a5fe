"""GLM-4-MoE-Lite family (``model_type`` ``glm4_moe_lite``): multi-head
latent attention over a cache of compressed KV rows, leading dense
layers before sparse ones with a sigmoid router and an ungated shared
expert.

The layer, with ``x`` the normed residual of one position ``t``:

* ``q = W_qb RMSNorm(W_qa x)``: ``n_head`` heads of ``qk_nope_head_dim +
  qk_rope_head_dim`` = ``[q_nope | q_rope]``; ``q_rope <- RoPE(q_rope,
  t)``.
* ``a = W_kva x``: ``kv_lora_rank + qk_rope_head_dim`` = ``[c | r]``;
  ``c <- RMSNorm(c)``; ``r <- RoPE(r, t)``, one ``r`` for all heads. The
  cache row of a position is ``[c | r]``.
* published form: ``[k_nope_h | v_h] = W_kvb,h c``; ``k_h = [k_nope_h |
  r]``; ``o_h = softmax_s(q_h . k_h,s / sqrt(qk_head_dim)) v_h,s``; ``y =
  W_o concat_h o_h``.
* absorbed form (what a served step computes, the same numbers): with
  ``W_kvb,h = [W_uk,h ; W_uv,h]``, ``q~_h = W_uk,h^T q_nope,h``; scores
  ``q~_h . c_s + q_rope,h . r_s``; ``u_h = sum_s p_s c_s``; ``o_h = W_uv,h
  u_h``.
* the first ``first_k_dense_replace`` layers have a dense SwiGLU of
  ``intermediate_size``; the others ``n_routed_experts`` experts of
  ``moe_intermediate_size`` and ``n_shared_experts`` shared ones: ``s =
  sigmoid(W_g x)`` in float32; the ``num_experts_per_tok`` experts with
  the largest ``s + b`` (``b`` = ``e_score_correction_bias``:
  ``noaux_tc``); weights ``s_e / sum_picked s`` (``norm_topk_prob``)
  times ``routed_scaling_factor``; ``y = sum_e w_e FFN_e(x) +
  FFN_shared(x)``, the shared expert ungated.

This module holds the configuration and the parameter tree with the
checkpoint's leaves (:func:`param_shapes`: shapes only, nothing is
run; :func:`seeded_params` draws them). The plain reference is the
benchmark's (``benchmarks/reference/glm4_moe_lite.py``); the served
trunk is ``inference/model_latent.py``.

Not built, refused by name in ``inference/factory.py``: rope scaling,
group-limited routing (``n_group``/``topk_group`` over 1), attention
biases. The multi-token-prediction module of the release
(``num_nextn_predict_layers``) changes no logit of the main model and
has no parameter here.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .mixtral import MixtralConfig


@dataclass(frozen=True)
class Glm4MoeLiteConfig(MixtralConfig):
    """``intermediate_size`` is the experts' width (as every sparse
    family's here), ``dense_intermediate_size`` the leading dense
    layers'; ``n_kv_head`` is 1 and ``head_width`` the query/key head's
    ``qk_nope_head_dim + qk_rope_head_dim``: one cached row serves all
    heads."""
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    n_kv_head: int = 1
    dropless: bool = True
    norm_topk_prob: bool = True
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    dense_intermediate_size: int = 10240
    first_k_dense_replace: int = 1
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    #: the router's score function: published as sigmoid for this family
    scoring_func: str = "sigmoid"

    def __post_init__(self):
        if not 0 <= self.first_k_dense_replace < self.n_layer:
            raise ValueError(
                f"first_k_dense_replace={self.first_k_dense_replace} must "
                f"leave a sparse layer of the {self.n_layer}")

    @property
    def head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_row_widths(self):
        """The cache row ``[c | r]`` as it lies in the two pools: ``c``
        whole, ``r`` rounded up to a whole 128-lane tile."""
        return (self.kv_lora_rank,
                -(-self.qk_rope_head_dim // 128) * 128)


def glm4_moe_lite_tiny(**kw):
    defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                    dense_intermediate_size=96, n_layer=3, n_head=4,
                    max_positions=256, num_experts=8, top_k=2,
                    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24,
                    qk_rope_head_dim=8, v_head_dim=16)
    defaults.update(kw)
    return Glm4MoeLiteConfig(**defaults)


def _s(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def layer_shapes(cfg: Glm4MoeLiteConfig, layer: int):
    """The leaves of ``layers_<layer>`` (kernels ``[in, out]``)."""
    d, H = cfg.hidden_size, cfg.n_head
    attn = {
        "q_a_proj": {"kernel": _s(d, cfg.q_lora_rank)},
        "q_a_layernorm": {"weight": _s(cfg.q_lora_rank)},
        "q_b_proj": {"kernel": _s(cfg.q_lora_rank, H * cfg.head_dim)},
        "kv_a_proj_with_mqa": {"kernel": _s(
            d, cfg.kv_lora_rank + cfg.qk_rope_head_dim)},
        "kv_a_layernorm": {"weight": _s(cfg.kv_lora_rank)},
        "kv_b_proj": {"kernel": _s(
            cfg.kv_lora_rank,
            H * (cfg.qk_nope_head_dim + cfg.v_head_dim))},
        "o_proj": {"kernel": _s(H * cfg.v_head_dim, d)},
    }

    def swiglu(f):
        return {"gate_proj": {"kernel": _s(d, f)},
                "up_proj": {"kernel": _s(d, f)},
                "down_proj": {"kernel": _s(f, d)}}

    if layer < cfg.first_k_dense_replace:
        mlp = swiglu(cfg.dense_intermediate_size)
    else:
        E, f = cfg.num_experts, cfg.intermediate_size
        mlp = {"gate": {"weight": _s(d, E),
                        "e_score_correction_bias": _s(E)},
               "experts": {"w1": _s(E, d, f), "w3": _s(E, d, f),
                           "w2": _s(E, f, d)},
               "shared_experts": swiglu(f * cfg.n_shared_experts)}
    return {"input_layernorm": {"weight": _s(d)},
            "post_attention_layernorm": {"weight": _s(d)},
            "self_attn": attn, "mlp": mlp}


def param_shapes(cfg: Glm4MoeLiteConfig):
    """The parameter tree as ``ShapeDtypeStruct``s: ``embed_tokens``,
    ``norm``, ``lm_head`` and ``layers_<i>``."""
    tree = {"embed_tokens": {"embedding": _s(cfg.vocab_size,
                                             cfg.hidden_size)},
            "norm": {"weight": _s(cfg.hidden_size)},
            "lm_head": {"kernel": _s(cfg.hidden_size, cfg.vocab_size)}}
    for i in range(cfg.n_layer):
        tree[f"layers_{i}"] = layer_shapes(cfg, i)
    return tree


def correction_bias(seed, layer, n_experts, scale=0.1):
    """A seeded ``e_score_correction_bias`` of layer ``layer``: uniform
    in ``+-scale``, small beside the scores it is added to and not zero,
    so that what it selects differs from what the scores alone would."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(layer), 47])
    return rng.uniform(-scale, scale, n_experts).astype(np.float32)


def seeded_params(cfg: Glm4MoeLiteConfig, seed: int = 0, dtype=None):
    """:func:`param_shapes` with seeded values, after
    ``models/seeded.py``'s rule (matrices normal with std ``1 /
    sqrt(fan_in)``, norm scales one), and each sparse layer's
    :func:`correction_bias`."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out_dtype = jnp.dtype(dtype) if dtype is not None else jnp.float32

    def draw(path, leaf, key):
        names = [str(getattr(k, "key", k)) for k in path]
        if names[-1] == "e_score_correction_bias":
            return jnp.asarray(correction_bias(
                seed, int(names[0].split("_")[1]), leaf.shape[0]))
        if leaf.ndim < 2:
            return jnp.ones(leaf.shape, out_dtype)
        fan_in = leaf.shape[-1] if names[-1] == "embedding" \
            else leaf.shape[-2]
        return (jax.random.normal(key, leaf.shape, jnp.float32)
                / np.sqrt(fan_in)).astype(out_dtype)

    return jax.tree_util.tree_unflatten(
        treedef, [draw(path, leaf, key)
                  for (path, leaf), key in zip(leaves, keys)])
