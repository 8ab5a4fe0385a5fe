"""Paged inference model for hybrid trunks: gated-delta-rule (linear
attention) layers beside full-attention layers (Olmo-Hybrid).

The llama paged trunk (:class:`PagedInferenceModel`) with two kinds of
per-sequence state. A full layer keeps K and V in the blocked pool
``[L_full, KV, P, D]``, written by one scatter and read by the paged
kernel at a layer index, as every other family's layers do. A linear
layer keeps one ``[H, d_k, d_v]`` float32 state and the last ``K - 1``
inputs of its convolution in a *slot* of two pools
(``[L_lin, slots + 1, ...]``, ``ragged/kv_cache.py HybridCache``): a
sequence holds one slot from admission to flush, whatever its length.

``_trunk`` scans over the periods of ``cfg.period`` (for Olmo-Hybrid three
linear layers, then a full one; the pattern is read from
``layer_types``), the linear and the full layers as two stacked trees
``[L_lin, ...]`` and ``[L_full, ...]``, each layer's weights read at a
dynamic index inside the matmul that uses them. All four pools are
**carried, never
scanned over**, donated and updated in place: the gated-delta kernels
(``ops/gated_delta.py``) pick ``[layer, slot]`` of the state pool in
their index map and alias it to their output, the convolution tail is a
``[B, K - 1, C]`` gather and scatter. Blank lanes of a bucket name the
spare slot, as pad tokens name slot ``P`` of the KV pool; a lane whose
slice starts at position 0 starts from zero state inside the program.

HCache: latents are the full layers' mixer inputs, ``[L_full, B, T, H]``
(the block has no input norm, so that is the residual stream), and
``restore_kv`` replays the full layers' K and V from them. A linear
layer's state cannot be replayed from a projection: eviction copies the
sequence's slot rows to the host whole and restore copies them back
(``InferenceEngineV2.snapshot_state`` / ``begin_restore(states=...)``).

What would need a snapshot of the recurrent state at a block boundary
(shared prefixes, speculative rollback, the fused decode loops) and what
is not written yet (tensor parallelism, weight quantisation) raise
:class:`RecurrentStateUnsupported` by name; none computes silently.
"""

import jax
import jax.numpy as jnp

from ..models.olmo_hybrid import FULL, LINEAR, OlmoHybridConfig
from ..ops.gated_delta import gated_delta_rule
from ..ops.rms_norm import reference_rms_norm, rms_norm
from .model import (PagedInferenceModel, layer_of, scan_periods,
                    stack_layer_params)
from .ragged.lanes import Lanes


class RecurrentStateUnsupported(NotImplementedError):
    """A feature that a trunk with recurrent layers cannot serve yet."""


def refuse(feature: str, needs: str = "a snapshot of every linear "
           "layer's recurrent state and convolution tail at a block "
           "boundary") -> RecurrentStateUnsupported:
    return RecurrentStateUnsupported(
        f"{feature} is not supported for a trunk with recurrent "
        f"(linear-attention) layers: it would need {needs}")


def serving_layout(cfg: OlmoHybridConfig, params):
    """The training-layout tree (``layers_<i>`` of either kind) as the
    serving forward reads it: the linear and the full layers as two
    stacks ``[L_lin, ...]`` and ``[L_full, ...]``."""
    def stack(kind):
        picked = [i for i, k in enumerate(cfg.layer_types) if k == kind]
        return stack_layer_params(
            {f"layers_{j}": params[f"layers_{i}"]
             for j, i in enumerate(picked)}, len(picked))

    return {"embed": params["embed_tokens"]["embedding"],
            "norm": params["norm"]["weight"],
            "lm_head": params["lm_head"]["kernel"],
            "lin_layers": stack(LINEAR),
            "full_layers": stack(FULL)}


class PagedHybridModel(PagedInferenceModel):
    """Serves :class:`~..models.olmo_hybrid.OlmoHybridConfig` trees
    through the ragged engine."""

    recurrent = True

    def __init__(self, cfg: OlmoHybridConfig, params, *, topology=None,
                 quantization=None, **kw):
        if topology is not None and topology.tensor_size > 1:
            raise refuse("tensor parallelism", "the state pool and the "
                         "gated-delta kernels sharded over heads")
        if quantization is not None and quantization.enabled:
            raise refuse("weight quantisation", "the k-major int8 layout "
                         "for the linear layers' projections")
        if cfg.rope_theta is not None or cfg.tie_word_embeddings or \
                cfg.attention_bias:
            raise NotImplementedError(
                "the hybrid trunk is written as Olmo-Hybrid publishes it: "
                "no rotary step (rope_theta null), untied head, no "
                "attention bias")
        if LINEAR not in cfg.period or FULL not in cfg.period:
            raise ValueError(
                f"a hybrid trunk has both layer kinds in its period, got "
                f"{cfg.period}; a trunk of one kind is the llama family")
        self.period = cfg.period
        self.n_periods = cfg.n_layer // len(cfg.period)
        self.lin_per = cfg.period.count(LINEAR)
        self.full_per = cfg.period.count(FULL)
        super().__init__(cfg, params, topology=None, quantization=None,
                         **kw)
        self.n_latent_layers = self.n_periods * self.full_per

    # -------------------------------------------------------------- #
    def load_params(self, params):
        self.params = self._finalize_params(
            serving_layout(self.cfg, params))

    @staticmethod
    def _keep_fp32(path) -> bool:
        """The decay parameters stay float32: ``exp(A_log)`` and
        ``softplus(dt_bias)`` set how fast a head forgets."""
        return str(getattr(path[-1], "key", path[-1])) in ("A_log",
                                                           "dt_bias")

    # -------------------------------------------------------------- #
    # Layer math (mirrors models/olmo_hybrid.py)
    # -------------------------------------------------------------- #
    def _post(self, x, y, weight):
        """``x + RMSNorm(y)``: the reordered-norm residual."""
        return x + rms_norm(y, weight, eps=self.cfg.rms_norm_eps) \
            .astype(self.cfg.compute_dtype)

    def _full_step(self, x, lp, ck, cv, layer, lanes):
        cfg = self.cfg
        attn = lp["self_attn"]
        latent = x.astype(self.latent_dtype) if self.capture_latents \
            else jnp.zeros((x.shape[0], x.shape[1], 0), x.dtype)
        q, k, v = self._full_qkv(attn, x)
        ck, cv = self._scatter_kv(ck, cv, layer, k, v, lanes)
        y = self._paged_attention(q, ck, cv, layer, lanes)
        x = self._post(x, self._mm(y, attn["o_proj"]["kernel"]),
                       lp["post_attention_layernorm"]["weight"])
        x = self._post(x, self._mlp_out(lp, x),
                       lp["post_feedforward_layernorm"]["weight"])
        return x.astype(cfg.compute_dtype), ck, cv, latent

    def _full_qkv(self, attn, x):
        """q and k normed over all channels, then split into heads; the
        barrier keeps the split out of the dots (``_qkv_heads``)."""
        cfg = self.cfg
        D = cfg.head_dim
        q, k, v = jax.lax.optimization_barrier(tuple(
            self._mm(x, attn[n]["kernel"])
            for n in ("q_proj", "k_proj", "v_proj")))
        q = rms_norm(q, attn["q_norm"]["weight"], eps=cfg.rms_norm_eps)
        k = rms_norm(k, attn["k_norm"]["weight"], eps=cfg.rms_norm_eps)
        return tuple(y.astype(cfg.compute_dtype).reshape(
            *y.shape[:-1], y.shape[-1] // D, D) for y in (q, k, v))

    def _causal_conv(self, la, pre, conv, layer, g):
        """The causal depthwise convolution of one lane group's ``pre``
        [B, T, C] over (tail | slice) by the taps of ``la``, and the
        pool of tails with the group's new ones."""
        B, T, _ = pre.shape
        K = self.cfg.linear_conv_kernel_dim
        f32 = jnp.float32
        tail = jnp.where((g.start == 0)[:, None], 0, conv[layer, g.slots]) \
            .astype(pre.dtype).reshape(B, K - 1, -1)
        xx = jnp.concatenate([tail, pre], axis=1)           # [B,T+K-1,C]
        taps = jnp.concatenate(
            [la[n]["kernel"] for n in ("q_conv", "k_conv", "v_conv")],
            axis=-1).astype(f32)                            # [K, C]
        y = jax.nn.silu(sum(xx[:, j:j + T].astype(f32) * taps[j]
                            for j in range(K)))
        # the last K-1 real inputs: pads leave the tail where it was
        new_tail = jax.vmap(lambda a, n: jax.lax.dynamic_slice_in_dim(
            a, n, K - 1, axis=0))(xx, g.t_len)
        return y, conv.at[layer, g.slots].set(
            new_tail.astype(conv.dtype).reshape(B, -1))

    def _linear_step(self, x, lp, state, conv, layer, lanes):
        cfg = self.cfg
        la = lp["linear_attn"]
        B, T, _ = x.shape
        H = cfg.linear_num_key_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        f32 = jnp.float32
        *pre, gate = jax.lax.optimization_barrier(tuple(
            self._mm(x, la[n]["kernel"])
            for n in ("q_proj", "k_proj", "v_proj", "g_proj")))
        pre = jnp.concatenate(pre, axis=-1)                 # [B, T, C]
        ys = []
        for g, pre_g in zip(lanes.groups, lanes.split(pre)):
            y, conv = self._causal_conv(la, pre_g, conv, layer, g)
            ys.append(y)
        y = lanes.join(ys)

        def heads(a, d):
            return a.reshape(B, T, H, d)

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

        q = unit(heads(y[..., :H * dk], dk))
        k = unit(heads(y[..., H * dk:2 * H * dk], dk))
        v = heads(y[..., 2 * H * dk:], dv)
        beta = jax.nn.sigmoid(jnp.dot(x, la["b_proj"]["kernel"],
                                      preferred_element_type=f32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(la["A_log"].astype(f32)) * jax.nn.softplus(
            jnp.dot(x, la["a_proj"]["kernel"], preferred_element_type=f32)
            + la["dt_bias"].astype(f32))
        rule, os = lanes.shared(gated_delta_rule), []
        for grp, *qkvgb in zip(lanes.groups, *(
                lanes.split(a) for a in (q, k, v, g, beta))):
            o, state = rule(*qkvgb, state, layer, grp.slots, grp.start,
                            grp.t_len)
            os.append(o)
        o = lanes.join(os)
        o = reference_rms_norm(o, la["o_norm"]["weight"],
                               cfg.rms_norm_eps)
        o = o * heads(jax.nn.silu(gate.astype(f32)), dv)
        y = o.reshape(B, T, H * dv).astype(cfg.compute_dtype)
        x = self._post(x, self._mm(y, la["o_proj"]["kernel"]),
                       lp["post_attention_layernorm"]["weight"])
        x = self._post(x, self._mlp_out(lp, x),
                       lp["post_feedforward_layernorm"]["weight"])
        return x.astype(cfg.compute_dtype), state, conv

    # -------------------------------------------------------------- #
    def _embed_extra(self, params, positions):
        return None

    def _trunk(self, params, cache_k, cache_v, state, conv, *columns):
        lanes = Lanes.of(columns, slot=True)
        x = self._embed_lanes(params, lanes, cache_k.shape[2])

        def linear(x, pools, layer):
            ck, cv, st, cn = pools
            x, st, cn = self._linear_step(
                x, layer_of(params["lin_layers"], layer), st, cn, layer,
                lanes)
            return x, (ck, cv, st, cn), None

        def full(x, pools, layer):
            ck, cv, st, cn = pools
            x, ck, cv, latent = self._full_step(
                x, layer_of(params["full_layers"], layer), ck, cv, layer,
                lanes)
            return x, (ck, cv, st, cn), latent

        x, (cache_k, cache_v, state, conv), latents = scan_periods(
            self.period, self.n_periods, x,
            (cache_k, cache_v, state, conv), {LINEAR: linear, FULL: full})
        x = self._final_norm(params, x)
        return cache_k, cache_v, state, conv, x, latents, lanes

    def _chunk_program(self, shapes=None):
        groups = 1 if shapes is None else len(shapes)
        return self._lane_program(self._forward_chunk, 1 + groups, pools=4,
                                  shapes=shapes)

    def _forward_chunk(self, params, cache_k, cache_v, state, conv,
                       *columns):
        """``_forward_chunk`` of the base model with the two slot pools
        and a fifth column, ``slots`` [B], a lane group. Returns
        ``(cache_k', cache_v', state', conv', logits [B, V], latents
        [L_full, B, T, H] a group)``."""
        cache_k, cache_v, state, conv, x, latents, lanes = self._trunk(
            params, cache_k, cache_v, state, conv, *columns)
        logits = self._head_logits(params, lanes.last_rows(x))
        return (cache_k, cache_v, state, conv, logits,
                *lanes.split(latents, lead=1))

    def _keep_pools(self, cache, ck, cv, state, conv, *out):
        cache.replace(ck, cv)
        cache.replace_state(state, conv)
        return out

    def forward_chunk(self, cache, tokens, start, tables, t_len, slots):
        return self._keep_pools(cache, *self._enqueue(
            self._fwd, (cache.k, cache.v, cache.state, cache.conv),
            tokens, start, tables, t_len, slots))

    def forward_step(self, cache, *groups):
        logits, *latents = self._keep_pools(cache, *self._enqueue_step(
            (cache.k, cache.v, cache.state, cache.conv), groups))
        return logits, latents

    # -------------------------------------------------------------- #
    # HCache restore: the full layers' K and V from their latents
    # -------------------------------------------------------------- #
    def _restore_layer(self, params, cache_k, cache_v, layer, latent,
                       start, tables, t_len):
        attn = jax.tree.map(lambda p: p[layer],
                            params["full_layers"]["self_attn"])
        _, k, v = self._full_qkv(attn,
                                 latent.astype(self.cfg.compute_dtype))
        return self._scatter_kv(
            cache_k, cache_v, layer, k, v, self._restore_lanes(
                latent, start, tables, t_len, cache_k.shape[2]))

    # -------------------------------------------------------------- #
    # What needs a state snapshot at a block boundary refuses by name
    # -------------------------------------------------------------- #
    def forward_chunk_tail(self, *a, **kw):
        raise refuse("the speculative verification forward (put_spec, "
                     "generate_lookup)")

    forward_chunk_tail_lat = forward_chunk_tail

    def decode_loop(self, *a, **kw):
        raise refuse("the fused decode loop (generate_fused)",
                     "the slot pools carried through the loop's program")

    def lookup_decode_loop(self, *a, **kw):
        raise refuse("the fused speculative decode loop "
                     "(generate_lookup_fused)")
