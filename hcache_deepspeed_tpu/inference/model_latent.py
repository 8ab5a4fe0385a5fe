"""Paged inference model for a latent-attention sparse trunk
(``models/glm4_moe_lite.py``): multi-head latent attention over a paged
pool of compressed KV rows, leading dense layers before a stack of
sparse ones with a sigmoid router and an ungated shared expert.

The same programs as every other family's (``PagedInferenceModel``: one
forward family over two donated pools carried whole through the layer
scan, the restore program, the tails and the fused loops), with what
differs overridden:

* **The pools.** A position's cache row of a layer is ``[c | r]``: the
  normed compressed KV (``kv_lora_rank`` values) and the rotary key all
  heads share (``qk_rope_head_dim``). It lies in two pools, ``c`` in
  ``cache.k`` ``[L, 1, P, C]`` and ``r`` in ``cache.v`` ``[L, 1, P, R]``
  with ``R`` the rotary width rounded up to a whole 128-lane tile
  (Mosaic takes a manual DMA window only in whole lane tiles, and a row
  of 576 is four and a half): 640 values, 1,280 B in bf16 a layer a
  token at the published widths, against 20,480 B of expanded K and V.
  One 640-wide pool would cost the same bytes and a second signature
  for every program; two pools keep the ``(cache_k, cache_v)`` of every
  other family, so the tails, the fused loops, the restore lane and
  shared prefixes reach this one unchanged.
* **The attention** is the absorbed form (``ops/latent_attention.py``):
  ``W_kvb`` is folded into the query on the way in and into the result
  on the way out, and the kernel fetches a block of rows once for
  scores and values.
* **The rotary step** takes its angles from the lanes' positions: no
  program carries a table of ``max_positions`` rows.
* **Leading dense layers** run before the scan over the sparse stack
  (``_lead_layers``), at the first layers of the pools.
* **HCache's saved state** is the cache row (``saved_state =
  "cache_row"``): the hidden state is ``hidden_size`` values a layer a
  token, 3.6 times the 576 of the row it would rebuild at the published
  widths, so what is captured is ``[c | r]`` and a restore is a ship
  and a write (``_restore_layer``), no projection replayed.

Tensor parallelism and weight quantisation are refused by name
(:class:`LatentAttentionUnsupported`).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.xla_metadata import set_xla_metadata

from ..moe.dropless import routed_expert_ffn
from ..ops.latent_attention import latent_attention
from ..ops.rms_norm import reference_rms_norm, rms_norm
from ..ops.rope import rope_at
from .model import stack_layer_params
from .model_moe import PagedMoEModel


class LatentAttentionUnsupported(NotImplementedError):
    """A feature that a latent-attention trunk cannot serve yet."""


def refuse(feature: str, needs: str) -> LatentAttentionUnsupported:
    return LatentAttentionUnsupported(
        f"{feature} is not supported for a latent-attention trunk "
        f"(kv_lora_rank > 0): it would need {needs}")


def saved_row_is_smaller(cfg) -> bool:
    """What HCache saves is read off the model's shape: the cache row
    where it is smaller than the hidden state."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim < cfg.hidden_size


class PagedLatentModel(PagedMoEModel):
    rope_from_positions = True
    saved_state = "cache_row"

    def __init__(self, cfg, params, **kw):
        topo = kw.get("topology")
        if topo is not None and topo.tensor_size > 1:
            raise refuse("tensor parallelism",
                         "the heads split over the shards with the one "
                         "cache row replicated")
        quant = kw.get("quantization")
        if quant is not None and quant.enabled:
            raise refuse("weight quantization",
                         "quantized low-rank projections whose absorbed "
                         "halves are read apart")
        if not saved_row_is_smaller(cfg):
            raise refuse("a cache row wider than the hidden state",
                         "the hidden state as the saved state and a "
                         "replay of the kv projection")
        #: the cache row's two widths, and ``r``'s pool's
        self.c_width, self.r_pool_width = cfg.cache_row_widths
        self.r_width = cfg.qk_rope_head_dim
        self.n_lead = cfg.first_k_dense_replace
        super().__init__(cfg, params, **kw)

    def pool_layout(self):
        """``(kv heads, k width, v width)`` of the two pools."""
        return 1, self.c_width, self.r_pool_width

    def attention_fits(self, tokens):
        """Raise where the latent kernel cannot tile a dispatch of
        ``tokens`` positions at this pool layout."""
        from ..ops.latent_attention import pick_tiles
        pick_tiles(tokens * self.cfg.n_head, self.c_width,
                   self.r_pool_width,
                   self.block_size, self.max_blocks_per_seq,
                   jnp.dtype(self.cfg.compute_dtype).itemsize)

    @property
    def saved_width(self):
        return self.c_width + self.r_width

    # -------------------------------------------------------------- #
    def load_params(self, params):
        """The training-layout tree (``layers_<i>``: the first
        ``first_k_dense_replace`` dense, the others sparse) into two
        stacks; a tree that holds ``lead_layers`` and ``layers`` already
        stacked is taken as it is."""
        n = self.cfg.n_layer
        if "layers" in params:
            lead, layers = params["lead_layers"], params["layers"]
        else:
            lead = stack_layer_params(params, self.n_lead)
            layers = stack_layer_params(
                {f"layers_{i - self.n_lead}": params[f"layers_{i}"]
                 for i in range(self.n_lead, n)}, n - self.n_lead)
        self.params = self._finalize_params({
            "embed": params["embed_tokens"]["embedding"],
            "norm": params["norm"]["weight"],
            "lm_head": params["lm_head"]["kernel"],
            "lead_layers": self._absorbed(lead),
            "layers": self._absorbed(layers)})

    def _absorbed(self, layers):
        """Stacked layers with ``kv_b_proj`` ``[L, C, heads * (nope +
        v)]`` cut into the two halves the absorbed form multiplies by,
        each laid out as its product reads it: ``w_uk`` ``[L, heads,
        nope, C]`` (into the query) and ``w_uv`` ``[L, heads, C, v]``
        (out of the result). Cut inside a program, each half is sliced
        out of the leaf and copied into that layout in every layer of
        every forward."""
        cfg = self.cfg
        attn = dict(layers["self_attn"])
        kvb = attn.pop("kv_b_proj")["kernel"]
        kvb = kvb.reshape(kvb.shape[0], self.c_width, cfg.n_head,
                          cfg.qk_nope_head_dim + cfg.v_head_dim)
        attn["w_uk"] = kvb[..., :cfg.qk_nope_head_dim].transpose(0, 2, 3, 1)
        attn["w_uv"] = kvb[..., cfg.qk_nope_head_dim:].transpose(0, 2, 1, 3)
        return {**layers, "self_attn": attn}

    @staticmethod
    def _keep_fp32(path) -> bool:
        """The router's weight and its selection bias stay float32."""
        names = [str(getattr(k, "key", k)) for k in path]
        return len(names) > 1 and names[-2] == "gate"

    def _whole_layers(self, layers):
        mlp = layers["mlp"]
        rest = {k: v for k, v in mlp.items() if k != "experts"}
        return {**layers, "mlp": rest}, mlp["experts"]

    def _with_whole(self, lp, whole, layer):
        return {**lp, "mlp": dict(lp["mlp"], experts=whole, layer=layer)}

    # -------------------------------------------------------------- #
    def _latent_qcr(self, attn, h, positions):
        """``h`` [B, T, H] through the low-rank projections: ``q`` [B, T,
        heads, C + R_pool] the absorbed query ``[q~ | q_rope | 0]``,
        ``c`` [B, T, C] the normed compressed KV and ``r`` [B, T, R] the
        rotary key."""
        cfg = self.cfg
        B, T, _ = h.shape
        eps = cfg.rms_norm_eps
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        # the barrier: as ``_qkv_heads``', so the dots read their layer
        # of the stacked leaves in place
        qa, kva = jax.lax.optimization_barrier((
            self._mm(h, attn["q_a_proj"]["kernel"]),
            self._mm(h, attn["kv_a_proj_with_mqa"]["kernel"])))
        qa = reference_rms_norm(qa, attn["q_a_layernorm"]["weight"],
                                eps).astype(h.dtype)
        q = jax.lax.optimization_barrier(
            self._mm(qa, attn["q_b_proj"]["kernel"]))
        q = q.reshape(B, T, cfg.n_head, nope + rope)
        c = reference_rms_norm(kva[..., :self.c_width],
                               attn["kv_a_layernorm"]["weight"],
                               eps).astype(h.dtype)
        theta = cfg.rope_theta
        r = rope_at(kva[..., None, self.c_width:], positions, theta)[:, :, 0]
        q_rope = rope_at(q[..., nope:], positions, theta)
        q_abs = jnp.einsum("bthd,hdc->bthc", q[..., :nope], attn["w_uk"])
        pad = jnp.zeros((B, T, cfg.n_head, self.r_pool_width - rope),
                        h.dtype)
        return jnp.concatenate([q_abs, q_rope, pad], axis=-1), c, r

    def _write_rows(self, ck, cv, layer, c, r, lanes):
        """``c`` [B, T, C] and ``r`` [B, T, R] into the two pools."""
        r = jnp.pad(r, ((0, 0), (0, 0), (0, self.r_pool_width -
                                          self.r_width)))
        return self._scatter_kv(ck, cv, layer, c[:, :, None], r[:, :, None],
                                lanes)

    def _latent_attention(self, q, ck, cv, layer, lanes):
        """The latent kernel over the rows ``q`` of all of ``lanes``, a
        call a group at the group's shape."""
        kernel = lanes.shared(latent_attention, (7, 8))
        return lanes.join([kernel(
            qg, ck, cv, layer, g.tables, g.positions[:, 0], g.kv_len,
            self.block_size, 1.0 / np.sqrt(self.cfg.head_dim))
            for g, qg in zip(lanes.groups, lanes.split(q))])

    def _layer_step(self, x, lp, ck, cv, layer, lanes):
        cfg = self.cfg
        attn = lp["self_attn"]
        B, T, _ = x.shape
        h = rms_norm(x, lp["input_layernorm"]["weight"],
                     eps=cfg.rms_norm_eps).astype(cfg.compute_dtype)
        # the scope names the layer's operations in a profile; a device
        # trace names an operation by its HLO text, which carries the
        # attribute and not the scope
        with jax.named_scope("latent_attn"), \
                set_xla_metadata(hds_layer="latent_attn"):
            q, c, r = self._latent_qcr(attn, h, lanes.positions)
            latent = jnp.concatenate([c, r], axis=-1).astype(
                self.latent_dtype) if self.capture_latents else jnp.zeros(
                (B, T, 0), h.dtype)
            ck, cv = self._write_rows(ck, cv, layer, c, r, lanes)
            u = self._latent_attention(q, ck, cv, layer, lanes)
            o = jnp.einsum("bthc,hcd->bthd", u, attn["w_uv"])
            proj = self._mm(o.reshape(B, T, cfg.n_head * cfg.v_head_dim),
                            attn["o_proj"]["kernel"])
        x = x + proj
        h2 = rms_norm(x, lp["post_attention_layernorm"]["weight"],
                      eps=cfg.rms_norm_eps).astype(cfg.compute_dtype)
        mlp, stats = self._mlp(lp, h2, lanes, ck.shape[2])
        x = x + mlp
        return x.astype(cfg.compute_dtype), ck, cv, latent, stats

    def _lead_layers(self, params, x, cache_k, cache_v, lanes):
        latents = []
        for i in range(self.n_lead):
            lp = jax.tree.map(lambda p: p[i], params["lead_layers"])
            x, cache_k, cache_v, latent, _ = self._layer_step(
                x, lp, cache_k, cache_v, jnp.int32(i), lanes)
            latents.append(latent)
        return x, cache_k, cache_v, latents

    # -------------------------------------------------------------- #
    def _mlp(self, lp, h2, lanes, pool_slots):
        if "experts" not in lp["mlp"]:          # a leading dense layer
            return self._swiglu(lp["mlp"], h2), {}
        out, experts = self._routed(lp, h2)
        picks = self._picks(experts, lanes.flat_idx < pool_slots,
                            lp["mlp"]["gate"]["weight"].shape[-1])
        # what the router read for each lane's last real row [B, H]
        return out, {"picks": picks, "router_in": lanes.last_rows(h2)}

    def _routed(self, lp, h2):
        mlp = lp["mlp"]
        cfg = self.cfg
        B, T, d = h2.shape
        with jax.named_scope("expert_ffn"), \
                set_xla_metadata(hds_layer="expert_ffn"):
            out, _aux, experts = routed_expert_ffn(
                h2.reshape(B * T, d), mlp["gate"]["weight"],
                mlp["experts"]["w1"], mlp["experts"]["w3"],
                mlp["experts"]["w2"], cfg.top_k, cfg.norm_topk_prob,
                layer=mlp.get("layer"), score=cfg.scoring_func,
                bias=mlp["gate"]["e_score_correction_bias"],
                scale=cfg.routed_scaling_factor)
        # the shared expert: every token, ungated, once
        return out.reshape(B, T, d) + self._swiglu(
            mlp["shared_experts"], h2), experts

    # -------------------------------------------------------------- #
    def _chunk_program(self, shapes=None):
        groups = 1 if shapes is None else len(shapes)
        return self._lane_program(self._forward_chunk_probed, 2 + groups,
                                  shapes=shapes)

    def _forward_chunk_probed(self, params, cache_k, cache_v, *columns):
        """``_forward_chunk`` that also returns, last, what each sparse
        layer's router read for every lane's last row ``[L_sparse, B,
        H]``: a check routes its reference's compared row by it."""
        params, cache_k, cache_v, x, latents, stats, lanes = self._trunk(
            params, cache_k, cache_v, *columns)
        logits = self._head_logits(params, lanes.last_rows(x))
        return (cache_k, cache_v, logits, *lanes.split(latents, lead=1),
                stats["router_in"])

    def forward_chunk(self, cache, tokens, start, tables, t_len):
        ck, cv, logits, latents, self.router_probe = self._enqueue(
            self._fwd, (cache.k, cache.v), tokens, start, tables, t_len)
        cache.replace(ck, cv)
        return logits, latents

    def forward_step(self, cache, *groups):
        ck, cv, logits, *latents, self.router_probe = self._enqueue_step(
            (cache.k, cache.v), groups)
        cache.replace(ck, cv)
        return logits, latents

    def _count_kv_write(self, T, positions, layers=None):
        """A position's write is one ``c`` row and one ``r`` row a
        layer."""
        path = "run" if T > 1 else "row"
        layers = self.n_layers if layers is None else layers
        self.kv_write_stats[path + "_dispatches"] += 1
        self.kv_write_stats[path + "_rows"] += int(positions) * 2 * layers

    # -------------------------------------------------------------- #
    def _restore_layer(self, params, cache_k, cache_v, layer, latent,
                       start, tables, t_len):
        """Put one layer's saved cache rows ``[B, T, C + R]`` back into
        the pools: a write, nothing replayed."""
        lanes = self._restore_lanes(latent, start, tables, t_len,
                                    cache_k.shape[2])
        latent = latent.astype(cache_k.dtype)
        return self._write_rows(
            cache_k, cache_v, layer, latent[..., :self.c_width],
            latent[..., self.c_width:], lanes)
