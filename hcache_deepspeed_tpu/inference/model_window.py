"""Paged inference model for a sparse trunk whose attention layers are of
two kinds, window and global, in a repeating period
(``models/cohere2_moe.py``: a parallel block, one bias-free LayerNorm a
layer, sigmoid-routed experts of which this parameter tree may hold a
share, averaged shared experts, a tied head).

The same programs as every other family's (``PagedInferenceModel``: one
forward family over donated pools carried whole through the layer loop,
the step program, the restore program), with what differs read off the
model's shape:

* **Two pools, two block lifetimes.** The global layers' K and V lie in
  ``cache.k``/``cache.v`` ``[L_global, KV, P_g, D]``, the window layers'
  in ``cache.wk``/``cache.wv`` ``[L_window, KV, P_w, D]``
  (``ragged/kv_cache.py WindowedKVCache``). A lane carries two block
  tables side by side (``table_width``); every write and read goes
  through the table of its pool (``Lanes.over_tables``). A window
  layer's blocks behind the window go back to their allocator while the
  sequence lives (``StateManager.release_behind_window``): their table
  entries are never read, the kernel's walk starts past them.
* **The layer loop** is ``model.py scan_periods`` over ``cfg.period``,
  as the hybrid trunk's: all four pools carried, each layer's weights
  read at a dynamic index of its kind's stack, the expert stacks whole
  beside the index (``ops/grouped_gemm.py``).
* **The block** is parallel: ``h = LN(x)``; ``x + Attn(h) + MoE(h)``.
  A window layer rotates q and k (``ops/rope.py rope_at`` on columns
  permuted once at load from the published interleaved pairing to the
  half-split one the op rotates: scores are the same under a common
  permutation of a head's channels) and masks by the window; a global
  layer has no positional step and the causal mask.
* **The expert layer** routes over all ``num_experts`` and computes the
  experts this tree holds (``cfg.experts_held``,
  ``moe/dropless.py routed_expert_ffn(held=...)``); the shared experts
  are one SwiGLU of their concatenated weights, scaled to their mean.
  ``picks`` counts positions an expert over all of them, and every
  forward leaves beside them, on the device, the rows that fell on the
  held experts and the held experts those rows touched
  (:data:`HELD_LOG`).
* **HCache's saved state** is the layer's K and V rows (``saved_state =
  "cache_row"``, ``2 * n_kv_head * head_dim`` values a layer a token,
  half the hidden state's bytes at the published widths), captured as
  they are written; a restore ships them and writes them back through
  ``ops/kv_write.py`` into both pools, nothing replayed, and of a window
  layer only the rows still inside the window
  (:meth:`PagedWindowModel.restore_pipeline`).

What would need a block behind the window (speculative rollback, shared
prefixes past the window) or is not written yet (the fused decode loops
over two pools, tensor parallelism, weight quantisation) raises
:class:`WindowedCacheUnsupported` by name; none computes silently.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.xla_metadata import set_xla_metadata

from ..models.cohere2_moe import GLOBAL, WINDOW, Cohere2MoeConfig
from ..moe.dropless import routed_expert_ffn
from ..ops.rope import rope_at
from .model import (RestorePipeline, layer_of, scan_periods,
                    stack_layer_params)
from .model_moe import PagedMoEModel

#: the forwards whose two counts (rows on held experts, held experts
#: touched; all layers summed) the device keeps apart before the host
#: folds them (:meth:`PagedWindowModel.take_picks`)
HELD_LOG = 1 << 16
from .ragged.lanes import Lanes

POOLS = {GLOBAL: "global", WINDOW: "window"}


class WindowedCacheUnsupported(NotImplementedError):
    """A feature that a trunk with window layers (two block pools, the
    window pool's blocks freed behind the window) cannot serve yet."""


def refuse(feature: str, needs: str) -> WindowedCacheUnsupported:
    return WindowedCacheUnsupported(
        f"{feature} is not supported for a trunk with window layers "
        f"(sliding_window in layer_types): it would need {needs}")


def half_split_columns(kernel, head_dim):
    """A q or k projection ``[..., in, heads * head_dim]`` with each
    head's columns reordered from the interleaved rotary pairing
    (channels ``2i``, ``2i + 1``) to the half-split one (``i``, ``i +
    head_dim / 2``) that ``ops/rope.py`` rotates."""
    order = np.concatenate([np.arange(0, head_dim, 2),
                            np.arange(1, head_dim, 2)])
    heads = kernel.shape[-1] // head_dim
    columns = (np.arange(heads)[:, None] * head_dim + order).reshape(-1)
    return kernel[..., columns] if isinstance(kernel, np.ndarray) \
        else jnp.take(kernel, columns, axis=-1)


def serving_layout(cfg: Cohere2MoeConfig, params):
    """The parameter tree as the serving forward reads it: the window
    and the global layers as two stacks, each kind's expert stacks
    ``[L_kind, held, ...]`` beside it (they stay out of the layer loop),
    the window layers' q and k columns in the half-split pairing.
    ``params``: the checkpoint's ``layers_<i>``, or ``window_layers`` /
    ``global_layers`` already stacked ``[L_kind, ...]`` (a tree that
    fills most of a chip cannot be held twice to stack it)."""
    out = {"embed": params["embed_tokens"]["embedding"],
           "norm": params["norm"]["weight"]}
    for kind, name in POOLS.items():
        if f"{name}_layers" in params:
            layers = params[f"{name}_layers"]
        else:
            picked = [i for i, k in enumerate(cfg.layer_types) if k == kind]
            layers = stack_layer_params(
                {f"layers_{j}": params[f"layers_{i}"]
                 for j, i in enumerate(picked)}, len(picked))
        mlp = dict(layers["mlp"])
        out[f"{name}_experts"] = mlp.pop("experts")
        attn = layers["self_attn"]
        if kind == WINDOW:
            attn = {**attn, **{p: {"kernel": half_split_columns(
                attn[p]["kernel"], cfg.head_dim)}
                for p in ("q_proj", "k_proj")}}
        out[f"{name}_layers"] = {**layers, "mlp": mlp, "self_attn": attn}
    return out


class _PoolSide:
    """What :class:`RestorePipeline` reads of a model and of a cache,
    for one of the two pools."""

    def __init__(self, model, cache, pool):
        self._model, self._cache, self.pool = model, cache, pool
        self.n_latent_layers = model.pool_layers[pool]
        self.restore_chunk_layers = model.restore_chunk_layers
        self.restore_chunk_bytes = model.restore_chunk_bytes
        self._restore = model._restore

    @property
    def params(self):
        return self._model.params

    @property
    def k(self):
        return self._cache.k if self.pool == "global" else self._cache.wk

    @property
    def v(self):
        return self._cache.v if self.pool == "global" else self._cache.wv

    def replace(self, k, v):
        if self.pool == "global":
            self._cache.replace(k, v)
        else:
            self._cache.replace_window(k, v)

    def _count_kv_write(self, T, positions, layers):
        self._model._count_kv_write(T, positions, layers, pool=self.pool)


class ChainedRestore:
    """Several :class:`RestorePipeline`\\ s as one, advanced in order:
    what the engine's restore lane holds for a lane group whose rows go
    back into more than one pool."""

    def __init__(self, pipes):
        self.pipes = pipes

    @property
    def chunks_total(self):
        return sum(p.chunks_total for p in self.pipes)

    @property
    def chunks_issued(self):
        return sum(p.chunks_issued for p in self.pipes)

    @property
    def done(self):
        return all(p.done for p in self.pipes)

    def prefetch(self):
        for pipe in self.pipes:
            if not pipe.done:
                return pipe.prefetch()
        return 0

    def advance(self, max_chunks: int = 0) -> int:
        issued = 0
        for pipe in self.pipes:
            if pipe.done:
                continue
            issued += pipe.advance(max_chunks - issued
                                   if max_chunks > 0 else 0)
            if 0 < max_chunks <= issued:
                break
        return issued


class PagedWindowModel(PagedMoEModel):
    rope_from_positions = True
    saved_state = "cache_row"

    def __init__(self, cfg: Cohere2MoeConfig, params, **kw):
        topo = kw.get("topology")
        if topo is not None and topo.tensor_size > 1:
            raise refuse("tensor parallelism",
                         "both pools and the two kernels' calls sharded "
                         "over the KV heads")
        quant = kw.get("quantization")
        if quant is not None and quant.enabled:
            raise refuse("weight quantisation",
                         "quantized expert stacks read in place by a "
                         "layer index")
        if WINDOW not in cfg.period or GLOBAL not in cfg.period:
            raise ValueError(
                f"a windowed trunk has both layer kinds in its period, "
                f"got {cfg.period}; a trunk of one kind keeps one pool")
        self.window = int(cfg.sliding_window)
        self.period = cfg.period
        self.n_periods = cfg.n_layer // len(cfg.period)
        #: layers of each pool
        self.pool_layers = {
            name: self.n_periods * cfg.period.count(kind)
            for kind, name in POOLS.items()}
        #: each layer's index in the saved state ``[L, ...]``, by pool
        self.pool_rows = {
            name: np.asarray([i for i, k in enumerate(cfg.layer_types)
                              if k == kind])
            for kind, name in POOLS.items()}
        #: the routing so far, counted on the device: the positions
        #: routed to each expert ``[E]``, the forwards counted, and a
        #: forward's ``(rows on held experts, held experts touched)``
        #: ``HELD_LOG`` times over, one flat array that every forward
        #: takes, writes its own into and hands back (donated, like a
        #: pool: no transfer and no program of its own a step), and what
        #: :meth:`take_picks` has folded to the host of it
        self._picks_dev = self._blank_counts(cfg.num_experts)
        self._picks_seen = np.zeros((cfg.num_experts,), np.int64)
        #: forward ``i``'s two counts ``[forwards folded, 2]``
        self.held_log = np.zeros((0, 2), np.int64)
        self.moe_dispatches = 0
        super().__init__(cfg, params, **kw)
        for stats in (self.kv_write_stats, self.paged_walk_stats):
            blank = dict(stats)
            for name in POOLS.values():     # the same counts, a pool
                stats[name] = dict(blank)

    @property
    def table_width(self):
        return 2 * self.max_blocks_per_seq

    @property
    def saved_width(self):
        return 2 * self.cfg.n_kv_head * self.cfg.head_dim

    def load_params(self, params):
        self.params = self._finalize_params(
            serving_layout(self.cfg, params))

    @staticmethod
    def _keep_fp32(path) -> bool:
        """The router's weight stays float32."""
        names = [str(getattr(k, "key", k)) for k in path]
        return len(names) > 1 and names[-2] == "gate"

    # -------------------------------------------------------------- #
    # Layer math (the equations of models/cohere2_moe.py)
    # -------------------------------------------------------------- #
    def _layer_norm(self, x, weight):
        """Cohere's bias-free LayerNorm, in float32."""
        x32 = x.astype(jnp.float32)
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.cfg.layer_norm_eps)
        return (y * weight.astype(jnp.float32)).astype(
            self.cfg.compute_dtype)

    def _final_norm(self, params, x):
        return self._layer_norm(x, params["norm"])

    def _head_logits(self, params, last):
        logits = super()._head_logits(params, last)
        scale = self.cfg.logit_scale
        return logits if scale == 1 else logits * scale

    def _embed_extra(self, params, positions):
        return None

    @staticmethod
    def _with_shared(routed, shared_mean):
        """How the averaged shared experts' output meets the routed sum:
        added whole (the configuration's ``assumed``; the reference's
        ``with_shared`` says the same)."""
        return routed + shared_mean

    def _routed(self, lp, h2):
        mlp = lp["mlp"]
        cfg = self.cfg
        B, T, d = h2.shape
        with jax.named_scope("expert_ffn"), \
                set_xla_metadata(hds_layer="expert_ffn"):
            routed, _aux, experts = routed_expert_ffn(
                h2.reshape(B * T, d), mlp["gate"]["weight"],
                mlp["experts"]["w1"], mlp["experts"]["w3"],
                mlp["experts"]["w2"], cfg.top_k, cfg.norm_topk_prob,
                layer=mlp["layer"], held=cfg.held, score=cfg.scoring_func)
            # the shared experts as one SwiGLU of their concatenated
            # weights: their sum; times 1 / n: their mean
            shared = self._swiglu(mlp["shared_experts"], h2) * \
                (1.0 / cfg.num_shared_experts)
            out = self._with_shared(routed.reshape(B, T, d), shared)
        return out, experts

    def _mlp(self, lp, h2, lanes, pool_slots):
        out, experts = self._routed(lp, h2)
        picks = self._picks(experts, lanes.flat_idx < pool_slots,
                            self.cfg.num_experts)
        # what the router read for each lane's last real row [B, H]
        return out, {"picks": picks, "router_in": lanes.last_rows(h2)}

    def _block(self, x, lp, experts, pk, pv, layer, lanes, window):
        """One parallel block over the pool ``(pk, pv)`` at ``layer`` of
        it, through ``lanes`` (that pool's tables); ``window``: the
        layer's window, ``None`` for a global layer, which has no
        positional step either. Returns ``(x', pk', pv', (saved rows,
        router_in, picks))``."""
        cfg = self.cfg
        B, T, _ = x.shape
        h = self._layer_norm(x, lp["input_layernorm"]["weight"])
        q, k, v = self._qkv_heads(lp["self_attn"], h)
        if window is not None:
            q = rope_at(q, lanes.positions, cfg.rope_theta)
            k = rope_at(k, lanes.positions, cfg.rope_theta)
        saved = jnp.concatenate(
            [k.reshape(B, T, -1), v.reshape(B, T, -1)], axis=-1).astype(
            self.latent_dtype) if self.capture_latents else jnp.zeros(
            (B, T, 0), h.dtype)
        pk, pv = self._scatter_kv(pk, pv, layer, k, v, lanes)
        attn = self._paged_attention(q, pk, pv, layer, lanes, window)
        proj = self._mm(attn, lp["self_attn"]["o_proj"]["kernel"])
        mlp = {"mlp": dict(lp["mlp"], experts=experts, layer=layer)}
        moe, stats = self._mlp(mlp, h, lanes, pk.shape[2])
        x = (x + proj + moe).astype(cfg.compute_dtype)
        return x, pk, pv, (saved, stats["router_in"], stats["picks"])

    # -------------------------------------------------------------- #
    def _trunk(self, params, gk, gv, wk, wv, *columns):
        NB = self.max_blocks_per_seq
        lanes = Lanes.of(columns)
        g_lanes = lanes.over_tables(0, NB)
        w_lanes = lanes.over_tables(NB, 2 * NB)
        x = self._embed_lanes(params, g_lanes, gk.shape[2])
        w_lanes.place_positions()
        w_lanes.place_slots(self.block_size, wk.shape[2])

        def window(x, pools, layer):
            gk, gv, wk, wv = pools
            x, wk, wv, out = self._block(
                x, layer_of(params["window_layers"], layer),
                params["window_experts"], wk, wv, layer, w_lanes,
                self.window)
            return x, (gk, gv, wk, wv), out

        def global_(x, pools, layer):
            gk, gv, wk, wv = pools
            x, gk, gv, out = self._block(
                x, layer_of(params["global_layers"], layer),
                params["global_experts"], gk, gv, layer, g_lanes, None)
            return x, (gk, gv, wk, wv), out

        x, pools, (saved, router_in, picks) = scan_periods(
            self.period, self.n_periods, x, (gk, gv, wk, wv),
            {WINDOW: window, GLOBAL: global_})
        x = self._final_norm(params, x)
        return pools, x, saved, router_in, picks, g_lanes

    def _chunk_program(self, shapes=None):
        groups = 1 if shapes is None else len(shapes)
        return self._lane_program(self._forward_chunk, 2 + groups, pools=5,
                                  shapes=shapes)

    @staticmethod
    def _blank_counts(n_experts):
        return jnp.zeros((n_experts + 1 + 2 * HELD_LOG,), jnp.int32)

    def _count(self, picked, picks):
        """``picked`` (:meth:`_blank_counts`'s layout) with a forward's
        ``picks`` ``[L, E]`` counted in: added an expert, and its rows
        on the held experts and the held experts with a row, layer by
        layer, written where the forwards counted so far point."""
        E = self.cfg.num_experts
        first, count = self.cfg.held
        held = picks[:, first:first + count]
        mine = jnp.stack([jnp.sum(held), jnp.sum(held > 0)]).astype(
            jnp.int32)
        at = E + 1 + 2 * (picked[E] % HELD_LOG)
        picked = jax.lax.dynamic_update_slice(picked, mine, (at,))
        return picked.at[:E].add(jnp.sum(picks, axis=0)).at[E].add(1)

    def _forward_chunk(self, params, gk, gv, wk, wv, picked, *columns):
        """``_forward_chunk`` of the base model over the four pools and
        the running counts ``picked`` of the routing
        (:meth:`_blank_counts`). Returns ``(gk', gv', wk', wv',
        picked', logits [B, V], saved rows [L, B, T, 2 KV D] a group,
        router_in [L, lanes, H])``: ``picked`` with this forward's
        picks of every layer counted in, and what each layer's router
        read for every lane's last row (a check routes its reference's
        compared row by it)."""
        pools, x, saved, router_in, picks, lanes = self._trunk(
            params, gk, gv, wk, wv, *columns)
        logits = self._head_logits(params, lanes.last_rows(x))
        return (*pools, self._count(picked, picks), logits,
                *lanes.split(saved, lead=1), router_in)

    def _keep(self, cache, gk, gv, wk, wv, picked, logits, *rest):
        cache.replace(gk, gv)
        cache.replace_window(wk, wv)
        self._picks_dev = picked
        *saved, self.router_probe = rest
        self.moe_dispatches += 1
        if self.moe_dispatches - len(self.held_log) == HELD_LOG:
            self.take_picks()       # the log is full (and an int32 a count)
        return logits, saved

    def take_picks(self):
        """The positions routed to each expert ``[E]`` over the forwards
        so far, all layers summed, on the host; :attr:`held_log` then
        holds every forward's two counts."""
        E = self.cfg.num_experts
        counts = np.asarray(self._picks_dev)
        self._picks_dev = self._blank_counts(E)
        self._picks_seen += counts[:E]
        self.held_log = np.concatenate([
            self.held_log,
            counts[E + 1:E + 1 + 2 * counts[E]].reshape(-1, 2)])
        return self._picks_seen.copy()

    def _pools(self, cache):
        return cache.k, cache.v, cache.wk, cache.wv, self._picks_dev

    def forward_chunk(self, cache, tokens, start, tables, t_len):
        logits, (saved,) = self._keep(cache, *self._enqueue(
            self._fwd, self._pools(cache), tokens, start, tables, t_len))
        return logits, saved

    def forward_step(self, cache, *groups):
        return self._keep(cache, *self._enqueue_step(
            self._pools(cache), groups))

    # -------------------------------------------------------------- #
    # Counters a pool
    # -------------------------------------------------------------- #
    def _count_kv_write(self, T, positions, layers=None, pool=None):
        """A position's write is ``2 * n_kv_head`` rows a layer; counted
        in the totals and under the pool's name (default: both pools,
        every layer of each)."""
        path = "run" if T > 1 else "row"
        self.kv_write_stats[path + "_dispatches"] += 1
        for name in POOLS.values() if pool is None else (pool,):
            n = self.pool_layers[name] if pool is None else layers
            rows = int(positions) * 2 * self.cfg.n_kv_head * n
            self.kv_write_stats[path + "_rows"] += rows
            self.kv_write_stats[name][path + "_dispatches"] += 1
            self.kv_write_stats[name][path + "_rows"] += rows

    def _count_lanes(self, tokens, start, tables, t_len, *_):
        """One lane group of a dispatch in ``kv_write_stats`` and
        ``paged_walk_stats``, a pool: the window layers' walk starts at
        the first block the lane's first row sees."""
        self._count_kv_write(np.shape(tokens)[1], np.sum(t_len))
        BS = self.block_size
        start, t_len = np.asarray(start), np.asarray(t_len)
        ends = -(-(start + t_len) // BS)
        walked = {"global": int(np.sum(ends)),
                  "window": int(np.sum(np.where(
                      t_len > 0, ends - np.maximum(
                          start - (self.window - 1), 0) // BS, 0)))}
        total = self.paged_walk_stats
        total["dispatches"] += 1
        total["table_slots"] += np.size(tables)
        for name, blocks in walked.items():
            total["blocks_walked"] += blocks
            stats = total[name]
            stats["dispatches"] += 1
            stats["table_slots"] += np.size(tables) // 2
            stats["blocks_walked"] += blocks

    # -------------------------------------------------------------- #
    # HCache restore: the saved rows back into both pools
    # -------------------------------------------------------------- #
    def _restore_layer(self, params, cache_k, cache_v, layer, latent,
                       start, tables, t_len):
        """Put one layer's saved rows ``[B, T, 2 KV D]`` back into the
        pool ``(cache_k, cache_v)`` of its kind by that pool's
        ``tables``: a write, nothing replayed."""
        lanes = self._restore_lanes(latent, start, tables, t_len,
                                    cache_k.shape[2])
        B, T, _ = latent.shape
        KV, D = self.cfg.n_kv_head, self.cfg.head_dim
        latent = latent.astype(cache_k.dtype)
        return self._scatter_kv(
            cache_k, cache_v, layer,
            latent[..., :KV * D].reshape(B, T, KV, D),
            latent[..., KV * D:].reshape(B, T, KV, D), lanes)

    def window_rows(self, start, t_len, T):
        """Of lanes restoring positions ``[start, start + t_len)``, the
        rows a window layer keeps: ``(first row [B], rows [B], T_w)``:
        those from the first block that a query past the lane's end
        still sees, in a slab of ``T_w`` rows."""
        BS, W = self.block_size, self.window
        start, t_len = np.asarray(start), np.asarray(t_len)
        end = start + t_len
        lo = np.maximum(np.maximum(end - W, 0) // BS * BS, start)
        most = -(-(W + BS) // BS) * BS
        return lo - start, end - lo, T if T <= most else most

    def restore_pipeline(self, cache, latents, start, tables, t_len,
                         progress_cb=None):
        """The saved rows ``latents`` ``[L, B, T, 2 KV D]`` back into
        both pools: the window layers' rows still inside the window
        first, then the global layers' whole, each through
        :class:`RestorePipeline` by its pool's tables."""
        latents = np.asarray(latents)
        start, t_len = np.asarray(start), np.asarray(t_len)
        tables = np.asarray(tables)
        NB = self.max_blocks_per_seq
        first, rows, T_w = self.window_rows(start, t_len, latents.shape[2])
        w_rows = self.pool_rows["window"]
        w_lat = np.zeros((len(w_rows), latents.shape[1], T_w,
                          latents.shape[3]), latents.dtype)
        for j, (lo, n) in enumerate(zip(first, rows)):
            w_lat[:, j, :n] = latents[w_rows, j, lo:lo + n]
        sides = {name: _PoolSide(self, cache, name)
                 for name in POOLS.values()}
        return ChainedRestore([
            RestorePipeline(sides["window"], sides["window"], w_lat,
                            start + first, tables[:, NB:], rows,
                            progress_cb=progress_cb),
            RestorePipeline(sides["global"], sides["global"],
                            latents[self.pool_rows["global"]], start,
                            tables[:, :NB], t_len,
                            progress_cb=progress_cb)])

    # -------------------------------------------------------------- #
    # What needs a block behind the window, or both pools in a fused
    # loop's program, refuses by name
    # -------------------------------------------------------------- #
    def forward_chunk_tail(self, *a, **kw):
        raise refuse("the speculative verification forward (put_spec, "
                     "generate_lookup)",
                     "a rollback across a window block that has gone "
                     "back to its allocator")

    forward_chunk_tail_lat = forward_chunk_tail

    def decode_loop(self, *a, **kw):
        raise refuse("the fused decode loop (generate_fused)",
                     "both pools and both tables carried through the "
                     "loop's program, and blocks freed inside it")

    def lookup_decode_loop(self, *a, **kw):
        raise refuse("the fused speculative decode loop "
                     "(generate_lookup_fused)",
                     "both pools carried through the loop's program and "
                     "a rollback across a freed window block")
