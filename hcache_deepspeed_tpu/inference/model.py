"""Paged-KV inference model for the Llama/GPT family.

Reference analogs:
* ``deepspeed/inference/v2/model_implementations/llama_v2/model.py`` —
  per-layer forward producing logits **and latents** (:203-220, the HCache
  fork delta) and ``restore_kv`` (:222-252),
* ``deepspeed/inference/v2/modules/implementations/attention/
  dense_blocked_attention.py`` — blocked flash attention + the
  cache-write-only ``restore_kv`` hook (:182),
* the ragged kernel set (``kernels/ragged_ops/``): here each of
  atom-builder/blocked-flash/kv-rotary collapses into a single jitted
  gather/scatter + attention program.

TPU-native design
-----------------
One compiled function family, bucketed on static shapes:

``forward_chunk(params, cache, tokens[B,T], start[B], tables[B,NB], len[B])``
    processes T new tokens for each of B sequences against the paged cache
    (T=1 ⇒ ragged decode batch; B=1, T=bucket ⇒ prefill, including chunked
    continuation since ``start`` offsets positions). Layers run under
    ``lax.scan`` over (layer index, stacked params); the donated
    ``[L, KV, P, D]`` pools ride in the scan's carry, each layer writes
    its KV with one scatter of rows at ``[layer, head, slot]`` (invalid
    lanes dropped) and the paged kernel reads the carried pool by layer
    index. No ``pool[layer]`` is ever formed, so the loop holds one buffer
    a pool and XLA updates it in place.

``restore_layer(layer_params, latents[B,T,H], ...)``
    the HCache delta: replay ONLY the K/V projection + RoPE + cache write
    from saved latents — one layer per dispatch so the engine can overlap
    host→HBM latent copies with compute (the reference's dual-stream
    io_stream/compute pattern, engine-side).

Latents = post-input_layernorm hidden states (the exact tensor the
reference snapshots at llama_v2/model.py:211).
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..models.llama import LlamaConfig
from ..ops.kv_write import kv_write, write_rows
from ..ops.paged_attention import paged_attention
from ..ops.rms_norm import reference_rms_norm, rms_norm
from ..ops.rope import apply_rope, rope_frequencies
from ..parallel.topology import TENSOR_AXIS
from ..telemetry.tracer import get_tracer
from .ragged.lanes import (Lanes, pack_lanes, pack_step, unpack_lanes,
                           unpack_step)


def join_path(path):
    """Stable "a/b/c" rendering of a pytree key path (DictKey.key for
    mappings, str(entry) otherwise) — the one place path-key handling
    lives for quantization skip-lists and TP name rules."""
    return "/".join(str(getattr(k, "key", k)) for k in path)


def maybe_quantize_serving_params(tree, quantization, skip_paths=()):
    """Weight-only int quantization of a serving param tree (reference:
    ``deepspeed/inference/quantization`` — v1's int8 QuantLinear).
    Routers and embedding tables keep full precision (the embedding
    doubles as the tied LM head; the fp32 router picks experts). The
    stacked per-layer weights quantize with layer-aligned groups so the
    compiled layer loop dequantizes ONE layer at a time — resident
    weights stay int8. ``skip_paths``: joined paths that must stay full
    precision (trunk leaves the fused k-major layout could not cover —
    the flat-layout dequant fallback would be SLOWER than dense bf16 at
    decode, 81 vs 18 ms/token measured at 7B)."""
    if not quantization:
        return tree
    from ..ops.quantizer import quantize_tree

    def skip(path):
        joined = join_path(path)
        return joined in skip_paths \
            or "wg" in joined or "embed" in joined or "wte" in joined \
            or "wpe" in joined

    def batched(path):
        s = join_path(path).split("/")
        return bool(s[0]) and s[0] == "layers"
    return quantize_tree(tree, group_size=quantization.group_size,
                         num_bits=quantization.bits,
                         min_size=quantization.min_size, skip=skip,
                         batched=batched)


def stack_layer_params(params: Dict[str, Any], n_layers: int,
                       prefix: str = "layers_"):
    """[per-layer dicts] -> one pytree with leading layer dim (scan xs).

    Host (numpy) inputs stack on HOST: a 7B model's stacked leaves are
    ~13.5 GB bf16 — jnp.stack would enqueue that as device compute
    before quantization/cast can shrink it (the serving OOM mode)."""
    layers = [params[f"{prefix}{i}"] for i in range(n_layers)]

    def stack(*xs):
        if all(not isinstance(x, jax.Array) for x in xs):
            return np.stack([np.asarray(x) for x in xs])
        return jnp.stack(xs)

    return jax.tree.map(stack, *layers)


def scan_periods(period, n_periods, x, pools, steps):
    """The layer loop of a trunk whose layers are of several kinds in a
    repeating ``period`` (a tuple of kinds): a scan over the periods,
    each step the period's layers one after the other.
    ``steps[kind](x, pools, index)`` runs one layer of that kind, the
    ``index``-th of its kind in the trunk, and returns ``(x, pools,
    out)``; ``out`` is what the layer hands back (a latent, a tree of
    arrays) or ``None``. ``pools`` (any tree) is **carried, never
    scanned over**: a scanned-over pool is two buffers of the loop, and
    each layer's weights are read where they lie, at a dynamic index of
    their kind's stack (a period's slab handed over as the scan's ``xs``
    is sliced out of the stack and copied first). Returns ``(x, pools,
    outs)``, ``outs`` stacked over the layers that gave one, in layer
    order."""
    per = {kind: period.count(kind) for kind in period}

    def step(carry, p):
        x, pools = carry
        outs, seen = [], dict.fromkeys(per, 0)
        for kind in period:
            x, pools, out = steps[kind](
                x, pools, p * per[kind] + seen[kind])
            seen[kind] += 1
            if out is not None:
                outs.append(out)
        return (x, pools), jax.tree.map(lambda *a: jnp.stack(a), *outs)

    (x, pools), outs = jax.lax.scan(step, (x, pools),
                                    jnp.arange(n_periods))
    return x, pools, jax.tree.map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), outs)


def layer_of(stack, index):
    """One layer's parameters out of a stacked tree ``[L, ...]`` at a
    (traced) ``index``, each leaf read where it lies."""
    return jax.tree.map(
        lambda p: jax.lax.dynamic_index_in_dim(
            p, index, axis=0, keepdims=False), stack)


class PagedInferenceModel:
    """Functional paged-attention transformer consuming *training* params
    from ``models.llama.LlamaForCausalLM`` (same names/shapes — a trained
    checkpoint drops in directly, the analog of the reference's checkpoint
    loading into inference containers)."""

    #: a trunk with recurrent layers: its lanes carry a state slot each
    recurrent = False
    #: the rotary step takes its angles from the lanes' positions
    #: (``ops/rope.py rope_at``): no ``[max_positions, D/2]`` tables are
    #: built, and no program carries them
    rope_from_positions = False
    #: what HCache saves of a layer and a token (``engine.latent_stats``,
    #: ``engine.restore_profile``): ``hidden`` is the pre-attention
    #: hidden state, put back by replaying the K/V projection;
    #: ``cache_row`` the layer's cache row itself, put back by a write
    saved_state = "hidden"
    #: what each sparse layer's router read for every lane's last row in
    #: the latest forward, on the device (``engine.router_inputs``);
    #: ``None``: the trunk's forward does not hand it back
    router_probe = None

    def __init__(self, cfg: LlamaConfig, params, *, block_size: int,
                 max_blocks_per_seq: int, capture_latents: bool = True,
                 topology=None, quantization=None,
                 restore_chunk_layers: int = 0,
                 restore_chunk_bytes: int = 64 * 1024 * 1024,
                 latent_dtype=""):
        self.cfg = cfg
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.capture_latents = capture_latents
        self.restore_chunk_layers = restore_chunk_layers
        self.restore_chunk_bytes = restore_chunk_bytes
        # "" ⇒ capture in the compute dtype (bit-exact restore)
        self.latent_dtype = jnp.dtype(latent_dtype) if latent_dtype \
            else jnp.dtype(cfg.compute_dtype)
        self.n_layers = cfg.n_layer
        #: layers that capture a latent and replay K/V on restore (a
        #: hybrid trunk's full-attention layers only)
        self.n_latent_layers = cfg.n_layer
        #: positions of a generation block of a model that generates by
        #: diffusion over blocks: the attention mask's static, and the
        #: width of a lane of :meth:`forward_block`; 1: causal
        self.mask_block = getattr(cfg, "diffusion_block_length", 1)
        #: dispatches, and the ``[D]`` rows of K and V they wrote into the
        #: pools, by the granularity of the write (``_scatter_kv``): a
        #: block run at a time (lanes of more than one position) or a
        #: row at a time. Reckoned on the host from each dispatch's shape
        #: (``_count_kv_write``); a kernel that gave way to the rows is
        #: in ``ops.fallback_report()``.
        self.kv_write_stats = {"run_dispatches": 0, "run_rows": 0,
                               "row_dispatches": 0, "row_rows": 0}
        #: forwards enqueued, and the host arrays (and their bytes) that
        #: described their lanes to the device: a transfer each
        #: (``_enqueue``)
        self.dispatch_stats = {"dispatches": 0, "h2d_arrays": 0,
                               "h2d_bytes": 0}
        #: what the paged kernel's walk is spared: of the ``table_slots``
        #: of the dispatches' buckets (lanes x table width, what a grid
        #: over the table stepped through) the ``blocks_walked`` that
        #: hold a lane's context (``_enqueue``)
        self.paged_walk_stats = {"dispatches": 0, "table_slots": 0,
                                 "blocks_walked": 0}
        self.topology = topology
        self.tp = topology.tensor_size if topology is not None else 1
        self.quantization = quantization if (
            quantization is not None and quantization.enabled) else None
        # TP + quantization works in both int8 modes: trunk kernels use
        # the k-major MatmulQuantizedTensor layout whose groups run down
        # K per column, so col/row shards stay group-pure (the former
        # flat-layout TP rejection no longer applies).

        self.tied = cfg.tie_word_embeddings
        if self.tp > 1:
            self._validate_tp()
        self.load_params(params)
        theta = getattr(cfg, "rope_theta", None)
        self.cos = self.sin = None
        if theta is not None and not self.rope_from_positions:
            self.cos, self.sin = rope_frequencies(cfg.head_dim,
                                                  cfg.max_positions,
                                                  theta)
        #: the forward over separate lane arrays, for the fused loops'
        #: bodies; a dispatch goes through ``_fwd``, over packed lanes
        self._fwd_inner = self._manual_tp(self._forward_chunk, 4, 2)
        self._fwd = self._chunk_program()
        #: the programs of a step's lane groups, by the groups' shapes
        #: (``forward_step``)
        self._fwd_step_cache = {}
        self._restore = jax.jit(
            self._manual_tp(self._restore_chunk, 5, 0),
            donate_argnums=(1, 2))
        self._fwd_block = self._lane_program(
            self._forward_block, 3, column=True)
        self._fwd_tail_cache = {}
        self._fwd_tail_lat_cache = {}
        self._fwd_tail_inner_cache = {}
        self._lookup_loop_jit = jax.jit(
            self._lookup_decode_loop,
            static_argnums=(10, 11, 12, 13, 14),
            donate_argnums=(1, 2))
        self._decode_loop_jit = jax.jit(self._decode_loop,
                                        static_argnums=(11, 12, 13, 14,
                                                        15, 16),
                                        donate_argnums=(1, 2))

    def load_params(self, params):
        """(Re)load training-layout parameters into the serving layout —
        stacked layers, sharded when TP. Called at construction and by the
        hybrid engine after each training phase (reference:
        runtime/hybrid_engine.py — inference containers refreshed from
        ZeRO training params). Shapes are unchanged, so the compiled
        forward/restore functions are reused without retracing.

        A tree that holds ``layers`` already stacked ``[L, ...]`` in
        place of ``layers_<i>`` is taken as it is: stacking holds every
        layer twice while it runs, which a model that fills most of a
        chip cannot afford."""
        new = {
            "embed": params["embed_tokens"]["embedding"],
            "norm": params["norm"]["weight"],
            "layers": params["layers"] if "layers" in params
            else stack_layer_params(params, self.cfg.n_layer),
        }
        if not self.tied:
            new["lm_head"] = params["lm_head"]["kernel"]
        self.params = self._finalize_params(new)

    def _finalize_params(self, new):
        """Shared load_params tail for every family: dtype cast (with
        the `_keep_fp32` exemptions), optional weight quantization, TP
        placement.

        When the incoming tree is host-resident (numpy — checkpoint
        loads, the serving bench) the cast runs on HOST and only the
        FINAL representation is shipped: for an int8-quantized 7B that
        is ~7 GB instead of 13.5 GB bf16 (or 27 GB fp32) of deferred
        device compute whose materialization OOMs a 16 GB chip. Device
        inputs (hybrid-engine refresh from live training params) keep
        the all-device path — no D2H round trip."""
        on_host = all(not isinstance(x, jax.Array)
                      for x in jax.tree.leaves(new))

        def cast(path, p):
            if on_host:
                p = np.asarray(p)
                if not jnp.issubdtype(p.dtype, jnp.floating):
                    return p
                target = (jnp.float32 if self._keep_fp32(path)
                          else self.cfg.compute_dtype)
                return p.astype(jnp.dtype(target))   # ml_dtypes bf16 ok
            p = jnp.asarray(p)
            if not jnp.issubdtype(p.dtype, jnp.floating):
                return p
            if self._keep_fp32(path):
                return p.astype(jnp.float32)
            return p.astype(self.cfg.compute_dtype)

        new = jax.tree_util.tree_map_with_path(cast, new)
        new = self._maybe_quantize(new)
        if self.tp > 1:
            new = jax.device_put(new, self._param_shardings_for(new))
        elif on_host:
            # one explicit transfer of the final (possibly int8) tree
            new = jax.device_put(new)
        return new

    def _maybe_quantize(self, tree):
        qc = self.quantization
        if not qc:
            return maybe_quantize_serving_params(tree, qc)
        # Stacked [L, K, N] projection kernels become
        # MatmulQuantizedTensor in BOTH int8 modes (consumed by _mm:
        # the fused Pallas kernel, or a k-major grouped-view dequant
        # XLA fuses into the dot; NOT dequantized by the scan step).
        # The flat-layout QuantizedTensor dequant lowers to a
        # reshape/slice chain that materializes full-precision copies —
        # measured 41.7 vs 3.1 ms/token at 1B decode. Non-trunk leaves
        # (embed/head) follow the flat dequant-on-use path.
        from ..ops.quantized_matmul import MatmulQuantizedTensor

        names = self._COL_NAMES + self._ROW_NAMES
        skipped = []   # trunk leaves that LOOK quantizable but are not

        def fused(path, leaf):
            # shape checks on the leaf as-is: a host (numpy) leaf must
            # NOT be shipped whole — make_batched streams it to the
            # device one layer at a time (a 7B stacked leaf's one-shot
            # fp32 group view OOMs a 16 GB chip)
            joined = join_path(path)
            is_trunk = (path and str(getattr(path[0], "key",
                                             path[0])) == "layers"
                        and getattr(leaf, "ndim", 0) == 3
                        and any(n in joined for n in names)
                        and joined.endswith("kernel")
                        and leaf.size >= qc.min_size)
            # untied LM head [H, V]: k-major too (tp==1; under TP the
            # early return keeps it full precision). The flat layout
            # dequantizes the WHOLE head every step inside _trunk —
            # ~0.4 GB of bf16 materialized per decoded token at 7B;
            # k-major streams it int8 through _mm like the trunk.
            is_head = (not self.tied and self.tp == 1
                       and joined in ("lm_head", "lm_head/kernel")
                       and getattr(leaf, "ndim", 0) == 2
                       and leaf.size >= qc.min_size)
            if is_head:
                if leaf.shape[-2] % qc.group_size:
                    # same misalignment as the trunk case below: the
                    # head silently staying dense would skew quantized
                    # decode measurements (the head is the single
                    # largest matmul per decoded token) — record it so
                    # the warning fires and the flat-layout fallback
                    # can't quietly re-quantize it either
                    skipped.append((joined, tuple(leaf.shape)))
                    return leaf
                return MatmulQuantizedTensor.make(
                    jnp.asarray(leaf), group_k=qc.group_size,
                    num_bits=qc.bits)
            if is_trunk and leaf.shape[-2] % qc.group_size:
                # K not a group multiple: the leaf stays full precision.
                # Record it — a silently-dense trunk matmul skews any
                # quantized measurement (e.g. group_size 512 leaves the
                # 7B down projection, 25% of weight bytes, bf16).
                skipped.append((joined, tuple(leaf.shape)))
                return leaf
            if not is_trunk:
                return leaf
            if self.tp > 1:
                # shard-alignment: col shards split N (scales follow);
                # row shards split K and its group dim, so the local K
                # must stay a group multiple. Misaligned leaves stay
                # full precision (sharded by the name rules as usual).
                K, N = leaf.shape[-2], leaf.shape[-1]
                if any(n in joined for n in self._ROW_NAMES):
                    if K % self.tp or (K // self.tp) % qc.group_size:
                        return leaf
                elif N % self.tp:
                    return leaf
            return MatmulQuantizedTensor.make_batched(
                leaf, group_k=qc.group_size, num_bits=qc.bits)
        tree = jax.tree_util.tree_map_with_path(fused, tree)
        if skipped:
            from ..utils.logging import log_dist
            log_dist(
                "quantization: %d trunk/head leaves stay full precision "
                "(K %% group_size=%d != 0): %s"
                % (len(skipped), qc.group_size,
                   ", ".join(f"{p}{s}" for p, s in skipped[:4])),
                level=30)   # WARNING — measurements must not read dense
        if self.tp > 1:
            # non-layer leaves (untied head) would quantize in the FLAT
            # layout whose groups straddle the vocab shard — they stay
            # full precision under TP
            return tree
        return maybe_quantize_serving_params(
            tree, qc, skip_paths=frozenset(p for p, _ in skipped))

    def _mm(self, x, w):
        """Matmul that transparently routes k-major-quantized weights:
        through the int8 Pallas kernel (``use_fused_kernel``), or the
        grouped-view dequant that XLA fuses into the dot (plain int8 —
        measured at the int8 bandwidth floor, unlike the flat-layout
        reshape chain ``QuantizedTensor.dequantize`` lowers to)."""
        from ..ops.quantized_matmul import (MatmulQuantizedTensor,
                                            reference_quantized_matmul)
        if isinstance(w, MatmulQuantizedTensor):
            if self.quantization and self.quantization.use_fused_kernel:
                return w.matmul(x)
            lead = x.shape[:-1]
            out = reference_quantized_matmul(
                x.reshape(-1, x.shape[-1]), w.q, w.scale,
                group_k=w.group_k)
            return out.reshape(*lead, w.q.shape[-1])
        return x @ w

    @staticmethod
    def _keep_fp32(path) -> bool:
        """Leaves that must stay fp32 regardless of compute dtype (the MoE
        family pins its router here — near-tie routing logits flip expert
        selection under bf16 rounding)."""
        return False

    # -------------------------------------------------------------- #
    # Tensor parallelism (reference: per-layer allreduce + sharded heads,
    # inference/v2/model_implementations/llama_v2/model.py:160,169 and
    # the sharding framework model_implementations/sharding/)
    # -------------------------------------------------------------- #
    def _validate_tp(self):
        cfg, tp = self.cfg, self.tp
        for name, val in (("n_head", cfg.n_head),
                          ("n_kv_head", cfg.n_kv_head),
                          ("intermediate_size", cfg.intermediate_size),
                          ("vocab_size", cfg.vocab_size)):
            if val % tp:
                raise ValueError(f"{name}={val} not divisible by "
                                 f"tensor parallel degree {tp}")

    #: per-family projection name tables for the TP spec builder: names
    #: matched as substrings of the param path. Subclasses override
    #: (falcon: dense_h_to_4h/dense_4h_to_h; phi: fc1/dense/fc2).
    _COL_NAMES = ("q_proj", "k_proj", "v_proj", "gate_proj", "up_proj")
    _ROW_NAMES = ("o_proj", "down_proj")
    #: a row-parallel projection bias is only legal when the family's
    #: layer math adds it AFTER the psum (phi does; llama has none)
    _ROW_BIAS_OK = False

    def _layer_leaf_spec(self, path, leaf):
        from jax.sharding import PartitionSpec as P
        joined = join_path(path)
        if any(n in joined for n in self._COL_NAMES):
            # stacked kernel [L, in, out] -> col; stacked bias [L, out]
            # follows its column shards
            return P(None, None, TENSOR_AXIS) if leaf.ndim == 3 \
                else P(None, TENSOR_AXIS)
        if any(n in joined for n in self._ROW_NAMES):
            if leaf.ndim != 3:
                if self._ROW_BIAS_OK:
                    return P()   # replicated, added once after the psum
                raise NotImplementedError(
                    "bias on a row-parallel projection would be "
                    "added once per shard before the psum")
            return P(None, TENSOR_AXIS, None)
        return P()

    def _top_leaf_spec(self, key, path, leaf):
        """Specs for the non-layer entries (embed / norm / lm_head)."""
        from jax.sharding import PartitionSpec as P
        if key == "embed":
            # tied: ONE vocab-row-sharded table serves embed + LM head
            # (the reference's vocab-parallel embedding); untied: embed
            # replicated, head column-sharded
            return P(TENSOR_AXIS, None) if self.tied else P()
        if key == "lm_head":
            names = [str(getattr(k, "key", k)) for k in path]
            if names and names[-1] == "bias":
                return P(TENSOR_AXIS)      # vocab-sharded head bias
            return P(None, TENSOR_AXIS)
        return P()                         # norms etc. replicate

    def _param_spec_tree(self, params=None):
        import functools
        params = params if params is not None else self.params
        specs = {}
        for key, sub in params.items():
            if key == "layers":
                specs[key] = jax.tree_util.tree_map_with_path(
                    self._layer_leaf_spec, sub)
            else:
                specs[key] = jax.tree_util.tree_map_with_path(
                    functools.partial(self._top_leaf_spec, key), sub)
        return specs

    def _param_shardings_for(self, params):
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = self.topology.mesh
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            self._param_spec_tree(params),
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    @property
    def table_width(self):
        """Entries of a lane's block table as a dispatch carries it: a
        table a block pool, side by side."""
        return self.max_blocks_per_seq

    def pool_layout(self):
        """``(kv heads, k width, v width)`` of the two block pools."""
        return self.cfg.n_kv_head, self.cfg.head_dim, self.cfg.head_dim

    def attention_fits(self, tokens):
        """Raise (``PagedAttentionBudgetError``: the rows, bytes and
        limit) where the attention kernel cannot tile a dispatch of
        ``tokens`` positions at this head layout and block size."""
        from ..ops.paged_attention import pick_tiles
        cfg = self.cfg
        pick_tiles(cfg.n_kv_head // self.tp,
                   tokens * (cfg.n_head // cfg.n_kv_head), cfg.head_dim,
                   self.block_size, jnp.dtype(cfg.compute_dtype).itemsize)

    def cache_sharding(self):
        """Sharding for the [L, KV, P, D] block pool: KV heads split over
        ``tensor``. None on single chip."""
        if self.tp == 1:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.topology.mesh,
                             P(None, TENSOR_AXIS, None, None))

    def _manual_tp(self, fn, operands: int, results: int):
        """``fn(params, cache_k, cache_v, *operands) -> (cache_k',
        cache_v', *results)`` over the tensor-parallel shards: the
        parameters and the pools split as they lie, ``operands`` and
        ``results`` replicated. ``fn`` itself on one chip."""
        if self.tp == 1:
            return fn
        from jax.sharding import PartitionSpec as P
        cache_spec = P(None, TENSOR_AXIS, None, None)  # [L, KV, P, D]
        # every mesh axis manual (no ``axis_names``): Mosaic refuses a
        # kernel while any axis, even of size one, is left automatic
        return jax.shard_map(
            fn, mesh=self.topology.mesh,
            in_specs=(self._param_spec_tree(), cache_spec, cache_spec)
            + (P(),) * operands,
            out_specs=(cache_spec, cache_spec) + (P(),) * results,
            check_vma=False)

    def _lane_program(self, fwd, results: int, pools: int = 2,
                      column=None, shapes=None):
        """The jitted program of one dispatch: ``fwd(params, *pools,
        tokens, start, tables, t_len[, column])`` with the lanes as one
        packed operand (``ragged/lanes.py``), cut inside the program;
        the pools donated. ``column``: the lanes carry a fifth column (a
        recurrent trunk's state slots, a block dispatch's flags);
        default: as the trunk is. ``shapes``: the operand holds several
        lane groups of these ``(B, T)`` (``pack_step``) and ``fwd`` takes
        every group's columns, one group after the other. Named after
        ``fwd`` in a profile."""
        column = self.recurrent if column is None else column
        blocks = self.table_width

        def program(params, *operands):
            lanes = operands[pools]
            columns = unpack_lanes(lanes, blocks, column) \
                if shapes is None else [c for group in unpack_step(
                    lanes, shapes, blocks, column) for c in group]
            return fwd(params, *operands[:pools], *columns)
        program.__name__ = fwd.__name__
        return jax.jit(self._manual_tp(program, 1, results),
                       donate_argnums=tuple(range(1, 1 + pools)))

    def _chunk_program(self, shapes=None):
        """The program of a forward over one lane group, or over the
        groups ``shapes`` of a step: ``(pools..., logits, latents a
        group)``."""
        groups = 1 if shapes is None else len(shapes)
        return self._lane_program(self._forward_chunk, 1 + groups,
                                  shapes=shapes)

    # -------------------------------------------------------------- #
    # Layer math (mirrors models/llama.py LlamaBlock exactly)
    # -------------------------------------------------------------- #
    def _qkv_heads(self, attn, h):
        """The q, k and v projections of ``h`` [B, T, H] by one layer's
        attention parameters (biased where the family has biases), split
        into heads: q [B, T, Hq, D], k/v [B, T, KV, D]. Head counts come
        from the kernel widths so the same code runs on the full model or
        a tensor-parallel shard (H/tp local heads).

        The barrier stands between the dots and the split into heads.
        Without it the TPU compiler folds the reshape into the dot, makes
        it a convolution over the head dimension that wants the kernel
        transposed, and so slices each kernel out of the stacked leaf
        into a buffer of its own and copies that into the other layout:
        two more passes over the layer's q, k and v weights in every
        program. Behind it the dots are plain matmuls and read their
        layer of the stacked leaf in place, as ``o_proj`` and the MLP do
        (``tests/unit/inference/test_kv_pool_in_place.py``)."""
        D = self.cfg.head_dim

        def proj(p):
            y = self._mm(h, p["kernel"])
            return y + p["bias"] if "bias" in p else y

        qkv = jax.lax.optimization_barrier(tuple(
            proj(attn[name]) for name in ("q_proj", "k_proj", "v_proj")))
        q, k, v = (y.reshape(*y.shape[:-1], y.shape[-1] // D, D)
                   for y in qkv)
        if "q_norm" in attn:
            # RMSNorm over each head's channels, before the rotary step
            # (plain jnp: rows of ``D`` fuse into their neighbours)
            eps = self.cfg.rms_norm_eps
            q = reference_rms_norm(q, attn["q_norm"]["weight"], eps)
            k = reference_rms_norm(k, attn["k_norm"]["weight"], eps)
        return q, k, v

    def _qkv(self, lp, h, positions):
        """h: [B, T, H]; returns q [B,T,Hq,D], k/v [B,T,KV,D] (roped)."""
        q, k, v = self._qkv_heads(lp["self_attn"], h)
        q = apply_rope(q, self.cos, self.sin, positions)
        k = apply_rope(k, self.cos, self.sin, positions)
        return q, k, v

    def _scatter_kv(self, ck, cv, layer, k, v, lanes):
        """ck/cv: the whole [L, KV, P, D] pools; k/v: the rows of
        ``layer`` of all of ``lanes``; a group's are [B, T, KV, D], the
        rows of positions ``start + [0, T)`` of each lane: those before
        ``kv_len`` are written to their slots by ``tables``
        (``flat_idx`` [B, T]: the same slots, reckoned once a program),
        the rest (padding) are not. One write a group at the granularity
        its shape wants (``ops/kv_write.py``): a lane of decode lanes is
        one row in a block of its own, a row an update; a lane of ``T``
        positions is runs of consecutive slots, a block an update."""
        for g, kg, vg in zip(lanes.groups, lanes.split(k), lanes.split(v)):
            start = g.positions[:, 0]   # consecutive positions a lane
            if kg.shape[1] > 1:
                ck, cv = lanes.shared(kv_write, (8,))(
                    ck, cv, kg, vg, layer, g.tables, start, g.kv_len,
                    self.block_size)
            else:
                ck, cv = write_rows(ck, cv, layer, kg, vg, g.flat_idx)
        return ck, cv

    def _paged_attention(self, q, ck, cv, layer, lanes, window=None):
        """q: the rows of all of ``lanes``, a group's [B, T, Hq, D];
        ck/cv: the whole [L, KV, P, D] pools, read at ``layer`` by each
        group's tables [B, NB], absolute positions [B, T] and valid
        cache length [B]. Returns the rows' [.., Hq*D].

        Dispatches to the Pallas ragged paged-attention kernel
        (``ops/paged_attention.py`` — the blocked_flash analog), a call
        a group at the group's shape: block-table-indexed flash over
        valid blocks only, no dense [B, S_max] gather, no GQA repeat.
        ``window``: the layer's sliding window (``None``: none)."""
        outs = []
        extra = () if window is None else (window,)
        static = (7, 8) + ((9,) if extra else ())
        for g, qg in zip(lanes.groups, lanes.split(q)):
            B, T, Hq, D = qg.shape
            start = g.positions[:, 0]
            out = lanes.shared(paged_attention, static)(
                qg, ck, cv, layer, g.tables, start, g.kv_len,
                self.block_size, self.mask_block, *extra)
            outs.append(out.reshape(B, T, Hq * D))
        return lanes.join(outs)

    def _layer_step(self, x, lp, ck, cv, layer, lanes):
        cfg = self.cfg
        # fp32 norm weights promote under standard dtype rules — pin the
        # residual stream to the compute dtype
        h = rms_norm(x, lp["input_layernorm"]["weight"],
                     eps=cfg.rms_norm_eps).astype(cfg.compute_dtype)
        latent = h.astype(self.latent_dtype) \
            if self.capture_latents else jnp.zeros(
            (x.shape[0], x.shape[1], 0), h.dtype)
        q, k, v = self._qkv(lp, h, lanes.positions)
        ck, cv = self._scatter_kv(ck, cv, layer, k, v, lanes)
        attn = self._paged_attention(q, ck, cv, layer, lanes)
        proj = self._mm(attn, lp["self_attn"]["o_proj"]["kernel"])
        if self.tp > 1:   # row-parallel partial sum (reference :160)
            proj = jax.lax.psum(proj, TENSOR_AXIS)
        x = x + proj
        h2 = rms_norm(x, lp["post_attention_layernorm"]["weight"],
                      eps=cfg.rms_norm_eps).astype(cfg.compute_dtype)
        mlp, stats = self._mlp(lp, h2, lanes, ck.shape[2])
        x = x + mlp
        return x.astype(cfg.compute_dtype), ck, cv, latent, stats

    def _mlp(self, lp, h2, lanes, pool_slots):
        """:meth:`_mlp_out` and what the layer has to say of itself, a
        dict of arrays that the layer scan stacks for
        :meth:`forward_block` (``lanes.flat_idx`` below ``pool_slots``
        are the real positions; the rest is padding): nothing here; the
        router's input and the picks an expert in the MoE family
        (``model_moe.py``)."""
        return self._mlp_out(lp, h2), {}

    def _mlp_out(self, lp, h2):
        """SwiGLU MLP on the post-attention hidden states. Overridden by
        the MoE family (model_moe.py) with routed grouped-GEMM experts."""
        gate = self._mm(h2, lp["mlp"]["gate_proj"]["kernel"])
        up = self._mm(h2, lp["mlp"]["up_proj"]["kernel"])
        mlp = self._mm(jax.nn.silu(gate) * up,
                       lp["mlp"]["down_proj"]["kernel"])
        if self.tp > 1:   # (reference :169)
            mlp = jax.lax.psum(mlp, TENSOR_AXIS)
        return mlp

    # -------------------------------------------------------------- #
    # forward_chunk: the one compiled family (prefill & ragged decode)
    # -------------------------------------------------------------- #
    def _trunk(self, params, cache_k, cache_v, *columns):
        """Embed → layer scan → final norm: the shared body of the
        chunk forwards, over the lanes ``columns`` (tokens, start,
        tables, t_len of one group, or of each group of a step). Returns
        (params', cache_k', cache_v', x [B, T, H] normed hidden states,
        latents, the layers' stats, the lanes): of several groups ``x``
        is ``[1, N, H]`` and the latents ``[L, 1, N, H]``, all rows, to
        be cut by the :class:`~.ragged.lanes.Lanes`."""
        from ..ops.quantizer import dequantize_tree
        # non-layer leaves (head) dequantize here; the stacked layers stay
        # int8 and dequantize ONE layer at a time inside the scan step —
        # resident HBM holds int8 weights + one bf16 layer, not L of them
        params = {k: (v if k == "layers" else dequantize_tree(v))
                  for k, v in params.items()}
        lanes = Lanes.of(columns)
        x = self._embed_lanes(params, lanes, cache_k.shape[2])

        scanned, whole = self._whole_layers(params["layers"])
        # layers of another kind that lead the stack (a dense layer
        # before sparse ones) run before the scan, at the first layers
        # of the pools
        x, cache_k, cache_v, lead = self._lead_layers(
            params, x, cache_k, cache_v, lanes)
        n_lead = len(lead)

        # the pools are carried, never scanned over: a scanned-over pool
        # is two buffers of the loop, every layer sliced out of one and
        # written back into the other
        def step(carry, xs):
            x, ck, cv = carry
            layer, lp = xs
            lp = dequantize_tree(lp)   # one layer's weights only
            if whole is not None:
                lp = self._with_whole(lp, whole, layer)
            x, ck, cv, latent, stats = self._layer_step(
                x, lp, ck, cv, layer + n_lead if n_lead else layer, lanes)
            # a layer with nothing to say adds nothing to the loop
            return (x, ck, cv), (latent, stats)

        (x, cache_k, cache_v), (latents, stats) = jax.lax.scan(
            step, (x, cache_k, cache_v),
            (jnp.arange(cache_k.shape[0] - n_lead), scanned))
        if n_lead:
            latents = jnp.concatenate([jnp.stack(lead), latents])

        x = self._final_norm(params, x)
        return params, cache_k, cache_v, x, latents, stats, lanes

    def _embed_lanes(self, params, lanes, pool_slots):
        """The embedded rows of ``lanes`` ([B, T, H]; [1, N, H] of
        several groups), and on every group and on ``lanes`` (all rows)
        what the layers read of them: ``positions``, ``kv_len``,
        ``flat_idx``."""
        lanes.place_positions()
        x = self._embed_lookup(params["embed"], lanes.rows("tokens"))
        extra = self._embed_extra(params, lanes.positions)
        if extra is not None:
            x = x + extra
        lanes.place_slots(self.block_size, pool_slots)
        return x

    def _lead_layers(self, params, x, cache_k, cache_v, lanes):
        """The layers that run before the layer scan, unrolled: ``(x,
        cache_k, cache_v, their latents)``. None here; the leading dense
        layers of a trunk whose other layers are sparse
        (``model_latent.py``)."""
        return x, cache_k, cache_v, []

    def _whole_layers(self, layers):
        """``(scanned, whole)`` of the stacked layers: what the layer
        scan slices a layer at a time, and what every layer is handed
        whole beside its index (:meth:`_with_whole`): leaves that a
        kernel reads in place by a layer index, which sliced out would be
        copied. Nothing here; the MoE family's expert stacks."""
        return layers, None

    def _with_whole(self, lp, whole, layer):
        """One layer's parameters ``lp`` with ``whole`` and the layer's
        index put where the layer looks for them."""
        raise NotImplementedError

    def _forward_chunk(self, params, cache_k, cache_v, *columns):
        """``columns``: tokens [B, T] int32; start [B] first absolute
        position; tables [B, NB]; t_len [B] valid new tokens (≤ T).
        Returns (cache_k', cache_v', logits [B, V], latents [L, B, T, H]).

        The same four columns once more a further lane group (a step's
        decode lanes ``[B_d, 1]``, then its prompt slice ``[B_s, T]``):
        one pass over the weights for all rows, each group's write and
        attention at its own shape; the logits are the groups' lanes one
        after the other, the latents an array a group."""
        params, cache_k, cache_v, x, latents, _, lanes = self._trunk(
            params, cache_k, cache_v, *columns)
        logits = self._head_logits(params, lanes.last_rows(x))
        if self.tp > 1:
            # vocab is sharded either way (tied: rows of the table;
            # untied: head columns) — gather the full logits row
            # (reference: allgather logits if tp>1, llama_v2/model.py:181)
            logits = jax.lax.all_gather(logits, TENSOR_AXIS, axis=1,
                                        tiled=True)
        return (cache_k, cache_v, logits, *lanes.split(latents, lead=1))

    def _forward_chunk_tail(self, params, cache_k, cache_v, tokens,
                            start, tables, t_len, tail):
        """Like ``_forward_chunk`` but projects the LAST ``tail`` valid
        positions through the LM head — the verification forward of
        speculative decoding (a drafted stretch needs target logits at
        every drafted position, not just the final one). Returns
        (cache_k', cache_v', logits [B, tail, V]); positions before a
        short sequence's first valid slot clamp to 0 and the caller
        masks by its own accept arithmetic."""
        params, cache_k, cache_v, x, *_ = self._trunk(
            params, cache_k, cache_v, tokens, start, tables, t_len)
        idx = jnp.maximum(
            t_len[:, None] - tail + jnp.arange(tail)[None, :], 0)  # [B,tail]
        xt = jnp.take_along_axis(x, idx[..., None], axis=1)   # [B,tail,H]
        logits = self._head_logits(params, xt)                # [B,tail,V]
        if self.tp > 1:
            logits = jax.lax.all_gather(logits, TENSOR_AXIS, axis=2,
                                        tiled=True)
        return cache_k, cache_v, logits

    def _forward_chunk_tail_lat(self, params, cache_k, cache_v,
                                tokens, start, tables, t_len, tail):
        """``_forward_chunk_tail`` that also returns the trunk's
        captured latents [L, B, T, H] — the verification forward of
        speculative decoding under latent preemption: the caller keeps
        the accepted span's latents (columns ``:acc+1`` of each lane)
        and discards the rolled-back tail. A separate compiled family
        (``_fwd_tail_lat_cache``): engines running exact-KV suspension
        never pay for the latent output."""
        params, cache_k, cache_v, x, latents, *_ = self._trunk(
            params, cache_k, cache_v, tokens, start, tables, t_len)
        idx = jnp.maximum(
            t_len[:, None] - tail + jnp.arange(tail)[None, :], 0)
        xt = jnp.take_along_axis(x, idx[..., None], axis=1)
        logits = self._head_logits(params, xt)
        if self.tp > 1:
            logits = jax.lax.all_gather(logits, TENSOR_AXIS, axis=2,
                                        tiled=True)
        return cache_k, cache_v, logits, latents

    def _forward_block(self, params, cache_k, cache_v, tokens, start,
                       tables, t_len, flags):
        """One pass over a generation block a lane (a model that
        generates by diffusion over blocks): the decode forward at ``T =
        mask_block``, position ``i``'s logits predicting position
        ``i``'s token, with the choice made here, greedy, so that no
        logits row leaves the device. ``flags`` [B]: bit 0 marks the one
        lane whose logits rows the caller wants, bit 1 the lanes that
        commit.

        Returns (cache_k', cache_v', packed, probe, latents). ``packed``
        is int32, one fetch: the tokens chosen [B * T], their confidence
        (the softmax probability of the chosen token, float32 bit for
        bit) [B * T], then the layers' counts summed [n + 1]:
        ``_mlp``'s ``picks`` and, last, how many of them are not zero
        layer by layer. ``probe``: of the flagged lane, the logits rows
        [T, V] and ``_mlp``'s ``router_in`` [L, T, H] (what each layer's
        router read: a check routes its reference by it, ``[L, T, 0]``
        where the trunk has no router). ``latents``: the committing
        lanes first, as a tuple of the first 8, 16, ... B lanes ``[L, n,
        T, H]``, so that the caller sends the host the bucket that holds
        its committing lanes and no second program cuts it."""
        params, cache_k, cache_v, x, latents, stats, _ = self._trunk(
            params, cache_k, cache_v, tokens, start, tables, t_len)
        B, T, H = x.shape
        # positions as rows from here on: a [B, T, V] result with T = 4
        # in its second-minor dimension is laid out afresh, and a dot over
        # [B, T, H] has the head's kernel copied into another layout
        logits = self._head_logits(params, x.reshape(B * T, H))  # [BT, V]
        if self.tp > 1:
            logits = jax.lax.all_gather(logits, TENSOR_AXIS, axis=1,
                                        tiled=True)
        # the mask token is no prediction: a position that chose it would
        # read as still masked and be fed again unchanged, for ever. The
        # bias folds into the reductions that read the logits
        allowed = logits + jnp.where(
            jnp.arange(logits.shape[-1]) == self.cfg.mask_token_id,
            -jnp.inf, 0.0)[None, :]
        chosen = jnp.argmax(allowed, axis=-1).astype(jnp.int32)
        picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)
        confidence = jnp.exp(
            picked[:, 0] - jax.nn.logsumexp(allowed, axis=-1))
        lane = jnp.argmax(flags & 1)
        router_in = stats.get("router_in",
                              jnp.zeros((latents.shape[0], B, T, 0),
                                        x.dtype))
        probe = (jax.lax.dynamic_slice_in_dim(logits, lane * T, T, axis=0),
                 jax.lax.dynamic_index_in_dim(router_in, lane, axis=1,
                                              keepdims=False))
        # the lanes that commit first, in their order: the caller takes
        # that many lanes of the latents to the host and no more
        latents = latents[:, jnp.argsort(1 - ((flags >> 1) & 1),
                                         stable=True)]
        latents = tuple(latents[:, :n] for n in self.latent_buckets(B))
        if "picks" in stats:
            picks = stats["picks"]                              # [L, E]
            counts = jnp.concatenate([
                jnp.sum(picks, axis=0),
                jnp.sum(picks > 0, dtype=jnp.int32)[None]])
        else:
            counts = jnp.zeros((1,), jnp.int32)
        packed = jnp.concatenate([
            chosen, jax.lax.bitcast_convert_type(confidence, jnp.int32),
            counts])
        return cache_k, cache_v, packed, probe, latents

    @staticmethod
    def latent_buckets(B):
        """The lane counts :meth:`_forward_block` cuts its latents to."""
        return [n for n in (8 << i for i in range(32)) if n < B] + [B]

    def forward_block(self, cache, tokens, start, tables, t_len, flags):
        """:meth:`_forward_block` over ``cache``; everything it returns
        stays on the device."""
        ck, cv, *out = self._enqueue(
            self._fwd_block, (cache.k, cache.v),
            tokens, start, tables, t_len, flags)
        cache.replace(ck, cv)
        return out

    def _final_norm(self, params, x):
        """Final RMSNorm; LayerNorm families (falcon) override."""
        return rms_norm(x, params["norm"], eps=self.cfg.rms_norm_eps)

    def _head_logits(self, params, last):
        """LM head on the last valid position; biased-head families
        (phi) override. ``_mm`` routes a k-major-quantized untied head
        through the fused int8 kernel."""
        if self.tied:
            return (last @ params["embed"].T).astype(jnp.float32)
        return self._mm(last, params["lm_head"]).astype(jnp.float32)

    def _embed_extra(self, params, positions):
        """Additive embedding term (learned positions in the gpt2/opt
        trunk); rope families add nothing here (``None``: no term)."""
        return jnp.zeros((), self.cfg.compute_dtype)

    def _embed_lookup(self, table, tokens):
        """Embedding lookup. Under TP with tied embeddings the table is
        vocab-row-sharded: mask out-of-range ids locally and psum (the
        reference's vocab-parallel embedding)."""
        if self.tp > 1 and self.tied:
            vshard = table.shape[0]
            vstart = jax.lax.axis_index(TENSOR_AXIS) * vshard
            rel = tokens - vstart
            ok = (rel >= 0) & (rel < vshard)
            x = table[jnp.clip(rel, 0, vshard - 1)]
            x = jnp.where(ok[..., None], x, 0)
            x = jax.lax.psum(x, TENSOR_AXIS)
        else:
            x = table[tokens]
        return x.astype(self.cfg.compute_dtype)

    def _count_kv_write(self, T, positions, layers=None):
        """One dispatch whose lanes carry ``T`` positions wrote
        ``positions`` of them (the lanes' ``t_len``, summed) into
        ``layers`` layers of both pools (default: every layer that has
        them)."""
        path = "run" if T > 1 else "row"
        if layers is None:
            layers = self.n_latent_layers
        self.kv_write_stats[path + "_dispatches"] += 1
        self.kv_write_stats[path + "_rows"] += \
            int(positions) * 2 * self.cfg.n_kv_head * layers

    def _enqueue(self, program, pools, tokens, start, tables, t_len,
                 slots=None):
        """One dispatch of ``program`` (a :meth:`_lane_program`) over
        ``pools``: the lanes packed into one host array and handed to the
        jitted call as they are, which makes the one transfer itself (a
        ``jnp.asarray`` in front would be ``device_put``'s Python on top
        of it). Counted in ``dispatch_stats``, ``kv_write_stats`` and
        ``paged_walk_stats``."""
        self._count_lanes(tokens, start, tables, t_len)
        return self._hand_over(program, pools, pack_lanes(
            tokens, start, tables, t_len, slots))

    def _count_lanes(self, tokens, start, tables, t_len, *_):
        """One lane group of a dispatch in ``kv_write_stats`` and
        ``paged_walk_stats``."""
        self._count_kv_write(np.shape(tokens)[1], np.sum(t_len))
        walk = self.paged_walk_stats
        walk["dispatches"] += 1
        walk["table_slots"] += np.size(tables)
        # a padded lane starts at 0 with nothing: no block
        walk["blocks_walked"] += int(np.sum(
            -(-np.add(start, t_len) // self.block_size)))

    def _hand_over(self, program, pools, lanes):
        """``program`` over ``pools`` and the packed host array
        ``lanes``, counted in ``dispatch_stats``."""
        stats = self.dispatch_stats
        stats["dispatches"] += 1
        stats["h2d_arrays"] += 1
        stats["h2d_bytes"] += lanes.nbytes
        return program(self.params, *pools, lanes)

    def forward_chunk(self, cache, tokens, start, tables, t_len):
        ck, cv, logits, latents = self._enqueue(
            self._fwd, (cache.k, cache.v), tokens, start, tables, t_len)
        cache.replace(ck, cv)
        return logits, latents

    # -------------------------------------------------------------- #
    # A step's lane groups in one program
    # -------------------------------------------------------------- #
    def step_program(self, shapes):
        """The program over lane groups of ``shapes`` ``((B, T), ...)``:
        built once a tuple of shapes."""
        program = self._fwd_step_cache.get(shapes)
        if program is None:
            program = self._fwd_step_cache[shapes] = \
                self._chunk_program(shapes)
        return program

    def _enqueue_step(self, pools, groups):
        """One dispatch of :meth:`step_program` over ``groups`` (each
        the lane arrays of :meth:`forward_chunk`), packed into one host
        array (``ragged/lanes.py pack_step``)."""
        program = self.step_program(tuple(
            np.shape(group[0]) for group in groups))
        lanes = pack_step(groups)
        for group in groups:
            if np.any(group[3]):    # a group of blank lanes wrote nothing
                self._count_lanes(*group)
        return self._hand_over(program, pools, lanes)

    def forward_step(self, cache, *groups):
        """:meth:`forward_chunk` over several lane groups in one
        program. Returns ``(logits [lanes of all groups, V], [latents
        [L, B, T, H] a group])``."""
        ck, cv, logits, *latents = self._enqueue_step(
            (cache.k, cache.v), groups)
        cache.replace(ck, cv)
        return logits, latents

    def _tail_forward(self, tail: int, latents: bool):
        """``_forward_chunk_tail`` (``_forward_chunk_tail_lat`` with
        ``latents``) at one ``tail``, a trace constant, over separate
        lane arrays."""
        fwd = self._forward_chunk_tail_lat if latents \
            else self._forward_chunk_tail

        def fwd_tail(params, ck, cv, tokens, start, tables, t_len):
            return fwd(params, ck, cv, tokens, start, tables, t_len, tail)
        return fwd_tail

    def _fwd_tail_for(self, tail: int):
        """Per-``tail`` compiled verification forward (tail is a trace
        constant: one program per (tail, batch-bucket, T-pad) triple,
        all reused across a generation)."""
        fn = self._fwd_tail_cache.get(tail)
        if fn is None:
            fn = self._fwd_tail_cache[tail] = self._lane_program(
                self._tail_forward(tail, latents=False), 1)
        return fn

    def forward_chunk_tail(self, cache, tokens, start, tables, t_len,
                           tail: int):
        """Verification forward: head logits for the last ``tail``
        positions of each lane (speculative decoding)."""
        ck, cv, logits = self._enqueue(
            self._fwd_tail_for(tail), (cache.k, cache.v), tokens, start,
            tables, t_len)
        cache.replace(ck, cv)
        return logits

    def _fwd_tail_lat_for(self, tail: int):
        """Latent-capturing sibling of :meth:`_fwd_tail_for` (its own
        program cache — the exact-KV tail forward never retraces when
        a latent engine shares the process)."""
        fn = self._fwd_tail_lat_cache.get(tail)
        if fn is None:
            fn = self._fwd_tail_lat_cache[tail] = self._lane_program(
                self._tail_forward(tail, latents=True), 2)
        return fn

    def forward_chunk_tail_lat(self, cache, tokens, start, tables,
                               t_len, tail: int):
        """Verification forward that also captures latents: the
        speculative verify step under latent preemption. Returns
        ``(logits [B, tail, V], latents [L, B, T, H])`` — latent
        columns align with ``tokens`` columns (left-aligned feeds), so
        a lane's accepted span is ``latents[:, j, :acc+1]``."""
        ck, cv, logits, latents = self._enqueue(
            self._fwd_tail_lat_for(tail), (cache.k, cache.v), tokens,
            start, tables, t_len)
        cache.replace(ck, cv)
        return logits, latents

    # -------------------------------------------------------------- #
    # HCache restore (the fork's flagship delta)
    # -------------------------------------------------------------- #
    def _restore_layer(self, params, cache_k, cache_v, layer, latent,
                       start, tables, t_len):
        """Replay K/V projection + RoPE + blocked cache write for ONE layer
        from saved latents (reference: llama_v2/model.py:222-252 +
        dense_blocked_attention.py:182 — QKV GEMM + kv-rotary cache write,
        no attention, no MLP). The full cache is donated, so each dispatch
        updates layer ``layer`` in place; the layer's weights are sliced
        from the stacked tree *inside* the compiled program (no per-call
        host-side slicing)."""
        from ..ops.quantizer import dequantize_tree
        # slice THEN dequantize: batched QuantizedTensors slice their
        # leading dim through tree.map, so only this layer's weights are
        # ever materialized full-precision
        lp = jax.tree.map(lambda p: p[layer], params["layers"])
        lp = dequantize_tree(lp)
        lanes = self._restore_lanes(latent, start, tables, t_len,
                                    cache_k.shape[2])
        _, k, v = self._qkv(lp, latent.astype(self.cfg.compute_dtype),
                            lanes.positions)
        return self._scatter_kv(cache_k, cache_v, layer, k, v, lanes)

    def _restore_lanes(self, latent, start, tables, t_len, pool_slots):
        """The lanes whose saved ``latent`` [B, T, ...] a restore
        replays, as a layer's write takes them."""
        lanes = Lanes.of((latent, start, tables, t_len))
        lanes.place_positions()
        lanes.place_slots(self.block_size, pool_slots)
        return lanes

    # -------------------------------------------------------------- #
    # Fused decode loop: N greedy steps in ONE device program
    # -------------------------------------------------------------- #
    @staticmethod
    def _sample_logits(logits, key, temperature, top_p, greedy, top_k,
                       use_top_p):
        """On-device sampling — the device-side mirror of the host
        sampler (``engine_v2._sample_host``). ``greedy``/``top_k``/
        ``use_top_p`` are static (they shape the program); ``temperature``
        and ``top_p`` are traced scalars so per-request values don't
        recompile the decode stretch."""
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        l = logits.astype(jnp.float32) / temperature
        k = min(top_k, l.shape[-1])
        if k > 0:
            kth = jax.lax.top_k(l, k)[0][..., -1:]
            l = jnp.where(l < kth, -jnp.inf, l)
        if use_top_p:
            # nucleus: keep the smallest prob-sorted set with mass>=top_p
            # (count-based keep scattered back through the sort order —
            # a probability threshold would keep every boundary TIE and
            # diverge from the host sampler)
            p = jax.nn.softmax(l, axis=-1)
            order = jnp.argsort(p, axis=-1,
                                descending=True)            # [B, V]
            sp = jnp.take_along_axis(p, order, axis=-1)
            keep_sorted = (jnp.cumsum(sp, axis=-1) - sp) < top_p
            rows = jnp.arange(l.shape[0])[:, None]
            keep = jnp.zeros(l.shape, bool).at[rows, order].set(
                keep_sorted)
            l = jnp.where(keep, l, -jnp.inf)
        return jax.random.categorical(key, l, axis=-1).astype(jnp.int32)

    def _step_sample(self, params, ck, cv, toks, pos, tables, t_step, key,
                     temperature, top_p, greedy, top_k, use_top_p,
                     want_logprobs):
        """One decode forward + sample; shared by the scan and
        while_loop bodies. Returns (ck, cv, nxt, latents, lp)."""
        ck, cv, logits, latents = self._fwd_inner(
            params, ck, cv, toks[:, None], pos, tables, t_step)
        nxt = self._sample_logits(logits, key, temperature, top_p,
                                  greedy, top_k, use_top_p)
        lp = None
        if want_logprobs:
            # raw-model logprob of the chosen token (RLHF consumers)
            lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            lp = jnp.take_along_axis(lsm, nxt[:, None], axis=-1)[:, 0]
        return ck, cv, nxt, latents, lp

    def _decode_loop(self, params, cache_k, cache_v, tokens, start, tables,
                     t_len, rng_key, temperature, top_p, eos_id, n_steps,
                     greedy, top_k, use_top_p, want_logprobs, has_eos):
        """``n_steps`` single-token forwards with the sampled token fed
        back on device — no host round-trip per generated token. The
        reference's engine (like every GPU serving stack) pays a host
        sync per step to route the next batch; on TPU the idiomatic
        serving shape compiles the whole decode stretch so the chip
        never waits on the host.

        Without an EOS the stretch is a ``lax.scan`` (static trip count
        — XLA pipelines it best). With ``has_eos`` it becomes a
        ``lax.while_loop`` that exits once every live lane has sampled
        ``eos_id``: lanes that finish stop feeding (their ``t_len``
        drops to 0 — no cache writes) and a batch whose generations all
        end early doesn't pay for the remaining steps.

        tokens: [B] the first token each lane feeds; start: [B] its
        position; t_len: [B] 1 for live lanes, 0 for padded lanes (their
        writes drop, their outputs are discarded). greedy/top_k/
        use_top_p/want_logprobs/has_eos are static; temperature/top_p/
        eos_id traced. Returns (cache_k', cache_v', tokens_out
        [n_steps, B], latents [n_steps, L, B, 1, H], logprobs
        [n_steps, B] or None when want_logprobs is off); with has_eos,
        rows past a lane's EOS (and past the early exit) are zeros —
        the engine truncates at EOS host-side."""
        if not has_eos:
            def step(carry, _):
                ck, cv, toks, pos, key = carry
                key, sub = jax.random.split(key)
                ck, cv, nxt, latents, lp = self._step_sample(
                    params, ck, cv, toks, pos, tables, t_len, sub,
                    temperature, top_p, greedy, top_k, use_top_p,
                    want_logprobs)
                ys = (nxt, latents) + ((lp,) if want_logprobs else ())
                return (ck, cv, nxt, pos + t_len, key), ys

            (cache_k, cache_v, _, _, _), ys = jax.lax.scan(
                step, (cache_k, cache_v, tokens, start, rng_key), None,
                length=n_steps)
            toks, lats = ys[0], ys[1]
            lps = ys[2] if want_logprobs else None
            return cache_k, cache_v, toks, lats, lps

        B = tokens.shape[0]
        lat_shape = jax.eval_shape(
            lambda p, k, v: self._fwd_inner(p, k, v, tokens[:, None],
                                            start, tables, t_len)[3],
            params, cache_k, cache_v)
        toks_buf = jnp.zeros((n_steps, B), jnp.int32)
        lat_buf = jnp.zeros((n_steps,) + lat_shape.shape, lat_shape.dtype)
        lp_buf = jnp.zeros((n_steps, B), jnp.float32) if want_logprobs \
            else jnp.zeros((0,), jnp.float32)
        done0 = t_len == 0   # padded lanes never block the early exit

        def cond(st):
            return (st[0] < n_steps) & jnp.logical_not(jnp.all(st[7]))

        def body(st):
            (i, ck, cv, toks, pos, key, t_buf, done, l_buf, p_buf) = st
            t_step = jnp.where(done, 0, t_len)
            key, sub = jax.random.split(key)
            ck, cv, nxt, latents, lp = self._step_sample(
                params, ck, cv, toks, pos, tables, t_step, sub,
                temperature, top_p, greedy, top_k, use_top_p,
                want_logprobs)
            t_buf = t_buf.at[i].set(jnp.where(done, 0, nxt))
            l_buf = l_buf.at[i].set(latents)
            if want_logprobs:
                p_buf = p_buf.at[i].set(jnp.where(done, 0.0, lp))
            done = done | (nxt == eos_id)
            return (i + 1, ck, cv, nxt, pos + t_step, key, t_buf, done,
                    l_buf, p_buf)

        st = (jnp.int32(0), cache_k, cache_v, tokens, start, rng_key,
              toks_buf, done0, lat_buf, lp_buf)
        st = jax.lax.while_loop(cond, body, st)
        _, cache_k, cache_v, _, _, _, toks, _, lats, lps = st
        return cache_k, cache_v, toks, lats, \
            (lps if want_logprobs else None)

    def _fwd_tail_inner_for(self, tail: int):
        """Un-jitted (TP-wrapped when tp>1) tail forward for use INSIDE
        other compiled loops (the fused speculative decoder)."""
        fn = self._fwd_tail_inner_cache.get(tail)
        if fn is None:
            fn = self._fwd_tail_inner_cache[tail] = self._manual_tp(
                self._tail_forward(tail, latents=False), 4, 1)
        return fn

    def _lookup_decode_loop(self, params, cache_k, cache_v, first_tok,
                            pos0, tables, live, hist0, hist_len0,
                            eos_id, max_new, ngram, max_draft, window,
                            has_eos):
        """Fused prompt-lookup speculative decoding: draft, verify,
        accept and roll back entirely on device inside one
        ``lax.while_loop`` — the host syncs once per *generation*, and
        each loop iteration can emit up to ``max_draft + 1`` tokens.

        Drafting is a vectorized n-gram match over a right-aligned
        rolling window of each lane's recent tokens; a bad draft only
        costs speed — acceptance compares drafts against the verified
        greedy targets, so output is bit-identical to token-by-token
        greedy decode regardless of what the draft proposes. Rejected
        draft KV stays past the lane's position cursor and is
        overwritten by the next iteration's writes (the same rollback
        arithmetic as the host-driven :meth:`generate_lookup` path,
        moved into the carry).

        first_tok/pos0/live: [B]; hist0: [B, window] right-aligned
        recent tokens; hist_len0: [B] valid counts. eos_id traced;
        max_new/ngram/max_draft/window/has_eos static. Returns
        (cache_k', cache_v', outs [B, max_new], out_len [B], iters,
        accepted [B], lane_iters [B]) — accepted and lane_iters ride
        the loop carry PER LANE, so serving can attribute acceptance
        per request instead of batch-averaging (the old scalar total
        is their sum; the old ``drafted`` upper bound is
        ``lane_iters * max_draft`` per lane)."""
        B = first_tok.shape[0]
        T = 1 + max_draft
        W = window
        fwd_tail = self._fwd_tail_inner_for(T)
        win_idx = jnp.arange(W - ngram)[:, None] + \
            jnp.arange(ngram)[None, :]              # [W-ngram, ngram]
        rows = jnp.arange(B)

        def draft(hist, hist_len, last_tok):
            key = hist[:, W - ngram:]                        # [B, ngram]
            wins = hist[:, win_idx]                  # [B, W-ngram, ngram]
            starts = jnp.arange(W - ngram)[None, :]
            valid = starts >= (W - hist_len)[:, None]        # in-window
            hit = (wins == key[:, None, :]).all(-1) & valid  # [B, W-ngram]
            any_hit = hit.any(axis=1)
            # most recent match wins
            i_star = jnp.max(jnp.where(hit, starts, -1), axis=1)
            src = jnp.clip(i_star + ngram, 0, W - 1)
            cols = jnp.clip(src[:, None] + jnp.arange(max_draft)[None, :],
                            0, W - 1)
            cand = hist[rows[:, None], cols]              # [B, max_draft]
            # no match: propose repeats of the last token (cheap; only
            # accepted if it IS the greedy continuation)
            return jnp.where(any_hit[:, None], cand,
                             last_tok[:, None].astype(hist.dtype))

        # +1 trash column: masked-out scatter lanes write there instead
        # of clipping onto a real slot (duplicate scatter indices have
        # last-write-wins semantics and would clobber the real token)
        outs0 = jnp.zeros((B, max_new + 1), jnp.int32)
        done0 = jnp.logical_not(live)

        def cond(st):
            i, done = st[0], st[7]
            return (i < max_new) & jnp.logical_not(jnp.all(done))

        def body(st):
            (i, ck, cv, last_tok, pos, hist, hist_len, done, outs,
             out_len, accepted, lane_iters) = st
            d = draft(hist, hist_len, last_tok)              # [B, k]
            toks = jnp.concatenate([last_tok[:, None], d], axis=1)
            t_step = jnp.where(done, 0, T)
            ck, cv, logits = fwd_tail(params, ck, cv, toks, pos, tables,
                                      t_step)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # leading drafts matching their verified targets
            match = d == greedy[:, :max_draft]
            acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                          axis=1)                             # [B]
            remaining = jnp.maximum(max_new - out_len, 0)
            c = jnp.minimum(acc + 1, remaining)               # emit count
            if has_eos:
                emit_mask = jnp.arange(T)[None, :] < c[:, None]
                is_eos = (greedy == eos_id) & emit_mask
                eos_pos = jnp.argmax(is_eos, axis=1)
                c = jnp.where(is_eos.any(axis=1),
                              jnp.minimum(c, eos_pos + 1), c)
            c = jnp.where(done, 0, c)
            # scatter greedy[:, :c] into outs at out_len; masked lanes
            # target the trash column (in-range cols are unique: off < c
            # implies out_len + off <= max_new - 1)
            mask = jnp.arange(T)[None, :] < c[:, None]
            col = jnp.where(mask,
                            out_len[:, None] + jnp.arange(T)[None, :],
                            max_new)
            outs = outs.at[rows[:, None], col].set(greedy)
            # roll the history window left by c and append the emitted
            ext = jnp.concatenate([hist, greedy], axis=1)   # [B, W+T]
            idx = jnp.arange(W)[None, :] + c[:, None]
            hist = ext[rows[:, None], idx]
            hist_len = jnp.minimum(hist_len + c, W)
            out_len = out_len + c
            new_done = done | (out_len >= max_new)
            if has_eos:
                new_done = new_done | (
                    (c > 0) & (jnp.take_along_axis(
                        outs, jnp.maximum(out_len - 1, 0)[:, None],
                        axis=1)[:, 0] == eos_id))
            # cached-valid tokens this round = c (fed token + c-1
            # accepted drafts); the last emitted token is the uncached
            # bonus fed next round
            pos = pos + jnp.where(done, 0, c)
            last_tok = jnp.take_along_axis(
                outs, jnp.maximum(out_len - 1, 0)[:, None], axis=1)[:, 0]
            # per-lane carries: accepted drafts and live iterations —
            # the serving attribution the batch-scalar version lost
            accepted = accepted + jnp.where(done, 0,
                                            jnp.maximum(c - 1, 0))
            lane_iters = lane_iters + jnp.where(done, 0, 1)
            return (i + 1, ck, cv, last_tok, pos, hist, hist_len,
                    new_done, outs, out_len, accepted, lane_iters)

        st = (jnp.int32(0), cache_k, cache_v, first_tok, pos0, hist0,
              hist_len0, done0, outs0, jnp.zeros((B,), jnp.int32),
              jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32))
        st = jax.lax.while_loop(cond, body, st)
        (iters, cache_k, cache_v, _, _, _, _, _, outs, out_len,
         accepted, lane_iters) = st
        return cache_k, cache_v, outs[:, :max_new], out_len, iters, \
            accepted, lane_iters

    def lookup_decode_loop(self, cache, first_tok, pos, tables, live,
                           hist, hist_len, *, max_new, ngram, max_draft,
                           window, eos_token_id=None):
        """Public fused speculative decoder (see _lookup_decode_loop).
        Returns ``(outs, out_len, iters, accepted, lane_iters)`` with
        ``accepted`` and ``lane_iters`` PER LANE ([B] int arrays)."""
        has_eos = eos_token_id is not None
        eos = jnp.int32(eos_token_id if has_eos else -1)
        (ck, cv, outs, out_len, iters, accepted,
         lane_iters) = self._lookup_loop_jit(
            self.params, cache.k, cache.v,
            jnp.asarray(first_tok, jnp.int32),
            jnp.asarray(pos, jnp.int32),
            jnp.asarray(tables, jnp.int32),
            jnp.asarray(live, bool),
            jnp.asarray(hist, jnp.int32),
            jnp.asarray(hist_len, jnp.int32),
            eos, max_new, ngram, max_draft, window, has_eos)
        cache.replace(ck, cv)
        lane_iters = np.asarray(lane_iters)
        # every iteration of a lane verifies a draft of max_draft + 1
        self._count_kv_write(max_draft + 1,
                             lane_iters.sum() * (max_draft + 1))
        return (np.asarray(outs), np.asarray(out_len), int(iters),
                np.asarray(accepted), lane_iters)

    def decode_loop(self, cache, tokens, start, t_len, tables, n_steps,
                    temperature=0.0, top_k=0, top_p=1.0, seed=0,
                    want_logprobs=False, eos_token_id=None):
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        ck, cv, toks, lats, lps = self._decode_loop_jit(
            self.params, cache.k, cache.v, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(start, jnp.int32), jnp.asarray(tables, jnp.int32),
            jnp.asarray(t_len, jnp.int32), jax.random.PRNGKey(seed),
            jnp.float32(max(temperature, 1e-6)), jnp.float32(top_p),
            jnp.int32(eos_token_id if eos_token_id is not None else -1),
            int(n_steps), temperature <= 0, int(top_k), top_p < 1.0,
            bool(want_logprobs), eos_token_id is not None)
        cache.replace(ck, cv)
        # the steps asked for; lanes that met EOS early stopped writing
        self._count_kv_write(1, int(n_steps) * np.count_nonzero(t_len))
        return toks, lats, lps     # on the device: the caller fetches

    def _restore_chunk(self, params, cache_k, cache_v, layer0, lat_chunk,
                       start, tables, t_len):
        """Replay layers ``layer0 .. layer0+C`` from one latent slab
        ``[C, B, T, H]`` in a single dispatch (C is static — set by the
        engine's chunking policy)."""
        def body(i, kv):
            ck, cv = kv
            return self._restore_layer(params, ck, cv, layer0 + i,
                                       lat_chunk[i], start, tables, t_len)
        return jax.lax.fori_loop(0, lat_chunk.shape[0], body,
                                 (cache_k, cache_v))

    def restore_pipeline(self, cache, latents, start, tables, t_len,
                         progress_cb=None) -> "RestorePipeline":
        """Incremental chunk-at-a-time restore of one staged lane group
        — the unit the serving scheduler interleaves with resident
        decode (see :class:`RestorePipeline`)."""
        return RestorePipeline(self, cache, latents, start, tables,
                               t_len, progress_cb=progress_cb)

    def restore_kv(self, cache, latents, start, tables, t_len,
                   progress_cb=None):
        """latents: host array [L, B, T, H] (numpy). Layer-CHUNKED
        dispatches with the next chunk's host→HBM copy issued before this
        chunk's compute — JAX's async dispatch gives the reference's
        dual-stream overlap (io_stream copy / compute wait-event chain,
        llama_v2/model.py:229) at chunk granularity. The reference's
        literal one-dispatch-per-layer shape is latency-bound on a slow
        host link, while one whole-stack dispatch can't overlap H2D with
        compute and needs the full latent slab in HBM (million-token
        contexts: tens of GB); the chunk size interpolates
        (``hcache.restore_chunk_layers`` / ``restore_chunk_bytes``).

        ``progress_cb(layer0, shipped_bytes)`` fires as each chunk's
        dispatch is ISSUED (still in flight) — the serving scheduler's
        staging-progress hook; ``shipped_bytes`` is 0 on the
        already-staged (HBM-resident) path.

        This is the run-to-completion driver over
        :class:`RestorePipeline`; the serving scheduler instead holds
        the pipeline open and advances it chunk by chunk between decode
        dispatches (``engine.begin_restore``/``advance_restores``)."""
        pipe = self.restore_pipeline(cache, latents, start, tables,
                                     t_len, progress_cb=progress_cb)
        while not pipe.done:
            pipe.advance()


class RestorePipeline:
    """One lane group's restore as a chunk pipeline with two lanes:

    * **ship lane** — ``jax.device_put`` of the next layer-chunk's
      latent slab, dispatched (async) ahead of the replay that will
      consume it, at most ``depth`` chunks in flight (bounds staging
      HBM; depth 2 is the classic double buffer);
    * **replay lane** — the jitted QKV-replay dispatch consuming the
      previously shipped chunk.

    ``advance(max_chunks)`` issues up to ``max_chunks`` replay
    dispatches (shipping ahead as it goes) and returns immediately —
    nothing here ever blocks on the device, so the caller can issue a
    resident-decode dispatch between advances and the link ship hides
    under that decode's compute (the reference's dedicated
    ``io_stream`` vs compute-stream overlap, ``engine_v2.py:108-129``,
    expressed through JAX async dispatch). The cache object is re-read
    at every advance and replaced after, so interleaved forwards
    (which donate and replace the same buffers) compose with an open
    pipeline; interleaved dispatches only read OTHER sequences' blocks,
    so results are bit-identical to a sequential restore-then-decode.
    """

    def __init__(self, model, cache, latents, start, tables, t_len,
                 progress_cb=None, depth: int = 2):
        self.model = model
        self.cache = cache
        self.progress_cb = progress_cb
        self.depth = max(1, depth)
        self._start = jnp.asarray(start, jnp.int32)
        self._tables = jnp.asarray(tables, jnp.int32)
        self._t_len = jnp.asarray(t_len, jnp.int32)
        self._positions = int(np.sum(t_len))
        self.staged = isinstance(latents, jax.Array)
        L = model.n_latent_layers
        C = model.restore_chunk_layers
        if C <= 0:
            per_layer = (int(np.prod(latents.shape[1:])) *
                         np.dtype(latents.dtype).itemsize)
            C = max(1, min(L, model.restore_chunk_bytes //
                           max(per_layer, 1)))
        self.chunk_layers = C
        self.bounds = list(range(0, L, C))
        self._next_replay = 0
        self._bufs = {}                 # chunk index -> shipped buffer
        # target placement: latents replicate over whatever mesh the
        # cache actually lives on (derived from the array, not the TP
        # degree: a hybrid engine hands over caches resident on the
        # TRAINING mesh, which can be multi-device even when the
        # serving tensor axis is 1)
        from jax.sharding import NamedSharding, PartitionSpec
        ck = cache.k
        if isinstance(ck.sharding, NamedSharding):
            self._dev = NamedSharding(ck.sharding.mesh, PartitionSpec())
        else:
            self._dev = list(ck.devices())[0]
        if self.staged:
            # already HBM-resident (hybrid-engine handoff, marginal
            # bench): chunked dispatches slice the slab on device. It
            # must still land on the CACHE's device assembly (a sharded
            # cache with a single-device slab fails the jitted call)
            if isinstance(ck.sharding, NamedSharding):
                if latents.sharding != self._dev:
                    latents = jax.device_put(latents, self._dev)
            elif latents.devices() != ck.devices():
                latents = jax.device_put(latents, list(ck.devices())[0])
            self.latents = latents
        else:
            self.latents = np.asarray(latents)

    # ------------------------------------------------------------- #
    @property
    def chunks_total(self) -> int:
        return len(self.bounds)

    @property
    def chunks_issued(self) -> int:
        return self._next_replay

    @property
    def done(self) -> bool:
        return self._next_replay >= len(self.bounds)

    # ------------------------------------------------------------- #
    def _ship(self, i):
        from ..resilience.faults import get_injector
        _inj = get_injector()
        if _inj.enabled:
            # before the H2D issue: a faulted ship is re-issuable
            _inj.fire("restore.ship", chunk=i)
        l0 = self.bounds[i]
        sl = self.latents[l0:l0 + self.chunk_layers]
        if self.staged:
            return sl                     # device slice, no transfer
        # the lane slab is layer-major contiguous (built by
        # _stage_restore_group / HostLatentStore), so this is a
        # straight block copy, not a gather
        with get_tracer().span("restore.ship", layer0=l0,
                               layers=sl.shape[0], bytes=sl.nbytes):
            return jax.device_put(np.ascontiguousarray(sl), self._dev)

    def prefetch(self) -> int:
        """Ship ahead: issue H2D for the next unshipped chunks up to
        the in-flight ``depth``. Returns chunks whose ship was issued.
        Call this as soon as the lane opens so the first chunk's link
        time hides under whatever the engine dispatches next."""
        issued = 0
        i = self._next_replay
        while i < len(self.bounds) and \
                len(self._bufs) < self.depth:
            if i not in self._bufs:
                self._bufs[i] = self._ship(i)
                issued += 1
            i += 1
        return issued

    def advance(self, max_chunks: int = 0) -> int:
        """Issue up to ``max_chunks`` replay dispatches (0 = all
        remaining), shipping the following chunk ahead of each replay.
        Async end to end — returns the number of replays issued."""
        from ..resilience.faults import get_injector
        tracer = get_tracer()
        _inj = get_injector()
        issued = 0
        L = self.model.n_latent_layers
        while not self.done and (max_chunks <= 0 or
                                 issued < max_chunks):
            i = self._next_replay
            l0 = self.bounds[i]
            if _inj.enabled:
                # before the cursor moves or the buffer is consumed —
                # a faulted replay retries from the same chunk
                _inj.fire("restore.replay", chunk=i, layer0=l0)
            cur = self._bufs.pop(i, None)
            nbytes = 0 if self.staged else int(
                np.prod(self.latents[l0:l0 + self.chunk_layers].shape)
                * np.dtype(self.latents.dtype).itemsize)
            # span covers ship-issue + dispatch-issue for this chunk
            # (both async — the host-side staging cost the restore
            # latency story attributes per layer chunk)
            with tracer.span("serve.restore.stage", layer0=l0,
                             layers=min(self.chunk_layers, L - l0),
                             bytes=nbytes):
                if cur is None:
                    cur = self._ship(i)
                self._next_replay = i + 1
                with tracer.span("restore.replay", layer0=l0,
                                 layers=min(self.chunk_layers, L - l0),
                                 bytes=nbytes):
                    self.model._count_kv_write(
                        self.latents.shape[2], self._positions,
                        min(self.chunk_layers, L - l0))
                    ck, cv = self.model._restore(
                        self.model.params, self.cache.k, self.cache.v,
                        jnp.int32(l0), cur, self._start, self._tables,
                        self._t_len)
                    self.cache.replace(ck, cv)
                # dual-lane: the NEXT chunks' H2D ships issue right
                # behind this (async) replay dispatch and ride the link
                # under it. Ordered after the replay so a faulted ship
                # can never strand a half-advanced cursor — every
                # injected fault lands either before this chunk mutated
                # anything or after it fully replayed (retry-safe).
                self.prefetch()
            if self.progress_cb is not None:
                self.progress_cb(l0, nbytes)
            issued += 1
        return issued
