"""Ragged-batching inference engine with HCache KV restoration.

Reference analog: ``deepspeed/inference/v2/engine_v2.py:30
InferenceEngineV2`` — ``put`` (:131), ``can_schedule``/``query``
(:191-264), ``flush`` (:275), ``serialize`` (:284) and the fork's
``restore_kv`` (:108-129).

TPU-native scheduling: a ``put`` batch is routed into at most one batched
decode dispatch (all single-token sequences together — the ragged decode
batch) plus one bucketed prefill dispatch per multi-token sequence; each
(batch, tokens) bucket shape compiles once and is cached by XLA. The
reference's atom-builder/CUDA-graph machinery dissolves into those static
buckets.
"""

import functools
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..resilience.faults import get_injector
from ..telemetry.tracer import get_tracer, traced
from ..utils.compile_cache import ensure_compile_cache
from ..utils.logging import log_dist
from .config import RaggedInferenceEngineConfig
from .model import PagedInferenceModel
from .ragged.kv_cache import (BlockedKVCache, HybridCache, StateManager,
                              WindowedKVCache, window_of)
from .ragged.latents import HostLink, LatentProgram, PendingLatents
from .scheduling import (BlockChoice, BlockPass, SchedulingError,
                         SchedulingResult)


@dataclass
class RestoreTicket:
    """Handle for one ``begin_restore`` batch: ``done`` flips when
    every lane the batch staged has issued its last replay chunk (the
    sequences are then decodable)."""
    uids: List[int] = field(default_factory=list)
    pending: int = 0          # lanes still open
    done: bool = False


@dataclass
class _RestoreLane:
    """One bucket group's open restore pipeline + the state ops owed
    at completion."""
    pipe: object
    seqs: List[object]
    uids: List[int]
    ticket: RestoreTicket


class DiffusionUnsupported(NotImplementedError):
    """A feature that assumes causal decoding, one token a sequence a
    step, asked of a model that generates by diffusion over blocks."""


def _bucket(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _token_count(seqs) -> int:
    """Tokens in the sequences ``seqs``: a span attribute, so only
    computed while the tracer is on."""
    return int(sum(len(t) for t in seqs))


def _nbytes(*arrays) -> int:
    """Bytes of the arrays that are not ``None`` (a span attribute)."""
    return int(sum(a.nbytes for a in arrays if a is not None))


def _logsumexp_rows(logits):
    """Row-wise logsumexp, keepdims (fp64 host math for the first-token
    logprob — the decode-loop tokens get theirs on device)."""
    x = logits.astype(np.float64)
    m = x.max(axis=-1, keepdims=True)
    return m + np.log(np.exp(x - m).sum(axis=-1, keepdims=True))


def _sample_host(row, rng, temperature, top_k, top_p):
    """Host-side token sampler (greedy / temperature / top-k / nucleus) —
    shared by generate()'s step loop and generate_fused()'s first token."""
    if temperature <= 0:
        return int(np.argmax(row))
    logits = row.astype(np.float64) / temperature
    k = min(top_k, len(logits))
    if k > 0:
        kth = np.partition(logits, -k)[-k]
        logits = np.where(logits < kth, -np.inf, logits)
    p = np.exp(logits - logits.max())
    p /= p.sum()
    if top_p < 1.0:
        # nucleus: smallest prob-sorted set with mass >= top_p
        order = np.argsort(p)[::-1]
        keep_sorted = np.cumsum(p[order]) - p[order] < top_p
        keep = np.zeros_like(p, dtype=bool)
        keep[order] = keep_sorted
        p = np.where(keep, p, 0.0)
        p /= p.sum()
    return int(rng.choice(len(p), p=p))


class InferenceEngineV2:

    #: latents still on the device, on their way to the host, may hold
    #: as much device memory as this many of the largest program seen:
    #: the one whose copy is on the link and the one behind it. Past
    #: that the oldest are waited for (``serve.latents.force``), which
    #: is what every dispatch did before landing was deferred.
    _PENDING_PROGRAMS = 2

    def __init__(self, model_config, params,
                 config: RaggedInferenceEngineConfig = None,
                 topology=None):
        """``topology``: a MeshTopology with a ``tensor`` axis enables
        tensor-parallel serving — sharded heads/KV blocks, per-layer
        allreduce (reference: TP sharding throughout the v2 model
        implementations, llama_v2/model.py:160,169)."""
        ensure_compile_cache()
        self.config = config or RaggedInferenceEngineConfig()
        self.topology = topology
        sm_cfg = self.config.state_manager
        kv_cfg = self.config.kv_cache

        self.block_size = kv_cfg.block_size
        self.max_context = min(sm_cfg.max_context,
                               model_config.max_positions)
        self.max_blocks_per_seq = -(-self.max_context // self.block_size)

        num_blocks = kv_cfg.num_blocks
        if num_blocks is None:
            # reserve mode, capped at what tracked sequences can ever use
            cap = sm_cfg.max_tracked_sequences * self.max_blocks_per_seq + 1
            num_blocks = min(self._size_cache_blocks(model_config, kv_cfg),
                             cap)
        self._model_config = model_config
        #: a trunk with window layers beside global ones keeps two block
        #: pools with two block lifetimes (``ragged/kv_cache.py
        #: WindowedKVCache``); read off its shape. 0: one pool
        self.window = window_of(model_config)
        from ..models.olmo_hybrid import OlmoHybridConfig
        #: a trunk with recurrent layers keeps a state slot a sequence
        #: beside its KV blocks (``ragged/kv_cache.py HybridCache``)
        self.recurrent = isinstance(model_config, OlmoHybridConfig)

        #: positions of a generation block (a model that generates by
        #: diffusion over blocks, ``put``'s ``blocks``); 1: causal
        self.block_len = getattr(model_config, "diffusion_block_length", 1)
        self.diffusion = self.block_len > 1
        #: the token an open block's undecided positions hold
        self.mask_token_id = getattr(model_config, "mask_token_id", None)
        if self.diffusion and (self.block_size % self.block_len or
                               sm_cfg.prefill_chunk % self.block_len):
            raise ValueError(
                f"a generation block of {self.block_len} positions must "
                f"divide kv_cache.block_size ({self.block_size}) and "
                f"state_manager.prefill_chunk ({sm_cfg.prefill_chunk}): "
                "a block never straddles two cache blocks or two slices")
        self._diffusion_stats = {"lane_passes": 0, "positions_fed": 0,
                                 "positions_masked": 0,
                                 "tokens_committed": 0}
        self._moe_stats = {"dispatches": 0, "picks": None, "touched": 0}
        #: the sequences whose routers' inputs :meth:`router_inputs`
        #: hands out (a check's probed requests; empty: nothing is kept)
        self.router_probe_uids = set()
        self._router_probes = {}

        num_window_blocks = 0
        if self.window:
            # what a sequence holds at most: the window, the slice in
            # flight and a block of misalignment
            most = -(-(self.window + (sm_cfg.prefill_chunk or
                                      sm_cfg.max_ragged_batch_size))
                     // self.block_size) + 1
            num_window_blocks = kv_cfg.num_window_blocks or min(
                num_blocks, sm_cfg.max_tracked_sequences * most + 1)
        self.state = StateManager(
            sm_cfg.max_tracked_sequences, num_blocks, self.block_size,
            self.max_context,
            state_slots=sm_cfg.max_tracked_sequences
            if self.recurrent else 0,
            window_blocks=num_window_blocks, window=self.window)
        # block 0 is reserved scratch: padded decode lanes write there
        self._scratch_block = self.state.allocator.allocate(1)[0]
        if self.window:
            self._window_scratch = \
                self.state.window_allocator.allocate(1)[0]

        self.prefix_caching = sm_cfg.prefix_caching
        if self.prefix_caching and self.recurrent:
            from .model_hybrid import refuse
            raise refuse("prefix_caching (shared prefixes)")
        if self.prefix_caching and self.window:
            from .model_window import refuse
            raise refuse(
                "prefix_caching (shared prefixes)",
                "a window layer's blocks behind the window, which have "
                "gone back to their allocator: a shared prefix longer "
                "than the window cannot be attached to them")
        if self.prefix_caching:
            self._refuse_diffusion(
                "prefix_caching (a shared block's K and V depend on the "
                "whole of the block that follows the shared prefix)")
        if self.prefix_caching and self.config.hcache.enable_latents:
            raise ValueError(
                "prefix_caching requires hcache.enable_latents=false: a "
                "shared prefix runs no forward, so its latents would be "
                "missing from the HCache restore contract")
        #: chained prefix index: (parent block id, this block's tokens)
        #: -> block id. KV content depends on the ENTIRE context, so the
        #: key must identify the full prefix — the parent block id does
        #: that transitively (a block is registered under exactly one
        #: chain, and a child entry keeps its parent alive through the
        #: owning sequence's refs), giving O(P) lookups instead of
        #: O(P^2) full-prefix tuples. _block_prefix is the reverse map
        #: for purge.
        self._prefix_index: Dict[tuple, int] = {}
        self._block_prefix: Dict[int, tuple] = {}
        #: parent block id -> chain keys registered under it (purge of a
        #: parent must drop its now-unreachable subtree)
        self._chain_children: Dict[int, set] = {}
        #: observability: prompts that attached >= 1 shared block, and
        #: prompt tokens whose prefill was skipped entirely
        self.prefix_stats = {"hits": 0, "shared_tokens": 0}
        #: bumped on every purge: sequences cache their chain-walk tip
        #: keyed on this epoch, so registration is O(new blocks) in the
        #: common case and only re-walks from the root after a purge
        self._index_epoch = 1

        from ..models.falcon import FalconConfig
        from ..models.gpt2 import GPT2Config
        from ..models.mixtral import MixtralConfig
        from ..models.opt import OPTConfig
        from ..models.phi import PhiConfig
        model_cls = PagedInferenceModel
        if self.window:
            from .model_window import PagedWindowModel
            model_cls = PagedWindowModel
        elif getattr(model_config, "kv_lora_rank", 0):
            # latent attention: a pool of compressed KV rows
            from .model_latent import PagedLatentModel
            model_cls = PagedLatentModel
        elif isinstance(model_config, GPT2Config):
            from .model_gpt2 import PagedGPT2Model
            model_cls = PagedGPT2Model
        elif isinstance(model_config, OPTConfig):
            from .model_opt import PagedOPTModel
            model_cls = PagedOPTModel
        elif isinstance(model_config, FalconConfig):
            from .model_falcon import PagedFalconModel
            model_cls = PagedFalconModel
        elif isinstance(model_config, PhiConfig):
            from .model_phi import PagedPhiModel
            model_cls = PagedPhiModel
        elif isinstance(model_config, MixtralConfig):
            from .model_moe import PagedMoEModel
            model_cls = PagedMoEModel
        elif self.recurrent:
            from .model_hybrid import PagedHybridModel
            model_cls = PagedHybridModel
        self.model = model_cls(
            model_config, params, block_size=self.block_size,
            max_blocks_per_seq=self.max_blocks_per_seq,
            capture_latents=self.config.hcache.enable_latents,
            restore_chunk_layers=self.config.hcache.restore_chunk_layers,
            restore_chunk_bytes=self.config.hcache.restore_chunk_bytes,
            latent_dtype=self.config.hcache.latent_dtype,
            topology=topology, quantization=self.config.quantization)
        self._check_paged_attention_fits(model_config)
        if self.recurrent:
            self.cache = HybridCache(
                self.model.n_latent_layers, num_blocks, self.block_size,
                model_config.n_kv_head, model_config.head_dim,
                n_linear_layers=model_config.n_layer
                - self.model.n_latent_layers,
                state_slots=self.state.state_slots,
                n_heads=model_config.linear_num_value_heads,
                key_dim=model_config.linear_key_head_dim,
                value_dim=model_config.linear_value_head_dim,
                conv_taps=model_config.linear_conv_kernel_dim,
                conv_channels=model_config.conv_channels,
                dtype=jnp.dtype(kv_cfg.cache_dtype))
        elif self.window:
            self.cache = WindowedKVCache(
                self.model.pool_layers["global"], num_blocks,
                self.model.pool_layers["window"], num_window_blocks,
                self.block_size, model_config.n_kv_head,
                model_config.head_dim, dtype=jnp.dtype(kv_cfg.cache_dtype))
        else:
            kv_heads, k_width, v_width = self.model.pool_layout()
            self.cache = BlockedKVCache(
                model_config.n_layer, num_blocks, self.block_size,
                kv_heads, k_width, v_head_dim=v_width,
                dtype=jnp.dtype(kv_cfg.cache_dtype),
                sharding=self.model.cache_sharding())
        #: recurrent-state evictions and returns (``snapshot_state``,
        #: ``begin_restore(states=...)``) and the bytes they moved
        self.state_stats = {"snapshots": 0, "restores": 0,
                            "bytes_out": 0, "bytes_in": 0}
        #: restore staging progress for the serving layer: cumulative
        #: counts of restore groups, sequences, per-chunk dispatches
        #: issued and latent bytes shipped host->device (a dispatch is
        #: counted when ISSUED, not when it lands — the serving
        #: scheduler overlaps the in-flight ship with resident decode)
        self.restore_stats = {"restores": 0, "sequences": 0,
                              "chunks_issued": 0, "bytes_shipped": 0}
        #: open decode-interleaved restore lanes (FIFO), advanced by
        #: advance_restores between the scheduler's decode dispatches
        self._restore_lanes: List[_RestoreLane] = []
        #: deferred latent landing (``ragged/latents.py``): the link's
        #: model and ledger, and weak references to the chunks handed
        #: out and not yet on the host, oldest first. Whoever holds a
        #: chunk (a request's store, a caller) keeps it alive; one that
        #: nobody holds is dropped, and its program with it.
        self._latent_link = HostLink()
        self._latent_parts: deque = deque()
        self._latent_program_max = 0
        self._latent_pending_peak = 0
        #: bytes of the latents of programs this put has enqueued and
        #: not collected yet (``_dispatch``)
        self._latent_launched = 0
        #: programs enqueued behind one of the same put that had not
        #: been collected: the device reached them without a host turn
        self._chained = 0
        #: programs that carried a step's decode lanes and its prompt
        #: slice together (``_launch_step``)
        self._fused = 0
        #: the slice shape such a program carries: a full
        #: ``prefill_chunk`` (or a tail in its bucket), one lane. 0: no
        #: step of this engine is one program (no chunked prefill; a
        #: model that generates by diffusion over blocks, whose block
        #: lanes stay a program of their own; prefix caching, whose
        #: waves are puts of their own)
        chunk = sm_cfg.prefill_chunk
        self._step_T = _bucket(chunk) if chunk and not (
            self.diffusion or self.prefix_caching) else 0
        #: the decode lanes such a program carries: the narrowest decode
        #: bucket. A step with more keeps its decode program and chains
        #: the step program behind it on blank decode lanes (blank lanes
        #: cost rows, a program costs set-up: one step program an engine)
        self._step_B = _bucket(1)
        if self.window:
            log_dist(f"InferenceEngineV2: {num_window_blocks} blocks in "
                     f"the window layers' pool (window {self.window})",
                     ranks=[0])
        log_dist(f"InferenceEngineV2: {num_blocks} KV blocks x "
                 f"{self.block_size} tokens, max_context="
                 f"{self.max_context}", ranks=[0])

    def _check_paged_attention_fits(self, model_config):
        """Refuse at construction a head layout / block size the
        attention kernel cannot tile (the model's ``attention_fits``
        raises with the rows, bytes and limit) — otherwise the first
        dispatch dies inside the Mosaic compiler. Query rows are tiled,
        so the dispatch length (``max_ragged_batch_size``/
        ``prefill_chunk``) does not enter; only where the kernel is what
        will run."""
        from ..ops import get_op_impl
        if get_op_impl("paged_attention").compatible():
            self.model.attention_fits(
                self.config.state_manager.max_ragged_batch_size)

    @staticmethod
    def _size_cache_blocks(model_config, kv_cfg) -> int:
        """'reserve' allocation mode: size the pool from free device memory
        (reference: memory_config reserve fraction)."""
        from ..platform import get_platform
        layer_types = getattr(model_config, "layer_types", None)
        kv_layers = model_config.n_layer if layer_types is None else \
            sum(1 for kind in layer_types if kind == "full_attention")
        k_width, v_width = getattr(
            model_config, "cache_row_widths",
            (model_config.head_dim, model_config.head_dim))
        per_token = BlockedKVCache.token_bytes(
            kv_layers, model_config.n_kv_head, k_width,
            kv_cfg.cache_dtype, v_head_dim=v_width)
        platform = get_platform()
        free = platform.available_memory()
        if free <= 0:
            if platform.name != "cpu":
                raise RuntimeError(
                    f"cannot size the KV pool: the {platform.name} backend "
                    f"reports no free device memory "
                    f"({platform.memory_stats()}); set kv_cache.num_blocks")
            free = 1 << 30     # CPU test platform reports no limit
        blocks = int(free * kv_cfg.memory_fraction /
                     (per_token * kv_cfg.block_size))
        return max(blocks, 16)

    # -------------------------------------------------------------- #
    # Scheduling API (reference: engine_v2.py:191-264)
    # -------------------------------------------------------------- #
    def query(self, uid: int, max_request_tokens: int,
              max_request_blocks: int) -> Tuple[int, int]:
        """Token/block budget for a request (reference :191): how many
        tokens of this sequence could be scheduled and the blocks needed."""
        seq = self.state.get_sequence(uid)
        seen = seq.seen_tokens if seq else 0
        max_tokens = min(max_request_tokens, self.max_context - seen)
        if self.recurrent and seq is None and \
                not self.state.free_state_slots:
            return 0, 0         # no recurrent-state slot to start in
        blocks = self.state.blocks_needed(seq, max_tokens)
        if self.window and not self._has_room([(seq, max_tokens)]):
            return 0, 0         # the window layers' pool is short
        return max_tokens, min(blocks, max_request_blocks)

    @property
    def free_blocks(self) -> int:
        """Free KV-pool blocks right now (serving-layer admission and
        preemption decisions read this between steps)."""
        return self.state.free_blocks

    def can_schedule(self, uids: Iterable[int],
                     lengths: Iterable[int]) -> SchedulingResult:
        uids, lengths = list(uids), list(lengths)
        sm = self.config.state_manager
        new_seqs = sum(1 for u in uids if self.state.get_sequence(u) is None)
        if self.state.n_tracked_sequences + new_seqs > \
                sm.max_tracked_sequences:
            return SchedulingResult.EngineSequenceLimitExceeded
        if self.recurrent and new_seqs > self.state.free_state_slots:
            # a new sequence takes a recurrent-state slot with its first
            # forward: none free is the tracked-sequence verdict
            return SchedulingResult.EngineSequenceLimitExceeded
        if len(uids) > sm.max_ragged_sequence_count:
            return SchedulingResult.BatchSequenceLimitExceeded
        # with chunked prefill each forward sees at most prefill_chunk
        # tokens per sequence, so the batch budget counts the chunk
        per_fwd = [min(n, sm.prefill_chunk) if sm.prefill_chunk else n
                   for n in lengths]
        if sum(per_fwd) > sm.max_ragged_batch_size:
            return SchedulingResult.BatchTokenLimitExceeded
        asks = []
        for uid, n in zip(uids, lengths):
            seq = self.state.get_sequence(uid)
            seen = seq.seen_tokens if seq else 0
            if seen + n > self.max_context:
                return SchedulingResult.SequenceTokenLimitExceeded
            asks.append((seq, n))
        if not self._has_room(asks):
            return SchedulingResult.KVCacheLimitExceeded
        return SchedulingResult.Success

    def _has_room(self, asks, behind: bool = True) -> bool:
        """``StateManager.has_room`` at this engine's prefill slice."""
        return self.state.has_room(
            asks, behind, self.config.state_manager.prefill_chunk)

    # -------------------------------------------------------------- #
    # put (reference: engine_v2.py:131)
    # -------------------------------------------------------------- #
    def put(self, batch_uids: Iterable[int],
            batch_tokens: Iterable, do_checks: bool = True,
            defer_fetch: bool = False, blocks=None):
        """One forward over a ragged batch. Returns
        ``(logits [n_seqs, vocab], latents)`` where ``latents[i]`` is the
        per-sequence host array [L, new_tokens, H] (None when HCache latent
        capture is disabled).

        ``defer_fetch=True`` skips every device→host fetch: calls then
        chain on-device without a host sync per dispatch (the
        marginal-cost measurement mode; plain path only — incompatible
        with latent capture, prefix caching and chunked prefill). The
        logits return is then a per-uid list of ``(device_array, lane)``
        pairs — ``np.asarray(device_array)[lane]`` is that uid's row;
        sequences dispatched in one group share the same padded device
        array.

        A model that generates by diffusion over blocks: ``blocks`` maps
        the uids whose tokens are an open block (``block_len`` of them,
        masks and all) to a :class:`BlockPass`; they ride the decode
        dispatch at ``T = block_len``, which writes the block's K and V
        over its slots and advances the sequence only when the pass
        commits. Every other uid feeds whole blocks of its prompt. The
        first return is then a list: a :class:`BlockChoice` a block
        lane, ``None`` a prompt slice (its logits row predicts nothing
        and is not fetched); latents come from prompt slices and
        committing passes only."""
        batch_uids = list(batch_uids)
        batch_tokens = list(batch_tokens)
        tracer = get_tracer()
        with tracer.span("hds.serve.put", n_seqs=len(batch_uids),
                         tokens=_token_count(batch_tokens)
                         if tracer.enabled else 0):
            return self._put(batch_uids, batch_tokens, do_checks,
                             defer_fetch, blocks or {})

    def _put(self, batch_uids, batch_tokens, do_checks, defer_fetch,
             blocks):
        """The body of :meth:`put`, in leaf spans: admit; then build and
        enqueue of every program of the step, back to back; then wait /
        fetch / scatter of each, in that order (:meth:`_dispatch`)."""
        tracer = get_tracer()
        with tracer.span("serve.put.admit"):
            batch_tokens = [np.asarray(t, np.int32).reshape(-1)
                            for t in batch_tokens]
            if do_checks:
                # NOTE: with prefix caching the block budget is
                # conservative (checked before any prefix attaches
                # reduce the real need)
                result = self.can_schedule(
                    batch_uids, [len(t) for t in batch_tokens])
                if result != SchedulingResult.Success:
                    raise SchedulingError(result)
            self._reject_suspended(batch_uids)
            _inj = get_injector()
            if _inj.enabled and batch_uids:
                # resilience fault site: before any state mutation, so
                # a faulted dispatch is retryable / its batch
                # quarantinable
                _inj.fire("engine.prefill"
                          if any(len(t) > 1 for t in batch_tokens)
                          else "engine.decode",
                          uid=batch_uids[-1], uids=tuple(batch_uids))
            if self.diffusion:
                self._check_blocks(batch_uids, batch_tokens, blocks)
            elif blocks:
                raise ValueError("put(blocks=...) is for a model that "
                                 "generates by diffusion over blocks")
            if defer_fetch and (self.prefix_caching or self.diffusion or
                                self.config.hcache.enable_latents or
                                self.config.state_manager.prefill_chunk):
                raise ValueError(
                    "defer_fetch supports only the plain put() path (no "
                    "prefix caching, latent capture, or chunked prefill)")
        if self.prefix_caching:
            # two-wave in-batch dedup: a new prompt that could share a
            # prefix with an EARLIER new prompt in this same call defers
            # to a second wave — wave 1 writes and registers the blocks,
            # wave 2 then attaches them from the index (sharing within
            # one dispatch is impossible: the blocks don't exist yet)
            wave2 = self._defer_in_batch_duplicates(batch_uids,
                                                    batch_tokens)
            if wave2:
                keep = [i for i in range(len(batch_uids))
                        if i not in wave2]
                l1, _ = self.put([batch_uids[i] for i in keep],
                                 [batch_tokens[i] for i in keep],
                                 do_checks=False)
                l2, _ = self.put([batch_uids[i] for i in wave2],
                                 [batch_tokens[i] for i in wave2],
                                 do_checks=False)
                logits = np.zeros((len(batch_uids),) + l1.shape[1:],
                                  l1.dtype)
                logits[keep] = l1
                logits[list(wave2)] = l2
                # dropping l1/l2 latents is only sound because the
                # constructor forbids prefix_caching with latent capture
                # — pin that invariant here so relaxing it elsewhere
                # can't silently lose latents
                assert not self.config.hcache.enable_latents, (
                    "wave-split put() discards latents; prefix_caching "
                    "with hcache.enable_latents must stay mutually "
                    "exclusive")
                return logits, [None] * len(batch_uids)
            batch_tokens = self._attach_shared_prefixes(batch_uids,
                                                        batch_tokens)
            processed = [list(t) for t in batch_tokens]

        # chunked prefill (Dynamic SplitFuse): run the leading chunks of
        # long prompts round by round — all sequences' chunk-k heads
        # share ONE dispatch (the shape can_schedule budgeted), KV
        # allocated as it grows, latents accumulated — leaving tails
        # <= chunk for the normal mixed decode/prefill batch below
        chunk = self.config.state_manager.prefill_chunk
        lead_latents: Dict[int, List] = {}
        if chunk:
            while True:
                long_idx = [i for i, t in enumerate(batch_tokens)
                            if len(t) > chunk]
                if not long_idx:
                    break
                heads: List = [None] * len(batch_tokens)
                with tracer.span("serve.put.admit"):
                    for i in long_idx:
                        heads[i] = batch_tokens[i][:chunk]
                        seq = self.state.get_or_create_sequence(
                            batch_uids[i])
                        self.state.maybe_allocate_kv(seq, chunk)
                        seq.pre_forward(chunk)
                part_l: List = [None] * len(batch_tokens)
                part_t: List = [None] * len(batch_tokens)
                if self._rides_decode({_bucket(chunk): long_idx}):
                    launch = functools.partial(
                        self._launch_step, batch_uids, heads, [], long_idx,
                        part_l, part_t)
                else:
                    launch = functools.partial(
                        self._launch_prefill, batch_uids, heads, long_idx,
                        _bucket(chunk), part_l, part_t)
                self._one_by_one([launch])
                with tracer.span("serve.scatter"):
                    for i in long_idx:
                        self.state.get_sequence(
                            batch_uids[i]).post_forward()
                        if self.config.hcache.enable_latents:
                            lead_latents.setdefault(i, []).append(
                                part_t[i])
                        batch_tokens[i] = batch_tokens[i][chunk:]
                    self._release_behind_windows(batch_uids[i]
                                                 for i in long_idx)

        with tracer.span("serve.put.admit"):
            for uid, tokens in zip(batch_uids, batch_tokens):
                seq = self.state.get_or_create_sequence(uid)
                self.state.maybe_allocate_kv(seq, len(tokens))
                seq.pre_forward(len(tokens))

            # route: single-token continuations -> one batched decode;
            # everything else -> per-sequence bucketed prefill
            decode_idx = [i for i, (u, t) in enumerate(
                zip(batch_uids, batch_tokens))
                if (u in blocks if self.diffusion else len(t) == 1 and
                    self.state.get_sequence(u).seen_tokens > 0)]
            prefill_idx = [i for i in range(len(batch_uids))
                           if i not in decode_idx]
            # prefills batch per length bucket: one dispatch per (B, T)
            # bucket instead of one jit call per sequence (round-1
            # latency hygiene finding; reference batches prefills in
            # one ragged pass)
            groups: Dict[int, List[int]] = {}
            for i in prefill_idx:
                groups.setdefault(_bucket(len(batch_tokens[i])),
                                  []).append(i)

        n = len(batch_uids)
        logits_out: List = [None] * n
        latents_out: List = [None] * n

        launches = []
        rides = self._rides_decode(groups)
        # more decode lanes than the step program holds: they keep their
        # program, and the slice takes its own on blank decode lanes
        riders = decode_idx if rides and \
            len(decode_idx) <= self._step_B else []
        if decode_idx and self.diffusion:
            launches.append(functools.partial(
                self._launch_blocks, batch_uids, batch_tokens, decode_idx,
                blocks, logits_out, latents_out))
        elif decode_idx and not riders:
            launches.append(functools.partial(
                self._launch_decode, batch_uids, batch_tokens, decode_idx,
                logits_out, latents_out, defer=defer_fetch))
        if rides:
            launches.append(functools.partial(
                self._launch_step, batch_uids, batch_tokens, riders,
                groups.pop(self._step_T), logits_out, latents_out))
        for T, idx in sorted(groups.items()):
            launches.append(functools.partial(
                self._launch_prefill, batch_uids, batch_tokens, idx, T,
                logits_out, latents_out, defer=defer_fetch))
        self._dispatch(launches)

        with tracer.span("serve.scatter"):
            for uid in batch_uids:
                self.state.get_sequence(uid).post_forward()
            self._release_behind_windows(batch_uids)

            if self.prefix_caching:
                for uid, toks in zip(batch_uids, processed):
                    seq = self.state.get_sequence(uid)
                    seq.history.extend(int(t) for t in toks)
                    self._register_full_blocks(seq)

            if lead_latents:   # chunked prefill: its slices, in order
                for i, parts in lead_latents.items():
                    tail = [latents_out[i]] if latents_out[i] is not None \
                        else []
                    latents_out[i] = PendingLatents.joined(parts + tail)

            if defer_fetch or self.diffusion:
                return logits_out, latents_out
            return np.stack(logits_out), latents_out

    def _dispatch(self, launches):
        """The programs of one put: every one built and enqueued
        (``launches``, each returning its collect, or ``None`` when it
        leaves nothing to fetch), then each waited for, fetched and
        scattered, in the same order. The device goes from a program to
        the next with no host turn between them (the donated pools chain
        them), and a program's results travel and are scattered while
        the next runs. One program: the sequence of :meth:`_one_by_one`."""
        self._latent_launched = 0   # a put that raised may have left some
        flying = []
        try:
            for launch in launches:
                collect = launch(chained=bool(flying))
                if collect is not None:
                    flying.append(collect)
        except Exception:
            # _one_by_one had collected these before it came to the
            # launch that raised, and a fault of theirs came first
            for collect in flying:
                collect()
            raise
        for collect in flying:
            collect()

    def _one_by_one(self, launches):
        """Each program collected before the next is built: the lead
        rounds of a prompt longer than ``prefill_chunk``, and what
        :meth:`_dispatch` is held to, result for result."""
        for launch in launches:
            collect = launch(chained=False)
            if collect is not None:
                collect()

    def _release_behind_windows(self, uids) -> None:
        """After a step: the window blocks of ``uids`` that lie wholly
        behind their windows go back to the window pool's allocator (a
        leaf span of its own inside ``serve.scatter``,
        ``serve.window_free``; nothing for a trunk with one pool)."""
        if not self.window:
            return
        with get_tracer().span("serve.window_free") as span:
            span.set(blocks=sum(
                self.state.release_behind_window(
                    self.state.get_sequence(uid)) for uid in uids))

    def _tables(self, idx, uids):
        return np.stack([
            self.state.block_table(self.state.get_sequence(uids[i]),
                                   self.max_blocks_per_seq) for i in idx])

    def _blank_lanes(self, B, T=1):
        """Padded-lane scaffolding shared by every batched dispatch:
        zeroed tokens/start/t_len plus tables whose padded lanes point at
        the scratch block (their writes drop on t_len=0 anyway)."""
        tok = np.zeros((B, T), np.int32)
        start = np.zeros((B,), np.int32)
        t_len = np.zeros((B,), np.int32)
        tables = np.zeros((B, self.model.table_width), np.int32)
        tables[:, 0] = self._scratch_block
        if self.window:     # the window pool's table follows
            tables[:, self.max_blocks_per_seq] = self._window_scratch
        return tok, start, t_len, tables

    def _count_chained(self, span):
        """The program enqueued inside ``span`` stands behind one of the
        same put that has not been collected: the device reaches it
        without a host turn."""
        self._chained += 1
        span.set(chained=1)

    def _forward(self, span, tok, start, tables, t_len, uids, idx,
                 chained):
        """One program over the built lanes, inside the enqueue span
        ``span``, which is told what the lanes' description cost in
        transfers. A trunk with recurrent layers also gets each lane's
        state slot, blank lanes the spare one."""
        lanes = (tok, start, tables, t_len) + self._slots(uids, idx,
                                                           len(t_len))
        stats = self.model.dispatch_stats
        arrays, nbytes = stats["h2d_arrays"], stats["h2d_bytes"]
        out = self.model.forward_chunk(self.cache, *lanes)
        span.set(h2d_arrays=stats["h2d_arrays"] - arrays,
                 h2d_bytes=stats["h2d_bytes"] - nbytes)
        if chained:
            self._count_chained(span)
        self._keep_router_probes(uids, idx)
        return out

    def _keep_router_probes(self, uids, idx, first=0):
        """Remember, for the uids that asked, the latest forward's
        router inputs and each one's lane in them (``idx`` are lanes
        ``first`` onwards)."""
        if self.router_probe_uids:
            for j, i in enumerate(idx):
                if uids[i] in self.router_probe_uids:
                    self._router_probes[uids[i]] = (
                        self.model.router_probe, first + j)

    def _decode_lanes(self, uids, tokens, idx, B):
        """The lanes of a decode dispatch of bucket ``B``."""
        tok, start, t_len, tables = self._blank_lanes(B)
        if idx:
            tables[:len(idx)] = self._tables(idx, uids)
        for j, i in enumerate(idx):
            tok[j, 0] = tokens[i][0]
            start[j] = self.state.get_sequence(uids[i]).seen_tokens
            t_len[j] = 1
        return tok, start, tables, t_len

    def _slice_lanes(self, uids, tokens, idx, B, T):
        """The lanes of a prefill dispatch of ``B`` lanes of ``T``."""
        tok, start, t_len, tables = self._blank_lanes(B, T)
        tables[:len(idx)] = self._tables(idx, uids)
        for j, i in enumerate(idx):
            seq = self.state.get_sequence(uids[i])
            tok[j, :len(tokens[i])] = tokens[i]
            start[j] = seq.seen_tokens
            t_len[j] = len(tokens[i])
        return tok, start, tables, t_len

    def _slots(self, uids, idx, B):
        """``((slots [B],),)`` of a recurrent trunk's lanes, the blank
        ones at the spare slot; ``()`` for any other trunk."""
        if not self.recurrent:
            return ()
        slots = np.full((B,), self.state.state_slots, np.int32)
        for j, i in enumerate(idx):
            slots[j] = self.state.get_sequence(uids[i]).state_slot
        return (slots,)

    # -------------------------------------------------------------- #
    # A step that holds decode lanes and a prompt slice: one program
    # -------------------------------------------------------------- #
    def _rides_decode(self, groups) -> bool:
        """Whether the put's prefill ``groups`` hold one lane of the
        slice shape that rides the decode lanes' program
        (``_step_T``): decided by the step's shape, and by one slice
        shape, so that an engine has one such program and not one a pair
        of buckets. Such a slice with no decode lane beside it (or with
        more than ``_step_B``, which keep their own program in front)
        takes the step program too, on blank decode lanes (eight rows
        more): that shape has one program wherever a put meets it, which
        a warm-up of each shape alone builds. Every other group (a short
        tail, two prompts' slices; ``defer_fetch``, which chunked
        prefill refuses) keeps a program of its own, chained behind."""
        return self._step_T and len(groups.get(self._step_T, ())) == 1

    def _launch_step(self, uids, tokens, d_idx, s_idx, logits_out,
                     latents_out, chained=False):
        """:meth:`_launch_decode` and :meth:`_launch_prefill` of one
        slice lane as one program (``model.forward_step``): the weights
        are read once for the rows of both groups. One enqueue span, the
        slice's, with ``decode_lanes`` and, where there are any,
        ``fused=1``; one wait and one fetch, the decode lanes' logits
        rows first. The put's first program unless its decode lanes were
        too many to ride (``chained`` then); other prefill groups of the
        put are chained behind it."""
        tracer = get_tracer()
        B, T = self._step_B, self._step_T
        fused = {"fused": 1} if d_idx else {}
        with tracer.span("serve.batch_build", bucket=B):
            decode = self._decode_lanes(uids, tokens, d_idx, B) + \
                self._slots(uids, d_idx, B)
            slice_ = self._slice_lanes(uids, tokens, s_idx, 1, T) + \
                self._slots(uids, s_idx, 1)
        with tracer.span("serve.prefill_dispatch", lanes=len(s_idx),
                         bucket=1, bucket_T=T, decode_lanes=len(d_idx),
                         **fused, tokens=_token_count(tokens[i] for i in s_idx)
                         if tracer.enabled else 0) as span:
            stats = self.model.dispatch_stats
            arrays, nbytes = stats["h2d_arrays"], stats["h2d_bytes"]
            logits, (lat_d, lat_s) = self.model.forward_step(
                self.cache, decode, slice_)
            span.set(h2d_arrays=stats["h2d_arrays"] - arrays,
                     h2d_bytes=stats["h2d_bytes"] - nbytes)
            self._fused += bool(d_idx)
            if chained:
                self._count_chained(span)
            self._keep_router_probes(uids, d_idx)
            self._keep_router_probes(uids, s_idx, first=B)
            lat_d = self._start_copies(logits, lat_d)
            lat_s = self._start_copies(logits, lat_s, fetch=False)

        def collect():
            rows = self._fetch(logits, lat_d, beside=lat_s)
            with tracer.span("serve.scatter"):
                for j, i in enumerate(d_idx):
                    logits_out[i] = rows[j]
                    if lat_d is not None:                  # [L, B, 1, H]
                        latents_out[i] = self._hand_out(lat_d, j, 1)
                for j, i in enumerate(s_idx):
                    logits_out[i] = rows[B + j]
                    if lat_s is not None:                  # [L, 1, T, H]
                        latents_out[i] = self._hand_out(
                            lat_s, j, len(tokens[i]))
        return collect

    def _launch_decode(self, uids, tokens, idx, logits_out, latents_out,
                       defer=False, chained=False):
        """Build and enqueue the decode program, its results' copies to
        the host started. Returns its collect: wait, fetch and scatter
        into ``logits_out`` / ``latents_out`` (``None`` under ``defer``:
        the logits stay on the device)."""
        tracer = get_tracer()
        B = _bucket(len(idx))
        with tracer.span("serve.batch_build", bucket=B):
            tok, start, tables, t_len = self._decode_lanes(uids, tokens,
                                                           idx, B)
        with tracer.span("serve.decode_dispatch",
                         lanes=len(idx), bucket=B) as span:
            logits, latents = self._forward(span, tok, start, tables,
                                            t_len, uids, idx, chained)
            if not defer:
                latents = self._start_copies(logits, latents)
        if defer:   # keep the device array whole (row slicing here would
            for j, i in enumerate(idx):   # dispatch an op per lane) —
                logits_out[i] = (logits, j)   # every uid gets its lane
            return None

        def collect():
            rows = self._fetch(logits, latents)
            with tracer.span("serve.scatter"):
                for j, i in enumerate(idx):
                    logits_out[i] = rows[j]
                    if latents is not None:                # [L, B, 1, H]
                        latents_out[i] = self._hand_out(latents, j, 1)
        return collect

    def _launch_prefill(self, uids, tokens, idx, T, logits_out,
                        latents_out, defer=False, chained=False):
        """As :meth:`_launch_decode`, one batched dispatch for all
        prefills in a length bucket; padded rows (t_len=0) write to the
        scratch block like padded decode lanes."""
        tracer = get_tracer()
        B = _bucket(len(idx), minimum=1)
        with tracer.span("serve.batch_build", bucket=B):
            tok, start, tables, t_len = self._slice_lanes(uids, tokens,
                                                          idx, B, T)
        with tracer.span("serve.prefill_dispatch",
                         lanes=len(idx), bucket=B, bucket_T=T,
                         tokens=_token_count(tokens[i] for i in idx)
                         if tracer.enabled else 0) as span:
            logits, latents = self._forward(span, tok, start, tables,
                                            t_len, uids, idx, chained)
            if not defer:
                latents = self._start_copies(logits, latents,
                                             fetch=not self.diffusion)
        if defer:
            for j, i in enumerate(idx):
                logits_out[i] = (logits, j)
            return None

        def collect():
            # a prompt slice of a diffusion model predicts nothing: its
            # first block starts from masks, and no logits row is fetched
            rows = self._fetch(logits, latents, fetch=not self.diffusion)
            with tracer.span("serve.scatter"):
                for j, i in enumerate(idx):
                    logits_out[i] = None if self.diffusion else rows[j]
                    if latents is not None:            # [L, B, T, H]
                        latents_out[i] = self._hand_out(
                            latents, j, len(tokens[i]))
        return collect

    def _start_copies(self, logits, latents, fetch=True):
        """Part of a dispatch: start the copies of its results to the
        host, the logits' first (the next dispatch waits for those
        alone; ``fetch`` false: they stay on the device). Returns the
        latents as a :class:`LatentProgram`, or ``None`` when HCache
        capture is off or ``latents`` is ``None``."""
        if fetch:
            logits.copy_to_host_async()
        if not self.config.hcache.enable_latents or latents is None:
            return None
        program = LatentProgram(latents, self._latent_link)
        self._latent_launched += program.nbytes
        return program

    def _fetch(self, logits, program, fetch=True, beside=None):
        """A dispatch's logits on the host. In the time the device
        needs for the program, what earlier programs left pending is
        landed (``serve.latents.land``); then the wait for the device
        (``serve.device_wait``) and the logits' copy
        (``serve.fetch``; none, and ``None`` back, when ``fetch`` is
        false). The latents stay where they are: ``program`` (and
        ``beside``, the second array of a dispatch of two lane groups)
        is only told when its copy could start."""
        tracer = get_tracer()
        held = None
        if program is not None:
            held = program.nbytes + (
                0 if beside is None else beside.nbytes)
            self._latent_launched -= held
        self._land_pending(logits, held)
        with tracer.span("serve.device_wait"):
            logits.block_until_ready()
        if program is not None:     # two assignments: the parent's time
            self._latent_link.enqueue(program, time.perf_counter())
            if beside is not None:
                self._latent_link.enqueue(beside, time.perf_counter())
        if not fetch:
            return None
        with tracer.span("serve.fetch",
                         bytes=_nbytes(logits) if tracer.enabled else 0):
            return np.asarray(logits)

    # -------------------------------------------------------------- #
    # Generation by diffusion over blocks: the decode dispatch at
    # T = block_len
    # -------------------------------------------------------------- #
    def _refuse_diffusion(self, feature: str) -> None:
        """Raise for a call that assumes causal one-token decoding."""
        if self.diffusion:
            raise DiffusionUnsupported(
                f"{feature} is not supported for a model that generates "
                f"by diffusion over blocks (diffusion_block_length="
                f"{self.block_len}): a step is a pass over a lane's "
                "whole block and yields no token until the block "
                "commits; serve it through put(blocks=...) or "
                "ServingServer")

    def _check_blocks(self, uids, tokens, blocks) -> None:
        """A block lane feeds one whole block at a block's edge; a
        prompt slice whole blocks."""
        B = self.block_len
        for uid, toks in zip(uids, tokens):
            seq = self.state.get_sequence(uid)
            seen = seq.seen_tokens if seq else 0
            if uid in blocks and len(toks) != B:
                raise ValueError(f"uid {uid}: a block pass feeds {B} "
                                 f"positions, got {len(toks)}")
            if len(toks) % B or seen % B:
                raise ValueError(
                    f"uid {uid}: {len(toks)} tokens from position {seen} "
                    f"are not whole blocks of {B}")

    def _launch_blocks(self, uids, tokens, idx, blocks, out, latents_out,
                       chained=False):
        """One pass over every open block: the decode dispatch with
        lanes of ``block_len`` positions, launched and collected as
        :meth:`_launch_decode`. The device chooses, greedy;
        what comes back is a token and a confidence a position and the
        experts' counts, in one array, plus the logits rows and the
        routers' inputs of the one lane that asked. Latents go to the host for the committing lanes only."""
        tracer = get_tracer()
        T, B = self.block_len, _bucket(len(idx))
        passes = [blocks[uids[i]] for i in idx]
        commits = [j for j, p in enumerate(passes) if p.commit]
        probe = next((j for j, p in enumerate(passes) if p.probe), None)
        rank = {j: r for r, j in enumerate(commits)}
        with tracer.span("serve.batch_build", bucket=B):
            tok, start, t_len, tables = self._blank_lanes(B, T)
            flags = np.zeros((B,), np.int32)
            tables[:len(idx)] = self._tables(idx, uids)
            for j, i in enumerate(idx):
                tok[j] = tokens[i]
                start[j] = self.state.get_sequence(uids[i]).seen_tokens
                t_len[j] = T
                flags[j] = (j == probe) | (passes[j].commit << 1)
            masked = int(np.count_nonzero(
                tok[:len(idx)] == self.mask_token_id))
        with tracer.span("serve.decode_dispatch", lanes=len(idx),
                         bucket=B, block=T, masked=masked,
                         commit_lanes=len(commits)) as span:
            stats = self.model.dispatch_stats
            arrays, nbytes = stats["h2d_arrays"], stats["h2d_bytes"]
            packed, probed, latents = self.model.forward_block(
                self.cache, tok, start, tables, t_len, flags)
            span.set(h2d_arrays=stats["h2d_arrays"] - arrays,
                     h2d_bytes=stats["h2d_bytes"] - nbytes)
            if chained:
                self._count_chained(span)
            if probe is not None:
                for a in probed:
                    a.copy_to_host_async()
            # the committing lanes lie first in the latents: only the
            # bucket that holds them goes
            buckets = self.model.latent_buckets(B)
            latents = self._start_copies(
                packed, latents[buckets.index(min(B, _bucket(len(commits))))]
                if commits else None)

        def collect():
            choice = self._fetch(packed, latents)
            if probe is not None:
                # the one fetch of logits rows there is: told apart by ``probe``
                with tracer.span("serve.fetch", probe=1,
                                 bytes=_nbytes(*probed)):
                    rows, router_in = (np.asarray(a) for a in probed)
            with tracer.span("serve.scatter"):
                chosen = choice[:B * T].reshape(B, T)
                confidence = choice[B * T:2 * B * T].view(np.float32) \
                    .reshape(B, T)
                for j, i in enumerate(idx):
                    out[i] = BlockChoice(chosen[j], confidence[j]) \
                        if j != probe else \
                        BlockChoice(chosen[j], confidence[j], rows,
                                    router_in)
                    seq = self.state.get_sequence(uids[i])
                    if not passes[j].commit:
                        seq.in_flight_tokens = 0    # provisional: not seen
                    elif latents is not None:
                        latents_out[i] = self._hand_out(latents, rank[j], T)
                self._count_blocks(len(idx), masked, len(commits),
                                   choice[2 * B * T:])
        return collect

    def _count_blocks(self, lanes, masked, commits, counts) -> None:
        d, T = self._diffusion_stats, self.block_len
        d["lane_passes"] += lanes
        d["positions_fed"] += lanes * T
        d["positions_masked"] += masked
        d["tokens_committed"] += commits * T
        if len(counts) > 1:                 # a sparse-expert trunk
            m = self._moe_stats
            m["dispatches"] += 1
            m["touched"] += int(counts[-1])
            picks = counts[:-1].astype(np.int64)
            m["picks"] = picks if m["picks"] is None else m["picks"] + picks

    def diffusion_stats(self) -> Dict[str, int]:
        """Block passes so far (a model that generates by diffusion over
        blocks): ``lane_passes`` forwards of one lane's block,
        ``positions_fed`` the positions they carried,
        ``positions_masked`` those that were still a mask,
        ``tokens_committed`` the positions made final. Committed over
        passes is the tokens a forward of a lane yields."""
        return dict(self._diffusion_stats)

    def moe_stats(self) -> Dict:
        """A sparse-expert trunk's routing over the block passes so far,
        counted on the device and fetched with the tokens: ``picks``
        ``[E]`` the positions routed to each expert, all layers summed
        (``None`` before the first pass); ``touched`` the experts with
        any pick, layer by layer and pass by pass; ``dispatches`` the
        passes counted. A causal trunk whose forwards keep their counts
        on the device (``model.take_picks``) answers over all the
        experts its router scores and, beside them, for the experts its
        parameter tree holds: ``picks_held``, ``held_log`` ``[forwards,
        2]`` (a forward's rows on held experts and the held experts
        those touched, all layers summed), and ``touched`` of the held
        experts alone."""
        m = self._moe_stats
        if hasattr(self.model, "take_picks"):
            picks = self.model.take_picks()
            first, count = self._model_config.held
            log = self.model.held_log
            return {"dispatches": self.model.moe_dispatches,
                    "touched": int(log[:, 1].sum()), "picks": picks,
                    "picks_held": int(picks[first:first + count].sum()),
                    "held_log": log.copy()}
        return {"dispatches": m["dispatches"], "touched": m["touched"],
                "picks": None if m["picks"] is None else m["picks"].copy()}

    def kv_pool_stats(self) -> Dict[str, Dict[str, int]]:
        """Each block pool's ``blocks``, ``in_use`` and ``peak_in_use``
        (the scratch block counted), and for the window layers' pool of
        a trunk that has one the blocks ``released`` behind windows
        while their sequences lived."""
        return self.state.pool_stats()

    def router_inputs(self, uid: int):
        """What each sparse layer's router read for the last row that
        ``uid`` fed in its latest forward, ``[L_sparse, hidden]`` on the
        host, or ``None``: kept for the uids of ``router_probe_uids``
        by a trunk whose forward hands it back (``model_latent.py``). A
        check routes its reference's compared row by it, so that a near
        tie falls the same way on both sides."""
        kept = self._router_probes.get(uid)
        if kept is None or kept[0] is None:
            return None
        # the whole array to the host, then the lane: a slice on the
        # device would be a program of its own
        return np.asarray(kept[0])[:, kept[1]]

    # -------------------------------------------------------------- #
    # Deferred latent landing (ragged/latents.py)
    # -------------------------------------------------------------- #
    def _hand_out(self, program, lane, n):
        """``n`` tokens of ``lane`` of ``program`` as a pending chunk,
        remembered here (weakly) until it has landed."""
        chunk = program.chunk(lane, n)
        self._latent_parts.append(weakref.ref(chunk.parts[0]))
        return chunk

    def _pending_parts(self) -> List:
        """The chunks handed out that are alive and not yet landed,
        oldest first; the rest are forgotten here."""
        live = [(ref, part) for ref in self._latent_parts
                if (part := ref()) is not None and not part.landed]
        self._latent_parts = deque(ref for ref, _ in live)
        return [part for _, part in live]

    def _land_pending(self, in_flight, new_bytes) -> None:
        """Copy what earlier programs left pending into the stores that
        adopted it, oldest first, while ``in_flight`` (this dispatch's
        logits) is not ready; what is left waits for the next dispatch.
        A program whose copy the link's model does not expect yet is
        left alone, and so is everything behind it — unless the
        latents still on the device, the ``new_bytes`` of this
        dispatch's program among them (``None``: it captured none), hold
        more than ``_PENDING_PROGRAMS`` of the largest program: then
        the oldest copies are waited for, as every dispatch did before
        landing was deferred. Programs of this put enqueued behind
        this one count in the peak from their launch and against
        that bound when their own collect comes, so nothing is waited
        for that was not before launches were chained."""
        held = 0
        if new_bytes is not None:
            held = new_bytes
            self._latent_program_max = max(self._latent_program_max, held)
        behind = self._latent_launched
        if not self._latent_parts:
            self._latent_pending_peak = max(self._latent_pending_peak,
                                            held + behind)
            return
        with get_tracer().span("serve.latents.land") as span:
            parts, last = self._pending_parts(), None
            for part in parts:      # a program's lanes lie together
                if part.program is not last:
                    last = part.program
                    held += last.device_bytes
            self._latent_pending_peak = max(self._latent_pending_peak,
                                            held + behind)
            over = held - self._PENDING_PROGRAMS * self._latent_program_max
            if new_bytes is not None and over > 0:
                self._force_pending(parts, over)
            link, now = self._latent_link, time.perf_counter()
            nbytes = chunks = 0
            for part in parts:
                store = part.store and part.store()
                if store is None or part.landed:
                    continue        # nobody's yet: nowhere to land it
                if part.program.device_bytes and \
                        not link.due(part.program, now):
                    break
                try:
                    while not part.landed and not in_flight.is_ready():
                        nbytes += store.land(part, hidden=True)
                except Exception as exc:   # the store truncated itself
                    log_dist(f"latent landing failed, payload "
                             f"truncated: {exc!r}", ranks=[0])
                    continue
                if not part.landed:
                    break           # the program in flight is done
                chunks += 1
            span.set(bytes=nbytes, chunks=chunks)

    def _force_pending(self, parts, over: int) -> None:
        """Wait for the oldest copies until ``over`` bytes of device
        memory are free again, landing what stores adopted."""
        with get_tracer().span("serve.latents.force", bytes=over):
            for part in parts:
                if over <= 0:
                    break
                over -= part.program.device_bytes
                store = part.store and part.store()
                try:
                    if store is None:
                        part.program.host()
                    while store is not None and not part.landed:
                        store.land(part, hidden=False)
                except Exception as exc:
                    log_dist(f"latent landing failed, payload "
                             f"truncated: {exc!r}", ranks=[0])

    def latent_stats(self) -> Dict[str, int]:
        """Where the captured latents went, in bytes of live lanes:
        landed while a program ran (``landed_hidden_bytes``), landed or
        read while the caller waited (``landed_forced_bytes``), released
        unread (``dropped_bytes``), still pending; and the most device
        memory latents on their way have held (``pending_peak_bytes``,
        whole padded programs)."""
        link = self._latent_link
        pending = sum(p.unread_bytes for p in self._pending_parts())
        return {
            "saved_state": self.model.saved_state,
            "captured_bytes": link.captured_bytes,
            "captured_tokens": link.captured_tokens,
            "landed_hidden_bytes": link.landed_hidden_bytes,
            "landed_forced_bytes": link.landed_forced_bytes,
            "dropped_bytes": link.captured_bytes - pending
            - link.landed_hidden_bytes - link.landed_forced_bytes,
            "pending_bytes": pending,
            "pending_peak_bytes": self._latent_pending_peak,
        }

    def kv_write_stats(self) -> Dict[str, int]:
        """Dispatches, and the rows of K and V they wrote into the
        pools, by the write they took: ``run_*`` a block run at a time
        (prompt slices, restore replays, verification tails: lanes of
        more than one position), ``row_*`` a row at a time (decode
        lanes). A row is one ``[head_dim]`` vector of one KV head of one
        layer, K and V counted apart. Counted on the host from each
        dispatch's shape; whether the run path's kernel ran or gave way
        to the rows is ``ops.fallback_report()``."""
        return dict(self.model.kv_write_stats)

    def dispatch_stats(self) -> Dict[str, int]:
        """Forwards enqueued (``dispatches``: prompt slices, decode
        steps, verification tails) and what described their lanes to
        the device: ``h2d_arrays`` host arrays handed to the programs, a
        host-to-device transfer each, and their ``h2d_bytes``. One
        packed array a dispatch (``ragged/lanes.py``), so ``h2d_arrays
        == dispatches``. Counted on the host where the arrays are
        handed over. ``chained``: those of :meth:`put`'s that were
        enqueued while an earlier program of the same put had not been
        collected (:meth:`_dispatch`), which the device reaches without
        a host turn; over ``dispatches``, their share. ``fused``: the
        programs that carried a step's decode lanes and its prompt slice
        together (:meth:`_launch_step`): over ``fused + chained``, the
        share of two-group steps that took one program."""
        return dict(self.model.dispatch_stats, chained=self._chained,
                    fused=self._fused)

    def paged_walk_stats(self) -> Dict[str, int]:
        """How much of the block tables the paged kernel's walk covers:
        over the ``dispatches`` enqueued, ``table_slots`` (lanes of the
        bucket x the table's width, padded lanes too: the steps of a
        grid over the table) and ``blocks_walked`` (the sum over live
        lanes of ``ceil((start + t_len) / block_size)``: the blocks the
        kernel's loop fetches a head tile and row tile, at most).
        Their ratio is the share of the table that holds a context.
        Counted on the host from each dispatch's ``start`` and
        ``t_len``."""
        return dict(self.model.paged_walk_stats)

    # -------------------------------------------------------------- #
    # Serving loop (reference: the generate() surface the v1 engine
    # exposes via HF and hybrid_engine.py wraps; v2's counterpart is the
    # mii serving loop — here a built-in utility)
    # -------------------------------------------------------------- #
    def generate(self, prompts, max_new_tokens: int = 32,
                 eos_token_id: int = None, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                 return_logits: bool = False):
        """Batched prefill + ragged decode loop.

        ``prompts``: list of token-id lists. Greedy when temperature==0,
        else softmax sampling (optionally top-k and/or nucleus top-p).
        Returns the generated continuations (without the prompt), plus
        per-step logits when ``return_logits`` (for RLHF-style log-prob
        computation). Sequences are flushed from the KV cache on
        completion.
        """
        self._refuse_diffusion("generate (the host-sampled decode loop)")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        rng = np.random.default_rng(seed)
        base = max(self.state._seqs.keys(), default=-1) + 1
        uids = [base + i for i in range(len(prompts))]

        def sample(row):
            return _sample_host(row, rng, temperature, top_k, top_p)

        outs = [[] for _ in prompts]
        logit_trace = [[] for _ in prompts]
        for p in prompts:
            if len(p) + max_new_tokens > self.max_context:
                raise SchedulingError(
                    SchedulingResult.SequenceTokenLimitExceeded)

        def need_blocks(i):
            """Whole-generation KV budget, committed at admission."""
            return -(-(len(prompts[i]) + max_new_tokens) //
                     self.block_size) + 1

        # Continuous batching (the FastGen scheduler semantics): every
        # iteration admits whatever pending prompts still fit, then runs
        # ONE ragged put() mixing their prefills with the active
        # sequences' decodes; finished sequences flush mid-flight and
        # their blocks let new prompts join without draining the batch.
        pending = list(range(len(prompts)))
        active: List[int] = []
        live: List[int] = []            # active + this step's admissions
        reserved: Dict[int, int] = {}   # admission-time block commitment
        cur: Dict[int, np.ndarray] = {}
        # admission headroom changes when a sequence finishes (KV blocks
        # free) AND one step after any prefill (the ragged token budget
        # that blocked a co-admission frees once the prefill becomes a
        # 1-token decode)
        headroom_changed = True
        try:
            while pending or active:
                admit = []
                if pending and headroom_changed:
                    # headroom the still-running reservations hold back
                    # (measured against the allocator's own state, not a
                    # re-derivation of its policy)
                    held = sum(
                        reserved[i] - self.state.get_sequence(
                            uids[i]).cur_allocated_blocks - 1
                        for i in active)
                    blocks_left = self.state.allocator.free_blocks - held
                    for i in list(pending):
                        cand = admit + [i]
                        if need_blocks(i) > blocks_left:
                            continue
                        lens = [1] * len(active) + \
                            [len(prompts[j]) for j in cand]
                        uid_c = [uids[j] for j in active + cand]
                        if self.can_schedule(uid_c, lens) == \
                                SchedulingResult.Success:
                            admit.append(i)
                            blocks_left -= need_blocks(i)
                headroom_changed = bool(admit)
                if not active and not admit:
                    # nothing fits even alone — surface the verdict
                    i = pending[0]
                    result = self.can_schedule([uids[i]],
                                               [len(prompts[i])])
                    raise SchedulingError(
                        result if result != SchedulingResult.Success
                        else SchedulingResult.KVCacheLimitExceeded)

                step = active + admit
                live = step   # put() may allocate before raising
                toks = [[outs[i][-1]] for i in active] + \
                    [prompts[i] for i in admit]
                step_logits, _ = self.put([uids[i] for i in step], toks)
                for j, i in enumerate(step):
                    cur[i] = step_logits[j]
                for i in admit:
                    reserved[i] = need_blocks(i)
                pending = [i for i in pending if i not in admit]
                active = step

                finished = []
                for i in active:
                    tok = sample(cur[i])
                    outs[i].append(tok)
                    if return_logits:
                        logit_trace[i].append(cur[i])
                    if (eos_token_id is not None and
                            tok == eos_token_id) or \
                            len(outs[i]) >= max_new_tokens:
                        finished.append(i)
                for i in finished:
                    self.flush(uids[i])
                    reserved.pop(i, None)
                    headroom_changed = True
                active = [i for i in active if i not in finished]
        finally:
            for i in set(active) | set(live):
                if self.state.get_sequence(uids[i]) is not None:
                    self.flush(uids[i])
        if return_logits:
            return outs, [np.stack(t) if t else None for t in logit_trace]
        return outs

    # -------------------------------------------------------------- #
    # Fused decode: N greedy steps per device program (TPU-native — the
    # host-driven generate() above pays a host round-trip per token; this
    # compiles the whole decode stretch, reference has no analog because
    # its engine must rebuild the ragged batch host-side each step)
    # -------------------------------------------------------------- #
    @traced("hds.serve.generate_fused")
    def generate_fused(self, prompts, max_new_tokens: int = 32,
                       eos_token_id: int = None, temperature: float = 0.0,
                       top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                       return_logprobs: bool = False):
        """Batched generation with on-device token feedback.

        Prefill runs through :meth:`put` (capturing latents as usual);
        the decode stretch then runs as ONE jitted ``lax.scan`` — the
        sampled token (greedy argmax when temperature<=0, else
        temperature/top-k/top-p via a threaded PRNG key) feeds the next
        step on device, so the host syncs once per *generation*, not
        once per token. temperature/top_p are traced (per-request values
        reuse the compiled program); only the sampling MODE, top_k and
        n_steps recompile. KV blocks for the whole stretch are reserved
        up front. Returns ``(outs, latents)`` — or ``(outs, latents,
        logprobs)`` with per-generated-token raw-model logprobs (RLHF
        consumers) when ``return_logprobs`` — where ``latents[i]``
        covers prompt + fed tokens (None when latent capture is off) —
        a returning sequence can be HCache-restored from them after a
        flush."""
        self._refuse_recurrent("generate_fused (the fused decode loop)")
        self._refuse_windowed(
            "generate_fused (the fused decode loop)",
            "both pools and both tables carried through the loop's "
            "program, and blocks freed inside it")
        self._refuse_diffusion("generate_fused (the fused decode loop)")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        base = max(self.state._seqs.keys(), default=-1) + 1
        uids = [base + i for i in range(len(prompts))]
        n_feed = max_new_tokens - 1   # tokens fed (and cached) on device
        # per-forward batch budget sees only the prompts (the fused loop
        # runs 1 token/lane); context + KV-block budgets must cover the
        # whole stretch
        result = self.can_schedule(uids, [len(p) for p in prompts])
        if result != SchedulingResult.Success:
            raise SchedulingError(result)
        blocks = 0
        for p in prompts:
            if len(p) + n_feed > self.max_context:
                raise SchedulingError(
                    SchedulingResult.SequenceTokenLimitExceeded)
            blocks += -(-(len(p) + n_feed) // self.block_size)
        if blocks > self.state.free_blocks:
            raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
        try:
            logits, latents = self.put(uids, prompts)
            host_rng = np.random.default_rng(seed)
            first = np.asarray(
                [_sample_host(row, host_rng, temperature, top_k, top_p)
                 for row in logits], np.int32)                    # [n]
            outs = [[int(t)] for t in first]
            logprobs = None
            if return_logprobs:
                lse = _logsumexp_rows(logits)
                logprobs = [[float(logits[j, first[j]] - lse[j, 0])]
                            for j in range(len(uids))]
            if n_feed > 0:
                n = len(uids)
                tok, start, t_len, tables = self._blank_lanes(_bucket(n))
                for j, uid in enumerate(uids):
                    seq = self.state.get_sequence(uid)
                    self.state.maybe_allocate_kv(seq, n_feed)
                    seq.pre_forward(n_feed)
                    tok[j, 0] = first[j]
                    start[j] = seq.seen_tokens
                    t_len[j] = 1
                tables[:n] = self._tables(list(range(n)), uids)
                # already-finished lanes (EOS on the first token) join
                # as done so they neither feed nor block the early exit
                if eos_token_id is not None:
                    for j in range(n):
                        if outs[j][0] == eos_token_id:
                            t_len[j] = 0
                tracer = get_tracer()
                with tracer.span("serve.fused_decode",
                                 lanes=n, n_feed=n_feed):
                    toks, lats, lps = self.model.decode_loop(
                        self.cache, tok[:, 0], start, t_len, tables,
                        n_feed, temperature=temperature, top_k=top_k,
                        top_p=top_p, seed=seed,
                        want_logprobs=return_logprobs,
                        eos_token_id=eos_token_id)
                with tracer.span("serve.device_wait"):
                    toks.block_until_ready()
                with tracer.span("serve.fetch",
                                 bytes=_nbytes(toks, lps)
                                 if tracer.enabled else 0):
                    toks = np.asarray(toks)
                    if lps is not None:
                        lps = np.asarray(lps)
                for j, uid in enumerate(uids):
                    self.state.get_sequence(uid).post_forward()
                    outs[j].extend(int(t) for t in toks[:, j])
                    if return_logprobs:
                        logprobs[j].extend(float(x) for x in lps[:, j])
                if self.config.hcache.enable_latents:
                    # slice to live lanes on device: padded bucket lanes
                    # would otherwise ride the D2H copy
                    lats = np.asarray(lats[:, :, :n])  # [n_feed,L,n,1,H]
                    for j in range(n):
                        fed = lats[:, :, j, 0].transpose(1, 0, 2)
                        latents[j] = np.concatenate([latents[j], fed],
                                                    axis=1)
        finally:
            for uid in uids:
                if self.state.get_sequence(uid) is not None:
                    self.flush(uid)
        if eos_token_id is not None:
            for j, o in enumerate(outs):
                if eos_token_id in o:
                    outs[j] = o[:o.index(eos_token_id) + 1]
                    if return_logprobs:
                        logprobs[j] = logprobs[j][:len(outs[j])]
                    if latents[j] is not None:
                        # keep the restore contract: latents cover
                        # prompt + fed tokens = prompt + len(outs)-1
                        latents[j] = latents[j][
                            :, :len(prompts[j]) + len(outs[j]) - 1]
        if return_logprobs:
            return outs, latents, [np.asarray(l, np.float32)
                                   for l in logprobs]
        return outs, latents

    def _refuse_windowed(self, feature: str, needs: str) -> None:
        """Raise for a trunk with window layers (two pools, the window
        pool's blocks freed behind the window): what would read or
        rewrite a block that has gone back."""
        if self.window:
            from .model_window import refuse
            raise refuse(feature, needs)

    def _refuse_recurrent(self, feature: str) -> None:
        """Raise for a call that would roll a recurrent state back or
        carry it through a program that does not hold it."""
        if self.recurrent:
            from .model_hybrid import refuse
            raise refuse(feature)

    @staticmethod
    def _lookup_draft(history, ngram: int, k: int):
        """Prompt-lookup drafting: find the most recent PRIOR occurrence
        of the trailing ``ngram`` tokens and propose the ``k`` tokens
        that followed it (PLD/"prompt lookup decoding" — no draft
        model; the sequence's own history is the proposer)."""
        n = len(history)
        if n < ngram + 1:
            return []
        arr = np.asarray(history, np.int64)
        key = arr[-ngram:]
        # windows ending strictly before the trailing ngram itself
        limit = n - ngram
        if limit <= 0:
            return []
        windows = np.lib.stride_tricks.sliding_window_view(
            arr[:n - 1], ngram)[:limit]
        hits = np.flatnonzero((windows == key).all(axis=1))
        if hits.size == 0:
            return []
        i = int(hits[-1]) + ngram      # first token after the match
        return [int(t) for t in arr[i:i + k]]

    def generate_lookup(self, prompts, max_new_tokens: int = 32,
                        ngram: int = 2, max_draft: int = 8,
                        eos_token_id: int = None):
        """Greedy generation with prompt-lookup speculative decoding.

        Beyond-reference feature (FastGen has no speculative path): each
        step drafts up to ``max_draft`` tokens from the sequence's own
        history (:meth:`_lookup_draft`), verifies the whole stretch in
        ONE batched dispatch via the tail-logits forward
        (``model.forward_chunk_tail``), accepts the matching prefix plus
        the bonus token, and rolls rejected draft KV back
        (``SequenceDescriptor.rollback`` — slots past ``seen_tokens``
        are never read and get overwritten by the next dispatch). Every
        dispatch has the same static shape (lane bucket × (1+max_draft)),
        so the whole generation reuses one compiled program. Exact:
        output is identical to token-by-token greedy decode; on
        repetitive text each dispatch yields up to ``max_draft+1``
        tokens instead of 1.

        Returns ``(outs, stats)`` with
        ``stats = {drafted, accepted, dispatches, tokens}``.
        """
        self._refuse_recurrent("generate_lookup (speculative rollback)")
        self._refuse_windowed(
            "generate_lookup (speculative rollback)",
            "a rollback across a window block that has gone back")
        self._refuse_diffusion("generate_lookup (prompt-lookup drafts)")
        if self.prefix_caching:
            raise ValueError(
                "generate_lookup with prefix_caching is unsupported: "
                "rolled-back draft KV must never be registered as a "
                "sharable prefix")
        if self.config.hcache.enable_latents:
            raise ValueError(
                "generate_lookup does not capture latents (rejected "
                "drafts would poison them); disable "
                "hcache.enable_latents")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if ngram < 1 or max_draft < 1:
            raise ValueError("ngram and max_draft must be >= 1")
        n = len(prompts)
        base = max(self.state._seqs.keys(), default=-1) + 1
        uids = [base + i for i in range(n)]
        result = self.can_schedule(uids, [len(p) for p in prompts])
        if result != SchedulingResult.Success:
            raise SchedulingError(result)
        # budget the whole stretch incl. a rejected draft tail beyond
        # the final accepted token (its KV transiently occupies slots)
        blocks = 0
        for p in prompts:
            span = len(p) + max_new_tokens - 1 + max_draft
            if span > self.max_context:
                raise SchedulingError(
                    SchedulingResult.SequenceTokenLimitExceeded)
            blocks += -(-span // self.block_size)
        if blocks > self.state.free_blocks:
            raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)

        stats = {"drafted": 0, "accepted": 0, "dispatches": 0,
                 "tokens": 0}
        T = 1 + max_draft
        try:
            logits, _ = self.put(uids, prompts)
            outs = [[int(np.argmax(l))] for l in logits]
            hist = [list(p) + outs[i] for i, p in enumerate(prompts)]
            done = {i for i in range(n)
                    if eos_token_id is not None
                    and outs[i][0] == eos_token_id}
            while True:
                live = [i for i in range(n)
                        if i not in done and len(outs[i]) < max_new_tokens]
                if not live:
                    break
                B = _bucket(len(live))
                tok, start, t_len, tables = self._blank_lanes(B, T)
                feeds = []
                for j, i in enumerate(live):
                    draft = self._lookup_draft(hist[i], ngram, max_draft)
                    draft = draft[:max_new_tokens - len(outs[i]) - 1]
                    feed = [outs[i][-1]] + draft
                    feeds.append(feed)
                    seq = self.state.get_sequence(uids[i])
                    self.state.maybe_allocate_kv(seq, len(feed))
                    seq.pre_forward(len(feed))
                    tok[j, :len(feed)] = feed
                    start[j] = seq.seen_tokens
                    t_len[j] = len(feed)
                    stats["drafted"] += len(draft)
                tables[:len(live)] = self._tables(live, uids)
                tail_logits = np.asarray(self.model.forward_chunk_tail(
                    self.cache, tok, start, tables, t_len, T))
                stats["dispatches"] += 1
                for j, i in enumerate(live):
                    seq = self.state.get_sequence(uids[i])
                    seq.post_forward()
                    feed = feeds[j]
                    m = len(feed) - 1            # drafted count
                    # logits for the last t_len positions sit at the END
                    # of the tail window
                    lane = tail_logits[j, T - len(feed):]
                    greedy = [int(np.argmax(lane[t]))
                              for t in range(len(feed))]
                    acc = 0
                    while acc < m and feed[1 + acc] == greedy[acc]:
                        acc += 1
                    new = greedy[:acc + 1]       # accepted + bonus
                    stats["accepted"] += acc
                    seq.rollback(m - acc)        # rejected draft KV
                    if eos_token_id is not None and eos_token_id in new:
                        new = new[:new.index(eos_token_id) + 1]
                        done.add(i)
                    outs[i].extend(new)
                    hist[i].extend(new)
                    stats["tokens"] += len(new)
                    if len(outs[i]) >= max_new_tokens:
                        done.add(i)
        finally:
            for uid in uids:
                if self.state.get_sequence(uid) is not None:
                    self.flush(uid)
        stats["tokens"] += n   # the first token from prefill
        return [o[:max_new_tokens] for o in outs], stats

    def generate_lookup_fused(self, prompts, max_new_tokens: int = 32,
                              ngram: int = 2, max_draft: int = 8,
                              window: int = 128,
                              eos_token_id: int = None):
        """Fully fused prompt-lookup speculative decoding: drafting,
        verification, acceptance and KV rollback all run inside ONE
        on-device ``lax.while_loop`` (``model.lookup_decode_loop``), so
        the host syncs once per generation AND each device step can
        emit up to ``max_draft+1`` tokens — the two serving wins
        (:meth:`generate_fused`, :meth:`generate_lookup`) composed.
        Greedy-exact like both. ``window`` caps the on-device n-gram
        search to each lane's most recent tokens (static shape).

        Returns ``(outs, stats)`` like :meth:`generate_lookup`, plus
        per-lane attribution: ``accepted_per_lane`` / ``drafted_per_
        lane`` ride the loop carry as [B] counters, so a serving layer
        can attribute acceptance per request instead of
        batch-averaging (``drafted`` remains the per-lane upper bound
        ``lane_iters*max_draft``, now summed over actual live
        iterations instead of ``iters*max_draft`` for the whole
        batch)."""
        self._refuse_recurrent("generate_lookup_fused (speculative "
                               "rollback in a fused loop)")
        self._refuse_windowed(
            "generate_lookup_fused (the fused speculative decode loop)",
            "both pools carried through the loop's program and a "
            "rollback across a freed window block")
        self._refuse_diffusion("generate_lookup_fused (the fused lookup "
                               "loop)")
        if self.prefix_caching:
            raise ValueError(
                "generate_lookup_fused with prefix_caching is "
                "unsupported: rolled-back draft KV must never be "
                "registered as a sharable prefix")
        if self.config.hcache.enable_latents:
            raise ValueError(
                "generate_lookup_fused does not capture latents; "
                "disable hcache.enable_latents")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if ngram < 1 or max_draft < 1 or window <= ngram:
            raise ValueError("need ngram>=1, max_draft>=1, window>ngram")
        n = len(prompts)
        base = max(self.state._seqs.keys(), default=-1) + 1
        uids = [base + i for i in range(n)]
        result = self.can_schedule(uids, [len(p) for p in prompts])
        if result != SchedulingResult.Success:
            raise SchedulingError(result)
        blocks = 0
        for p in prompts:
            span = len(p) + max_new_tokens - 1 + max_draft
            if span > self.max_context:
                raise SchedulingError(
                    SchedulingResult.SequenceTokenLimitExceeded)
            blocks += -(-span // self.block_size)
        if blocks > self.state.free_blocks:
            raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)

        try:
            logits, _ = self.put(uids, prompts)
            first = [int(np.argmax(l)) for l in logits]
            outs = [[t] for t in first]
            if max_new_tokens == 1 or (
                    eos_token_id is not None
                    and all(t == eos_token_id for t in first)):
                return outs, {"drafted": 0, "accepted": 0,
                              "dispatches": 0, "tokens": n,
                              "accepted_per_lane": [0] * n,
                              "drafted_per_lane": [0] * n}
            B = _bucket(n)
            first_tok, pos, t_blank, tables = self._blank_lanes(B)
            del t_blank
            live = np.zeros((B,), bool)
            hist = np.zeros((B, window), np.int32)
            hist_len = np.zeros((B,), np.int32)
            for j, uid in enumerate(uids):
                seq = self.state.get_sequence(uid)
                # reserve the whole stretch incl. transient rejected
                # tails (generate_fused-style up-front reservation)
                self.state.maybe_allocate_kv(
                    seq, max_new_tokens - 1 + max_draft)
                full = list(prompts[j]) + [first[j]]
                w = min(len(full), window)
                hist[j, window - w:] = full[-w:]
                hist_len[j] = w
                pos[j] = seq.seen_tokens
                first_tok[j, 0] = first[j]
                live[j] = not (eos_token_id is not None
                               and first[j] == eos_token_id)
            tables[:n] = self._tables(list(range(n)), uids)
            out_buf, out_len, iters, accepted, lane_iters = \
                self.model.lookup_decode_loop(
                    self.cache, first_tok[:, 0], pos, tables, live,
                    hist, hist_len, max_new=max_new_tokens - 1,
                    ngram=ngram, max_draft=max_draft, window=window,
                    eos_token_id=eos_token_id)
            for j in range(n):
                outs[j].extend(int(t) for t in out_buf[j, :out_len[j]])
            drafted_per_lane = [int(lane_iters[j]) * max_draft
                                for j in range(n)]
            stats = {"drafted": sum(drafted_per_lane),
                     "accepted": int(accepted[:n].sum()),
                     "dispatches": int(iters),
                     "tokens": n + int(out_len[:n].sum()),
                     "accepted_per_lane": [int(accepted[j])
                                           for j in range(n)],
                     "drafted_per_lane": drafted_per_lane}
        finally:
            for uid in uids:
                if self.state.get_sequence(uid) is not None:
                    self.flush(uid)
        return [o[:max_new_tokens] for o in outs], stats

    # -------------------------------------------------------------- #
    # fused speculative verify step (the serving speculation surface)
    # -------------------------------------------------------------- #
    #: ``put_spec`` captures accepted-span latents through the
    #: latent-capturing tail forward (``forward_chunk_tail_lat``), so
    #: the serving scheduler may speculate against this engine under
    #: latent preemption as well as in exact-KV suspension mode
    spec_latent_capture = True

    @traced("hds.serve.put_spec")
    def put_spec(self, batch_uids: Iterable[int], batch_feeds,
                 do_checks: bool = True):
        """One fused speculative verify step over tracked decode
        residents: each feed is ``[fed_token] + draft``; ONE tail-
        logits dispatch (``model.forward_chunk_tail``, the same
        verification forward :meth:`generate_lookup` drives) verifies
        every stretch, the matching draft prefix plus the bonus token
        is accepted, and rejected draft KV rolls back
        (``SequenceDescriptor.rollback``). Greedy-exact per lane.

        Returns ``(emitted, latents)``. Under
        ``hcache.enable_latents`` the dispatch runs the
        latent-capturing tail forward and each lane's entry is its
        ACCEPTED span's latent chunk ``[L, acc+1, H]`` (the fed token
        plus accepted drafts — rolled-back positions never reach a
        latent payload); in exact-KV mode the entries are all None.
        ``prefix_caching`` stays unsupported (rolled-back KV must
        never register as a sharable prefix)."""
        self._refuse_recurrent("put_spec (rollback of rejected drafts)")
        self._refuse_windowed(
            "put_spec (rollback of rejected drafts)",
            "a rollback across a window block that has gone back to "
            "its allocator")
        self._refuse_diffusion("put_spec (speculative verification)")
        capture = bool(self.config.hcache.enable_latents)
        if self.prefix_caching:
            raise RuntimeError(
                "put_spec with prefix_caching is unsupported: "
                "rolled-back draft KV must never be registered as a "
                "sharable prefix")
        batch_uids = list(batch_uids)
        batch_feeds = [list(np.asarray(f, np.int32).reshape(-1))
                       for f in batch_feeds]
        if any(len(f) < 1 for f in batch_feeds):
            raise ValueError("put_spec feeds need >= 1 token "
                             "(the fed token)")
        if do_checks:
            result = self.can_schedule(
                batch_uids, [len(f) for f in batch_feeds])
            if result != SchedulingResult.Success:
                raise SchedulingError(result)
        self._reject_suspended(batch_uids)
        for uid in batch_uids:
            if self.state.get_sequence(uid) is None:
                raise KeyError(
                    f"put_spec: unknown sequence {uid} (speculation "
                    "runs on decode residents only)")
        inj = get_injector()
        if inj.enabled and batch_uids:
            inj.fire("engine.spec", uid=batch_uids[-1],
                     uids=tuple(batch_uids))
        n = len(batch_uids)
        T = max(len(f) for f in batch_feeds)
        B = _bucket(n)
        tok, start, t_len, tables = self._blank_lanes(B, T)
        starts = []
        for j, (uid, feed) in enumerate(zip(batch_uids, batch_feeds)):
            seq = self.state.get_sequence(uid)
            self.state.maybe_allocate_kv(seq, len(feed))
            starts.append(seq.seen_tokens)
            seq.pre_forward(len(feed))
            tok[j, :len(feed)] = feed
            start[j] = starts[j]
            t_len[j] = len(feed)
        tables[:n] = self._tables(list(range(n)), batch_uids)
        tracer, lat = get_tracer(), None
        with tracer.span("serve.spec_dispatch", lanes=n,
                         tokens=_token_count(batch_feeds)
                         if tracer.enabled else 0):
            if capture:
                tail_logits, lat = self.model.forward_chunk_tail_lat(
                    self.cache, tok, start, tables, t_len, T)
            else:
                tail_logits = self.model.forward_chunk_tail(
                    self.cache, tok, start, tables, t_len, T)
        with tracer.span("serve.device_wait"):
            tail_logits.block_until_ready()
        with tracer.span("serve.fetch",
                         bytes=_nbytes(tail_logits, lat)
                         if tracer.enabled else 0):
            tail_logits = np.asarray(tail_logits)
            if capture:
                lat = np.asarray(lat)          # [L, B, T, H]
        emitted_out: List[List[int]] = []
        lat_out: List = []
        for j, (uid, feed) in enumerate(zip(batch_uids, batch_feeds)):
            seq = self.state.get_sequence(uid)
            seq.post_forward()
            d = len(feed) - 1
            # logits for the last t_len positions sit at the END of
            # the tail window (the forward_chunk_tail contract)
            lane = tail_logits[j, T - len(feed):]
            greedy = [int(np.argmax(lane[t]))
                      for t in range(len(feed))]
            acc = 0
            while acc < d and feed[1 + acc] == greedy[acc]:
                acc += 1
            seq.rollback(d - acc)        # rejected draft KV
            emitted_out.append(greedy[:acc + 1])
            # feeds are left-aligned at column 0, so the accepted
            # span's latents are the first acc+1 columns of the lane
            lat_out.append(lat[:, j, :acc + 1].copy() if capture
                           else None)
        return emitted_out, lat_out

    # -------------------------------------------------------------- #
    # HCache restore (fork: engine_v2.py:108)
    # -------------------------------------------------------------- #
    @traced("hds.serve.restore_kv")
    def restore_kv(self, batch_uids: Iterable[int], batch_tokens: Iterable,
                   batch_latents: Iterable, states=None) -> None:
        """Rebuild the blocked KV cache for ``batch_uids`` from saved
        latents without a full forward: allocate blocks, then per layer
        replay the K/V projection + RoPE + cache write with host→HBM copies
        double-buffered against compute.

        Run-to-completion driver over the restore lane
        (:meth:`begin_restore` + :meth:`advance_restores`); the serving
        scheduler holds the lane open instead and trickles chunks
        between resident decode dispatches."""
        self.begin_restore(batch_uids, batch_tokens, batch_latents,
                           states=states)
        self.advance_restores()

    def begin_restore(self, batch_uids: Iterable[int],
                      batch_tokens: Iterable,
                      batch_latents: Iterable,
                      states=None) -> "RestoreTicket":
        """Open a restore lane: validate + admit the batch
        all-or-nothing, allocate KV blocks, build the padded lane slabs
        and issue the FIRST layer-chunks' host→device ships — but
        dispatch no replay yet. The returned ticket completes as
        :meth:`advance_restores` drains the lane; until then the
        sequences are tracked and in-flight (their blocks are held, and
        they must not be decoded). The ship of chunk 0 is already on
        the link when this returns, so whatever the engine dispatches
        next (typically the residents' decode) computes under it.

        ``states``: for a trunk with recurrent layers, each sequence's
        :meth:`snapshot_state` (latents replay the full layers' K and V
        only; a recurrent state is copied back whole into the fresh
        slot). A sequence without one is refused."""
        batch_uids = list(batch_uids)
        self._reject_suspended(batch_uids)
        by_uid = {}
        if self.recurrent:
            batch_latents = list(batch_latents)
            by_uid = dict(zip(batch_uids, states or ()))
            missing = [u for u, lat in zip(batch_uids, batch_latents)
                       if lat is not None and by_uid.get(u) is None]
            if missing:
                from .model_hybrid import refuse
                raise refuse(
                    f"restore of sequences {missing} from latents alone",
                    "their recurrent state (snapshot_state at eviction): "
                    "it cannot be replayed from a projection")
        # group sequences by length bucket: ONE batched restore dispatch
        # chain per bucket (the per-sequence loop costs a full layer-chunk
        # dispatch chain per uid — latency-bound on slow host links)
        items = []
        for uid, tokens, latents in zip(batch_uids, batch_tokens,
                                        batch_latents):
            if latents is None:
                continue
            tokens = np.asarray(tokens, np.int32).reshape(-1)
            latents = np.asarray(latents)          # [L, T, H]
            if latents.shape[1] != len(tokens):
                raise ValueError(
                    f"uid {uid}: {len(tokens)} tokens but latents for "
                    f"{latents.shape[1]}")
            items.append((uid, tokens, latents))
        uid_list = [it[0] for it in items]
        if len(set(uid_list)) != len(uid_list):
            # grouped lanes read seen_tokens before any post_forward — a
            # duplicated uid would overwrite its own slots silently
            raise ValueError(f"duplicate uids in restore_kv: {uid_list}")
        # all-or-nothing admission: a mid-group failure would strand
        # earlier lanes with in-flight accounting and no KV
        new_seqs = sum(1 for uid in uid_list
                       if self.state.get_sequence(uid) is None)
        if self.state.n_tracked_sequences + new_seqs > \
                self.config.state_manager.max_tracked_sequences:
            raise SchedulingError(
                SchedulingResult.EngineSequenceLimitExceeded)
        asks = []
        for uid, tokens, _ in items:
            seq = self.state.get_sequence(uid)
            seen = seq.seen_tokens if seq else 0
            if seen + len(tokens) > self.max_context:
                raise SchedulingError(
                    SchedulingResult.SequenceTokenLimitExceeded)
            asks.append((seq, len(tokens)))
        # a restore writes a window layer's rows still inside the window
        if not self._has_room(asks, behind=False):
            raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
        groups: Dict[int, List] = {}
        for item in items:
            groups.setdefault(_bucket(len(item[1])), []).append(item)
        self.restore_stats["restores"] += 1
        self.restore_stats["sequences"] += len(items)

        def _progress(layer0, nbytes):
            self.restore_stats["chunks_issued"] += 1
            self.restore_stats["bytes_shipped"] += int(nbytes)

        ticket = RestoreTicket(uids=list(uid_list))
        # the umbrella span covers STAGING (state ops + slab build +
        # first ships); the replay chunks get their own
        # serve.restore.stage spans as advance_restores issues them
        tracer = get_tracer()
        with tracer.span(
                "serve.restore_kv", sequences=len(items),
                tokens=_token_count(it[1] for it in items)
                if tracer.enabled else 0,
                latent_bytes=_nbytes(*(it[2] for it in items))
                if tracer.enabled else 0):
            for T, group in sorted(groups.items()):
                lat, start, t_len, tables, seqs = \
                    self._stage_restore_group(group, T)
                if self.recurrent:
                    self._put_states(seqs, [by_uid[it[0]] for it in group])
                pipe = self.model.restore_pipeline(
                    self.cache, lat, start, tables, t_len,
                    progress_cb=_progress)
                pipe.prefetch()   # chunk 0's H2D rides the link now
                ticket.pending += 1
                self._restore_lanes.append(
                    _RestoreLane(pipe=pipe, seqs=seqs,
                                 uids=[it[0] for it in group],
                                 ticket=ticket))
        if ticket.pending == 0:
            ticket.done = True
        return ticket

    # -------------------------------------------------------------- #
    # Recurrent state of a hybrid trunk: out whole at eviction, back
    # whole at restore
    # -------------------------------------------------------------- #
    def snapshot_state(self, uid: int):
        """The sequence's rows of the recurrent-state pools on the host
        (``(state [L_lin, H, d_k, d_v], conv [L_lin, (K - 1) * C])``), to be
        handed back to :meth:`begin_restore` after its flush; ``None``
        for a trunk with no recurrent layer. Waits for the programs that
        wrote the slot."""
        if not self.recurrent:
            return None
        seq = self.state.get_sequence(uid)
        if seq is None:
            raise KeyError(f"unknown sequence {uid}")
        with get_tracer().span("serve.state.snapshot", uid=uid,
                               bytes=self.cache.slot_bytes):
            rows = tuple(np.asarray(r) for r in self._take_state(
                self.cache.state, self.cache.conv,
                jnp.int32(seq.state_slot)))
        self.state_stats["snapshots"] += 1
        self.state_stats["bytes_out"] += rows[0].nbytes + rows[1].nbytes
        return rows

    def _put_states(self, seqs, rows) -> None:
        """Copy snapshots back into the (fresh) slots of ``seqs``."""
        slots = np.asarray([s.state_slot for s in seqs], np.int32)
        state = np.stack([r[0] for r in rows], axis=1)
        conv = np.stack([r[1] for r in rows], axis=1)
        with get_tracer().span("serve.state.restore", sequences=len(seqs),
                               bytes=state.nbytes + conv.nbytes):
            self.cache.replace_state(*self._swap_in_state(
                self.cache.state, self.cache.conv, jnp.asarray(slots),
                jnp.asarray(state), jnp.asarray(conv)))
        self.state_stats["restores"] += len(seqs)
        self.state_stats["bytes_in"] += state.nbytes + conv.nbytes

    @staticmethod
    @jax.jit
    def _take_state(state, conv, slot):
        return state[:, slot], conv[:, slot]

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def _swap_in_state(state, conv, slots, rows, conv_rows):
        return (state.at[:, slots].set(rows),
                conv.at[:, slots].set(conv_rows.astype(conv.dtype)))

    def advance_restores(self, max_chunks: int = 0):
        """Issue up to ``max_chunks`` replay-chunk dispatches across
        the open restore lanes, oldest lane first (0 = drain
        everything). Entirely async — the caller may dispatch decode
        forwards between calls and the pending chunks' H2D ships hide
        under that compute. Returns ``(chunks_issued, completed_uids,
        touched_uids)`` — ``touched`` are the lanes that issued >= 1
        chunk this call (the scheduler's overlap accounting);
        a lane's sequences become decodable (their ``post_forward``
        runs) exactly when the lane's last chunk has been issued."""
        issued = 0
        completed: List[int] = []
        touched: List[int] = []
        while self._restore_lanes and (max_chunks <= 0 or
                                       issued < max_chunks):
            lane = self._restore_lanes[0]
            budget = 0 if max_chunks <= 0 else max_chunks - issued
            n = lane.pipe.advance(budget)
            issued += n
            if n:
                touched.extend(lane.uids)
            if not lane.pipe.done:
                break
            for seq in lane.seqs:
                seq.post_forward()
            completed.extend(lane.uids)
            lane.ticket.pending -= 1
            if lane.ticket.pending <= 0:
                lane.ticket.done = True
            self._restore_lanes.pop(0)
        return issued, completed, touched

    def abort_restore(self, uid: int) -> List[int]:
        """Abort the open restore lane holding ``uid`` (resilience
        path: retry exhaustion or the scheduler's stuck-lane watchdog).
        Every sequence the lane staged is flushed — its blocks and
        tracked slot free immediately; chunks already replayed into the
        cache are unreachable once the block table is gone, so a
        partially-restored lane leaves no visible state. Returns the
        aborted uids ([] when no lane holds ``uid``). The host latent
        payload belongs to the caller and survives for a later re-begin
        or recompute re-entry."""
        for i, lane in enumerate(self._restore_lanes):
            if uid in lane.uids:
                self._restore_lanes.pop(i)
                for u in lane.uids:
                    self.state.flush_sequence(u)
                lane.ticket.pending -= 1
                if lane.ticket.pending <= 0:
                    lane.ticket.done = True
                get_tracer().instant("serve.restore_abort",
                                     uids=list(lane.uids))
                return list(lane.uids)
        return []

    @property
    def pending_restore_chunks(self) -> int:
        """Replay chunks not yet issued across all open lanes."""
        return sum(l.pipe.chunks_total - l.pipe.chunks_issued
                   for l in self._restore_lanes)

    @property
    def restoring_uids(self) -> List[int]:
        return [u for l in self._restore_lanes for u in l.uids]

    def restore_profile(self) -> Dict:
        """Static shape facts the restore-vs-recompute crossover model
        (``serving/crossover.py``) seeds itself from: latent bytes per
        token, the replay/prefill FLOPs split, and how many replay
        chunks a restore costs (each chunk is one dispatch — the fixed
        overhead that makes recompute win at short prompts)."""
        cfg = self._model_config
        latent_itemsize = jnp.dtype(self.model.latent_dtype).itemsize
        if self.model.saved_state == "cache_row":
            # the saved state is the cache row: a restore ships it and
            # writes it, and replays nothing
            return {
                "n_layer": cfg.n_layer,
                "saved_state": "cache_row",
                "latent_bytes_per_token": self.model.saved_width
                * latent_itemsize * self.model.n_latent_layers,
                # of a window layer a restore ships the rows still
                # inside the window (0: the trunk has no such layer)
                "window_layers": self.model.pool_layers["window"]
                if self.window else 0,
                "window": self.window,
                "replay_flops_frac": 0.0,
                "restore_chunk_layers": self.model.restore_chunk_layers,
                "restore_chunk_bytes": self.model.restore_chunk_bytes,
            }
        H = cfg.hidden_size
        kvd = cfg.n_kv_head * cfg.head_dim
        qd = cfg.n_head * cfg.head_dim
        # matmul flops per token per layer (factor 2 folded out — only
        # the ratio matters): replay runs the q/k/v projections; a full
        # forward adds the o-projection and the 3 SwiGLU matmuls
        replay = H * (qd + 2 * kvd)
        full = replay + H * qd + 3 * H * cfg.intermediate_size
        return {
            "n_layer": cfg.n_layer,
            "saved_state": "hidden",
            "latent_bytes_per_token": cfg.hidden_size * latent_itemsize
            * self.model.n_latent_layers,
            "replay_flops_frac": replay / full,
            "restore_chunk_layers": self.model.restore_chunk_layers,
            "restore_chunk_bytes": self.model.restore_chunk_bytes,
        }

    def _stage_restore_group(self, group, T=None):
        """State ops + lane slab for ONE bucket group of
        ``(uid, tokens, latents)`` items: allocates KV, marks the
        sequences in-flight (caller must ``post_forward()`` each returned
        seq after the cache write lands) and builds the padded latent
        slab [L, n, T, H] with its lane metadata. Shared by
        ``restore_kv`` and the marginal-cost benchmark so both time the
        same compiled program."""
        if T is None:
            T = _bucket(max(len(it[1]) for it in group))
        # lane count buckets too: each distinct n would otherwise
        # shape-specialize (and recompile) the restore chain
        n = _bucket(len(group), minimum=1)
        L = group[0][2].shape[0]
        H = group[0][2].shape[2]
        lat = np.zeros((L, n, T, H), group[0][2].dtype)
        _, start, t_len, tables = self._blank_lanes(n)
        seqs = []
        for j, (uid, tokens, latents) in enumerate(group):
            seq = self.state.get_or_create_sequence(uid)
            self.state.maybe_allocate_kv(seq, len(tokens), behind=False)
            seq.pre_forward(len(tokens))
            lat[:, j, :len(tokens)] = latents
            start[j] = seq.seen_tokens
            t_len[j] = len(tokens)
            tables[j] = self.state.block_table(
                seq, self.max_blocks_per_seq)
            seqs.append(seq)
        return lat, start, t_len, tables, seqs

    # -------------------------------------------------------------- #
    # Prefix caching (no reference analog — FastGen lacks it): full KV
    # blocks shared by refcount across sequences with identical prompt
    # prefixes; a new sequence attaches the matched blocks and prefills
    # only the tail (the same start>0 continuation path chunked prefill
    # uses).
    # -------------------------------------------------------------- #
    @staticmethod
    def _chain_key(parent_bid, block_tokens):
        return (parent_bid, tuple(int(t) for t in block_tokens))

    def _match_chain(self, tokens, max_blocks):
        """Walk the index: block ids for the longest registered prefix
        of ``tokens``, up to ``max_blocks``."""
        BS = self.block_size
        blocks = []
        parent = -1
        for k in range(max_blocks):
            key = self._chain_key(parent, tokens[k * BS:(k + 1) * BS])
            bid = self._prefix_index.get(key)
            if bid is None:
                break
            blocks.append(bid)
            parent = bid
        return blocks

    def _defer_in_batch_duplicates(self, uids, tokens_list):
        """Indices of NEW long prompts whose first block token-matches
        an earlier new prompt in the same batch AND whose prefix is not
        already registered (cheap sufficient trigger: equal first
        blocks ⇒ sharing is possible after wave 1 registers; unequal —
        or already in the global index, where a single wave attaches
        for everyone — ⇒ no reason to split the dispatch)."""
        BS = self.block_size
        seen_first = set()
        wave2 = []
        for i, (uid, tokens) in enumerate(zip(uids, tokens_list)):
            seq = self.state.get_sequence(uid)
            if (seq is not None and seq.seen_tokens > 0) or \
                    len(tokens) <= BS:
                continue
            first = tuple(int(t) for t in tokens[:BS])
            shareable = (len(tokens) - 1) // BS
            if first in seen_first and \
                    len(self._match_chain(tokens, shareable)) < shareable:
                # the index covers less than this duplicate could share
                # — wave 1 (the first occurrence) will extend it
                wave2.append(i)
            else:
                seen_first.add(first)
        return wave2

    def _attach_shared_prefixes(self, uids, tokens_list):
        BS = self.block_size
        out = []
        for uid, tokens in zip(uids, tokens_list):
            seq = self.state.get_sequence(uid)
            if (seq is not None and seq.seen_tokens > 0) or \
                    len(tokens) <= BS:
                out.append(tokens)
                continue
            # new sequence: longest fully-indexed block-prefix match
            # (walking the chain), capped so at least one token still
            # runs the forward (the caller needs logits)
            blocks = self._match_chain(tokens, (len(tokens) - 1) // BS)
            if not blocks:
                out.append(tokens)
                continue
            matched = len(blocks) * BS
            seq = self.state.get_or_create_sequence(uid)
            for b in blocks:
                self.state.allocator.acquire(b)
            seq.extend_blocks(blocks)
            seq.seen_tokens = matched
            seq.history.extend(int(t) for t in tokens[:matched])
            # prime the chain-walk cache: registration resumes after
            # the attached blocks
            seq.registered_full = len(blocks)
            seq.chain_parent = blocks[-1]
            seq.chain_epoch = self._index_epoch
            self.prefix_stats["hits"] += 1
            self.prefix_stats["shared_tokens"] += matched
            out.append(tokens[matched:])
        return out

    def _register_full_blocks(self, seq) -> None:
        """Index this sequence's FULL blocks along the canonical prefix
        chain. The walk runs from the root so the parent is always the
        INDEXED block for that prefix (which may belong to another
        sequence) — chaining on our own unshared duplicate would create
        unreachable entries — but only when a NEW full block completed
        since the last walk (a per-decode-token full rewalk would put
        O(context) host work on every step; the trade-off is that
        entries dropped by a subtree purge re-heal at the next block
        boundary, not the next token). Sequences whose history does not
        cover every cached token (restore_kv-built ones) are skipped:
        their block k holds KV for unknown tokens, and indexing it
        under later-decoded history would share wrong KV. Partial tail
        blocks are never shared (still being written)."""
        BS = self.block_size
        if len(seq.history) != seq.seen_tokens:
            return
        n_full = seq.seen_tokens // BS
        if n_full == seq.registered_full and \
                seq.chain_epoch == self._index_epoch:
            return
        if seq.chain_epoch == self._index_epoch and \
                seq.registered_full > 0:
            start, parent = seq.registered_full, seq.chain_parent
        else:
            start, parent = 0, -1      # a purge invalidated cached tips
        for k in range(start, n_full):
            key = self._chain_key(parent,
                                  seq.history[k * BS:(k + 1) * BS])
            bid = self._prefix_index.get(key)
            if bid is None:
                bid = seq.blocks[k]
                self._prefix_index[key] = bid
                self._block_prefix[bid] = key
                if parent != -1:
                    self._chain_children.setdefault(parent,
                                                    set()).add(key)
            parent = bid
        seq.registered_full = n_full
        seq.chain_parent = parent
        seq.chain_epoch = self._index_epoch

    def _unindex_subtree(self, block) -> None:
        """Drop entries chained under ``block`` — unreachable once its
        entry died. Their blocks may still be alive (other owners); if
        those owners keep decoding, re-registration self-heals with a
        fresh chain. Iterative: a chain is one level per block, so a
        long shared prefix (64k tokens = 1000+ blocks) would blow the
        recursion limit."""
        stack = [block]
        while stack:
            b = stack.pop()
            for ckey in self._chain_children.pop(b, set()):
                cbid = self._prefix_index.pop(ckey, None)
                if cbid is not None:
                    if self._block_prefix.get(cbid) == ckey:
                        del self._block_prefix[cbid]
                    stack.append(cbid)

    def _purge_freed_blocks(self, blocks) -> None:
        purged = False
        for b in blocks:
            if self.state.allocator.refcount(b) == 0:
                key = self._block_prefix.pop(b, None)
                if key is not None:
                    self._prefix_index.pop(key, None)
                    if key[0] != -1 and key[0] in self._chain_children:
                        self._chain_children[key[0]].discard(key)
                    purged = True
                if self._chain_children.get(b):
                    purged = True
                self._unindex_subtree(b)
        if purged:
            self._index_epoch += 1    # cached chain tips are now stale

    # -------------------------------------------------------------- #
    # Lifecycle (reference: flush :275, serialize :284)
    # -------------------------------------------------------------- #
    def flush(self, uid: int) -> None:
        if self._restore_lanes and uid in self.restoring_uids:
            raise RuntimeError(
                f"sequence {uid} has an open restore lane; its blocks "
                "cannot be freed while replay chunks are in flight")
        seq = self.state.get_sequence(uid)
        held = list(seq.blocks) if seq is not None else []
        get_tracer().instant("serve.flush", uid=uid,
                             blocks=len(held))
        self.state.flush_sequence(uid)
        self._router_probes.pop(uid, None)
        if self.prefix_caching and held:
            self._purge_freed_blocks(held)

    # -------------------------------------------------------------- #
    # Host offload of a sequence's KV (reference: BlockedKVCache's
    # optional host-offloaded blocks, ragged/kv_cache.py:40). Unlike
    # HCache restore (recompute-from-latents), suspend/resume moves the
    # EXACT cache contents — bit-identical continuation, no QKV replay.
    # -------------------------------------------------------------- #
    def _reject_suspended(self, uids):
        """Both cache write paths (put, restore_kv) must refuse suspended
        sequences BEFORE any allocation/bookkeeping — writing against the
        stale seen_tokens would corrupt the host copy's accounting.
        Likewise sequences whose restore lane is still open: their
        ``seen_tokens`` only advances when the lane completes, so a
        forward now would write over the restoring slots."""
        restoring = set(self.restoring_uids) if self._restore_lanes \
            else ()
        for uid in uids:
            if uid in restoring:
                raise RuntimeError(
                    f"sequence {uid} has an open restore lane; drain "
                    "advance_restores before forwarding it")
            seq = self.state.get_sequence(uid)
            if seq is not None and seq.host_kv is not None:
                raise RuntimeError(
                    f"sequence {uid} is suspended (KV on host); call "
                    "resume_sequence first")

    def _token_slots(self, seq, n):
        """Flat pool indices of the sequence's first n token slots."""
        t = np.arange(n)
        blocks = np.asarray(seq.blocks, np.int64)
        return blocks[t // self.block_size] * self.block_size + \
            t % self.block_size

    def suspend_sequence(self, uid: int) -> None:
        """Copy the sequence's KV to host memory and free its pool
        blocks. The sequence stays tracked; ``resume_sequence`` swaps it
        back in (possibly into different blocks)."""
        self._refuse_windowed(
            "suspend_sequence (a host copy of the exact KV)",
            "both pools' blocks copied out and back; eviction to saved "
            "rows (latent preemption) serves this trunk")
        seq = self.state.get_sequence(uid)
        if seq is None:
            raise KeyError(f"unknown sequence {uid}")
        if seq.host_kv is not None:
            return   # already suspended
        idx = self._token_slots(seq, seq.seen_tokens)
        seq.host_kv = (np.asarray(self.cache.k[:, :, idx]),
                       np.asarray(self.cache.v[:, :, idx]))
        if seq.blocks:
            held = list(seq.blocks)
            self.state.allocator.free(seq.blocks)
            seq.blocks = []
            if self.prefix_caching:
                self._purge_freed_blocks(held)
                seq.registered_full = 0   # fresh blocks on resume
                seq.chain_parent = -1

    def resume_sequence(self, uid: int) -> None:
        seq = self.state.get_sequence(uid)
        if seq is None:
            raise KeyError(f"unknown sequence {uid}")
        if seq.host_kv is None:
            return   # not suspended
        need = self.state.blocks_needed(seq, 0)
        if need > self.state.free_blocks:
            raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
        self.state.maybe_allocate_kv(seq, 0)
        host_k, host_v = seq.host_kv
        seq.host_kv = None
        if seq.seen_tokens == 0:
            return
        idx = self._token_slots(seq, seq.seen_tokens)
        k, v = self._swap_in(
            self.cache.k, self.cache.v, jnp.asarray(idx),
            jnp.asarray(host_k, self.cache.k.dtype),
            jnp.asarray(host_v, self.cache.v.dtype))
        self.cache.replace(k, v)

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def _swap_in(k, v, idx, host_k, host_v):
        """Donated scatter: the pool buffers update in place instead of
        allocating a second full-size pool copy (the pool is sized to
        nearly fill HBM in reserve mode — an eager .at[].set would OOM
        exactly at production sizes)."""
        return k.at[:, :, idx].set(host_k), v.at[:, :, idx].set(host_v)

    def serialize(self) -> Dict:
        """Host-side engine state (reference serializes scheduling state)."""
        return {
            "sequences": {
                uid: {"seen_tokens": s.seen_tokens, "blocks": list(s.blocks)}
                for uid, s in self.state._seqs.items()
            },
            "free_blocks": self.state.free_blocks,
        }
