#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives both main paths once, through the entry points a user calls, at the
full width of Mistral-7B-v0.1 (hidden 4096, 32 query / 8 KV heads of 128,
FFN 14336, vocabulary 32000, RoPE theta 10000, RMSNorm eps 1e-5, bf16).
Weights are random, from a seed. No width is cut; depth is, to what one
16 GB chip holds, and the script prints what it kept.

* serve: ``build_hf_engine`` -> ``ServingServer`` -> ``start()`` -> a
  handful of ``submit()``s of unequal length, one of them longer than a
  prefill dispatch (chunking runs), over a KV pool small enough that a
  low-priority resident is preempted to host latents and comes back
  through ``restore_kv`` beside resident decode. One restored sequence's
  next-token logits are compared with an uninterrupted run of the same
  context on a fresh engine.
* train: ``hds.initialize`` -> ``train_batch`` on a fixed batch, ZeRO
  stage 0 on one chip; the loss is finite and falls.
* with four chips or more: ZeRO stage 3 over ``mesh: {"data": 4}`` against
  the one-chip losses, and the serve phase again on a ``tensor=4``
  topology against the one-chip logits.

One process, no child processes: a chip belongs to one process at a time.
It refuses to run without a TPU — nothing here falls back to the CPU —
and exits non-zero without a result line if any check fails. The times it
prints are a smoke's (cold compiles included) and are not a record of
speed. Contexts stay under 4096 tokens, so Mistral's published sliding
window, which the llama trunk does not implement, never binds.

The last line of standard output is ``{"ok": true, "device": {...}}``.
"""

import gc
import json
import os
import sys
import time
from dataclasses import dataclass

import numpy as np

try:
    import hcache_deepspeed_tpu as hds
except ImportError as exc:
    sys.exit(f"chip_smoke.py runs from a checkout of the repository it "
             f"tests: {exc}")

#: seconds a request may stay live before the serve phase gives up; the
#: first request of a fresh engine waits behind one cold compile per
#: dispatch shape
SERVE_TIMEOUT_S = 900.0


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


@dataclass(frozen=True)
class Size:
    """One model shape with the traffic and pool that exercise it."""
    name: str
    hf_config: dict
    serve_layers: int
    train_layers: int
    block_size: int
    prefill_chunk: int
    max_context: int
    #: (name, prompt tokens, new tokens, priority, wave). Wave 1 is
    #: submitted once every wave-0 request has its first token, so the
    #: urgent request meets two decoding residents and a full pool; the
    #: scheduler evicts the lowest priority first and among equals the
    #: youngest, which is the long, chunked wave-0 prompt.
    requests: tuple
    train_batch: int
    train_seq: int
    train_steps: int
    #: restored-vs-uninterrupted logits: allowed max |difference| as a
    #: fraction of the reference row's max |logit|
    logit_tol: float
    #: ZeRO-3 vs one-chip loss, per step: allowed difference as a
    #: fraction of the loss (of 1.0 once the loss is under 1)
    loss_tol: float

    def blocks(self, tokens):
        return -(-tokens // self.block_size)

    @property
    def pool_blocks(self):
        """Scratch block + room for the two wave-0 requests at full
        growth + one spare: they fit together, the urgent prompt beside
        them does not, and the victim fits back beside the resident."""
        wave0 = [r for r in self.requests if r[4] == 0]
        return 2 + sum(self.blocks(r[1] + r[2]) for r in wave0)


#: bf16 keeps 8 significant bits (eps = 2^-8 = 0.0039). The restored run
#: reaches the compared token through chunked prefill, decode steps and a
#: QKV replay from saved hidden states; the reference through one chunked
#: prefill. Those are different compiled programs with different
#: accumulation orders, so hidden states differ by a few eps per layer and
#: the logits by a few percent of their scale. Tokens are not compared:
#: with random weights the top logits are near ties and rounding flips
#: the argmax. Measured on a v5e at 8 layers: 0.007; the bound leaves
#: four times that.
_BF16_LOGIT_TOL = 0.03
#: same data, same seed, fp32 master weights; what differs between ZeRO-3
#: on four chips and stage 0 on one is the order of bf16 reductions
_BF16_LOSS_TOL = 0.02

MISTRAL_7B = Size(
    name="mistral-7b-v0.1",
    hf_config={"model_type": "mistral", "vocab_size": 32000,
               "hidden_size": 4096, "intermediate_size": 14336,
               "num_attention_heads": 32, "num_key_value_heads": 8,
               "max_position_embeddings": 4096, "rms_norm_eps": 1e-5,
               "rope_theta": 10000.0, "torch_dtype": "bfloat16"},
    # 32 layers are 14.5 GB in bf16 and leave no room for a cache. Eight
    # (3.5 GB + 0.5 GB of embedding and head) leave room for the pool, a
    # second copy while layers are stacked, and the tensor=4 reshard.
    serve_layers=8,
    # training holds 18 bytes a parameter (bf16 + fp32 master, two Adam
    # moments, fp32 gradient): embedding + head + one layer, 480M
    # parameters, are 8.6 GB; a second layer would not leave room for
    # the step's temporaries on 16 GB.
    train_layers=1,
    block_size=64, prefill_chunk=512, max_context=2048,
    requests=(("resident", 200, 96, 0, 0),
              ("victim", 1100, 64, 0, 0),
              ("urgent", 700, 16, 5, 1),
              ("short", 90, 8, 1, 1)),
    train_batch=4, train_seq=512, train_steps=4,
    logit_tol=_BF16_LOGIT_TOL, loss_tol=_BF16_LOSS_TOL)

#: llama_tiny widths in fp32, for the CPU test of the phases. Chosen by
#: the caller, never fallen back to.
TINY = Size(
    name="tiny",
    hf_config={"model_type": "mistral", "vocab_size": 256,
               "hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
               "rope_theta": 10000.0, "torch_dtype": "float32"},
    serve_layers=2, train_layers=2,
    block_size=16, prefill_chunk=32, max_context=128,
    requests=(("resident", 20, 40, 0, 0),
              ("victim", 70, 24, 0, 0),
              ("urgent", 56, 4, 5, 1),
              ("short", 10, 4, 1, 1)),
    train_batch=8, train_seq=128, train_steps=4,
    logit_tol=1e-3, loss_tol=1e-3)


# ------------------------------------------------------------------ #
# compilation accounting
# ------------------------------------------------------------------ #
class CompileMeter:
    """Programs built, seconds spent building or fetching them, and how
    many came out of the persistent cache, since the last ``take()``."""

    def __init__(self):
        import jax
        self.programs = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self):
        out = {"programs": self.programs, "cache_hits": self.hits,
               "seconds": round(self.seconds, 1)}
        self.programs = self.hits = 0
        self.seconds = 0.0
        return out


def _against_last_run(cache_dir, total):
    """This run's compile seconds beside those of the last run that used
    the same cache directory (kept in a note inside it, so it lasts
    exactly as long as the cache does)."""
    note = os.path.join(cache_dir, "chip_smoke_last_run.json")
    try:
        with open(note) as f:
            last = json.load(f)
        said = (f"the last run on this cache spent {last['seconds']}s on "
                f"{last['programs']} programs ({last['cache_hits']} cached); "
                f"this one {total['seconds']}s ({total['cache_hits']} cached)")
    except (OSError, ValueError, KeyError):
        said = "no earlier run on this cache to compare with"
    os.makedirs(cache_dir, exist_ok=True)
    with open(note, "w") as f:
        json.dump(total, f)
    return said


# ------------------------------------------------------------------ #
# header
# ------------------------------------------------------------------ #
def require_chip():
    """The device this run is about, or SmokeFailure if it is hidden or
    absent. Reads the environment first so that no backend is touched
    when a variable already rules the chip out."""
    for var in ("JAX_PLATFORMS", "HDS_PLATFORM"):
        val = os.environ.get(var, "")
        if val and val.split(",")[0].strip().lower() != "tpu":
            raise SmokeFailure(f"{var}={val!r} hides the chip")
    if os.environ.get("HDS_DISABLE_PALLAS") == "1":
        raise SmokeFailure("HDS_DISABLE_PALLAS=1 swaps every kernel for "
                           "its reference")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(
            f"JAX found no accelerator (platform "
            f"{devices[0].platform!r}); this smoke measures nothing on "
            "a CPU")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def print_header(device, cache_dir):
    import importlib.metadata as md

    import jax

    from hcache_deepspeed_tpu import ops
    print(f"platform: {device['platform']}")
    print(f"device_kind: {device['kind']}")
    print(f"device_count: {device['count']}")
    print(f"jax {jax.__version__}, jaxlib {md.version('jaxlib')}, "
          f"libtpu {md.version('libtpu')}")
    print(f"compile cache: {cache_dir}")
    print(ops.op_report(), flush=True)


def _peak_bytes():
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _n_params(tree):
    import jax
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


# ------------------------------------------------------------------ #
# serve
# ------------------------------------------------------------------ #
def _engine(size, params, topology):
    from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
    from hcache_deepspeed_tpu.inference.factory import build_hf_engine
    hf = dict(size.hf_config, num_hidden_layers=size.serve_layers)
    config = RaggedInferenceEngineConfig(
        state_manager={"max_tracked_sequences": 8,
                       "max_ragged_sequence_count": 8,
                       "max_context": size.max_context,
                       "prefill_chunk": size.prefill_chunk},
        kv_cache={"block_size": size.block_size,
                  "num_blocks": size.pool_blocks,
                  "cache_dtype": hf["torch_dtype"]})
    return build_hf_engine(hf, params, config, topology=topology)


def _serve_params(size):
    from hcache_deepspeed_tpu.inference.factory import MODEL_FAMILIES
    from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM
    from hcache_deepspeed_tpu.models.seeded import seeded_params
    hf = dict(size.hf_config, num_hidden_layers=size.serve_layers)
    model_config = MODEL_FAMILIES[hf["model_type"]](hf)
    return seeded_params(LlamaForCausalLM(model_config),
                         {"input_ids": np.zeros((1, 128), np.int32)},
                         seed=0, dtype=hf["torch_dtype"])


def _uninterrupted_logits(engine, context):
    """Next-token logits of ``context`` prefilled in one go."""
    logits, _ = engine.put([0], [context])
    engine.flush(0)
    return np.asarray(logits[0], np.float32)


def _logit_gap(got, ref):
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def _program_text(engine, B, T):
    """Optimised HLO of the engine's forward over ``B`` lanes of ``T``
    positions, as this device's compiler wrote it. A trunk with
    recurrent layers also takes its state pools and the lanes' slots."""
    from hcache_deepspeed_tpu.inference.ragged.lanes import pack_lanes
    tok, start, t_len, tables = engine._blank_lanes(B, T)
    model, cache = engine.model, engine.cache
    pools = (cache.k, cache.v)
    slots = None
    if engine.recurrent:
        pools += (cache.state, cache.conv)
        slots = np.full((B,), engine.state.state_slots, np.int32)
    return model._fwd.lower(
        model.params, *pools, pack_lanes(tok, start, tables, t_len, slots)
    ).compile().as_text()


def _check_slice_program(engine, pool_shape, prefill_chunk):
    """A prompt slice's program holds the pool in place too, and where
    the platform has the kernel writes its K and V a block run at a time
    (``ops/kv_write.py``): no scatter of one-row updates into a pool."""
    from hcache_deepspeed_tpu.inference.ragged.kv_cache import (
        pool_scatters, pool_sized_copies)
    from hcache_deepspeed_tpu.platform import get_platform
    text = _program_text(engine, 1, prefill_chunk)
    copies = pool_sized_copies(text, pool_shape)
    check(not copies,
          "the slice program neither copies nor slices the KV pool or a "
          f"layer of it: {copies}")
    if get_platform().supports_pallas():
        scatters = pool_scatters(text, pool_shape)
        check(not scatters and "hds_kv_write" in text,
              "the slice program writes its K and V through hds_kv_write, "
              f"a block run at a time, and scatters no row: {scatters}")


def serve_phase(size, topology=None, one_chip=None):
    """Serve ``size.requests`` through a ServingServer in thread mode and
    check the preempt -> host latents -> restore round trip. Returns the
    compared context and its uninterrupted logits; given those of the
    one-chip run as ``one_chip``, also checks this topology's logits on
    that context against them."""
    from hcache_deepspeed_tpu.serving import (RequestState, ServerConfig,
                                              ServingServer)
    from hcache_deepspeed_tpu.serving.scheduler import greedy_sample

    rng = np.random.default_rng(0)
    vocab = size.hf_config["vocab_size"]
    params = _serve_params(size)
    engine = _engine(size, params, topology)
    print(f"  serving {size.name}: n_layer={size.serve_layers}, "
          f"{_n_params(params):,} parameters, pool "
          f"{size.pool_blocks} blocks x {size.block_size} tokens, "
          f"prefill_chunk={size.prefill_chunk}", flush=True)

    after_restore = {}   # uid -> (tokens out before the sample, logits)

    def sample(req, row):
        if req.n_restores and req.uid not in after_restore:
            after_restore[req.uid] = (len(req.tokens_out),
                                      np.array(row, np.float32))
        return greedy_sample(req, row)

    server = ServingServer(
        engine, sample_fn=sample,
        config=ServerConfig(prefill_chunk=size.prefill_chunk))
    server.start()
    live = {}
    try:
        for wave in (0, 1):
            for name, n_prompt, n_new, priority, w in size.requests:
                if w != wave:
                    continue
                prompt = [int(t) for t in rng.integers(0, vocab, n_prompt)]
                live[name] = server.submit(prompt=prompt,
                                           max_new_tokens=n_new,
                                           priority=priority)
            if wave == 0:
                deadline = time.monotonic() + SERVE_TIMEOUT_S
                while any(r.first_token_at is None for r in live.values()):
                    if server.error is not None:
                        raise server.error
                    if time.monotonic() > deadline:
                        raise SmokeFailure(
                            "wave 0 produced no first token in "
                            f"{SERVE_TIMEOUT_S:g}s")
                    time.sleep(0.002)
        for req in live.values():
            server.wait(req, timeout=SERVE_TIMEOUT_S)
    finally:
        server.stop()

    for name, req in live.items():
        print(f"  {name}: prompt {len(req.prompt)}, "
              f"{len(req.tokens_out)} tokens out, state "
              f"{req.state.name}, preemptions {req.n_preemptions}, "
              f"restores {req.n_restores}", flush=True)
    faults = server.scheduler.fault_summary()
    events = server.scheduler.events
    overlapped = server.scheduler.overlapped_restores
    leaked = engine.state.allocator.num_blocks - 1 - engine.free_blocks
    restore_stats = dict(engine.restore_stats)
    # the device holds one engine at a time: nothing below may keep the
    # served one (or its scheduler) alive
    del server, engine
    gc.collect()

    check(all(r.state == RequestState.DONE and
              len(r.tokens_out) == r.max_new_tokens
              for r in live.values()),
          "every request finished with all its tokens")
    check(faults["total_faults"] == 0,
          f"no engine fault was absorbed: {faults}")
    chunked = [e for e in events if e[1] == "admit" and
               int(e[3].split("=")[1]) > size.prefill_chunk]
    check(chunked, "a prompt longer than one prefill dispatch was "
                   "admitted and chunked")
    check(restore_stats["restores"] >= 1 and after_restore,
          f"restore_kv ran: {restore_stats}, overlapped with resident "
          f"decode: {overlapped}")
    check(leaked == 0, f"{leaked} KV blocks leaked")

    # the uninterrupted run: same weights, fresh engine, one prefill of
    # the exact context the restored sequence sampled from
    uid = min(after_restore)
    n_out, got = after_restore[uid]
    req = next(r for r in live.values() if r.uid == uid)
    context = list(req.prompt) + req.tokens_out[:n_out]
    fresh = _engine(size, params, topology)
    import jax
    from hcache_deepspeed_tpu.inference.ragged.kv_cache import (
        pool_sized_copies, stacked_layer_copies)
    ref = _uninterrupted_logits(fresh, context)
    # under tensor parallelism the program is one device's: its pool and
    # its weights are this device's shards
    local = lambda x: x.addressable_shards[0].data.shape
    _check_slice_program(fresh, local(fresh.cache.k), size.prefill_chunk)
    text = _program_text(fresh, 8, 1)
    copies = pool_sized_copies(text, local(fresh.cache.k))
    check(not copies,
          "the decode program neither copies nor slices the KV pool or a "
          f"layer of it: {copies}")
    copies = stacked_layer_copies(
        text, [local(leaf) for leaf in
               jax.tree.leaves(fresh.model.params["layers"])
               if leaf.ndim == 3])
    check(not copies,
          "the decode program reads each layer's weights inside the "
          f"matmul that uses them: {copies}")
    gap = _logit_gap(got, ref)
    check(np.all(np.isfinite(got)) and got.shape == (vocab,),
          f"restored logits are finite, shape {got.shape}")
    check(gap <= size.logit_tol,
          f"restored vs uninterrupted logits after {len(context)} tokens: "
          f"max |diff| is {gap:.4f} of max |logit| "
          f"(tolerance {size.logit_tol})")
    if one_chip is not None:
        gap = _logit_gap(
            _uninterrupted_logits(fresh, one_chip["context"]),
            one_chip["ref_logits"])
        check(gap <= size.logit_tol,
              f"tensor={topology.tensor_size} vs one-chip logits on the "
              f"one-chip context: max |diff| is {gap:.4f} of max |logit| "
              f"(tolerance {size.logit_tol})")
    print(f"  peak_bytes_in_use (process lifetime): {_peak_bytes()}",
          flush=True)
    return {"context": context, "ref_logits": ref}


# ------------------------------------------------------------------ #
# serve, a hybrid trunk (recurrent layers beside full attention)
# ------------------------------------------------------------------ #
#: one period of Olmo-Hybrid-7B at its published widths: three
#: gated-delta-rule layers and a full-attention layer (1.6 B parameters
#: with the embedding and the head, 3.2 GB in bf16)
OLMO_HYBRID_PERIOD = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 4,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "max_position_embeddings": 8192, "rms_norm_eps": 1e-6,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "torch_dtype": "bfloat16"}
#: the same period at toy width in fp32, for the CPU test of the phase
TINY_HYBRID_PERIOD = dict(
    OLMO_HYBRID_PERIOD, vocab_size=256, hidden_size=64,
    intermediate_size=128, num_attention_heads=4, num_key_value_heads=4,
    max_position_embeddings=256, linear_num_key_heads=4,
    linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, torch_dtype="float32")


def hybrid_phase(hf, block_size=64, prefill_chunk=512):
    """A prompt of three slices and a few decode steps through the
    hybrid trunk's two pools, then the chip's own decode program read
    for what it must not hold: a layer of a stacked weight leaf, a copy
    of the KV pool or of the recurrent-state pool."""
    import jax

    from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
    from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                        build_hf_engine)
    from hcache_deepspeed_tpu.inference.ragged.kv_cache import (
        pool_sized_copies, stacked_layer_copies)
    from hcache_deepspeed_tpu.models.olmo_hybrid import \
        OlmoHybridForCausalLM
    from hcache_deepspeed_tpu.models.seeded import seeded_params
    params = seeded_params(
        OlmoHybridForCausalLM(MODEL_FAMILIES[hf["model_type"]](hf)),
        {"input_ids": np.zeros((1, 128), np.int32)}, seed=0,
        dtype=hf["torch_dtype"])
    n_prompt = 2 * prefill_chunk + prefill_chunk // 3
    engine = build_hf_engine(hf, params, RaggedInferenceEngineConfig(
        state_manager={"max_tracked_sequences": 8,
                       "max_ragged_sequence_count": 8,
                       "max_context": 4 * prefill_chunk,
                       "prefill_chunk": prefill_chunk},
        kv_cache={"block_size": block_size,
                  "num_blocks": 2 + 4 * prefill_chunk // block_size,
                  "cache_dtype": hf["torch_dtype"]}))
    del params
    rng = np.random.default_rng(0)
    logits, latents = engine.put(
        [0], [rng.integers(0, hf["vocab_size"], n_prompt)])
    for _ in range(3):
        logits, _ = engine.put([0], [[int(np.argmax(logits[0]))]])
    check(np.all(np.isfinite(logits)),
          f"a {n_prompt}-token prompt in three slices and three decode "
          "steps through both pools give finite logits")
    check(np.asarray(latents[0]).shape[0] == 1,
          "latents left the program for the full layer only")
    model, cache = engine.model, engine.cache
    _check_slice_program(engine, cache.k.shape, prefill_chunk)
    text = _program_text(engine, 8, 1)
    copies = pool_sized_copies(text, cache.k.shape) + \
        pool_sized_copies(text, cache.state.shape)
    check(not copies,
          "the hybrid decode program neither copies nor slices the KV "
          f"pool, the recurrent-state pool or a layer of either: {copies}")
    copies = stacked_layer_copies(
        text, [leaf.shape for stack in ("lin_layers", "full_layers")
               for leaf in jax.tree.leaves(model.params[stack])
               if leaf.ndim == 3])
    check(not copies,
          "the hybrid decode program reads each layer's weights inside "
          f"the matmul that uses them: {copies}")
    engine.flush(0)
    check(engine.state.state_slots_in_use == 0 and
          engine.free_blocks == cache.num_blocks - 1,
          "the flush gave back the state slot and every KV block")
    print(f"  peak_bytes_in_use (process lifetime): {_peak_bytes()}",
          flush=True)


# ------------------------------------------------------------------ #
# serve, a latent-attention trunk (a pool of compressed KV rows)
# ------------------------------------------------------------------ #
#: GLM-4.7-Flash at its published widths, its leading dense layer and
#: one sparse layer (64 experts, one shared) under the whole vocabulary:
#: 1.36 B parameters, 2.7 GB in bf16
GLM_LATENT_PAIR = {
    "model_type": "glm4_moe_lite", "vocab_size": 154880,
    "hidden_size": 2048, "intermediate_size": 10240,
    "moe_intermediate_size": 1536, "num_hidden_layers": 2,
    "num_attention_heads": 20, "num_key_value_heads": 20,
    "max_position_embeddings": 8192, "rms_norm_eps": 1e-5,
    "rope_theta": 1000000, "rope_scaling": None, "n_routed_experts": 64,
    "num_experts_per_tok": 4, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1.8,
    "first_k_dense_replace": 1, "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "attention_bias": False, "torch_dtype": "bfloat16"}
#: the same pair at toy width in fp32, for the CPU test of the phase
TINY_LATENT_PAIR = dict(
    GLM_LATENT_PAIR, vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=4,
    max_position_embeddings=256, n_routed_experts=8, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
    v_head_dim=16, torch_dtype="float32")


def latent_phase(hf, block_size=64, prefill_chunk=512, logit_tol=0.02):
    """A prompt of three slices and a decode step through the latent
    pool; then the sequence evicted to its host cache rows and brought
    back through ``restore_kv`` (a ship and a write), its next logits
    against the uninterrupted ones; and the chip's own programs read for
    what they must not hold: a copy of either pool, a layer of a stacked
    weight leaf."""
    import jax

    from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
    from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                        build_hf_engine)
    from hcache_deepspeed_tpu.inference.ragged.kv_cache import (
        pool_sized_copies, stacked_layer_copies)
    from hcache_deepspeed_tpu.models.glm4_moe_lite import seeded_params
    cfg = MODEL_FAMILIES[hf["model_type"]](hf)
    params = seeded_params(cfg, seed=0, dtype=hf["torch_dtype"])
    n_prompt = 2 * prefill_chunk + prefill_chunk // 3
    engine = build_hf_engine(hf, params, RaggedInferenceEngineConfig(
        state_manager={"max_tracked_sequences": 8,
                       "max_ragged_sequence_count": 8,
                       "max_context": 4 * prefill_chunk,
                       "prefill_chunk": prefill_chunk},
        kv_cache={"block_size": block_size,
                  "num_blocks": 2 + 8 * prefill_chunk // block_size,
                  "cache_dtype": hf["torch_dtype"]}))
    del params
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(0, hf["vocab_size"], n_prompt)]
    logits, rows = engine.put([0], [prompt])
    fed = int(np.argmax(logits[0]))
    uninterrupted, _ = engine.put([0], [[fed]])
    rows = np.asarray(rows[0])
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    check(rows.shape == (cfg.n_layer, n_prompt, width),
          f"what went to the host is the cache row of every layer "
          f"({width} values a layer a token): {rows.shape}")
    engine.flush(0)
    before = dict(engine.restore_stats)
    engine.restore_kv([0], [prompt], [rows])
    restored, _ = engine.put([0], [[fed]])
    shipped = engine.restore_stats["bytes_shipped"] - before["bytes_shipped"]
    gap = _logit_gap(np.asarray(restored[0], np.float32),
                     np.asarray(uninterrupted[0], np.float32))
    check(np.all(np.isfinite(restored)) and gap <= logit_tol,
          f"evicted to host cache rows and restored ({shipped} bytes "
          f"shipped, nothing replayed): restored vs uninterrupted logits "
          f"after {n_prompt + 1} tokens differ by {gap:.5f} of max |logit| "
          f"(tolerance {logit_tol})")
    check(engine.latent_stats()["saved_state"] == "cache_row" and
          engine.restore_profile()["replay_flops_frac"] == 0.0,
          "the engine names its saved state: the cache row")
    model, cache = engine.model, engine.cache
    for pool in (cache.k, cache.v):
        _check_slice_program(engine, pool.shape, prefill_chunk)
    text = _program_text(engine, 8, 1)
    copies = pool_sized_copies(text, cache.k.shape) + \
        pool_sized_copies(text, cache.v.shape)
    check(not copies,
          "the latent decode program neither copies nor slices the pool "
          f"of c rows, the pool of r rows or a layer of either: {copies}")
    copies = stacked_layer_copies(
        text, [leaf.shape for stack in ("lead_layers", "layers")
               for leaf in jax.tree.leaves(model.params[stack])
               if leaf.ndim >= 3])
    check(not copies,
          "the latent decode program reads each layer's weights inside "
          f"the product that uses them: {copies}")
    engine.flush(0)
    check(engine.free_blocks == cache.num_blocks - 1,
          "the flush gave back every block of the latent pool")
    print(f"  peak_bytes_in_use (process lifetime): {_peak_bytes()}",
          flush=True)


# ------------------------------------------------------------------ #
# serve, a trunk with window and global layers (two block pools)
# ------------------------------------------------------------------ #
#: Command A+ at its published widths, one period (three window layers
#: and a global one), 4 of the 128 routed experts held beside the four
#: shared ones, an eighth of the vocabulary: 2.3 B parameters, 4.6 GB in
#: bf16 (the engine holds them twice while it stacks them)
COMMAND_A_PERIOD = {
    "model_type": "cohere2_moe", "vocab_size": 32768, "hidden_size": 4096,
    "intermediate_size": 4096, "num_hidden_layers": 4,
    "num_attention_heads": 128, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 8192, "layer_norm_eps": 1e-5,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": 4096, "rope_theta": 50000, "num_experts": 128,
    "num_experts_per_tok": 8, "num_shared_experts": 4,
    "norm_topk_prob": True, "experts_held": [0, 4], "logit_scale": 1,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16"}
#: the same period at toy width in fp32, for the CPU test of the phase
TINY_WINDOW_PERIOD = dict(
    COMMAND_A_PERIOD, vocab_size=256, hidden_size=64, intermediate_size=32,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    max_position_embeddings=512, sliding_window=64, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=2, experts_held=[2, 4],
    torch_dtype="float32")


def window_phase(hf, block_size=64, prefill_chunk=512, logit_tol=0.02):
    """A prompt longer than the window through both pools (the window
    layers' blocks behind the window freed on the way) and a decode
    step; then the sequence evicted to its host K/V rows and brought
    back through ``restore_kv`` into both pools (a ship and a write, of
    the window layers only the rows inside the window), its next logits
    against the uninterrupted ones."""
    from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
    from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                        build_hf_engine)
    from hcache_deepspeed_tpu.models.cohere2_moe import seeded_params
    cfg = MODEL_FAMILIES[hf["model_type"]](hf)
    params = seeded_params(cfg, seed=0, dtype=hf["torch_dtype"])
    window = cfg.sliding_window
    n_prompt = window + 2 * prefill_chunk + prefill_chunk // 3
    per_seq = -(-(window + prefill_chunk) // block_size) + 1
    engine = build_hf_engine(hf, params, RaggedInferenceEngineConfig(
        state_manager={"max_tracked_sequences": 8,
                       "max_ragged_sequence_count": 8,
                       "max_context": -(-(n_prompt + 8) // block_size)
                       * block_size,
                       "prefill_chunk": prefill_chunk},
        kv_cache={"block_size": block_size,
                  "num_blocks": 2 + 2 * -(-n_prompt // block_size),
                  "num_window_blocks": 2 + 2 * per_seq,
                  "cache_dtype": hf["torch_dtype"]}))
    del params
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(0, hf["vocab_size"], n_prompt)]
    logits, rows = engine.put([0], [prompt])
    fed = int(np.argmax(logits[0]))
    uninterrupted, _ = engine.put([0], [[fed]])
    rows = np.asarray(rows[0])
    width = 2 * cfg.n_kv_head * cfg.head_dim
    check(rows.shape == (cfg.n_layer, n_prompt, width),
          f"what went to the host is the K and V rows of every layer "
          f"({width} values a layer a token): {rows.shape}")
    pools = engine.kv_pool_stats()
    check(pools["window"]["released"] >= (n_prompt - window) // block_size
          - 1 and pools["window"]["peak_in_use"] - 1 <= per_seq,
          f"the window layers' blocks behind the window went back while "
          f"the sequence lived, and it never held more than {per_seq}: "
          f"{pools['window']}")
    engine.flush(0)
    before = dict(engine.restore_stats)
    engine.restore_kv([0], [prompt], [rows])
    restored, _ = engine.put([0], [[fed]])
    shipped = engine.restore_stats["bytes_shipped"] - before["bytes_shipped"]
    gap = _logit_gap(np.asarray(restored[0], np.float32),
                     np.asarray(uninterrupted[0], np.float32))
    check(np.all(np.isfinite(restored)) and gap <= logit_tol,
          f"evicted to host K/V rows and restored into both pools "
          f"({shipped} bytes shipped, nothing replayed): restored vs "
          f"uninterrupted logits after {n_prompt + 1} tokens differ by "
          f"{gap:.5f} of max |logit| (tolerance {logit_tol})")
    check(shipped < rows.nbytes,
          f"of the window layers only the rows inside the window were "
          f"shipped: {shipped} of {rows.nbytes} bytes")
    check(engine.latent_stats()["saved_state"] == "cache_row" and
          engine.restore_profile()["replay_flops_frac"] == 0.0,
          "the engine names its saved state: the cache row")
    moe = engine.moe_stats()
    first, count = cfg.held
    check(0 < moe["picks_held"] < moe["picks"].sum(),
          f"picks over all {cfg.num_experts} experts, {moe['picks_held']} "
          f"of {int(moe['picks'].sum())} on the {count} held")
    engine.flush(0)
    pools = engine.kv_pool_stats()
    check(pools["global"]["in_use"] == pools["window"]["in_use"] == 1,
          f"the flush gave back every block of both pools: {pools}")
    print(f"  peak_bytes_in_use (process lifetime): {_peak_bytes()}",
          flush=True)


# ------------------------------------------------------------------ #
# train
# ------------------------------------------------------------------ #
def train_phase(size, devices, zero_stage=0):
    """``hds.initialize`` + ``train_batch`` on a fixed batch over
    ``devices`` (data parallel). Returns the losses and how the
    parameters lie on the devices."""
    import jax

    from hcache_deepspeed_tpu.inference.factory import MODEL_FAMILIES
    from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM
    from hcache_deepspeed_tpu.parallel.topology import (MeshTopology,
                                                        TopologySpec)
    from dataclasses import replace
    hf = dict(size.hf_config, num_hidden_layers=size.train_layers)
    model_config = replace(MODEL_FAMILIES[hf["model_type"]](hf),
                           max_positions=size.train_seq)
    rng = np.random.default_rng(1)
    batch = {"input_ids": rng.integers(
        0, model_config.vocab_size, (size.train_batch, size.train_seq),
        dtype=np.int32)}
    config = {
        "train_batch_size": size.train_batch,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 3e-5, "weight_decay": 0.01}},
        "bf16": {"enabled": hf["torch_dtype"] == "bfloat16"},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10 ** 9,
    }
    topology = MeshTopology(TopologySpec(data=len(devices)),
                            devices=devices)
    engine, _, _, _ = hds.initialize(
        model=LlamaForCausalLM(model_config), config=config,
        example_batch=batch, topology=topology)
    losses = []
    for _ in range(size.train_steps):
        losses.append(engine.train_batch(batch=batch))
    jax.block_until_ready(engine.state["params"])
    losses = [float(x) for x in losses]
    print(f"  training {size.name}: n_layer={size.train_layers}, "
          f"{_n_params(engine.state['params']):,} parameters, ZeRO stage "
          f"{zero_stage} over {len(devices)} device(s), batch "
          f"{size.train_batch} x {size.train_seq}", flush=True)
    print(f"  losses: {[round(x, 4) for x in losses]}", flush=True)
    check(all(np.isfinite(losses)), "every loss is finite")
    check(losses[-1] < losses[0],
          f"the loss falls: {losses[0]:.4f} -> {losses[-1]:.4f}")
    print(f"  peak_bytes_in_use (process lifetime): {_peak_bytes()}",
          flush=True)
    leaves = jax.tree.leaves(engine.state["params"])
    held = {}
    for x in leaves:
        for shard in x.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) + \
                shard.data.nbytes
    biggest = max(leaves, key=lambda x: x.size)
    return {"losses": losses, "held_bytes": held,
            "param_bytes": sum(x.nbytes for x in leaves),
            "largest_leaf": biggest.shape,
            "largest_leaf_split":
                biggest.size // biggest.addressable_shards[0].data.size}


# ------------------------------------------------------------------ #
# four chips
# ------------------------------------------------------------------ #
def zero3_phase(size, devices, one_chip_losses):
    out = train_phase(size, devices, zero_stage=3)
    for step, (a, b) in enumerate(zip(out["losses"], one_chip_losses)):
        check(abs(a - b) <= size.loss_tol * max(abs(b), 1.0),
              f"step {step}: ZeRO-3 loss {a:.4f} vs one-chip {b:.4f} "
              f"(tolerance {size.loss_tol} of the loss, absolute under "
              f"a loss of 1)")
    held, total = out["held_bytes"], out["param_bytes"]
    print(f"  parameter bytes held per device: {held} of {total}",
          flush=True)
    check(out["largest_leaf_split"] == len(devices),
          f"the largest leaf {out['largest_leaf']} is split "
          f"{len(devices)} ways")
    # leaves under zero_optimization.stage3_param_persistence_threshold
    # stay replicated by design; at 7B width they are the norm scales
    check(len(held) == len(devices) and max(held.values()) < 0.9 * total,
          f"no device holds the parameters whole (largest share "
          f"{max(held.values()) / total:.2f})")


def tensor_phase(size, devices, one_chip):
    from hcache_deepspeed_tpu.parallel.topology import (MeshTopology,
                                                        TopologySpec)
    topology = MeshTopology(TopologySpec(data=1, tensor=len(devices)),
                            devices=devices)
    serve_phase(size, topology=topology, one_chip=one_chip)


# ------------------------------------------------------------------ #
def main():
    from hcache_deepspeed_tpu import ops
    from hcache_deepspeed_tpu.utils.compile_cache import compile_cache_dir
    t_start = time.monotonic()
    device = require_chip()
    import jax
    print_header(device, compile_cache_dir())
    meter = CompileMeter()
    size = MISTRAL_7B
    verdicts = {}

    def run(name, fn, *args):
        print(f"\n== {name} ==", flush=True)
        t0 = time.monotonic()
        out = fn(*args)
        took = time.monotonic() - t0
        compiles = meter.take()
        print(f"  {name}: passed in {took:.0f}s (a smoke's time, cold "
              f"compiles included: {compiles['programs']} programs, "
              f"{compiles['seconds']}s building or fetching them, "
              f"{compiles['cache_hits']} from the persistent cache)",
              flush=True)
        verdicts[name] = dict(compiles, seconds_total=round(took, 1))
        gc.collect()
        return out

    one_chip = run("serve", serve_phase, size)
    run("serve hybrid", hybrid_phase, OLMO_HYBRID_PERIOD)
    run("serve latent", latent_phase, GLM_LATENT_PAIR)
    run("serve window", window_phase, COMMAND_A_PERIOD)
    losses = run("train", train_phase, size, jax.devices()[:1])["losses"]
    if device["count"] >= 4:
        four = jax.devices()[:4]
        run("zero3 data=4", zero3_phase, size, four, losses)
        run("serve tensor=4", tensor_phase, size, four, one_chip)
    else:
        print(f"\nfour-chip phases: not run ({device['count']} device)")

    print("\n== footer ==")
    report = ops.fallback_report()
    check(not report, f"no op fell back to its reference: {report}")
    total = {k: round(sum(v[k] for v in verdicts.values()), 1)
             for k in ("programs", "cache_hits", "seconds")}
    for name, v in verdicts.items():
        print(f"  {name}: passed {v}")
    print(f"  compilation: {total['programs']} programs, "
          f"{total['cache_hits']} served by the persistent cache (it keeps "
          f"what took a second or more to build), {total['seconds']}s "
          f"building or fetching; whole run "
          f"{time.monotonic() - t_start:.0f}s (smoke times, not a record "
          f"of speed)")
    print("  " + _against_last_run(compile_cache_dir(), total))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as exc:
        sys.exit(f"chip_smoke: FAILED: {exc}")
