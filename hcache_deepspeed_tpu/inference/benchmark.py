"""Serving benchmark: prefill + ragged-decode throughput.

Reference analog: the FastGen benchmark harness behind
``blogs/deepspeed-fastgen/README.md`` (throughput/latency curves for the
v2 ragged engine). Measures, for a model served by
:class:`InferenceEngineV2`:

* prefill tokens/sec at a given prompt length,
* steady-state decode tokens/sec at several concurrent-batch sizes,
* decode latency as a function of *actual* context length (the paged
  kernel's work should scale with tokens in cache, not max_context).

CLI: ``bin/hds_serve_bench`` (JSON lines, one per measurement).
"""

import argparse
import functools
import json
import os
import time

import jax
import numpy as np

from .scheduling import SchedulingError, SchedulingResult

_PARAM_CACHE = {}


def _emit(results, row):
    # append + stream one result row (partial results survive a crash);
    # every row names the device it was measured on
    from ..platform import device_row
    row = dict(row, **device_row())
    results.append(row)
    print(json.dumps(row), flush=True)


_MODEL_SIZES = {
    "tiny": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 n_layer=2, n_head=4, n_kv_head=2),
    "1b": dict(vocab_size=32000, hidden_size=2048,
               intermediate_size=5504, n_layer=24, n_head=16,
               n_kv_head=16),
    "7b": dict(vocab_size=32000, hidden_size=4096,
               intermediate_size=11008, n_layer=32, n_head=32,
               n_kv_head=32),
}


def _model_config(model_size: str, max_context: int):
    """Config alone (shape math, no weights — the decode diag's
    floors-only mode must not pay a 7B host init for four tuples)."""
    from ..models.llama import LlamaConfig
    return LlamaConfig(max_positions=max_context, dtype="bfloat16",
                       use_flash=False, **_MODEL_SIZES[model_size])


def _model_params(model_size: str, max_context: int):
    """Config + params for one model size, built ONCE per process:
    seeded random weights drawn leaf by leaf in the serving dtype
    (``models/seeded.py``), so a 7B-width tree never exists in fp32 and
    nothing is traced through a kernel to make it."""
    from ..models.llama import LlamaForCausalLM
    from ..models.seeded import seeded_params

    key = (model_size, max_context)
    if key not in _PARAM_CACHE:
        cfg = _model_config(model_size, max_context)
        params = seeded_params(
            LlamaForCausalLM(cfg),
            {"input_ids": np.zeros((1, 8), np.int32)}, seed=0,
            dtype=cfg.compute_dtype)
        _PARAM_CACHE[key] = (cfg, params)
    return _PARAM_CACHE[key]


def _engine(model_size: str, max_context: int, batch: int,
            quantize: str = "", prefill_chunk: int = 0,
            latents: bool = False, latent_dtype: str = "",
            prefix_caching: bool = False):
    from .config import RaggedInferenceEngineConfig
    from .engine_v2 import InferenceEngineV2

    cfg, params = _model_params(model_size, max_context)
    blocks_needed = batch * (-(-max_context // 64)) + 2
    quant = {}
    if quantize:
        # group 128 = one TPU lane row: sub-lane groups (e.g. 64) pad
        # the stored int8 q and every quantization temp 2x. For the
        # k-major fused layout a LARGER group halves scale rows and
        # kernel grid steps — overridable for measurement sweeps.
        group = int(os.environ.get("HDS_QUANT_GROUP", "128"))
        quant = {"enabled": True, "bits": 8, "group_size": group,
                 "min_size": 1024,
                 "use_fused_kernel": quantize == "fused"}
    eng = InferenceEngineV2(
        cfg, params,
        config=RaggedInferenceEngineConfig(
            state_manager={"max_tracked_sequences": max(batch, 8),
                           "max_ragged_batch_size": 8192,
                           "max_ragged_sequence_count": max(batch, 8),
                           "max_context": max_context,
                           "prefill_chunk": prefill_chunk,
                           "prefix_caching": prefix_caching},
            kv_cache={"block_size": 64, "num_blocks": blocks_needed,
                      "cache_dtype": "bfloat16"},
            quantization=quant,
            hcache={"enable_latents": latents,
                    "latent_dtype": latent_dtype}))
    return cfg, eng


def run_restore(model_size="tiny", max_context=512, prompt_len=128,
                batches=(1, 4), quantize="", prefill_chunk=0,
                latent_dtype=""):
    """HCache headline: time-to-cache-ready for a returning sequence —
    ``restore_kv`` (QKV-only replay from saved latents) vs a full prefill
    recompute. This is the fork's distinctive capability
    (reference: ``engine_v2.py:108`` restore_kv vs re-``put``); the
    restore path runs one GEMM triple per layer instead of the whole
    transformer stack, so the speedup should approach
    total-FLOPs / QKV-FLOPs as the model grows.

    The latents are harvested once from a latents-enabled twin engine;
    the timed engine runs with latent capture OFF so the prefill baseline
    is a plain recompute (no latent materialization + D2H in the timed
    loop — that cost belongs to the *first* pass, not the re-prefill
    being compared against)."""
    results = []
    emit = functools.partial(_emit, results)

    rng = np.random.default_rng(0)
    for batch in batches:
        # harvest latents (same seed ⇒ identical weights as the timed
        # engine), then drop this engine
        cfg, eng_lat = _engine(model_size, max_context, batch,
                               latents=True, quantize=quantize,
                               prefill_chunk=prefill_chunk,
                               latent_dtype=latent_dtype)
        prompts = [list(rng.integers(0, cfg.vocab_size, (prompt_len,)))
                   for _ in range(batch)]
        uids = list(range(batch))
        _, latents = eng_lat.put(uids, prompts)
        del eng_lat

        cfg, eng = _engine(model_size, max_context, batch, latents=False,
                           quantize=quantize, prefill_chunk=prefill_chunk,
                           latent_dtype=latent_dtype)

        def sync():
            jax.block_until_ready(eng.cache.k)

        def clear():
            for u in uids:
                if eng.state.get_sequence(u) is not None:
                    eng.flush(u)

        # warm both programs (compile)
        eng.put(uids, prompts)
        clear()
        eng.restore_kv(uids, prompts, latents)
        sync()
        clear()

        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            eng.put(uids, prompts)
            sync()
            clear()
        prefill_ms = (time.perf_counter() - t0) / reps * 1000

        # the timed restore window runs under the span tracer so the
        # JSONL row carries the per-chunk staging breakdown (where the
        # restore time goes: chunks, shipped bytes, host staging ms)
        from ..telemetry import bench_extra
        from ..telemetry.tracer import get_tracer
        tracer = get_tracer()
        tracer_was = tracer.enabled
        tracer.configure(enabled=True)
        tracer.clear()
        t0 = time.perf_counter()
        for _ in range(reps):
            eng.restore_kv(uids, prompts, latents)
            sync()
            clear()
        restore_ms = (time.perf_counter() - t0) / reps * 1000
        tracer.configure(enabled=tracer_was)
        breakdown = bench_extra(tracer.events())

        emit({
            "phase": "hcache-restore", "batch": batch,
            "prompt_len": prompt_len,
            "latent_dtype": latent_dtype,
            "latent_mb": round(sum(l.nbytes for l in latents) / 2**20, 1),
            "prefill_recompute_ms": round(prefill_ms, 2),
            "restore_kv_ms": round(restore_ms, 2),
            "speedup": round(prefill_ms / restore_ms, 2),
            "extra": {"step_breakdown": breakdown}})
        del eng
    return results


def run_restore_marginal(model_size="tiny", max_context=512,
                         prompt_len=128, batches=(1, 4), quantize="",
                         latent_dtype="", chain=8):
    """Marginal-cost decomposition of the HCache restore story.

    Where the host link is slow the end-to-end numbers ``run_restore``
    reports are link-bound, not device-bound — both sides of the
    comparison measure the link. This splits the three components by
    chaining ``chain`` dispatches with ONE final sync and fitting the
    slope (the same fixed-vs-marginal method as ``hds_decode_diag``):

      * ``prefill_ms``  — marginal device cost of a full-stack prefill
        (``put(defer_fetch=True)``: no per-call logits D2H);
      * ``replay_ms``   — marginal device cost of the QKV-only restore
        replay from HBM-staged latents (``model.restore_kv`` on a
        ``jax.Array`` slab: no ship);
      * ``link_gbps`` / ``ship_ms`` — measured H2D bandwidth and the
        latent-slab ship at that bandwidth (double-buffered behind
        compute in the real path).

    ``speedup_replay = prefill_ms / replay_ms`` is the hardware story:
    what a co-located host (multi-GB/s DMA, where ship hides entirely
    under replay) gets back per returning sequence."""
    import jax

    results = []
    emit = functools.partial(_emit, results)
    rng = np.random.default_rng(0)
    for batch in batches:
        cfg, eng_lat = _engine(model_size, max_context, batch,
                               latents=True, quantize=quantize,
                               latent_dtype=latent_dtype)
        prompts = [list(rng.integers(0, cfg.vocab_size, (prompt_len,)))
                   for _ in range(batch)]
        uids = list(range(batch))
        _, latents = eng_lat.put(uids, prompts)
        del eng_lat

        cfg, eng = _engine(model_size, max_context, batch, latents=False,
                           quantize=quantize, latent_dtype=latent_dtype)

        def sync():
            jax.block_until_ready(eng.cache.k)

        def clear():
            for u in uids:
                if eng.state.get_sequence(u) is not None:
                    eng.flush(u)

        # --- the engine's own group staging (shared helper): creates
        # the sequences/blocks and the padded lane slab, so the staged
        # replay times the same compiled program restore_kv runs
        items = [(uid, np.asarray(p, np.int32), np.asarray(latents[j]))
                 for j, (uid, p) in enumerate(zip(uids, prompts))]
        lat, start, t_len, tables, seqs = eng._stage_restore_group(items)

        # --- measured H2D link bandwidth (the slab itself)
        jax.device_put(lat[:1]).block_until_ready()   # warm transfer path
        t0 = time.perf_counter()
        slab_dev = jax.device_put(lat)
        slab_dev.block_until_ready()
        ship_s = time.perf_counter() - t0
        link_gbps = lat.nbytes / max(ship_s, 1e-9) / 1e9

        # --- staged replay: warm (compile), then slope over `chain`
        eng.model.restore_kv(eng.cache, slab_dev, start, tables, t_len)
        sync()
        for seq in seqs:   # the staged group is now cache-resident
            seq.post_forward()

        def timed(fn, k):
            t0 = time.perf_counter()
            for _ in range(k):
                fn()
            sync()
            return time.perf_counter() - t0

        def replay_once():
            eng.model.restore_kv(eng.cache, slab_dev, start, tables,
                                 t_len)

        t1 = timed(replay_once, 1)
        tk = timed(replay_once, 1 + chain)
        replay_ms = max(tk - t1, 1e-9) / chain * 1000

        # --- full-stack prefill, deferred fetch (device cost only)
        clear()
        eng.put(uids, prompts, defer_fetch=True)   # warm the plain path
        sync()

        def prefill_once():
            clear()
            eng.put(uids, prompts, defer_fetch=True)

        t1 = timed(prefill_once, 1)
        tk = timed(prefill_once, 1 + chain)
        prefill_ms = max(tk - t1, 1e-9) / chain * 1000

        # --- end-to-end restore through this link (ship included)
        clear()

        def restore_once():
            clear()
            eng.restore_kv(uids, prompts, latents)

        restore_once()   # warm lane/group compile for this path
        t1 = timed(restore_once, 1)
        tk = timed(restore_once, 1 + chain)
        restore_e2e_ms = max(tk - t1, 1e-9) / chain * 1000

        def ratio(num, den):
            # slopes under the timer floor (CPU noise) make the ratio
            # meaningless — emit null rather than a absurd number
            return round(num / den, 2) if den > 1e-2 else None

        emit({
            "phase": "hcache-restore-marginal", "batch": batch,
            "prompt_len": prompt_len, "latent_dtype": latent_dtype,
            "latent_mb": round(lat.nbytes / 2**20, 2),
            "chain": chain,
            "link_gbps": round(link_gbps, 3),
            "ship_ms": round(ship_s * 1000, 2),
            "prefill_ms": round(prefill_ms, 2),
            "replay_ms": round(replay_ms, 2),
            "restore_e2e_ms": round(restore_e2e_ms, 2),
            "speedup_replay": ratio(prefill_ms, replay_ms),
            "speedup_e2e": ratio(prefill_ms, restore_e2e_ms)})
        clear()
        del eng
    return results


def run_restore_crossover(model_size="tiny", max_context=512,
                          prompt_lens=(32, 64, 128, 256), batch=1,
                          quantize="", latent_dtype="", chain=8,
                          out="RESTORE_CROSSOVER.jsonl"):
    """Crossover curve: marginal restore cost vs full prefill replay
    across prompt lengths, plus the analytic model's verdicts.

    For each prompt length the marginal device cost of a full-stack
    prefill and of the end-to-end restore (ship + QKV replay) are
    measured with the chained-dispatch slope method
    (:func:`run_restore_marginal`), the measured link bandwidth and
    prefill rate are fed into a :class:`~..serving.crossover.
    RestoreCrossoverModel` through its ``observe_*`` calibration hooks,
    and one JSONL row per length records both the measurement and the
    model's prediction — so the artifact shows where the measured
    curves cross AND whether the scheduler's analytic model would pick
    the cheaper side there. A summary row carries the calibrated rates
    and the first measured crossover length.

    Rows append to ``out`` (``out=""`` for stdout only)."""
    import jax

    from ..serving.crossover import CrossoverConfig, RestoreCrossoverModel

    results = []
    fh = open(out, "w") if out else None

    def emit(row):
        results.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if fh is not None:
            fh.write(line + "\n")
            fh.flush()

    rng = np.random.default_rng(0)
    cfg, eng_lat = _engine(model_size, max_context, batch, latents=True,
                           quantize=quantize, latent_dtype=latent_dtype)
    cfg, eng = _engine(model_size, max_context, batch, latents=False,
                       quantize=quantize, latent_dtype=latent_dtype)
    model = RestoreCrossoverModel(eng_lat.restore_profile(),
                                  CrossoverConfig(min_samples=1))

    def sync():
        jax.block_until_ready(eng.cache.k)

    def clear(engine, uids):
        for u in uids:
            if engine.state.get_sequence(u) is not None:
                engine.flush(u)

    def timed(fn, k):
        t0 = time.perf_counter()
        for _ in range(k):
            fn()
        sync()
        return time.perf_counter() - t0

    curve = []
    for prompt_len in prompt_lens:
        if prompt_len >= max_context:
            continue
        prompts = [list(rng.integers(0, cfg.vocab_size, (prompt_len,)))
                   for _ in range(batch)]
        uids = list(range(batch))
        _, latents = eng_lat.put(uids, prompts)
        clear(eng_lat, uids)

        # marginal full-stack prefill (deferred fetch: device cost only)
        eng.put(uids, prompts, defer_fetch=True)   # warm
        sync()

        def prefill_once():
            clear(eng, uids)
            eng.put(uids, prompts, defer_fetch=True)

        t1 = timed(prefill_once, 1)
        tk = timed(prefill_once, 1 + chain)
        prefill_ms = max(tk - t1, 1e-9) / chain * 1000

        # measured link bandwidth for THIS length's latent slab
        clear(eng, uids)
        items = [(uid, np.asarray(p, np.int32), np.asarray(latents[j]))
                 for j, (uid, p) in enumerate(zip(uids, prompts))]
        lat, start, t_len, tables, seqs = eng._stage_restore_group(items)
        jax.device_put(lat[:1]).block_until_ready()
        t0 = time.perf_counter()
        jax.device_put(lat).block_until_ready()
        ship_s = time.perf_counter() - t0
        for seq in seqs:   # undo the staging state ops
            seq.post_forward()
        clear(eng, uids)

        # marginal end-to-end restore (ship + replay, double-buffered)
        def restore_once():
            clear(eng, uids)
            eng.restore_kv(uids, prompts, latents)

        restore_once()   # warm the restore chain at this bucket
        t1 = timed(restore_once, 1)
        tk = timed(restore_once, 1 + chain)
        restore_ms = max(tk - t1, 1e-9) / chain * 1000
        clear(eng, uids)

        tokens = batch * prompt_len
        model.observe_ship(lat.nbytes, ship_s)
        model.observe_prefill(tokens, prefill_ms / 1000)
        model.observe_replay(tokens, restore_ms / 1000)
        curve.append((prompt_len, prefill_ms, restore_ms))

        emit({
            "phase": "restore-crossover", "model": model_size,
            "batch": batch, "prompt_len": prompt_len,
            "latent_dtype": latent_dtype,
            "latent_mb": round(lat.nbytes / 2**20, 3),
            "link_gbps": round(lat.nbytes / max(ship_s, 1e-9) / 1e9, 3),
            "prefill_ms": round(prefill_ms, 3),
            "restore_ms": round(restore_ms, 3),
            "measured_winner": "restore" if restore_ms <= prefill_ms
            else "recompute",
            "model_choice": model.decide(prompt_len),
            "restore_pred_ms": round(
                model.restore_cost_s(prompt_len) * 1000, 3),
            "recompute_pred_ms": round(
                model.recompute_cost_s(prompt_len) * 1000, 3)})

    # first measured crossover: the shortest length where restore wins
    cross_at = next((pl for pl, pre, res in curve if res <= pre), None)
    emit({"phase": "restore-crossover-summary", "model": model_size,
          "batch": batch, "prompt_lens": [c[0] for c in curve],
          "crossover_prompt_len": cross_at,
          "calibration": model.summary()})
    if fh is not None:
        fh.close()
    return results


def run_sweep(model_size="tiny", max_context=512, prompt_len=128,
              max_new=32, rates=(1.0, 2.0, 4.0), n_requests=16,
              max_batch=8, seed=0, quantize="", prefill_chunk=0,
              prefix_caching=False):
    """Throughput-latency curve under open-loop Poisson arrivals — the
    FastGen headline benchmark shape (reference:
    ``blogs/deepspeed-fastgen/README.md`` throughput vs latency at a
    token-rate SLA). For each offered request rate: requests arrive on
    a Poisson clock, are admitted into the continuous ragged batch as
    KV blocks allow, and decode to ``max_new`` tokens; reports
    effective rps, time-to-first-token and end-to-end latency
    percentiles, and generated tokens/sec."""
    results = []
    emit = functools.partial(_emit, results)

    cfg, eng = _engine(model_size, max_context, max_batch,
                       quantize=quantize, prefill_chunk=prefill_chunk,
                       prefix_caching=prefix_caching)
    rng = np.random.default_rng(seed)
    # with prefix caching, model the system-prompt workload: every
    # request shares the same leading half of the prompt
    shared_prefix = list(rng.integers(0, cfg.vocab_size,
                                      (prompt_len // 2,))) \
        if prefix_caching else []
    if prompt_len + max_new - 1 > min(max_context, cfg.max_positions):
        raise ValueError(
            f"prompt_len {prompt_len} + max_new {max_new} exceeds "
            f"max_context {max_context}")

    def percentile(xs, q):
        return round(float(np.percentile(np.asarray(xs), q)), 3)

    # Warm EVERY program shape ONCE, off-clock (shapes depend on
    # prompt_len/max_batch, not the rate): prefill lane counts covering
    # each power-of-two bucket up to _bucket(max_batch) — admission can
    # batch that many prefills into one dispatch — and the ragged
    # decode dispatch at every decode bucket that can occur (bucket
    # minimum is 8). A compile landing inside a timed loop would
    # corrupt that rate's percentiles and flatter later rates.
    # under prefix caching the timed loop's prompts ATTACH the shared
    # prefix and prefill only the tail — warm with the same shape, and
    # keep one warm sequence alive so the registered chain survives the
    # warmup flushes into the timed phase (steady-state behavior)
    if prefix_caching:
        warm_prompt = shared_prefix + list(
            rng.integers(0, cfg.vocab_size,
                         (prompt_len - len(shared_prefix),)))
    else:
        warm_prompt = list(rng.integers(0, cfg.vocab_size,
                                        (prompt_len,)))
    warm_counts = []
    b = 1
    while b < max_batch:
        warm_counts.append(b)
        b *= 2
    warm_counts.append(max_batch)
    from .engine_v2 import _bucket
    keeper_uid = 10 ** 6
    if prefix_caching:
        eng.put([keeper_uid], [warm_prompt])   # owns the shared chain
    warmed_decode = set()
    for k in warm_counts:
        warm_uids = list(range(k))
        eng.put(warm_uids, [warm_prompt] * k)
        if _bucket(k) not in warmed_decode:
            # decode lane buckets: _bucket(k, minimum=8) — warm each
            # distinct bucket any in-flight count 1..max_batch can
            # produce (warm_counts covers every power of two, so the
            # bucket set is complete)
            eng.put(warm_uids, [[1]] * k)
            warmed_decode.add(_bucket(k))
        for u in warm_uids:
            eng.flush(u)

    for rps in rates:
        stats0 = dict(eng.prefix_stats) if prefix_caching else None
        prompts = [shared_prefix +
                   list(rng.integers(0, cfg.vocab_size,
                                     (prompt_len - len(shared_prefix),)))
                   for _ in range(n_requests)]
        arrive = np.cumsum(rng.exponential(1.0 / rps, n_requests))
        state = {}      # i -> dict(start, first=None, end=None, left, tok)
        pending = list(range(n_requests))
        active = []
        t0 = time.perf_counter()
        while pending or active:
            now = time.perf_counter() - t0
            # admit arrived requests that fit (block budget, batch cap)
            admit = []
            for i in list(pending):
                if arrive[i] > now or len(active) + len(admit) >= max_batch:
                    break
                cand = active + admit + [i]
                # budget the WHOLE stretch (prompt + decode tokens) at
                # admission, like generate(): a request admitted on
                # prefill-only arithmetic could run out of blocks or
                # context mid-decode and abort the sweep
                lens = [1] * len(active) + \
                    [len(prompts[j]) + max_new - 1 for j in admit + [i]]
                if eng.can_schedule([100 + j for j in cand], lens) != \
                        SchedulingResult.Success:
                    break
                admit.append(i)
            if not active and not admit:
                if arrive[pending[0]] <= now:
                    # first arrived request can never fit — surface the
                    # verdict for the SAME whole-stretch length the
                    # admission check used
                    raise SchedulingError(eng.can_schedule(
                        [100 + pending[0]],
                        [len(prompts[pending[0]]) + max_new - 1]))
                # idle until the next arrival
                time.sleep(max(0.0, arrive[pending[0]] -
                               (time.perf_counter() - t0)))
                continue
            for i in admit:
                pending.remove(i)
                state[i] = {"start": arrive[i], "first": None,
                            "end": None, "left": max_new, "tok": None}
            step = active + admit
            toks = [[state[i]["tok"]] if i in active else prompts[i]
                    for i in step]
            step_logits, _ = eng.put([100 + i for i in step], toks)
            now = time.perf_counter() - t0
            finished = []
            for j, i in enumerate(step):
                st = state[i]
                if st["first"] is None:
                    st["first"] = now - st["start"]   # TTFT
                st["tok"] = int(np.argmax(step_logits[j]))
                st["left"] -= 1
                if st["left"] <= 0:
                    st["end"] = now - st["start"]
                    finished.append(i)
            for i in finished:
                eng.flush(100 + i)
            active = [i for i in step if i not in finished]

        makespan = max(s["end"] + s["start"] for s in state.values())
        row_extra = {}
        if prefix_caching:
            # per-rate delta, not engine-lifetime cumulative counters
            row_extra = {"prefix_stats": {
                k: eng.prefix_stats[k] - stats0[k]
                for k in eng.prefix_stats}}
        emit({"phase": "sweep", "decode_path": "host-driven",
              "offered_rps": rps, **row_extra,
              "effective_rps": round(n_requests / makespan, 3),
              "ttft_s": {"p50": percentile(
                  [s["first"] for s in state.values()], 50),
                  "p90": percentile(
                      [s["first"] for s in state.values()], 90)},
              "e2e_s": {"p50": percentile(
                  [s["end"] for s in state.values()], 50),
                  "p90": percentile(
                      [s["end"] for s in state.values()], 90)},
              "gen_tokens_per_sec": round(
                  n_requests * max_new / makespan, 1)})
    return results


def run_sweep_fused(model_size="tiny", max_context=512, prompt_len=128,
                    max_new=32, rates=(1.0, 2.0, 4.0), n_requests=16,
                    max_batch=8, seed=0, quantize="", prefill_chunk=0):
    """Throughput-latency curve on the on-device ``generate_fused``
    loop, batch-synchronous: arrived requests form a wave (up to
    max_batch), the whole wave decodes on device in ONE program, and
    arrivals during a wave queue for the next one.

    Honesty notes vs :func:`run_sweep` (rows carry ``decode_path`` so
    artifacts can't be conflated): no mid-stretch admission — this is a
    different scheduling discipline than continuous batching, traded
    for one host sync per wave instead of per token. TTFT is not
    separable on-device, so rows report end-to-end latency (queue wait
    + wave) only."""
    results = []
    emit = functools.partial(_emit, results)
    cfg, eng = _engine(model_size, max_context, max_batch,
                       quantize=quantize, prefill_chunk=prefill_chunk)
    rng = np.random.default_rng(seed)
    if prompt_len + max_new - 1 > min(max_context, cfg.max_positions):
        raise ValueError(
            f"prompt_len {prompt_len} + max_new {max_new} exceeds "
            f"max_context {max_context}")

    def percentile(xs, q):
        return round(float(np.percentile(np.asarray(xs), q)), 3)

    # warm every decode-lane bucket a wave can produce (n_steps and the
    # lane bucket are the static args; a compile inside the timed loop
    # would corrupt that rate's percentiles)
    warm_prompt = list(rng.integers(0, cfg.vocab_size, (prompt_len,)))
    k = 1
    warm_counts = []
    while k < max_batch:
        warm_counts.append(k)
        k *= 2
    warm_counts.append(max_batch)
    for k in warm_counts:
        eng.generate_fused([warm_prompt] * k, max_new_tokens=max_new)

    for rps in rates:
        prompts = [list(rng.integers(0, cfg.vocab_size, (prompt_len,)))
                   for _ in range(n_requests)]
        arrive = np.cumsum(rng.exponential(1.0 / rps, n_requests))
        pending = list(range(n_requests))
        e2e = {}
        waves = 0
        t0 = time.perf_counter()
        while pending:
            now = time.perf_counter() - t0
            ready = [i for i in pending if arrive[i] <= now]
            if not ready:
                time.sleep(max(0.0, arrive[pending[0]] -
                               (time.perf_counter() - t0)))
                continue
            wave = ready[:max_batch]
            eng.generate_fused([prompts[i] for i in wave],
                               max_new_tokens=max_new)
            done_at = time.perf_counter() - t0
            for i in wave:
                e2e[i] = done_at - arrive[i]
                pending.remove(i)
            waves += 1
        makespan = max(e2e[i] + arrive[i] for i in e2e)
        emit({"phase": "sweep-fused", "decode_path": "fused",
              "offered_rps": rps, "waves": waves,
              "effective_rps": round(n_requests / makespan, 3),
              "e2e_s": {"p50": percentile(list(e2e.values()), 50),
                        "p90": percentile(list(e2e.values()), 90)},
              "gen_tokens_per_sec": round(
                  n_requests * max_new / makespan, 1)})
    return results


def run_serve_loop(model_size="tiny", max_context=128, prompt_len=48,
                   max_new=24, rps=50.0, n_requests=64, seed=0,
                   num_blocks=10, block_size=16, max_lanes=4,
                   virtual_clock=False, parity_checks=3,
                   out="SERVE_LOOP.jsonl"):
    """Continuous-batching serving loop over a Poisson arrival trace.

    Drives the ``serving/`` subsystem end-to-end against a real engine:
    requests arrive open-loop at ``rps``, the scheduler admits them into
    the ragged batch, and the deliberately small KV pool (``num_blocks``)
    plus mixed priority classes force preempt→suspend-to-latents→
    ``restore_kv`` cycles mid-trace — the restore dispatch overlapped
    with resident decode. After the trace, every preempted request's
    token stream is re-derived with an uninterrupted ``generate`` run on
    the (now empty) engine and compared exactly: restore correctness is
    part of the artifact, not a side claim.

    Emits one jsonl row per request plus a summary row with TTFT/TPOT/
    queue-wait percentiles, preemption/restore counters, the restore
    overlap ratio and the parity verdict; rows also append to ``out``
    (set ``out=""`` to skip the file).

    ``virtual_clock=True`` replays the same trace on the deterministic
    simulated timeline instead of wall time (policy debugging; the
    acceptance path runs with it off).
    """
    from ..serving import (Request, ServerConfig, ServingServer,
                           VirtualClock)
    from .config import RaggedInferenceEngineConfig
    from .engine_v2 import InferenceEngineV2

    results = []
    fh = open(out, "w") if out else None

    def emit(row):
        results.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if fh is not None:
            fh.write(line + "\n")
            fh.flush()

    if prompt_len + max_new > max_context:
        raise ValueError(f"prompt_len {prompt_len} + max_new {max_new} "
                         f"exceeds max_context {max_context}")
    cfg, params = _model_params(model_size, max_context)

    def build_engine():
        return InferenceEngineV2(
            cfg, params,
            config=RaggedInferenceEngineConfig(
                state_manager={"max_tracked_sequences": 2 * max_lanes,
                               "max_ragged_batch_size": 4096,
                               "max_ragged_sequence_count": max_lanes,
                               "max_context": max_context},
                kv_cache={"block_size": block_size,
                          "num_blocks": num_blocks,
                          "cache_dtype": "bfloat16"},
                hcache={"enable_latents": True}))

    eng = build_engine()
    rng = np.random.default_rng(seed)

    # warm every program the trace can hit, off-clock: each prefill
    # lane bucket the pool can hold concurrently, the ragged decode
    # bucket, and the restore chain at both token buckets a mid-trace
    # restore can land in (a compile inside the trace would corrupt
    # the percentiles)
    warm_prompt = list(rng.integers(0, cfg.vocab_size, (prompt_len,)))
    per_req = -(-prompt_len // block_size)
    fit = max(1, min(max_lanes, (num_blocks - 1) // per_req))
    for k in range(1, fit + 1):
        uids = list(range(k))
        eng.put(uids, [warm_prompt] * k)
        if k == 1:
            # decode lanes bucket to 8 regardless of count, so one
            # decode warms the dispatch for every in-flight size
            eng.put(uids, [[1]])
        for u in uids:
            eng.flush(u)
    for t in sorted({prompt_len,
                     min(prompt_len + max_new - 1, max_context - 1)}):
        toks = list(rng.integers(0, cfg.vocab_size, (t,)))
        _, lat = eng.put([0], [toks])
        eng.flush(0)
        eng.restore_kv([0], [toks], [lat[0]])
        eng.flush(0)

    arrive = np.cumsum(rng.exponential(1.0 / rps, n_requests))
    clock = VirtualClock() if virtual_clock else None
    server = ServingServer(
        eng, clock=clock,
        config=ServerConfig(max_queue_depth=n_requests + 1,
                            kv_demand_fraction=float("inf")))
    # arrival times are trace-relative; rebase onto the server's clock
    # (VirtualClock starts at 0, MonotonicClock wherever it is now)
    base = server.clock.now()
    reqs = []
    for i in range(n_requests):
        prompt = list(rng.integers(0, cfg.vocab_size, (prompt_len,)))
        # mixed priority classes: the high-priority minority arrives
        # into a loaded pool and evicts low-priority residents
        reqs.append(Request(uid=i, prompt=prompt, max_new_tokens=max_new,
                            arrival_time=base + float(arrive[i]),
                            priority=5 if i % 5 == 4 else 0))
    # the traced window covers the whole served trace: the summary row
    # then carries the span-derived breakdown (restore staging chunks,
    # bytes, the pair-computed overlap ratio) beside the counters it
    # must agree with
    from ..telemetry import bench_extra
    from ..telemetry.tracer import get_tracer
    tracer = get_tracer()
    tracer_was = tracer.enabled
    tracer.configure(enabled=True)
    tracer.clear()
    t0 = time.perf_counter()
    metrics = server.run_trace(reqs)
    wall_s = time.perf_counter() - t0
    tracer.configure(enabled=tracer_was)
    step_breakdown = bench_extra(tracer.events())

    dropped = [r for r in reqs if r.state.name != "DONE"]
    for r in reqs:
        emit({"phase": "serve-loop", "request": r.uid,
              "priority": r.priority, "state": r.state.name,
              "tokens": len(r.tokens_out),
              "ttft_s": None if r.ttft() is None
              else round(r.ttft(), 4),
              "tpot_s": None if r.tpot() is None
              else round(r.tpot(), 5),
              "queue_wait_s": None if r.queue_wait() is None
              else round(r.queue_wait(), 4),
              "preemptions": r.n_preemptions,
              "restores": r.n_restores})

    # restore correctness: preempted streams must equal uninterrupted
    # greedy decode of the same prompt (the engine is empty post-trace)
    preempted = sorted((r for r in reqs if r.n_preemptions > 0),
                       key=lambda r: r.uid)
    parity = {"checked": 0, "ok": 0}
    for r in preempted[:parity_checks]:
        ref = eng.generate([r.prompt], max_new_tokens=r.max_new_tokens)
        parity["checked"] += 1
        parity["ok"] += int(ref[0] == r.tokens_out)

    s = metrics.summary()
    emit({"phase": "serve-loop-summary", "model": model_size,
          "n_requests": n_requests, "rps": rps,
          "prompt_len": prompt_len, "max_new": max_new,
          "kv_blocks": num_blocks, "block_size": block_size,
          "virtual_clock": bool(virtual_clock),
          "dropped": len(dropped),
          "wall_s": round(wall_s, 3),
          "ttft_s": s["ttft_s"], "tpot_s": s["tpot_s"],
          "queue_wait_s": s["queue_wait_s"],
          "preemptions": s["counters"]["preemptions"],
          "restores": s["counters"]["restores"],
          "restore_overlap_ratio":
              s["gauges"]["restore_overlap_ratio"],
          "restore_stats": dict(eng.restore_stats),
          "parity": parity,
          "gen_tokens_per_sec": round(
              s["counters"]["tokens_out"] / max(wall_s, 1e-9), 1),
          "extra": {"step_breakdown": step_breakdown}})

    # SLO burn rates + a format-validated Prometheus snapshot: the
    # exposition payload itself is operator surface, the artifact
    # records that it validated and what the burn gauges read at
    # trace end (ROADMAP item 4's future degradation input signal)
    from ..telemetry.prometheus import validate_prometheus_text
    snap = server.metrics_snapshot()
    prom_errors = validate_prometheus_text(snap["prometheus"])
    emit({"phase": "serve-loop-slo",
          "burn_rates": {o["name"]: o["burn_rate"]
                         for o in s.get("slo", {}).get("objectives",
                                                       [])},
          "objectives": s.get("slo", {}).get("objectives", []),
          "degraded_fraction":
              s.get("slo", {}).get("degraded_fraction", 0.0),
          "prometheus_bytes": len(snap["prometheus"]),
          "prometheus_valid": not prom_errors,
          "prometheus_errors": prom_errors[:5]})

    # regression sentinel self-compare vs the committed trajectory
    # (non-fatal: the artifact records the verdicts, `perf check`
    # gates with an exit code)
    from ..perf import self_check_rows
    emit(self_check_rows(out or "SERVE_LOOP.jsonl", results))
    if fh is not None:
        fh.close()
    if prom_errors:
        raise RuntimeError(
            f"prometheus snapshot failed validation: {prom_errors}")
    if dropped:
        raise RuntimeError(
            f"serve_loop dropped {len(dropped)} requests: "
            f"{[(r.uid, r.state.name, r.reject_reason) for r in dropped]}")
    if parity["checked"] and parity["ok"] != parity["checked"]:
        raise RuntimeError(f"restore parity failed: {parity}")
    return results


def run_chaos_serve(seed=0, n_requests=32, runs=2,
                    out="CHAOS_SERVE.jsonl", **chaos_kw):
    """Chaos serving mode: seeded fault plans replayed over the
    virtual-clock simulation (``resilience.chaos.run_chaos``), with
    the robustness invariants asserted and the determinism gate run
    inline (``runs`` identical-seed replays must produce identical
    event digests). Emits one jsonl row per request, one per fault
    site, a checkpoint-hardening phase (save retry under an injected
    ``ckpt.write`` fault + corrupt-manifest fallback), and a summary
    row. Exits nonzero (raises) on any invariant violation — the
    artifact IS the acceptance evidence."""
    import shutil
    import tempfile

    from ..resilience import run_chaos
    from ..resilience.faults import FaultPlan, FaultRule, injected

    results = []
    fh = open(out, "w") if out else None

    def emit(row):
        results.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if fh is not None:
            fh.write(line + "\n")
            fh.flush()

    chaos = [run_chaos(seed=seed, n_requests=n_requests, **chaos_kw)
             for _ in range(max(1, runs))]
    r = chaos[0]
    digests = [c.event_digest for c in chaos]
    deterministic = len(set(digests)) == 1
    emit({"phase": "chaos-plan", "seed": seed, "plan": r.plan})
    for req in r.requests:
        emit({"phase": "chaos-request", **req})
    for site, n in sorted(r.fault_summary["by_site"].items()):
        emit({"phase": "chaos-fault-site", "site": site, "fired": n})

    # checkpoint-hardening phase: a transient ckpt.write fault is
    # absorbed by the bounded save retry; a corrupted manifest on the
    # newest checkpoint falls back to the previous one on restore
    from ..runtime.checkpoint_engine import SyncCheckpointEngine
    from ..runtime.checkpointing import load_checkpoint, save_checkpoint
    tmp = tempfile.mkdtemp(prefix="hds_chaos_ckpt_")
    try:
        state_v1 = {"params": np.arange(8, dtype=np.float32)}
        state_v2 = {"params": np.arange(8, dtype=np.float32) * 2}
        save_checkpoint(tmp, "step1", state_v1, {"step": 1},
                        checkpoint_engine=SyncCheckpointEngine())
        with injected(FaultPlan(seed=seed, rules=[
                FaultRule("ckpt.write", at_hits=(1,))])):
            save_checkpoint(tmp, "step2", state_v2, {"step": 2},
                            checkpoint_engine=SyncCheckpointEngine())
        retried_ok = True
        manifest = os.path.join(tmp, "step2", "hds_manifest.json")
        with open(manifest, "w") as mf:
            mf.write("{corrupt json")
        template = {"params": np.zeros(8, np.float32)}
        restored, meta = load_checkpoint(tmp, None, template)
        fallback_ok = (restored is not None and
                       meta.get("fallback_from") == "step2" and
                       np.array_equal(restored["params"],
                                      state_v1["params"]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    emit({"phase": "chaos-ckpt", "save_retry_ok": retried_ok,
          "fallback_ok": bool(fallback_ok),
          "sites": ["ckpt.write", "ckpt.read"]})

    emit({"phase": "chaos-summary", "seed": seed,
          "n_requests": n_requests, "runs": len(chaos),
          "deterministic": deterministic,
          "event_digest": digests[0],
          "invariants_ok": all(c.ok for c in chaos),
          "violations": sum((c.violations for c in chaos), []),
          "invariants": r.invariants,
          "fault_summary": r.fault_summary,
          "counters": r.metrics["counters"],
          "failures": r.metrics["failures"],
          "rejected": r.metrics["rejected"]})
    if fh is not None:
        fh.close()
    if not all(c.ok for c in chaos):
        raise RuntimeError(
            f"chaos invariants violated: "
            f"{sum((c.violations for c in chaos), [])}")
    if not deterministic:
        raise RuntimeError(
            f"chaos determinism gate failed: digests {digests}")
    if not fallback_ok:
        raise RuntimeError("checkpoint fallback-to-previous failed")
    return results


def run_fleet_serve(seed=0, n_replicas=3, n_requests=48, runs=2,
                    out="FLEET_SERVE.jsonl", **chaos_kw):
    """Fleet serving mode: the N-replica router + latent-migration
    stack under seeded replica crash/hang/partition faults on the
    shared virtual clock (``resilience.chaos.run_fleet_chaos``). The
    first run is traced so the migration/decode overlap ratio in the
    artifact is SPAN-derived (``fleet.step`` spans carry both sides of
    the pair) and must agree with the fleet's counters; ``runs``
    identical-seed replays gate byte-identical event digests. Emits
    per-replica occupancy rows, per-migration rows, and a summary the
    perf registry indexes. Raises on any invariant violation — the
    artifact IS the acceptance evidence."""
    from ..resilience import run_fleet_chaos
    from ..telemetry.tracer import get_tracer

    results = []
    fh = open(out, "w") if out else None

    def emit(row):
        results.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if fh is not None:
            fh.write(line + "\n")
            fh.flush()

    # every run traced: the crossover model mines the span buffer when
    # the tracer is on, so mixing traced/untraced runs would change
    # calibration (and the digest) between them
    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)
    chaos = []
    span_events = None
    try:
        for _ in range(max(1, runs)):
            tracer.clear()
            chaos.append(run_fleet_chaos(
                seed=seed, n_replicas=n_replicas,
                n_requests=n_requests, **chaos_kw))
            if span_events is None:
                span_events = tracer.events()
    finally:
        tracer.configure(enabled=was)
    r = chaos[0]
    digests = [c.event_digest for c in chaos]
    deterministic = len(set(digests)) == 1

    # span-derived migration/decode overlap: each fleet.step span
    # carries (in_transit, decode_lanes); the ratio read off the spans
    # must equal the counter-derived one in the summary
    steps = [e for e in span_events
             if e.get("ph") == "X" and e.get("name") == "fleet.step"]
    transit = [e for e in steps
               if (e.get("args") or {}).get("in_transit", 0) > 0]
    overlapped = [e for e in transit
                  if (e.get("args") or {}).get("decode_lanes", 0) > 0]
    span_ratio = len(overlapped) / len(transit) if transit else 0.0
    counter_ratio = r.invariants["migration_overlap_ratio"]
    spans_agree = abs(span_ratio - counter_ratio) < 1e-9

    emit({"phase": "fleet-plan", "seed": seed,
          "n_replicas": n_replicas, "n_requests": n_requests,
          "plan": r.plan})
    for rid, rep in sorted(r.fleet_summary["replicas"].items()):
        emit({"phase": "fleet-replica", "replica": int(rid),
              "state": rep["state"], "steps": rep["steps"],
              "mean_occupancy": rep["mean_occupancy"],
              "kv_util_peak": rep["kv_util_peak"],
              "free_blocks": rep["free_blocks"],
              "initial_free_blocks": rep["initial_free_blocks"],
              "done": rep["done"],
              "preemptions": rep["counters"]["preemptions"],
              "restores": rep["counters"]["restores"],
              "recompute_reentries":
                  rep["counters"]["recompute_reentries"]})
    for m in r.migrations:
        emit({"phase": "fleet-migration", **m})
    for req in r.requests:
        emit({"phase": "fleet-request", **req})
    c = r.invariants["counters"]
    emit({"phase": "fleet-summary", "seed": seed,
          "n_replicas": n_replicas, "n_requests": n_requests,
          "runs": len(chaos),
          "deterministic": deterministic,
          "event_digest": digests[0],
          "invariants_ok": all(x.ok for x in chaos),
          "violations": sum((x.violations for x in chaos), []),
          "migration_balance_ok":
              r.invariants["migration_balance_ok"],
          "evictions": c["evictions"], "landings": c["landings"],
          "recompute_landings": c["recompute_landings"],
          "expired_in_transit": c["expired_in_transit"],
          "replica_crashes": c["replica_crashes"],
          "replica_hangs": c["replica_hangs"],
          "replica_partitions": c["replica_partitions"],
          "migration_overlap_ratio": counter_ratio,
          "span_overlap_ratio": round(span_ratio, 6),
          "span_counter_agreement": spans_agree,
          "replica_states": r.invariants["replica_states"],
          "router": r.fleet_summary["router"]})

    # regression sentinel self-compare vs the committed trajectory
    # (non-fatal: the artifact records verdicts; `perf check` gates)
    from ..perf import self_check_rows
    emit(self_check_rows(out or "FLEET_SERVE.jsonl", results))
    if fh is not None:
        fh.close()
    if not all(x.ok for x in chaos):
        raise RuntimeError(
            f"fleet chaos invariants violated: "
            f"{sum((x.violations for x in chaos), [])}")
    if not deterministic:
        raise RuntimeError(
            f"fleet determinism gate failed: digests {digests}")
    if not spans_agree:
        raise RuntimeError(
            f"span-derived overlap {span_ratio} != counter ratio "
            f"{counter_ratio}")
    return results


def run_disagg_serve(seed=0, n_prefill=1, n_decode=3, runs=2,
                     out="DISAGG_SERVE.jsonl", **compare_kw):
    """Disaggregated prefill/decode serving mode: the tier coordinator
    (``serving/disagg.py``) vs an equal-replica colocated fleet on one
    seeded mixed long-prompt + chatty trace, on the shared virtual
    clock. The acceptance gates run inline and the artifact records
    them: decode-tier TPOT p99 strictly better than the colocated
    baseline, bitwise disagg-vs-colocated token-stream parity, a
    span-derived handoff/decode overlap ratio (> 0, counter-agreeing),
    and byte-identical event digests across ``runs`` same-seed runs.
    Also emits an int8-latent-wire phase (wire-bytes attribution +
    stream parity vs the full-width wire), a chunked-prefill phase
    (chunk accounting on the prefill tier), and a tier-chaos phase
    (``resilience.chaos.run_disagg_chaos`` invariants + two-run
    determinism). Raises on any gate failure — the artifact IS the
    acceptance evidence."""
    from ..comm.comms_logging import get_comms_logger
    from ..resilience import run_disagg_chaos
    from ..serving import DisaggConfig, compare_disagg_vs_colocated

    results = []
    fh = open(out, "w") if out else None

    def emit(row):
        results.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if fh is not None:
            fh.write(line + "\n")
            fh.flush()

    r = compare_disagg_vs_colocated(seed=seed, n_prefill=n_prefill,
                                    n_decode=n_decode, runs=runs,
                                    **compare_kw)
    emit({"phase": "disagg-plan", "seed": seed,
          "n_prefill": n_prefill, "n_decode": n_decode,
          "runs": runs, "trace": r.trace_kw})
    for tier, t in sorted(r.tier_summary.items()):
        emit({"phase": "disagg-tier", "tier": tier, **t})
    for row in r.requests:
        emit({"phase": "disagg-request", **row})
    for h in r.handoffs:
        emit({"phase": "disagg-handoff", **h})

    m = r.metrics
    c = r.summary["counters"]
    emit({"phase": "disagg-summary", "seed": seed,
          "n_prefill": n_prefill, "n_decode": n_decode,
          "runs": runs,
          "deterministic": r.deterministic,
          "event_digest": r.disagg_digests[0],
          "colocated_digest": r.colocated_digest,
          "stream_parity": r.stream_parity,
          "invariants_ok": r.ok,
          "violations": r.violations,
          "handoffs": c["handoffs"],
          "handoff_landings": c["handoff_landings"],
          "colocated_decodes": c["colocated_decodes"],
          "handoff_overlap_ratio":
              r.summary["handoff_overlap_ratio"],
          "span_handoff_ratio": round(r.span_handoff_ratio, 6),
          "span_counter_agreement": r.span_counter_agreement,
          "decode_tier_tpot_p95":
              m["disagg"]["decode_tier_tpot_p95"],
          "decode_tier_tpot_p99":
              m["disagg"]["decode_tier_tpot_p99"],
          "colocated_tpot_p95": m["colocated"]["tpot_p95"],
          "colocated_tpot_p99": m["colocated"]["tpot_p99"],
          "disagg_tpot_p99": m["disagg"]["tpot_p99"],
          "disagg_ttft_p99": m["disagg"]["ttft_p99"],
          "colocated_ttft_p99": m["colocated"]["ttft_p99"],
          "handoff_transit_p99":
              m["disagg"]["handoff_transit_p99"],
          "metrics": m})

    # int8 latent wire: same comparison with the quantized handoff
    # payload; the streams must stay bitwise-equal to the full-width
    # run and the wire bytes must be attributed as a matched pair
    logger = get_comms_logger()
    logger_was = logger.enabled
    logger.configure(enabled=True)
    logger.reset()
    try:
        r8 = compare_disagg_vs_colocated(
            seed=seed, n_prefill=n_prefill, n_decode=n_decode,
            runs=runs,
            disagg=DisaggConfig(n_prefill=n_prefill,
                                n_decode=n_decode,
                                handoff_amortization=2.0,
                                handoff_wire_bits=8),
            **compare_kw)
        wire = logger.wire_savings_summary().get("latent_handoff", {})
    finally:
        logger.reset()
        logger.configure(enabled=logger_was)
    int8_parity = all(a["tokens"] == b["tokens"]
                      for a, b in zip(r.requests, r8.requests))
    emit({"phase": "disagg-int8-wire", "seed": seed,
          "invariants_ok": r8.ok, "violations": r8.violations,
          "deterministic": r8.deterministic,
          "stream_parity_vs_fullwidth": int8_parity,
          "wire_bytes": wire.get("wire_bytes"),
          "unquantized_equiv_bytes":
              wire.get("unquantized_equiv_bytes"),
          "wire_fraction": wire.get("fraction"),
          "op_kind": wire.get("op_kind")})

    # chunked prefill on the prefill tier (ROADMAP item 4, first
    # slice): same comparison with scheduler-grain chunking — chunk
    # accounting must be non-zero and every gate must still hold
    rc = compare_disagg_vs_colocated(
        seed=seed, n_prefill=n_prefill, n_decode=n_decode, runs=runs,
        prefill_chunk=16, **compare_kw)
    chunks = sum(
        rep["counters"]["prefill_chunks"]
        for rep in rc.summary["replicas"].values())
    emit({"phase": "disagg-chunked-prefill", "seed": seed,
          "prefill_chunk": 16,
          "invariants_ok": rc.ok, "violations": rc.violations,
          "deterministic": rc.deterministic,
          "stream_parity": rc.stream_parity,
          "prefill_chunks": chunks,
          "decode_tier_tpot_p99":
              rc.metrics["disagg"]["decode_tier_tpot_p99"],
          "colocated_tpot_p99":
              rc.metrics["colocated"]["tpot_p99"]})

    # tier-scoped chaos: prefill + decode replica crashes mid-trace,
    # never-dropped semantics, two-run digest determinism
    chaos = [run_disagg_chaos(seed=seed) for _ in range(max(1, runs))]
    cdigests = [x.event_digest for x in chaos]
    emit({"phase": "disagg-chaos", "seed": seed,
          "runs": len(chaos),
          "deterministic": len(set(cdigests)) == 1,
          "event_digest": cdigests[0],
          "invariants_ok": all(x.ok for x in chaos),
          "violations": sum((x.violations for x in chaos), []),
          "crashed_tiers": chaos[0].invariants["crashed_tiers"],
          "replica_states": chaos[0].invariants["replica_states"],
          "counters": chaos[0].invariants["counters"]})

    from ..perf import self_check_rows
    emit(self_check_rows(out or "DISAGG_SERVE.jsonl", results))
    if fh is not None:
        fh.close()
    failures = []
    if not r.ok:
        failures.append(f"disagg gates: {r.violations}")
    if not r8.ok or not int8_parity:
        failures.append(f"int8 wire: {r8.violations} "
                        f"parity={int8_parity}")
    if not rc.ok or not chunks:
        failures.append(f"chunked prefill: {rc.violations} "
                        f"chunks={chunks}")
    if not all(x.ok for x in chaos) or len(set(cdigests)) != 1:
        failures.append("tier chaos invariants/determinism")
    if failures:
        raise RuntimeError(f"disagg-serve gates failed: {failures}")
    return results


def _spec_digest(events) -> str:
    import hashlib
    payload = json.dumps(events, sort_keys=True,
                         separators=(",", ":")).encode()
    return hashlib.sha256(payload).hexdigest()


def run_spec_serve(seed=0, runs=2, out="SPEC_SERVE.jsonl"):
    """``--spec-serve``: CPU-deterministic audit of scheduler-
    dispatched speculative decoding + fleet-wide radix prefix reuse
    with latent prefix broadcast (docs/serving.md), on the shared
    virtual clock. Four phases, each gated inline — the artifact IS
    the acceptance evidence:

    * ``spec-lookup`` — lookup-friendly trace on one replica:
      speculative vs non-speculative scheduler, gating bitwise stream
      parity, accepted-tokens/step > 1.3 and a virtual-clock speedup;
    * ``spec-mixed`` — chatty + agent-swarm shared-prefix +
      long-prompt mix on a 3-replica fleet, speculation + prefix
      reuse + broadcast ON vs the affinity-only non-speculative
      fleet: stream parity, TTFT/TPOT p99s, leak/terminal invariants;
    * ``spec-prefix`` — the affinity-vs-load conflict trace: the warm
      replica is pinned hot so the router places sharers cold and the
      fleet must broadcast the common prefix ONCE over the latent
      wire; gates broadcasts >= 1, landings == planned terminal, and
      re-prefill savings (prompt tokens restored instead of
      re-prefilled) > 0;
    * ``spec-slo`` — an unmeetable TTFT objective drives the
      SLO-aware ladder (speculation off => chunked prefill => shed);
      gates that it escalated and that the trace still drained.

    Every phase runs ``runs`` times with one seed and gates
    byte-identical event digests. Self-compares against the committed
    perf trajectory before writing. Runs on the CPU."""
    from ..inference.config import RaggedInferenceEngineConfig
    from ..serving import (FleetConfig, PrefixReuseConfig, Request,
                           RouterConfig, ServerConfig, ServingFleet,
                           ServingServer, SimulatedEngine,
                           SLOModeConfig, SpeculationConfig,
                           VirtualClock)
    from ..serving.metrics import ServingMetrics
    from ..telemetry.slo import SLOObjective, SLOTracker

    results = []
    fh = open(out, "w") if out else None

    def emit(row):
        results.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if fh is not None:
            fh.write(line + "\n")
            fh.flush()

    SPEC = SpeculationConfig(ngram=2, max_draft=4, window=64)
    violations = []

    def make_engine(num_blocks=64, lanes=6, tracked=10,
                    max_context=160, vocab=16):
        return SimulatedEngine(RaggedInferenceEngineConfig(
            state_manager={"max_tracked_sequences": tracked,
                           "max_ragged_batch_size": 256,
                           "max_ragged_sequence_count": lanes,
                           "max_context": max_context},
            kv_cache={"block_size": 8, "num_blocks": num_blocks},
            hcache={"enable_latents": True}), vocab_size=vocab)

    # ---------------- phase 1: spec-lookup ------------------------- #
    def lookup_trace():
        rng = np.random.default_rng([seed, 0x51EC])
        return [Request(uid=i,
                        prompt=[int(t) for t in
                                rng.integers(1, 14, (8,))],
                        max_new_tokens=48,
                        arrival_time=0.01 * i) for i in range(12)]

    def run_single(speculation):
        server = ServingServer(
            make_engine(), clock=VirtualClock(),
            config=ServerConfig(max_queue_depth=64,
                                kv_demand_fraction=float("inf"),
                                speculation=speculation))
        reqs = lookup_trace()
        server.run_trace(reqs)
        return (server, reqs,
                _spec_digest([list(e)
                              for e in server.scheduler.events]))

    base_srv, base_reqs, _ = run_single(None)
    spec_runs = [run_single(SPEC) for _ in range(max(1, runs))]
    spec_srv, spec_reqs, _ = spec_runs[0]
    spec_digests = [d for _, _, d in spec_runs]
    lookup_parity = ({r.uid: r.tokens_out for r in base_reqs} ==
                     {r.uid: r.tokens_out for r in spec_reqs})
    accepted_per_step = spec_srv.metrics.gauges[
        "spec_accepted_tokens_per_step"]
    lookup_speedup = base_srv.clock.now() / max(spec_srv.clock.now(),
                                                1e-12)
    if not lookup_parity:
        violations.append("spec-lookup: stream parity broken")
    if accepted_per_step <= 1.3:
        violations.append(
            f"spec-lookup: accepted_tokens_per_step "
            f"{accepted_per_step:.3f} <= 1.3")
    emit({"phase": "spec-lookup", "seed": seed,
          "requests": len(base_reqs),
          "stream_parity": lookup_parity,
          "accepted_tokens_per_step": round(accepted_per_step, 6),
          "virtual_speedup": round(lookup_speedup, 6),
          "spec_counters": {
              k: spec_srv.metrics.counters[k]
              for k in ("spec_steps", "spec_lane_steps",
                        "spec_drafted", "spec_accepted",
                        "spec_emitted", "spec_rollback_tokens")},
          "baseline_virtual_s": round(base_srv.clock.now(), 6),
          "spec_virtual_s": round(spec_srv.clock.now(), 6),
          "deterministic": len(set(spec_digests)) == 1,
          "event_digest": spec_digests[0]})

    # ---------------- phase 2: spec-mixed fleet -------------------- #
    def mixed_trace():
        rng = np.random.default_rng([seed, 0x513D])
        reqs = []
        uid = 0
        shared = [int(t) for t in rng.integers(1, 14, (20,))]
        for i in range(10):          # chatty
            reqs.append(Request(
                uid=uid, prompt=[int(t) for t in
                                 rng.integers(1, 14, (6,))],
                max_new_tokens=6,
                arrival_time=float(i) * 0.01))
            uid += 1
        for i in range(12):          # agent swarm: shared prefix
            reqs.append(Request(
                uid=uid, prompt=shared + [i % 7 + 1, i % 5 + 1],
                max_new_tokens=10,
                arrival_time=0.05 + 0.008 * i))
            uid += 1
        for i in range(4):           # long prompt, long decode
            reqs.append(Request(
                uid=uid, prompt=[int(t) for t in
                                 rng.integers(1, 14, (40,))],
                max_new_tokens=40,
                arrival_time=0.02 + 0.03 * i))
            uid += 1
        return reqs

    def run_fleet(speculation, prefix, trace_fn, n_replicas=3,
                  prefix_weight=0.30):
        fleet = ServingFleet(
            engines=[make_engine(num_blocks=48, lanes=4, tracked=8)
                     for _ in range(n_replicas)],
            clock=VirtualClock(),
            config=FleetConfig(
                n_replicas=n_replicas,
                server=ServerConfig(max_queue_depth=128,
                                    kv_demand_fraction=float("inf"),
                                    speculation=speculation),
                router=RouterConfig(prefix_weight=prefix_weight),
                prefix=prefix))
        reqs = trace_fn()
        fleet.run_trace(reqs)
        return fleet, reqs, _spec_digest(fleet.event_log())

    def fleet_invariants(tag, fleet, reqs):
        terminal = {"DONE", "REJECTED", "FAILED"}
        for r in reqs:
            if r.state.name not in terminal:
                violations.append(
                    f"{tag}: request {r.uid} non-terminal")
            holders = sum(1 for rep in fleet.replicas
                          if r.uid in rep.scheduler.done)
            holders += 1 if r.uid in fleet.done else 0
            if holders != 1:
                violations.append(
                    f"{tag}: request {r.uid} terminal in "
                    f"{holders} places")
        for rep in fleet.replicas:
            if rep.engine.state.free_blocks != \
                    rep.initial_free_blocks:
                violations.append(f"{tag}: replica {rep.id} leaked")
            if rep.engine.state.n_tracked_sequences:
                violations.append(
                    f"{tag}: replica {rep.id} still tracking")
        if not fleet.migration_balance_ok:
            violations.append(f"{tag}: migration imbalance")

    def p99(fleet, which):
        vals = []
        for rep in fleet.replicas:
            hist = getattr(rep.server.metrics, which)
            v = hist.percentile(99)
            if v is not None:
                vals.append(v)
        return max(vals) if vals else None

    prefix_cfg = PrefixReuseConfig(min_adopt_tokens=6,
                                   min_broadcast_tokens=6)
    base_fleet, base_mreqs, _ = run_fleet(None, None, mixed_trace)
    mixed_runs = [run_fleet(SPEC, prefix_cfg, mixed_trace)
                  for _ in range(max(1, runs))]
    mix_fleet, mix_reqs, _ = mixed_runs[0]
    mix_digests = [d for _, _, d in mixed_runs]
    mixed_parity = ({r.uid: r.tokens_out for r in base_mreqs} ==
                    {r.uid: r.tokens_out for r in mix_reqs})
    if not mixed_parity:
        violations.append("spec-mixed: stream parity broken")
    fleet_invariants("spec-mixed", mix_fleet, mix_reqs)
    mixed_row = {
        "phase": "spec-mixed", "seed": seed,
        "requests": len(mix_reqs),
        "stream_parity": mixed_parity,
        "deterministic": len(set(mix_digests)) == 1,
        "event_digest": mix_digests[0],
        "baseline_virtual_s": round(base_fleet.clock.now(), 6),
        "spec_virtual_s": round(mix_fleet.clock.now(), 6),
        "virtual_speedup": round(
            base_fleet.clock.now() /
            max(mix_fleet.clock.now(), 1e-12), 6),
        "ttft_p99_baseline": p99(base_fleet, "ttft"),
        "ttft_p99_spec": p99(mix_fleet, "ttft"),
        "tpot_p99_baseline": p99(base_fleet, "tpot"),
        "tpot_p99_spec": p99(mix_fleet, "tpot"),
        "spec_lane_steps": sum(
            rep.server.metrics.counters["spec_lane_steps"]
            for rep in mix_fleet.replicas),
        "prefix_adoptions": sum(
            rep.server.metrics.counters["prefix_adoptions"]
            for rep in mix_fleet.replicas),
    }
    emit(mixed_row)

    # ---------------- phase 3: spec-prefix broadcast --------------- #
    def conflict_trace():
        """One sharer warms a replica; affinity-pinned long decodes
        then saturate it, so later sharers route cold and the fleet
        must broadcast the prefix once instead of re-prefilling it."""
        shared = [(7 * j) % 13 + 1 for j in range(16)]
        reqs = [Request(uid=0, prompt=shared + [9, 9],
                        max_new_tokens=4, arrival_time=0.0)]
        for i in range(1, 5):
            reqs.append(Request(uid=i, prompt=shared + [i],
                                max_new_tokens=60,
                                arrival_time=0.03 + 0.001 * i))
        for i in range(5, 14):
            reqs.append(Request(uid=i, prompt=shared + [i % 7 + 1,
                                                        i % 5 + 1],
                                max_new_tokens=6,
                                arrival_time=0.06 + 0.004 * i))
        return reqs

    def run_conflict(prefix):
        return run_fleet(SPEC, prefix, conflict_trace, n_replicas=2,
                         prefix_weight=0.05)

    aff_fleet, aff_reqs, _ = run_conflict(None)
    pfx_runs = [run_conflict(prefix_cfg) for _ in range(max(1, runs))]
    pfx_fleet, pfx_reqs, _ = pfx_runs[0]
    pfx_digests = [d for _, _, d in pfx_runs]
    pfx_parity = ({r.uid: r.tokens_out for r in aff_reqs} ==
                  {r.uid: r.tokens_out for r in pfx_reqs})
    fleet_invariants("spec-prefix", pfx_fleet, pfx_reqs)
    reused = sum(rep.server.metrics.counters["prefix_tokens_reused"]
                 for rep in pfx_fleet.replicas)
    aff_prefill = sum(rep.server.metrics.counters["prefill_tokens"]
                      for rep in aff_fleet.replicas)
    pfx_prefill = sum(rep.server.metrics.counters["prefill_tokens"]
                      for rep in pfx_fleet.replicas)
    savings = (aff_prefill - pfx_prefill) / max(aff_prefill, 1)
    broadcasts = pfx_fleet.counters["prefix_broadcasts"]
    landings = pfx_fleet.counters["prefix_broadcast_landings"]
    failed_bc = pfx_fleet.counters["prefix_broadcast_failed"]
    if not pfx_parity:
        violations.append("spec-prefix: stream parity broken")
    if broadcasts < 1:
        violations.append("spec-prefix: no prefix broadcast fired")
    if landings + failed_bc != broadcasts:
        violations.append(
            f"spec-prefix: broadcast imbalance ({broadcasts} sent, "
            f"{landings} landed, {failed_bc} failed)")
    if reused <= 0 or savings <= 0:
        violations.append(
            f"spec-prefix: no re-prefill savings (reused={reused}, "
            f"savings={savings:.4f})")
    emit({"phase": "spec-prefix", "seed": seed,
          "requests": len(pfx_reqs),
          "stream_parity": pfx_parity,
          "deterministic": len(set(pfx_digests)) == 1,
          "event_digest": pfx_digests[0],
          "prefix_broadcasts": broadcasts,
          "prefix_broadcast_landings": landings,
          "prefix_broadcast_failed": failed_bc,
          "prefix_adoptions": sum(
              rep.server.metrics.counters["prefix_adoptions"]
              for rep in pfx_fleet.replicas),
          "prefix_tokens_reused": reused,
          "affinity_prefill_tokens": aff_prefill,
          "reuse_prefill_tokens": pfx_prefill,
          "reprefill_savings": round(savings, 6),
          "affinity_virtual_s": round(aff_fleet.clock.now(), 6),
          "reuse_virtual_s": round(pfx_fleet.clock.now(), 6),
          "router": {k: v for k, v
                     in pfx_fleet.router.summary().items()
                     if "prefix" in k or "reuse" in k}})

    # ---------------- phase 4: SLO-aware ladder -------------------- #
    def run_slo():
        slo = SLOTracker(objectives=[
            SLOObjective("ttft", target=0.95, threshold_s=1e-9,
                         window_s=60.0)])
        server = ServingServer(
            make_engine(), clock=VirtualClock(),
            metrics=ServingMetrics(slo=slo),
            config=ServerConfig(
                max_queue_depth=128,
                kv_demand_fraction=float("inf"),
                speculation=SPEC,
                slo_mode=SLOModeConfig(ttft_burn_threshold=1.0,
                                       tpot_burn_threshold=1e9,
                                       hot_steps=2, calm_steps=1000,
                                       chunked_prefill_tokens=4)))
        rng = np.random.default_rng([seed, 0x510])
        reqs = [Request(uid=i,
                        prompt=[int(t) for t in
                                rng.integers(1, 14, (10,))],
                        max_new_tokens=12,
                        arrival_time=0.002 * i) for i in range(24)]
        server.run_trace(reqs)
        return (server, reqs,
                _spec_digest([list(e)
                              for e in server.scheduler.events]))

    slo_runs = [run_slo() for _ in range(max(1, runs))]
    slo_srv, slo_reqs, _ = slo_runs[0]
    slo_digests = [d for _, _, d in slo_runs]
    slo_level = slo_srv.scheduler.slo.level
    slo_degraded = slo_srv.metrics.counters["slo_degraded_steps"]
    if slo_degraded <= 0 or slo_level < 1:
        violations.append(
            f"spec-slo: ladder never escalated (level={slo_level}, "
            f"degraded_steps={slo_degraded})")
    if any(not r.finished for r in slo_reqs):
        violations.append("spec-slo: trace did not drain")
    emit({"phase": "spec-slo", "seed": seed,
          "requests": len(slo_reqs),
          "final_level": int(slo_level),
          "slo_degraded_steps": slo_degraded,
          "shed": slo_srv.metrics.counters["shed"],
          "rejected": dict(slo_srv.metrics.rejected),
          "prefill_chunks":
              slo_srv.metrics.counters["prefill_chunks"],
          "deterministic": len(set(slo_digests)) == 1,
          "event_digest": slo_digests[0]})

    # ---------------- summary + self-compare ----------------------- #
    deterministic = (len(set(spec_digests)) == 1 and
                     len(set(mix_digests)) == 1 and
                     len(set(pfx_digests)) == 1 and
                     len(set(slo_digests)) == 1)
    if not deterministic:
        violations.append("determinism gate failed")
    emit({"phase": "spec-serve-summary", "seed": seed,
          "runs": max(1, runs),
          "accepted_tokens_per_step": round(accepted_per_step, 6),
          "lookup_virtual_speedup": round(lookup_speedup, 6),
          "mixed_virtual_speedup": mixed_row["virtual_speedup"],
          "reprefill_savings": round(savings, 6),
          "prefix_broadcasts": broadcasts,
          "prefix_tokens_reused": reused,
          "stream_parity": bool(lookup_parity and mixed_parity and
                                pfx_parity),
          "deterministic": deterministic,
          "slo_final_level": int(slo_level),
          "invariants_ok": not violations,
          "violations": violations})

    from ..perf import self_check_rows
    emit(self_check_rows(out or "SPEC_SERVE.jsonl", results))
    if fh is not None:
        fh.close()
    if violations:
        raise RuntimeError(f"spec-serve gates failed: {violations}")
    return results


def run_fabric_serve(seed=0, n_replicas=3, n_requests=24, runs=2,
                     out="FABRIC_SERVE.jsonl"):
    """``--fabric``: deployment-fabric audit — the same seeded
    migration-heavy trace served through BOTH replica transports
    (docs/fabric.md), plus the literal kill-a-process chaos leg. The
    artifact IS the acceptance evidence; gates run inline:

    * ``fabric-parity`` — one fleet per transport on one seed. The
      in-memory twin runs ``runs`` times gating byte-identical event
      digests; the process fleet (one spawned worker per replica,
      migrations crossing real sockets as int8-framable latent frames
      + versioned trace wire dicts) must produce the SAME digest and
      bitwise-identical per-request token streams — the transport
      moves bytes, never outcomes. Gates at least one two-hop
      (src worker -> dst worker) crossing, measured wall-clock wire
      throughput recorded beside the priced ``link_bytes_per_s``
      (``FleetRouter.observe_wire`` calibration), and at least one
      request whose trace context counts >= 2 wire hops — real
      process boundaries in the causal DAG, which must stay connected;
    * ``fabric-chaos`` — ``resilience.run_fabric_chaos``: the busiest
      worker is SIGKILLed mid-trace and the fleet recovers with
      never-dropped accounting (exactly one terminal state per
      request, zero survivor leaks, migration balance, >= 1 request
      finished after the kill, zero bootstrap digest mismatches).

    CPU-only. Wall-clock readings appear
    ONLY in measured-wire fields — every gate the digests depend on is
    virtual-clock deterministic."""
    from ..fabric import (InMemoryTransport, ProcessTransport,
                          canonical_digest)
    from ..resilience import run_fabric_chaos
    from ..resilience.chaos import (_trace_gates, _trace_row,
                                    build_chaos_trace)
    from ..serving import (FleetConfig, RouterConfig, ServerConfig,
                           ServingFleet, SimulatedEngine, VirtualClock)
    from .config import RaggedInferenceEngineConfig

    results = []
    fh = open(out, "w") if out else None

    def emit(row):
        results.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if fh is not None:
            fh.write(line + "\n")
            fh.flush()

    violations = []

    def make_engine():
        # deliberately tight KV budget: pressure evictions make the
        # trace migration-heavy, so bytes actually cross the fabric
        return SimulatedEngine(RaggedInferenceEngineConfig(
            state_manager={"max_tracked_sequences": 8,
                           "max_ragged_batch_size": 256,
                           "max_ragged_sequence_count": 4,
                           "max_context": 64},
            kv_cache={"block_size": 8, "num_blocks": 12},
            hcache={"enable_latents": True}))

    def drive(transport):
        """One full kill-free serve of the seeded trace."""
        fleet = ServingFleet(
            engines=[make_engine() for _ in range(n_replicas)],
            clock=VirtualClock(),
            config=FleetConfig(
                n_replicas=n_replicas,
                server=ServerConfig(max_queue_depth=n_requests + 1,
                                    kv_demand_fraction=float("inf")),
                router=RouterConfig(),
                transport=transport))
        reqs = build_chaos_trace(
            seed, n_requests, fleet.replicas[0].engine.vocab_size,
            max_new=10, rps=400.0, prompt_hi=24)
        with fleet.transport:
            arrivals = sorted(reqs,
                              key=lambda r: (r.arrival_time, r.uid))
            steps = 0
            while arrivals or fleet.has_work:
                now = fleet.clock.now()
                while arrivals and arrivals[0].arrival_time <= now:
                    fleet.submit(request=arrivals.pop(0))
                if not fleet.has_work and arrivals:
                    fleet.clock.advance_to(arrivals[0].arrival_time)
                    continue
                fleet.step()
                steps += 1
                if steps > 1_000_000:
                    raise RuntimeError("fabric serve livelock:\n"
                                       + fleet.snapshot())
        return fleet, reqs, canonical_digest(fleet.event_log())

    # ------------- phase 1: cross-transport parity ----------------- #
    mem_runs = [drive(InMemoryTransport())
                for _ in range(max(1, runs))]
    mem_digests = [d for _, _, d in mem_runs]
    deterministic = len(set(mem_digests)) == 1
    mem_fleet, mem_reqs, mem_digest = mem_runs[0]
    proc_fleet, proc_reqs, proc_digest = drive(ProcessTransport())
    wire = proc_fleet.transport.wire_stats()
    stream_parity = ({r.uid: r.tokens_out for r in mem_reqs} ==
                     {r.uid: r.tokens_out for r in proc_reqs})
    digest_invariant = proc_digest == mem_digest
    max_hops = max((getattr(r.trace, "hops", 0) or 0)
                   for r in proc_reqs)
    trace_inv = _trace_gates(proc_reqs, violations)
    measured_link = proc_fleet.summary()["router"].get(
        "measured_link")
    if not deterministic:
        violations.append(
            f"fabric-parity: in-memory twin digests diverged across "
            f"{len(mem_digests)} runs")
    if not stream_parity:
        violations.append(
            "fabric-parity: process-vs-in-memory token streams differ")
    if not digest_invariant:
        violations.append(
            "fabric-parity: event digest depends on the transport "
            f"({proc_digest[:12]} != {mem_digest[:12]})")
    if wire["shipped"] < 1 or wire["deliveries"] < 1:
        violations.append(
            f"fabric-parity: no bytes crossed the fabric ({wire})")
    if wire["two_hop_deliveries"] < 1:
        violations.append(
            "fabric-parity: no two-hop (worker-to-worker) crossing")
    if wire["measured_wire_bytes_per_s"] <= 0:
        violations.append(
            "fabric-parity: measured wire throughput missing")
    if measured_link is None or measured_link["samples"] < 1:
        violations.append(
            "fabric-parity: router measured-link calibration absent")
    if max_hops < 2:
        violations.append(
            f"fabric-parity: max trace hops {max_hops} < 2 — no trace "
            "crossed a real process boundary")
    if wire["bootstrap_mismatches"]:
        violations.append(
            f"fabric-parity: {wire['bootstrap_mismatches']} bootstrap "
            "digest mismatches")
    for r in proc_reqs:
        emit({"phase": "fabric-request", "uid": r.uid,
              "state": r.state.name, "tokens": len(r.tokens_out),
              "migrations": r.n_migrations, **_trace_row(r)})
    emit({"phase": "fabric-parity", "seed": seed,
          "n_replicas": n_replicas, "n_requests": n_requests,
          "runs": len(mem_runs),
          "deterministic": deterministic,
          "event_digest": mem_digest,
          "process_digest": proc_digest,
          "digest_transport_invariant": digest_invariant,
          "stream_parity": stream_parity,
          "transports": [mem_fleet.transport.name,
                         proc_fleet.transport.name],
          "wire": wire,
          "priced_link_bytes_per_s":
              proc_fleet.config.link_bytes_per_s,
          "measured_link": measured_link,
          "max_trace_hops": max_hops,
          "trace": trace_inv})

    # ------------- phase 2: literal kill-a-process ----------------- #
    chaos = run_fabric_chaos(seed=seed, n_replicas=n_replicas)
    violations.extend(f"fabric-chaos: {v}" for v in chaos.violations)
    emit({"phase": "fabric-chaos", "seed": seed,
          "victim": chaos.victim,
          "event_digest": chaos.event_digest,
          "ok": chaos.ok,
          "wire": chaos.wire,
          "invariants": chaos.invariants})

    c = chaos.invariants["counters"]
    emit({"phase": "fabric-summary", "seed": seed,
          "n_replicas": n_replicas, "n_requests": n_requests,
          "runs": len(mem_runs),
          "deterministic": deterministic,
          "event_digest": mem_digest,
          "digest_transport_invariant": digest_invariant,
          "stream_parity": stream_parity,
          "two_hop_deliveries": wire["two_hop_deliveries"],
          "wire_bytes": wire["wire_bytes"],
          "measured_wire_bytes_per_s":
              wire["measured_wire_bytes_per_s"],
          "priced_link_bytes_per_s":
              proc_fleet.config.link_bytes_per_s,
          "max_trace_hops": max_hops,
          "trace_connected": trace_inv["connected"],
          "chaos_ok": chaos.ok,
          "chaos_kills": chaos.wire["kills"],
          "replica_crashes": c["replica_crashes"],
          "done_after_kill": chaos.invariants["done_after"],
          "bootstrap_mismatches":
              wire["bootstrap_mismatches"] +
              chaos.wire["bootstrap_mismatches"],
          "invariants_ok": not violations,
          "violations": violations})

    from ..perf import self_check_rows
    emit(self_check_rows(out or "FABRIC_SERVE.jsonl", results))
    if fh is not None:
        fh.close()
    if violations:
        raise RuntimeError(
            f"fabric serve gates violated: {violations}")
    return results


def run_fabric_obs(seed=0, n_replicas=3, n_requests=24, runs=2,
                   out="FABRIC_OBS.jsonl"):
    """``--fabric-obs``: cross-process telemetry-plane audit
    (docs/observability.md). The fabric's observability must be
    *free* where it matters — the serving core's committed digests —
    and *real* where humans look. Gates run inline:

    * ``obs-invariance`` — the seeded kill-free trace served through
      the process fleet with harvest ON (``runs`` times, gating
      2-run digest determinism), harvest OFF, and the in-memory twin:
      all event digests must be byte-identical (the telemetry plane
      is digest-invisible), and the measured harvest overhead
      (``transport.harvest_seconds`` against the fabric leg's wall
      time) must stay <= 5%;
    * ``obs-timeline`` — the fabric chaos run traced end-to-end; the
      assembled cross-process timeline must be Perfetto-validator
      clean with one real process row per worker carrying harvested
      spans and >= 1 migration flow arrow spanning two actual worker
      processes;
    * ``obs-postmortem`` — the SIGKILL's ``worker_kill``
      flight-recorder bundle must carry the victim's last-harvested
      telemetry (spans + counters) as wall-clock attachments;
    * per-link wire percentiles (p50/p99 latency and bytes/s from the
      router's quantile sketches) are recorded as informational
      trajectory — wall-clock readings on whatever host ran this.

    CPU-only."""
    from ..fabric import (InMemoryTransport, ProcessTransport,
                          canonical_digest)
    from ..resilience import run_fabric_chaos
    from ..resilience.chaos import build_chaos_trace
    from ..serving import (FleetConfig, RouterConfig, ServerConfig,
                           ServingFleet, SimulatedEngine, VirtualClock)
    from ..telemetry import get_flight_recorder, get_tracer
    from ..telemetry.assemble import (WORKER_PID_BASE,
                                      assemble_process_fleet_trace)
    from ..telemetry.export import validate_trace
    from .config import RaggedInferenceEngineConfig

    results = []
    fh = open(out, "w") if out else None

    def emit(row):
        results.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if fh is not None:
            fh.write(line + "\n")
            fh.flush()

    violations = []

    def make_engine():
        return SimulatedEngine(RaggedInferenceEngineConfig(
            state_manager={"max_tracked_sequences": 8,
                           "max_ragged_batch_size": 256,
                           "max_ragged_sequence_count": 4,
                           "max_context": 64},
            kv_cache={"block_size": 8, "num_blocks": 12},
            hcache={"enable_latents": True}))

    def drive(transport):
        """One full kill-free serve; returns the fleet, the event
        digest, and the leg's wall time (overhead denominator)."""
        fleet = ServingFleet(
            engines=[make_engine() for _ in range(n_replicas)],
            clock=VirtualClock(),
            config=FleetConfig(
                n_replicas=n_replicas,
                server=ServerConfig(max_queue_depth=n_requests + 1,
                                    kv_demand_fraction=float("inf")),
                router=RouterConfig(),
                transport=transport))
        reqs = build_chaos_trace(
            seed, n_requests, fleet.replicas[0].engine.vocab_size,
            max_new=10, rps=400.0, prompt_hi=24)
        t0 = time.perf_counter()
        with fleet.transport:
            arrivals = sorted(reqs,
                              key=lambda r: (r.arrival_time, r.uid))
            steps = 0
            while arrivals or fleet.has_work:
                now = fleet.clock.now()
                while arrivals and arrivals[0].arrival_time <= now:
                    fleet.submit(request=arrivals.pop(0))
                if not fleet.has_work and arrivals:
                    fleet.clock.advance_to(arrivals[0].arrival_time)
                    continue
                fleet.step()
                steps += 1
                if steps > 1_000_000:
                    raise RuntimeError("fabric obs livelock:\n"
                                       + fleet.snapshot())
        wall = time.perf_counter() - t0
        return fleet, canonical_digest(fleet.event_log()), wall

    # ------------- phase 1: harvest digest invariance -------------- #
    _, mem_digest, _ = drive(InMemoryTransport())
    on_runs = [drive(ProcessTransport()) for _ in range(max(1, runs))]
    on_digests = [d for _, d, _ in on_runs]
    _, off_digest, _ = drive(ProcessTransport(harvest_telemetry=False))
    deterministic = len(set(on_digests)) == 1
    harvest_digest_invariant = (
        deterministic and on_digests[0] == off_digest ==
        mem_digest)
    on_fleet, _, on_wall = on_runs[0]
    tr = on_fleet.transport
    overhead = (tr.harvest_seconds / on_wall) if on_wall > 0 else 0.0
    if not deterministic:
        violations.append(
            f"obs-invariance: harvest-on digests diverged across "
            f"{len(on_digests)} runs")
    if not harvest_digest_invariant:
        violations.append(
            "obs-invariance: telemetry harvest is digest-VISIBLE "
            f"(on {on_digests[0][:12]} / off {off_digest[:12]} / "
            f"mem {mem_digest[:12]})")
    if tr.harvests < 1:
        violations.append(
            "obs-invariance: harvest plane never harvested (the "
            "invariance gate tested nothing)")
    if overhead > 0.05:
        violations.append(
            f"obs-invariance: harvest overhead {overhead:.4f} of "
            "fabric-leg wall time exceeds the 5% budget")
    measured_link = on_fleet.summary()["router"].get(
        "measured_link") or {}
    links = measured_link.get("links", {})
    busiest = max(sorted(links),
                  key=lambda k: links[k]["latency_s"]["count"]) \
        if links else ""
    if not links:
        violations.append(
            "obs-invariance: no per-link wire sketches recorded")
    emit({"phase": "obs-invariance", "seed": seed,
          "runs": len(on_runs),
          "deterministic": deterministic,
          "harvest_digest_invariant": harvest_digest_invariant,
          "event_digest": mem_digest,
          "harvest_on_digest": on_digests[0],
          "harvest_off_digest": off_digest,
          "harvests": tr.harvests,
          "harvest_failures": tr.harvest_failures,
          "harvest_seconds": round(tr.harvest_seconds, 6),
          "leg_wall_seconds": round(on_wall, 6),
          "harvest_overhead_fraction": round(overhead, 6),
          "worker_telemetry": tr.telemetry_stats()})
    emit({"phase": "obs-wire", "seed": seed,
          "links": links, "busiest_link": busiest,
          "priced_link_bytes_per_s":
              on_fleet.config.link_bytes_per_s})

    # ------------- phase 2: assembled cross-process timeline ------- #
    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)
    tracer.clear()
    flight = get_flight_recorder()
    flight.clear()
    try:
        chaos = run_fabric_chaos(seed=seed, n_replicas=n_replicas)
        parent_events = tracer.events()
        parent_dropped = tracer.dropped
    finally:
        tracer.configure(enabled=was)
    violations.extend(f"obs-chaos: {v}" for v in chaos.violations)
    workers = chaos.telemetry.get("workers", {})
    assembled, warnings = assemble_process_fleet_trace(
        parent_events, workers, dropped=parent_dropped)
    timeline_valid = True
    timeline_error = ""
    try:
        stats = validate_trace(assembled)
    except ValueError as exc:
        timeline_valid = False
        timeline_error = str(exc)
        stats = {"events": len(assembled), "spans": 0, "pairs": 0}
        violations.append(
            f"obs-timeline: assembled trace invalid: {exc}")
    worker_rows = sum(
        1 for e in assembled
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and e.get("pid", 0) >= WORKER_PID_BASE)
    worker_spans = sum(
        1 for e in assembled
        if e.get("pid", 0) >= WORKER_PID_BASE and
        e.get("ph") in ("X", "B", "i"))
    cross_worker_arrows = sum(
        1 for e in assembled
        if e.get("ph") == "s" and e.get("cat") == "fabric")
    if worker_rows < n_replicas:
        violations.append(
            f"obs-timeline: only {worker_rows} worker process rows "
            f"for {n_replicas} workers")
    if worker_spans < 1:
        violations.append(
            "obs-timeline: no harvested spans landed on any worker "
            "row")
    if cross_worker_arrows < 1:
        violations.append(
            "obs-timeline: no migration flow arrow spans two real "
            "worker processes")
    emit({"phase": "obs-timeline", "seed": seed,
          "timeline_valid": timeline_valid,
          "timeline_error": timeline_error,
          "events": stats["events"], "spans": stats["spans"],
          "worker_rows": worker_rows,
          "worker_spans": worker_spans,
          "cross_worker_arrows": cross_worker_arrows,
          "assembly_warnings": warnings,
          "chaos_ok": chaos.ok,
          "chaos_digest": chaos.event_digest,
          "harvest": chaos.telemetry.get("harvest", {})})

    # ------------- phase 3: SIGKILL postmortem bundle -------------- #
    kill_bundles = [b for b in list(flight.bundles)
                    if b["trigger"] == "worker_kill"]
    bundle = kill_bundles[0] if kill_bundles else {}
    attach = bundle.get("attachments", {})
    postmortem_has_telemetry = bool(
        kill_bundles and
        bundle.get("snapshot", {}).get("victim") == chaos.victim and
        attach.get("counters") and
        attach.get("harvests", 0) >= 1)
    if not kill_bundles:
        violations.append(
            "obs-postmortem: no worker_kill flight bundle recorded")
    elif not postmortem_has_telemetry:
        violations.append(
            "obs-postmortem: worker_kill bundle lacks the victim's "
            "last-harvested telemetry")
    emit({"phase": "obs-postmortem", "seed": seed,
          "bundles": len(kill_bundles),
          "victim": chaos.victim,
          "postmortem_has_telemetry": postmortem_has_telemetry,
          "bundle_digest": bundle.get("digest", ""),
          "bundle_spans": len(bundle.get("spans", [])),
          "attachment_counters":
              sorted(attach.get("counters", {})),
          "attachment_harvests": attach.get("harvests", 0)})

    # ------------- summary ----------------------------------------- #
    blink = links.get(busiest, {})
    emit({"phase": "fabric-obs-summary", "seed": seed,
          "n_replicas": n_replicas, "n_requests": n_requests,
          "runs": len(on_runs),
          "deterministic": deterministic,
          "harvest_digest_invariant": harvest_digest_invariant,
          "event_digest": mem_digest,
          "harvests": tr.harvests,
          "harvest_failures": tr.harvest_failures,
          "harvest_overhead_fraction": round(overhead, 6),
          "timeline_valid": timeline_valid,
          "worker_rows": worker_rows,
          "worker_spans": worker_spans,
          "cross_worker_arrows": cross_worker_arrows,
          "postmortem_has_telemetry": postmortem_has_telemetry,
          "chaos_ok": chaos.ok,
          "busiest_link": busiest,
          "wire_latency_p50_s":
              blink.get("latency_s", {}).get("p50"),
          "wire_latency_p99_s":
              blink.get("latency_s", {}).get("p99"),
          "wire_bytes_per_s_p50":
              blink.get("bytes_per_s", {}).get("p50"),
          "wire_bytes_per_s_p99":
              blink.get("bytes_per_s", {}).get("p99"),
          "invariants_ok": not violations,
          "violations": violations})

    from ..perf import self_check_rows
    emit(self_check_rows(out or "FABRIC_OBS.jsonl", results))
    if fh is not None:
        fh.close()
    if violations:
        raise RuntimeError(
            f"fabric obs gates violated: {violations}")
    return results


def run_request_trace(seed=0, runs=2, out="REQUEST_TRACE.jsonl",
                      closure_tol=0.01):
    """Causal request-tracing audit (``bench.py --request-trace``):
    replay the committed chaos workloads — the single-engine storm,
    the fleet crash/hang/partition run, and the disaggregated tier
    run — and gate, per leg and fleet-wide:

    * **connected span DAGs** — every terminal request's TraceContext
      chain tiles its timeline with no orphan spans, across >=1 crash
      evacuation and >=1 prefill→decode handoff;
    * **attribution closure** — per-request critical-path attribution
      sums to the measured E2E latency within ``closure_tol`` (1%);
    * **determinism** — same-seed event digests byte-identical across
      ``runs`` replays;
    * **flight recorder** — each leg's anomaly triggers (breaker
      trips, SLO burn) produce the same bundle count with pairwise
      byte-identical bundle digests across same-seed runs.

    The summary row carries the headline p99-TTFT attribution profile
    (which stage owns the TTFT tail). Raises on any gate failure —
    the artifact IS the acceptance evidence. Pure CPU/virtual-clock.
    """
    from ..resilience.chaos import (run_chaos, run_disagg_chaos,
                                    run_fleet_chaos)
    from ..telemetry.flight import get_flight_recorder

    results = []
    fh = open(out, "w") if out else None

    def emit(row):
        results.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if fh is not None:
            fh.write(line + "\n")
            fh.flush()

    emit({"phase": "request-trace-plan", "seed": seed, "runs": runs,
          "closure_tol": closure_tol,
          "legs": ["chaos", "fleet", "disagg"]})

    recorder = get_flight_recorder()
    legs = (("chaos", lambda: run_chaos(seed=seed)),
            ("fleet", lambda: run_fleet_chaos(seed=seed)),
            ("disagg", lambda: run_disagg_chaos(seed=seed)))
    violations, leg_results = [], {}
    ttft_attrs, flight_total = [], 0
    flight_det_all, det_all, connected_all, closure_ok = \
        True, True, True, True
    max_residual = 0.0
    for name, fn in legs:
        digests, flight_digests, first = [], [], None
        for _ in range(max(1, runs)):
            recorder.clear()
            res = fn()
            digests.append(res.event_digest)
            flight_digests.append(recorder.digests())
            if first is None:
                first = res
        leg_results[name] = first
        if not first.ok:
            violations.append(f"{name}: invariants failed: "
                              f"{first.violations[:4]}")
        tr = first.invariants.get("trace", {})
        if not tr.get("connected", False):
            connected_all = False
            violations.append(f"{name}: span DAG not connected")
        res_max = float(tr.get("max_closure_residual", 1.0))
        max_residual = max(max_residual, res_max)
        if res_max > closure_tol:
            closure_ok = False
            violations.append(
                f"{name}: closure residual {res_max} > {closure_tol}")
        deterministic = len(set(digests)) == 1
        det_all = det_all and deterministic
        if not deterministic:
            violations.append(f"{name}: digests diverged {digests}")
        flight_det = len({tuple(d) for d in flight_digests}) == 1
        flight_det_all = flight_det_all and flight_det
        if not flight_det:
            violations.append(
                f"{name}: flight bundles diverged across same-seed "
                f"runs ({[len(d) for d in flight_digests]})")
        flight_total += len(flight_digests[0])
        for row in first.requests:
            if row.get("ttft_attr"):
                ttft_attrs.append(row["ttft_attr"])
        emit({"phase": "request-trace-leg", "leg": name,
              "runs": len(digests),
              "event_digest": digests[0],
              "deterministic": deterministic,
              "connected": tr.get("connected", False),
              "traced_requests": tr.get("traced_requests", 0),
              "max_closure_residual": res_max,
              "flight_bundles": len(flight_digests[0]),
              "flight_triggers": sorted(
                  {b["trigger"] for b in recorder.bundles}),
              "flight_digests": flight_digests[0],
              "flight_deterministic": flight_det})
        for row in first.requests:
            emit({"phase": "request-trace-request", "leg": name,
                  **row})

    # the coverage floor: the legs must actually exercise the wire —
    # a crash evacuation (fleet) and a tier handoff (disagg)
    fleet_c = leg_results["fleet"].invariants["counters"]
    disagg_c = leg_results["disagg"].invariants["counters"]
    if not fleet_c.get("replica_crashes"):
        violations.append("fleet leg had no crash evacuation")
    if not disagg_c.get("handoffs"):
        violations.append("disagg leg had no handoffs")
    if not flight_total:
        violations.append("no flight-recorder bundle was triggered")

    # headline p99-TTFT attribution across the fleet+disagg requests:
    # absent phases count 0.0 so percentiles compare like-for-like
    phases = sorted({p for a in ttft_attrs for p in a})
    ttft_p99 = {p: round(float(np.percentile(
        np.asarray([a.get(p, 0.0) for a in ttft_attrs]), 99)), 9)
        for p in phases} if ttft_attrs else {}
    ttft_totals = [sum(a.values()) for a in ttft_attrs]
    summary = {
        "phase": "request-trace-summary", "seed": seed,
        "runs": runs, "closure_tol": closure_tol,
        "dag_connected": connected_all,
        "closure_ok": closure_ok,
        "closure_max_residual": round(max_residual, 9),
        "deterministic": det_all,
        "flight_deterministic": flight_det_all,
        "flight_bundles": flight_total,
        "traced_requests": sum(
            r.invariants["trace"]["traced_requests"]
            for r in leg_results.values()),
        "crash_evacuations": fleet_c.get("replica_crashes", 0),
        "handoffs": disagg_c.get("handoffs", 0),
        "ttft_p99_s": round(float(np.percentile(
            np.asarray(ttft_totals), 99)), 9) if ttft_totals else None,
        "ttft_attr_p99_s": ttft_p99,
        "violations": violations,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    emit(summary)

    from ..perf import self_check_rows
    emit(self_check_rows(out or "REQUEST_TRACE.jsonl", results))
    if fh is not None:
        fh.close()
    if violations:
        raise RuntimeError(
            f"request-trace gates failed: {violations}")
    return results


def run_autoscale_serve(seed=7, n_requests=800, horizon_s=20.0,
                        runs=2, out="AUTOSCALE_SERVE.jsonl"):
    """``--autoscale``: SLO-driven elastic autoscaling audit — the
    hysteresis control loop (``serving/autoscale.py``) over the bursty
    diurnal multi-tenant trace, with scale events treated as a
    first-class failure domain. The artifact IS the acceptance
    evidence; gates run inline:

    * ``autoscale-main`` — the autoscaled fleet serves the seeded
      trace ``runs`` times gating byte-identical event digests. Every
      scale event must be span-verified through the causal trace DAG:
      each ``fleet.scale_up`` / ``fleet.retire`` async span opened by
      the fleet must close with a terminal status, and the span counts
      must equal the fleet's scale counters. Per-request trace DAGs
      stay connected across migrations caused by drain-retirement.
    * ``autoscale-static`` — the SAME trace through static fleets at
      the start size and at the autoscaler's peak size. Gates: SLO
      attainment (TTFT <= threshold over DONE requests) >= the best
      static fleet of equal peak size, at strictly lower cost
      (replica-steps actually consumed).
    * ``autoscale-chaos`` — ``resilience.run_autoscale_chaos`` twice:
      scale-up killed mid-bootstrap, replica crashed mid-drain, faulted
      pre-warm; identical digests + all invariants.
    * ``autoscale-process`` — ProcessTransport leg: a REAL worker
      process is spawned by scale-up with the first spawn killed by an
      injected ``scale.spawn`` fault (supervised retry recovers), and
      the retired replica's worker is reaped only after its drain
      lands. Zero requests lost.

    CPU-only, virtual-clock deterministic in every gated field."""
    from ..fabric import ProcessTransport, canonical_digest
    from ..resilience import (FaultPlan, FaultRule, injected,
                              run_autoscale_chaos)
    from ..resilience.chaos import _trace_gates
    from ..serving import (AutoscaleConfig, Autoscaler, FleetConfig,
                           PrefixReuseConfig, RequestState,
                           ServerConfig, ServingFleet,
                           SimulatedEngine, VirtualClock,
                           build_autoscale_trace)
    from ..serving.spec import SLOModeConfig
    from ..telemetry.tracer import get_tracer
    from .config import RaggedInferenceEngineConfig

    results = []
    fh = open(out, "w") if out else None

    def emit(row):
        results.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if fh is not None:
            fh.write(line + "\n")
            fh.flush()

    violations = []

    def make_engine():
        return SimulatedEngine(RaggedInferenceEngineConfig(
            state_manager={"max_tracked_sequences": 8,
                           "max_ragged_batch_size": 256,
                           "max_ragged_sequence_count": 4,
                           "max_context": 64},
            kv_cache={"block_size": 8, "num_blocks": 16},
            hcache={"enable_latents": True}))

    slo_ttft_s = 1.0
    start_replicas, peak_replicas = 2, 4

    def make_fleet(n):
        return ServingFleet(
            engine_factory=make_engine,
            clock=VirtualClock(),
            config=FleetConfig(
                n_replicas=n,
                server=ServerConfig(max_queue_depth=n_requests + 1,
                                    kv_demand_fraction=float("inf"),
                                    slo_mode=SLOModeConfig()),
                prefix=PrefixReuseConfig(broadcast=True,
                                         min_adopt_tokens=4)))

    def make_trace():
        return build_autoscale_trace(seed=seed, n_requests=n_requests,
                                     horizon_s=horizon_s,
                                     new_tokens=(8, 16))

    def score(fleet, reqs):
        done = [r for r in reqs if r.state is RequestState.DONE]
        attained = [r for r in done
                    if r.ttft() is not None
                    and r.ttft() <= slo_ttft_s]
        cost = sum(rep.steps for rep in fleet.replicas)
        return {"done": len(done),
                "slo_attainment": round(len(attained)
                                        / max(1, len(reqs)), 6),
                "cost_replica_steps": cost}

    def drive_auto():
        fleet = make_fleet(start_replicas)
        asc = Autoscaler(fleet, AutoscaleConfig(
            min_replicas=1, max_replicas=peak_replicas,
            hot_steps=2, calm_steps=60, cooldown_steps=40,
            flap_window_steps=60))
        reqs = make_trace()
        summary = asc.run(reqs)
        return fleet, asc, reqs, summary, \
            canonical_digest(fleet.event_log())

    # ------------- phase 1: autoscaled serve + spans --------------- #
    # every run traced (the crossover model mines the span buffer when
    # the tracer is on; mixing traced/untraced runs would change the
    # digest) at a capacity that cannot displace scale-event spans
    tracer = get_tracer()
    was = tracer.enabled
    cap_was = tracer._capacity
    tracer.configure(enabled=True, capacity=1 << 20)
    auto_runs = []
    span_events = None
    try:
        for _ in range(max(1, runs)):
            tracer.clear()
            auto_runs.append(drive_auto())
            if span_events is None:
                span_events = tracer.events()
        digests = [d for *_, d in auto_runs]
        deterministic = len(set(digests)) == 1
        fleet, asc, reqs, summary, digest = auto_runs[0]

        # span-verify every scale event through the trace DAG: each
        # fleet.scale_up / fleet.retire async begin pairs with exactly
        # one terminal-status end, and span counts match the counters
        def _async(names):
            by = {}
            for e in span_events:
                if e.get("name") in names and e.get("ph") in ("b", "e"):
                    key = (e["name"], e.get("cat"), e.get("id"))
                    by.setdefault(key, []).append(e)
            return by
        spans = _async({"fleet.scale_up", "fleet.retire"})
        # the same replica id may scale up / retire repeatedly, so a
        # key holds an interleaved history — it must strictly
        # alternate b, e, b, e, ... and close
        unpaired = sorted(
            k[0] + ":" + str(k[2]) for k, evs in spans.items()
            if [x["ph"] for x in evs]
            != ["b", "e"] * (len(evs) // 2) or len(evs) % 2)
        statuses = sorted(
            (e.get("args") or {}).get("status", "?")
            for evs in spans.values() for e in evs
            if e["ph"] == "e")
        c = fleet.counters
        n_up_spans = sum(
            1 for k, evs in spans.items() for e in evs
            if k[0] == "fleet.scale_up" and e["ph"] == "b")
        n_ret_spans = sum(
            1 for k, evs in spans.items() for e in evs
            if k[0] == "fleet.retire" and e["ph"] == "b")
        span_counts_agree = (
            n_up_spans == c["scale_ups"] + c["scale_up_aborts"]
            and n_ret_spans == c["retires"])
        scale_events_span_verified = (
            not unpaired and span_counts_agree
            and n_up_spans >= 1 and n_ret_spans >= 1
            and all(s in ("ready", "aborted", "completed", "crashed")
                    for s in statuses))
        if tracer.dropped:
            violations.append(
                f"autoscale-main: tracer displaced {tracer.dropped} "
                "events — span verification is not trustworthy")
    finally:
        tracer.configure(enabled=was, capacity=cap_was)

    trace_inv = _trace_gates(reqs, violations)
    auto_score = score(fleet, reqs)
    if not deterministic:
        violations.append(
            f"autoscale-main: digests diverged across "
            f"{len(digests)} runs")
    if unpaired:
        violations.append(
            f"autoscale-main: unpaired scale spans {unpaired}")
    if not span_counts_agree:
        violations.append(
            f"autoscale-main: scale spans ({n_up_spans} up, "
            f"{n_ret_spans} retire) disagree with counters "
            f"(ups {c['scale_ups']}+{c['scale_up_aborts']} aborted, "
            f"retires {c['retires']})")
    if not scale_events_span_verified:
        violations.append(
            "autoscale-main: scale events not span-verified "
            f"(statuses {statuses})")
    if c["scale_ups"] < 1 or c["retires_completed"] < 1:
        violations.append(
            "autoscale-main: the trace never exercised a full "
            f"scale-up + drain-retirement cycle ({dict(c)})")
    if asc.flaps > asc.config.max_flaps:
        violations.append(
            f"autoscale-main: flap bound {asc.flaps} > "
            f"{asc.config.max_flaps}")
    for step, action, detail in asc.decisions:
        emit({"phase": "autoscale-decision", "step": step,
              "action": action, "detail": detail})
    emit({"phase": "autoscale-main", "seed": seed,
          "n_requests": n_requests, "runs": len(auto_runs),
          "deterministic": deterministic,
          "event_digest": digest,
          "scale_ups": c["scale_ups"],
          "scale_up_aborts": c["scale_up_aborts"],
          "retires": c["retires"],
          "retires_completed": c["retires_completed"],
          "reroles": c["reroles"],
          "prewarm_broadcasts": c["prewarm_broadcasts"],
          "flaps": asc.flaps,
          "replicas_final": len(fleet.replicas),
          "replicas_live": fleet.live_replicas,
          "scale_events_span_verified": scale_events_span_verified,
          "span_statuses": statuses,
          "trace": trace_inv,
          **auto_score})

    # ------------- phase 2: vs static fleets ----------------------- #
    statics = {}
    for n in (start_replicas, peak_replicas):
        sfleet = make_fleet(n)
        sreqs = make_trace()
        sfleet.run_trace(sreqs)
        statics[n] = score(sfleet, sreqs)
        emit({"phase": "autoscale-static", "seed": seed,
              "n_replicas": n, **statics[n]})
    peak = statics[peak_replicas]
    slo_vs_static_ok = (auto_score["slo_attainment"]
                        >= peak["slo_attainment"])
    cost_vs_static_ok = (auto_score["cost_replica_steps"]
                         < peak["cost_replica_steps"])
    savings = 1.0 - (auto_score["cost_replica_steps"]
                     / max(1, peak["cost_replica_steps"]))
    if not slo_vs_static_ok:
        violations.append(
            f"autoscale-static: attainment "
            f"{auto_score['slo_attainment']} < static-{peak_replicas}"
            f" {peak['slo_attainment']}")
    if not cost_vs_static_ok:
        violations.append(
            f"autoscale-static: cost "
            f"{auto_score['cost_replica_steps']} not strictly below "
            f"static-{peak_replicas} {peak['cost_replica_steps']}")

    # ------------- phase 3: scale-event chaos ---------------------- #
    chaos = [run_autoscale_chaos(seed=seed)
             for _ in range(max(1, runs))]
    chaos_det = len({x.event_digest for x in chaos}) == 1
    violations.extend(f"autoscale-chaos: {v}"
                      for x in chaos for v in x.violations)
    if not chaos_det:
        violations.append(
            "autoscale-chaos: digests diverged across runs")
    emit({"phase": "autoscale-chaos", "seed": seed,
          "runs": len(chaos),
          "deterministic": chaos_det,
          "event_digest": chaos[0].event_digest,
          "ok": all(x.ok for x in chaos),
          "fault_fired": chaos[0].invariants["fault_fired"],
          "invariants": chaos[0].invariants})

    # ------------- phase 4: process-mode scale lifecycle ----------- #
    pfleet = ServingFleet(
        engine_factory=make_engine,
        clock=VirtualClock(),
        config=FleetConfig(
            n_replicas=start_replicas,
            server=ServerConfig(max_queue_depth=n_requests + 1,
                                kv_demand_fraction=float("inf")),
            prefix=PrefixReuseConfig(broadcast=True,
                                     min_adopt_tokens=4),
            transport=ProcessTransport()))
    preqs = build_autoscale_trace(seed=seed, n_requests=48,
                                  horizon_s=3.0, new_tokens=(6, 10))
    spawn_kill = FaultPlan(seed=seed, rules=[
        FaultRule("scale.spawn", at_hits=(1,), max_faults=1)])
    with injected(spawn_kill) as inj:
        with pfleet.transport:
            arrivals = sorted(preqs,
                              key=lambda r: (r.arrival_time, r.uid))
            steps = 0
            new_rid = None
            while arrivals or pfleet.has_work:
                now = pfleet.clock.now()
                while arrivals and arrivals[0].arrival_time <= now:
                    pfleet.submit(request=arrivals.pop(0))
                if not pfleet.has_work and arrivals:
                    pfleet.clock.advance_to(arrivals[0].arrival_time)
                    continue
                pfleet.step()
                steps += 1
                if steps == 4:
                    # scale-up mid-trace: first spawn is killed by the
                    # injected fault, the supervisor must retry
                    new_rid = pfleet.add_replica()
                if steps == 12 and new_rid is not None:
                    pfleet.retire_replica(new_rid)
                if steps > 1_000_000:
                    raise RuntimeError("autoscale process livelock:\n"
                                       + pfleet.snapshot())
            pwire = pfleet.transport.wire_stats()
        spawn_fired = dict(inj.fired)
    terminal = {"DONE", "REJECTED", "FAILED"}
    lost = [r.uid for r in preqs if r.state.name not in terminal]
    process_ok = True
    if new_rid is None:
        process_ok = False
        violations.append("autoscale-process: scale-up never ran")
    if spawn_fired.get("scale.spawn", 0) < 1 \
            or pwire["scale_spawn_failures"] < 1:
        process_ok = False
        violations.append(
            "autoscale-process: the mid-scale-up kill never fired "
            f"({spawn_fired}, {pwire['scale_spawn_failures']} spawn "
            "failures)")
    if pwire["scale_spawns"] < 1:
        process_ok = False
        violations.append(
            "autoscale-process: no worker spawned by scale-up")
    if pwire["scale_retired"] < 1:
        process_ok = False
        violations.append(
            "autoscale-process: retired worker never reaped")
    if lost:
        process_ok = False
        violations.append(
            f"autoscale-process: requests lost {lost}")
    if not pfleet.migration_balance_ok or pfleet.in_transit:
        process_ok = False
        violations.append(
            "autoscale-process: migration imbalance "
            f"({dict(pfleet.counters)})")
    emit({"phase": "autoscale-process", "seed": seed,
          "n_requests": len(preqs),
          "new_replica": new_rid,
          "process_ok": process_ok,
          "scale_spawns": pwire["scale_spawns"],
          "scale_spawn_failures": pwire["scale_spawn_failures"],
          "scale_retired": pwire["scale_retired"],
          "io_timeouts": pwire["io_timeouts"],
          "fault_fired": spawn_fired,
          "counters": dict(pfleet.counters)})

    emit({"phase": "autoscale-summary", "seed": seed,
          "n_requests": n_requests, "runs": len(auto_runs),
          "deterministic": deterministic,
          "event_digest": digest,
          "slo_attainment": auto_score["slo_attainment"],
          "cost_replica_steps": auto_score["cost_replica_steps"],
          "static_peak_attainment": peak["slo_attainment"],
          "static_peak_cost": peak["cost_replica_steps"],
          "slo_vs_static_ok": slo_vs_static_ok,
          "cost_vs_static_ok": cost_vs_static_ok,
          "cost_savings_fraction": round(savings, 6),
          "scale_ups": c["scale_ups"],
          "retires_completed": c["retires_completed"],
          "flaps": asc.flaps,
          "scale_events_span_verified": scale_events_span_verified,
          "chaos_deterministic": chaos_det,
          "chaos_invariants_ok": all(x.ok for x in chaos),
          "process_ok": process_ok,
          "trace_connected": trace_inv["connected"],
          "invariants_ok": not violations,
          "violations": violations})

    from ..perf import self_check_rows
    emit(self_check_rows(out or "AUTOSCALE_SERVE.jsonl", results))
    if fh is not None:
        fh.close()
    if violations:
        raise RuntimeError(
            f"autoscale serve gates violated: {violations}")
    return results


def run(model_size="tiny", max_context=512, prompt_len=128,
        decode_steps=64, batches=(1, 4, 8), quantize="",
        prefill_chunk=0, fused=False, lookup=False):
    """ONE engine (sized for the largest batch) serves every measurement:
    engine-per-config both re-casts the weights each time and, at 1B+
    sizes, OOMs the pool while two engines overlap. Rows print as they
    are produced so a crash keeps partial results."""
    results = []
    emit = functools.partial(_emit, results)

    rng = np.random.default_rng(0)
    cfg, eng = _engine(model_size, max_context, max(batches),
                       quantize=quantize, prefill_chunk=prefill_chunk)
    for batch in batches:
        prompts = [list(rng.integers(0, cfg.vocab_size, (prompt_len,)))
                   for _ in range(batch)]
        uids = list(range(batch))

        # warm the prefill program off-clock (timing the compile as
        # "prefill" reported 0.4 tok/s for what is a ~ms dispatch),
        # then time the real rate
        warm_uids = [10 ** 7 + u for u in uids]
        eng.put(warm_uids, prompts)
        for u in warm_uids:
            eng.flush(u)
        t0 = time.perf_counter()
        logits, _ = eng.put(uids, prompts)   # returns host arrays (sync)
        prefill_s = time.perf_counter() - t0
        emit({"phase": "prefill", "batch": batch,
              "prompt_len": prompt_len,
              "tokens_per_sec": round(batch * prompt_len / prefill_s, 1)})

        ctx0 = prompt_len + 1
        if lookup:
            # speculative decoding: same greedy stream, fewer dispatches.
            # A repetitive prompt half models the system-prompt/code
            # workloads PLD targets; the random half keeps it honest.
            for u in uids:
                eng.flush(u)
            cyc = [int(x) for x in rng.integers(0, cfg.vocab_size, (4,))]
            spec_prompts = [(cyc * prompt_len)[:prompt_len // 2] +
                            p[:prompt_len - prompt_len // 2]
                            for p in prompts]
            eng.generate_lookup(spec_prompts,
                                max_new_tokens=decode_steps + 1)  # warm
            t0 = time.perf_counter()
            _, stats = eng.generate_lookup(
                spec_prompts, max_new_tokens=decode_steps + 1)
            dt = time.perf_counter() - t0
            emit({"phase": "decode-lookup", "batch": batch,
                  "context": [ctx0, ctx0 + decode_steps],
                  "note": "includes one prefill; repetitive-half prompts",
                  "tokens_per_sec": round(batch * decode_steps / dt, 1),
                  "dispatches": stats["dispatches"],
                  "drafted": stats["drafted"],
                  "accepted": stats["accepted"],
                  "tokens_per_dispatch": round(
                      batch * decode_steps / max(stats["dispatches"], 1),
                      2)})
            # fully fused variant: same workload, one host sync total
            eng.generate_lookup_fused(spec_prompts,
                                      max_new_tokens=decode_steps + 1)
            t0 = time.perf_counter()
            _, fstats = eng.generate_lookup_fused(
                spec_prompts, max_new_tokens=decode_steps + 1)
            dt = time.perf_counter() - t0
            emit({"phase": "decode-lookup-fused", "batch": batch,
                  "context": [ctx0, ctx0 + decode_steps],
                  "note": "includes one prefill; repetitive-half prompts",
                  "tokens_per_sec": round(batch * decode_steps / dt, 1),
                  "device_steps": fstats["dispatches"],
                  "accepted": fstats["accepted"],
                  "tokens_per_device_step": round(
                      batch * decode_steps /
                      max(fstats["dispatches"], 1), 2)})
        elif fused:
            # on-device decode loop: one program for the whole stretch
            for u in uids:
                eng.flush(u)
            # warm with the SAME length: n_steps is a static arg, a
            # different value would recompile inside the timed region
            try:
                eng.generate_fused(prompts, max_new_tokens=decode_steps + 1)
            except Exception as e:  # noqa: BLE001 — XLA OOM surfaces as
                # a backend-specific RuntimeError subclass; at 7B bf16
                # the fused program's stacked-QKV layout copies exceed a
                # 16 GB chip (docs/inference.md). A dead stage loses the
                # whole chip-session slot — fall back to the host-driven
                # loop and say so in the artifact instead.
                if "RESOURCE_EXHAUSTED" not in str(e) \
                        and "Resource" not in type(e).__name__:
                    raise
                detail = (str(e) or type(e).__name__).splitlines()[0]
                emit({"phase": "decode-fused", "batch": batch,
                      "error": "fused decode program OOM; falling back "
                               "to host-driven decode",
                      "detail": detail[:300]})
                # generate_fused flushes its own uids in a finally, so
                # the engine is clean: re-prefill and host-step. The
                # host path spends one extra token on its warm step, so
                # clamp to the context budget (the fused call accepts
                # prompt+steps == max_context exactly).
                fb_steps = min(decode_steps,
                               max_context - prompt_len - 1)
                if fb_steps < 1:
                    # prompt fills the context minus the fused budget's
                    # last token; nothing left for warm + timed steps
                    continue
                logits, _ = eng.put(uids, prompts)
                nxt = [int(np.argmax(l)) for l in logits]
                logits, _ = eng.put(uids, [[t] for t in nxt])
                t0 = time.perf_counter()
                for _ in range(fb_steps):
                    nxt = [int(np.argmax(l)) for l in logits]
                    logits, _ = eng.put(uids, [[t] for t in nxt])
                dt = time.perf_counter() - t0
                emit({"phase": "decode", "batch": batch,
                      "note": "host-driven fallback after fused OOM",
                      "context": [ctx0, ctx0 + fb_steps],
                      "tokens_per_sec": round(batch * fb_steps / dt, 1),
                      "ms_per_step": round(dt / fb_steps * 1000, 2)})
            else:
                t0 = time.perf_counter()
                eng.generate_fused(prompts,
                                   max_new_tokens=decode_steps + 1)
                dt = time.perf_counter() - t0
                emit({"phase": "decode-fused", "batch": batch,
                      "context": [ctx0, ctx0 + decode_steps],
                      "note": "includes one prefill",
                      "tokens_per_sec": round(batch * decode_steps / dt, 1),
                      "ms_per_step": round(dt / decode_steps * 1000, 2)})
        else:
            # warm the decode dispatch, then steady-state loop
            nxt = [int(np.argmax(l)) for l in logits]
            logits, _ = eng.put(uids, [[t] for t in nxt])
            t0 = time.perf_counter()
            for _ in range(decode_steps):
                nxt = [int(np.argmax(l)) for l in logits]
                logits, _ = eng.put(uids, [[t] for t in nxt])
            dt = time.perf_counter() - t0
            emit({"phase": "decode", "batch": batch,
                  "context": [ctx0, ctx0 + decode_steps],
                  "tokens_per_sec": round(batch * decode_steps / dt, 1),
                  "ms_per_step": round(dt / decode_steps * 1000, 2)})
        for u in uids:
            if eng.state.get_sequence(u) is not None:
                eng.flush(u)

    # context scaling: decode step latency must track tokens-in-cache
    # (the paged kernel reads valid blocks only), not max_context
    batch = batches[0]
    for ctx in (max_context // 4, max_context // 2,
                max_context - decode_steps - 1):
        if ctx < 8:
            continue
        prompts = [list(rng.integers(0, cfg.vocab_size, (ctx,)))
                   for _ in range(batch)]
        uids = list(range(batch))
        logits, _ = eng.put(uids, prompts)
        nxt = [int(np.argmax(l)) for l in logits]
        logits, _ = eng.put(uids, [[t] for t in nxt])   # warm decode
        t0 = time.perf_counter()
        for _ in range(decode_steps):
            nxt = [int(np.argmax(l)) for l in logits]
            logits, _ = eng.put(uids, [[t] for t in nxt])
        dt = time.perf_counter() - t0
        emit({"phase": "decode-context-scaling", "batch": batch,
              "context": ctx,
              "ms_per_step": round(dt / decode_steps * 1000, 2)})
        for u in uids:
            eng.flush(u)
    return results


def _main_serve_loop(argv):
    p = argparse.ArgumentParser(
        "hds_serve_bench serve_loop",
        description="continuous-batching serving loop over a Poisson "
                    "trace (the serving/ subsystem end-to-end)")
    p.add_argument("--model", default="tiny",
                   choices=("tiny", "1b", "7b"))
    p.add_argument("--max-context", type=int, default=128)
    p.add_argument("--prompt-len", type=int, default=48)
    p.add_argument("--max-new", type=int, default=24)
    p.add_argument("--rps", type=float, default=50.0)
    p.add_argument("--n-requests", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--num-blocks", type=int, default=10,
                   help="KV pool size; small on purpose so preemption "
                        "cycles occur mid-trace")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-lanes", type=int, default=4,
                   help="max sequences per ragged forward")
    p.add_argument("--virtual-clock", action="store_true",
                   help="replay on the deterministic simulated "
                        "timeline instead of wall time")
    p.add_argument("--chaos", action="store_true",
                   help="chaos mode: seeded fault injection over the "
                        "virtual-clock simulation, invariant + "
                        "determinism gates, CHAOS_SERVE.jsonl artifact")
    p.add_argument("--chaos-runs", type=int, default=2,
                   help="identical-seed replays for the determinism "
                        "gate (chaos/fleet modes)")
    p.add_argument("--fleet", action="store_true",
                   help="fleet mode: N-replica router + latent "
                        "migration under replica crash/hang/partition "
                        "chaos on the shared virtual clock, "
                        "FLEET_SERVE.jsonl artifact")
    p.add_argument("--n-replicas", type=int, default=3,
                   help="engine replicas in fleet mode")
    p.add_argument("--disagg", action="store_true",
                   help="disaggregated mode: N-prefill + M-decode "
                        "tiers with latent-wire handoff vs an "
                        "equal-replica colocated baseline on the "
                        "shared virtual clock, DISAGG_SERVE.jsonl "
                        "artifact")
    p.add_argument("--n-prefill", type=int, default=1,
                   help="prefill-tier replicas in disagg mode")
    p.add_argument("--n-decode", type=int, default=3,
                   help="decode-tier replicas in disagg mode")
    p.add_argument("--request-trace", action="store_true",
                   help="causal-tracing mode: connected cross-replica "
                        "span DAGs + attribution closure + flight-"
                        "recorder determinism over the chaos/fleet/"
                        "disagg legs, REQUEST_TRACE.jsonl artifact")
    p.add_argument("--out", default="SERVE_LOOP.jsonl",
                   help="also append rows to this jsonl file "
                        "('' = stdout only)")
    args = p.parse_args(argv)
    if args.request_trace:
        out = args.out if args.out != "SERVE_LOOP.jsonl" \
            else "REQUEST_TRACE.jsonl"
        run_request_trace(seed=args.seed, runs=args.chaos_runs,
                          out=out)
        return 0
    if args.disagg:
        out = args.out if args.out != "SERVE_LOOP.jsonl" \
            else "DISAGG_SERVE.jsonl"
        run_disagg_serve(seed=args.seed, n_prefill=args.n_prefill,
                         n_decode=args.n_decode,
                         runs=args.chaos_runs, out=out)
        return 0
    if args.fleet:
        out = args.out if args.out != "SERVE_LOOP.jsonl" \
            else "FLEET_SERVE.jsonl"
        run_fleet_serve(seed=args.seed, n_replicas=args.n_replicas,
                        n_requests=args.n_requests,
                        runs=args.chaos_runs, out=out)
        return 0
    if args.chaos:
        out = args.out if args.out != "SERVE_LOOP.jsonl" \
            else "CHAOS_SERVE.jsonl"
        run_chaos_serve(seed=args.seed, n_requests=args.n_requests,
                        runs=args.chaos_runs, out=out)
        return 0
    run_serve_loop(args.model, args.max_context, args.prompt_len,
                   max_new=args.max_new, rps=args.rps,
                   n_requests=args.n_requests, seed=args.seed,
                   num_blocks=args.num_blocks,
                   block_size=args.block_size,
                   max_lanes=args.max_lanes,
                   virtual_clock=args.virtual_clock, out=args.out)
    return 0


def main(argv=None):
    if argv is None:
        import sys
        argv = sys.argv[1:]
    if argv and argv[0] == "serve_loop":
        return _main_serve_loop(argv[1:])
    p = argparse.ArgumentParser("hds_serve_bench")
    p.add_argument("--model", default="tiny", choices=("tiny", "1b", "7b"))
    p.add_argument("--max-context", type=int, default=512)
    p.add_argument("--prompt-len", type=int, default=128)
    p.add_argument("--decode-steps", type=int, default=64)
    p.add_argument("--batches", type=int, nargs="+", default=[1, 4, 8])
    p.add_argument("--quantize", default="", choices=("", "int8", "fused"),
                   help="weight-only int8 serving; 'fused' routes through "
                        "the int8-weight Pallas kernel")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="Dynamic-SplitFuse chunk size (0 = off)")
    p.add_argument("--prefix-caching", action="store_true",
                   help="sweep with a shared system prefix and prefix "
                        "caching on")
    p.add_argument("--sweep", action="store_true",
                   help="throughput-latency curve under Poisson "
                        "arrivals (FastGen benchmark shape)")
    p.add_argument("--rps", type=float, nargs="+",
                   default=[1.0, 2.0, 4.0],
                   help="offered request rates for --sweep")
    p.add_argument("--max-new", type=int, default=32,
                   help="tokens generated per request in --sweep")
    p.add_argument("--n-requests", type=int, default=16)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--latent-dtype", default="",
                   help="HCache latent capture dtype (e.g. "
                        "float8_e4m3fn halves host-link bytes)")
    p.add_argument("--restore", action="store_true",
                   help="HCache mode: restore_kv vs full-prefill "
                        "time-to-cache-ready")
    p.add_argument("--restore-marginal", action="store_true",
                   help="HCache marginal-cost mode: chained dispatches "
                        "split device replay cost from host-link ship "
                        "cost")
    p.add_argument("--restore-crossover", action="store_true",
                   help="restore-vs-recompute crossover curve across "
                        "prompt lengths + the analytic model's verdicts "
                        "(JSONL artifact)")
    p.add_argument("--prompt-lens", type=int, nargs="+",
                   default=[32, 64, 128, 256],
                   help="prompt lengths for --restore-crossover")
    p.add_argument("--crossover-out", default="RESTORE_CROSSOVER.jsonl",
                   help="JSONL file for --restore-crossover rows "
                        "('' = stdout only)")
    p.add_argument("--fused-decode", action="store_true",
                   help="measure the on-device generate_fused loop "
                        "instead of host-driven per-step decode")
    p.add_argument("--lookup-decode", action="store_true",
                   help="measure prompt-lookup speculative decoding "
                        "(greedy-exact; reports acceptance + "
                        "tokens/dispatch)")
    args = p.parse_args(argv)
    from ..platform import require_chip
    require_chip("hds_serve_bench")
    # rows print as produced (partial results survive an OOM/crash)
    if args.sweep and args.fused_decode:
        if args.prefix_caching:
            raise SystemExit("--prefix-caching requires the host-driven "
                             "sweep (fused waves reserve whole stretches)")
        run_sweep_fused(args.model, args.max_context, args.prompt_len,
                        max_new=args.max_new, rates=tuple(args.rps),
                        n_requests=args.n_requests,
                        max_batch=args.max_batch, quantize=args.quantize,
                        prefill_chunk=args.prefill_chunk)
    elif args.sweep:
        run_sweep(args.model, args.max_context, args.prompt_len,
                  max_new=args.max_new, rates=tuple(args.rps),
                  n_requests=args.n_requests, max_batch=args.max_batch,
                  quantize=args.quantize,
                  prefill_chunk=args.prefill_chunk,
                  prefix_caching=args.prefix_caching)
    elif args.restore_crossover:
        run_restore_crossover(args.model, args.max_context,
                              tuple(args.prompt_lens),
                              batch=min(args.batches),
                              quantize=args.quantize,
                              latent_dtype=args.latent_dtype,
                              out=args.crossover_out)
    elif args.restore_marginal:
        run_restore_marginal(args.model, args.max_context,
                             args.prompt_len, tuple(args.batches),
                             quantize=args.quantize,
                             latent_dtype=args.latent_dtype)
    elif args.restore:
        run_restore(args.model, args.max_context, args.prompt_len,
                    tuple(args.batches), quantize=args.quantize,
                    prefill_chunk=args.prefill_chunk,
                    latent_dtype=args.latent_dtype)
    else:
        run(args.model, args.max_context, args.prompt_len,
            args.decode_steps, tuple(args.batches),
            quantize=args.quantize, prefill_chunk=args.prefill_chunk,
            fused=args.fused_decode, lookup=args.lookup_decode)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
