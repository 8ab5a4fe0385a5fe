"""``serve_diffusion``: one paced-traffic cell through the serving main
path for a model that generates by diffusion over blocks (``model_type``
``sdar_moe``: SDAR-30B-A3B-Chat, 128 experts top-8).

The same road as ``runners/serve.py`` and what of it does not assume one
token a sequence a step (``window_numbers``, the step log's base, the
constants): ``MODEL_FAMILIES[...]`` -> ``build_hf_engine`` ->
``ServingServer`` in thread mode, an open-loop generator on this
process's main thread. Its own:

* ``build``: the stacked layers are made on the device a layer at a
  time into buffers that are donated back (``stacked_layers``: the same
  keys and values as ``weights.seeded_tree``), and handed to the engine
  stacked: 8.73 GB of weights cannot be held twice while they are
  stacked.
* ``warm``: every prompt-slice bucket the traffic's whole blocks reach
  and every bucket of block lanes.
* the token callback: a stamp a token, all of a block's at its commit.
* the check (``check_passes``): for eight requests (the shortest prompt,
  the longest, whose context passes 1,536 tokens, and six between) every
  pass of their first block and of their ninth (behind eight blocks that
  this run denoised and committed), as the timed path made them: the
  logits rows against ``reference/sdar_moe.py``'s full forward of
  (committed context + block) under the block mask, layer by layer and
  expert by expert from the same bf16 leaves; and each pass's block
  against what the reference's choice and remasking rule make of the
  pass before it, down to the tokens the request was handed.
  ``tools/diffusion_controls.py`` runs the same check against the
  reference computed wrong in six ways, each of which has to fail.
* the evidence: tokens a forward, block lanes, fetched bytes a committed
  token, the experts' picks, the grouped products' and the paged
  kernel's calls.

Files of this cell (PR 42): ``configs/sdar-30b-a3b-serve-1chip.json``,
``traffic/diffusion-chat-256.json``, this runner,
``reference/sdar_moe.py``, ``flops_moe.py``,
``tools/diffusion_controls.py`` and seven metric files
(``tokens_per_forward``, ``block_lanes_mean``,
``fetch_bytes_per_token``, ``moe_share``, ``expert_gemm_roofline``,
``expert_load_imbalance``, ``paged_block_roofline``). Its traced line
also holds the metrics of the files that name their cells by the kind
``serve``, undeclared, as ``serve_hybrid``'s does.
"""

import functools
import gc
import time
import zlib

import numpy as np
from hcache_deepspeed_tpu.serving.metrics import ServingMetrics
from hcache_deepspeed_tpu.serving.request import RequestState

from .. import contract, flops_moe, layer_metrics, weights
from ..reference import sdar_moe as reference
from ..stats import mean, percentile
from ..trace import xplane
from .common import TracedStretch, device_line, fallback_count
from .serve import (GRACE_S, TRACE_S, _WARM_UID, _bucket, hf_config,
                    window_numbers)

#: The compared rows: four positions' logits of one pass, the engine's in
#: bf16 weights and activations through prompt slices and earlier passes
#: over the paged cache, the reference's in float32 at "highest"
#: precision in one full forward from the same bf16 leaves. A row's gap
#: is its largest difference over the reference row's largest |logit|
#: (``reference.row_gaps``). Routing is discrete: where a position's
#: eighth and ninth expert lie within the bf16 stream's rounding of each
#: other the two streams pick differently in some layer, and such a row
#: moves by 0.03 to 0.2 of its scale: more than half of all rows (my
#: chip runs, PR 42). So the reference routes the block's positions by
#: what the engine's routers read there (``BlockProbe.router_in``,
#: ``reference.logits``' ``route_from``), in its own float32 router: the
#: picks agree unless the engine's router is not the float32 one, and
#: every row is held to ``LOGIT_TOL``, the limit of the precision. The
#: context's positions still route from each side's own stream; their
#: flips reach a row through attention over all of the context.
#: ``LOGIT_TOL`` lies between two readings of a run's largest row
#: (PERF.md section 4; my chip runs, PR 42): the change's, 0.0185 to
#: 0.0300 of 184-188 rows a run over twenty-one runs, and the served
#: path's against the reference with its residual stream rounded after
#: every layer to float8_e4m3, the nearest precision below the bf16 the
#: configuration states, 0.0581 and 0.0637 on two seeds (its median row
#: 0.046-0.047), which has to fail; the least of the other controls
#: reads 0.0524 and 0.0628.
LOGIT_TOL = 0.04
#: requests a run probes, and the blocks of each (``Request.probe_blocks``:
#: its first, and one behind ``LATER_BLOCK`` blocks that this run
#: denoised and committed through the cache)
PROBED = 8
LATER_BLOCK = 8
#: the long compared request's context passes this many prompt slices
#: (1,536 tokens at the cell's 512-token chunk)
LONG_SLICES = 3
#: the reference runs at a context padded to a multiple of this
PAD = 512
#: The reference computed wrong, each of which ``check_passes`` has to
#: tell from the right one (``tools/diffusion_controls.py``): keys of
#: the architecture the reference is handed; ``stale_commit`` leaves the
#: last committed block of a later block's context as its last denoise
#: pass wrote it (two positions still masks).
CONTROLS = {
    "causal_mask_in_block": {"diffusion_block_length": 1},
    "bf16_router": {"router_dtype": "bfloat16"},
    "dropped_eighth_pick": {"num_experts_per_tok": 7},
    "no_qk_norm": {"qk_norm": False},
    "float8_e4m3_stream": {"stream_dtype": "float8_e4m3fn"},
    "denoise_kv_left_in_place": {"stale_commit": 2},
}
#: events the program's tracer keeps in a traced run (its default ring
#: of 65,536 holds some twenty seconds of this cell's spans)
TRACER_EVENTS = 1 << 21


def stacked_layers(shapes, seed, dtype, n_layer):
    """The ``layers_<i>`` subtrees of ``shapes`` as one tree of stacked
    leaves ``[L, ...]`` on the device, leaf ``[i]`` holding exactly what
    ``weights.seeded_tree`` gives ``layers_<i>`` there: matrices normal
    with std ``1 / sqrt(fan_in)`` keyed by the leaf's path, vectors one.
    A layer at a time into a donated buffer: the most beside the result
    is one layer's float32 draw."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    word = np.uint32(int(seed) & 0xFFFFFFFF)

    @functools.partial(jax.jit, donate_argnums=0, static_argnums=4)
    def put(buf, i, fold, seed_word, fan_in):
        root = jax.random.fold_in(jax.random.PRNGKey(0), seed_word)
        layer = jax.random.normal(jax.random.fold_in(root, fold),
                                  buf.shape[1:], jnp.float32) \
            * np.float32(1.0 / np.sqrt(fan_in))
        return jax.lax.dynamic_update_index_in_dim(
            buf, layer.astype(buf.dtype), i, 0)

    def leaf(path, like):
        names = weights._path_names(path)
        if like.ndim < 2:               # a norm's scale: ones, as _draw
            return jnp.ones((n_layer,) + like.shape, dtype)
        buf = jnp.zeros((n_layer,) + like.shape, dtype)
        for i in range(n_layer):
            name = "/".join((f"layers_{i}",) + names)
            buf = put(buf, np.int32(i),
                      np.uint32(zlib.crc32(name.encode()) & 0x7FFFFFFF),
                      word, like.shape[-2])
        return buf

    return jax.tree_util.tree_map_with_path(leaf, shapes["layers_0"])


class BlockTokens:
    """The server's ``block_token_fn``: a stamp a token on the
    generator's clock (a block's tokens arrive together, at its
    commit)."""

    def __init__(self):
        self.stamps = {}        # uid -> [t of each output token]

    def __call__(self, req, token):
        self.stamps.setdefault(req.uid, []).append(time.monotonic())


class BlockStepLog(ServingMetrics):
    """``ServingMetrics`` that also keeps what each scheduler step
    dispatched: the block lanes with their contexts, the prompt slices
    with their lengths and end positions (read off each request's
    progress since the step before) and what the device counted of the
    experts' routing meanwhile."""

    def __init__(self, block_len, chunk):
        super().__init__()
        self.block_len, self.chunk = block_len, chunk
        self.steps = []
        self._seen = {}         # uid -> (prefill_pos, committed, passes)
        self._moe = (0, 0)      # dispatches, touched so far

    def on_step(self, report, scheduler):
        super().on_step(report, scheduler)
        lanes, slices = [], []
        done = [scheduler.done[u] for u in report.finished
                if u in scheduler.done]
        for req in list(scheduler.running.values()) + done:
            now = (req.prefill_pos, req.block.committed, req.block.passes)
            was = self._seen.get(req.uid, (0, 0, 0))
            if req.finished:
                self._seen.pop(req.uid, None)
            else:
                self._seen[req.uid] = now
            if now[0] > was[0]:                     # a prompt slice
                slices.append((now[0] - was[0], now[0]))
            elif now[1:] != was[1:] or req.finished:    # a block pass
                lanes.append(max(was[1], now[0]) + self.block_len)
        stats = scheduler.engine.moe_stats()
        touched = stats["touched"] - self._moe[1]
        self._moe = (stats["dispatches"], stats["touched"])
        self.steps.append({
            "t": report.t, "lanes": report.block_lanes,
            "commit_lanes": report.commit_lanes,
            "prefill_tokens": report.prefill_tokens,
            "preempted": len(report.preempted),
            "block_ctx": lanes, "slices": slices, "touched": touched})


def build(ctx):
    """Weights, engine and server for ``ctx.config``."""
    import jax

    from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
    from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                        build_hf_engine)
    from hcache_deepspeed_tpu.models.sdar_moe import SdarMoeForCausalLM
    from hcache_deepspeed_tpu.serving import ServerConfig, ServingServer

    hf = hf_config(ctx.config)
    dep = ctx.config["deployment"]
    model_config = MODEL_FAMILIES[hf["model_type"]](hf)
    with ctx.phase("weights"):
        shapes = weights.param_shapes(
            SdarMoeForCausalLM(model_config),
            {"input_ids": np.zeros((1, 128), np.int32)})
        params = weights.seeded_tree(
            shapes, ctx.seed, hf["torch_dtype"],
            only=("embed_tokens", "norm", "lm_head"))
        params["layers"] = stacked_layers(
            shapes, ctx.seed, hf["torch_dtype"], model_config.n_layer)
        jax.block_until_ready(params)
    with ctx.phase("engine"):
        engine = build_hf_engine(hf, params, RaggedInferenceEngineConfig(
            state_manager={
                "max_tracked_sequences": dep["max_tracked_sequences"],
                "max_ragged_sequence_count":
                    dep["max_ragged_sequence_count"],
                "max_ragged_batch_size": dep["max_ragged_batch_size"],
                "max_context": dep["max_context"],
                "prefill_chunk": dep["prefill_chunk"]},
            kv_cache={"block_size": dep["block_size"],
                      "num_blocks": dep["num_blocks"],
                      "cache_dtype": hf["torch_dtype"]}))
        del params              # the engine holds the stacked leaves
        gc.collect()
    tokens = BlockTokens()
    steps = BlockStepLog(model_config.diffusion_block_length,
                         dep["prefill_chunk"])
    server = ServingServer(
        engine, block_token_fn=tokens, metrics=steps,
        config=ServerConfig(prefill_chunk=dep["prefill_chunk"],
                            denoising_steps=dep["denoising_steps"]))
    return {"engine": engine, "server": server, "tokens": tokens,
            "steps": steps, "shapes": shapes, "hf": hf,
            "model_config": model_config,
            # the traffic never draws the mask token or what lies past it
            "vocab": model_config.mask_token_id}


def warm_plan(traffic, deployment, block_len):
    """The dispatch shapes the traffic can reach: ``(prefill, lanes)``,
    ``prefill`` as ``runners/serve.py warm_plan`` gives it, over the
    slices of the prompts' whole blocks (a prompt's partial last block
    stands in its first open block), and ``lanes`` the counts that reach
    each bucket of block lanes."""
    gen = contract.load_kind("generators", traffic["kind"])
    chunk = deployment["prefill_chunk"]
    budget = deployment["max_ragged_batch_size"]
    together = int(traffic.get("prefills_together", 3))
    by_bucket = {}
    for n in gen.quantile_lengths(traffic["prompt_tokens"],
                                  int(traffic["block"])):
        whole = n // block_len * block_len
        for piece in {min(whole, chunk), whole % chunk}:
            if piece:
                by_bucket.setdefault(_bucket(piece, 8), []).append(piece)
    prefill = []
    for _, lens in sorted(by_bucket.items()):
        smallest = min(lens)
        most = max(1, min(together, budget // smallest))
        for lanes in sorted({_bucket(k, 1) for k in range(1, most + 1)}):
            prefill.append((lanes // 2 + 1 if lanes > 1 else 1, smallest))
    lanes, n = [], 8
    while n <= _bucket(deployment["max_tracked_sequences"], 8):
        lanes.append(min(n // 2 + 1, deployment["max_tracked_sequences"]))
        n *= 2
    return prefill, lanes


def warm_engine(engine, traffic, deployment, vocab):
    """Run every shape of :func:`warm_plan` once through ``engine.put``,
    then free what it allocated."""
    from hcache_deepspeed_tpu.inference.scheduling import BlockPass
    B = engine.block_len
    prefill, lanes = warm_plan(traffic, deployment, B)
    rng = np.random.default_rng(0)
    live = []

    def admit(count, length):
        uids = [_WARM_UID + len(live) + i for i in range(count)]
        engine.put(uids, [rng.integers(0, vocab, length) for _ in uids])
        live.extend(uids)

    try:
        for count, length in prefill:
            admit(count, length)
        count, length = max(prefill)             # cheapest way to add lanes
        while len(live) < max(lanes):
            admit(min(count, max(lanes) - len(live)), length)
        for n in lanes:                 # a third of the lanes commit
            engine.put(live[:n], [[1] * B] * n, blocks={
                uid: BlockPass(commit=j % 3 == 0)
                for j, uid in enumerate(live[:n])})
    finally:
        for uid in live:
            engine.flush(uid)
    return {"prefill_shapes": len(prefill), "decode_shapes": len(lanes)}


def offer(server, arrivals, t0, probed):
    """The open loop (``runners/serve.py offer``), asking the requests
    of the arrivals ``probed`` for their first and a later block's
    passes."""
    rows = []
    for k, a in enumerate(arrivals):
        due = t0 + a.due_s
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        sent = time.monotonic()
        req = server.submit(prompt=list(a.prompt),
                            max_new_tokens=a.max_new_tokens,
                            priority=a.priority,
                            probe_blocks=[0, LATER_BLOCK]
                            if k in probed else [])
        rows.append({"arrival": a, "due": due, "sent": sent,
                     "submitted": time.monotonic(), "req": req})
    return rows


def pick_probed(arrivals, half_s):
    """Which arrivals the check compares: of those due inside the
    window's first half, the shortest prompt, the longest, and others
    evenly spaced among them, ``PROBED`` in all."""
    early = [(len(a.prompt), k) for k, a in enumerate(arrivals)
             if a.in_window and a.due_s < half_s]
    if not early:
        return {}
    probed = {"short": min(early)[1], "long": max(early)[1]}
    rest = [k for _, k in early if k not in probed.values()]
    for j in range(min(PROBED - 2, len(rest))):
        probed[f"other{j}"] = rest[j * len(rest) // (PROBED - 2)]
    return probed


def check_passes(ctx, built, rows, probed, control=None):
    """The probed requests' probed blocks against the reference: every
    row of every pass within ``LOGIT_TOL`` of the reference's full
    forward on the same token ids, and every pass's block what the
    reference's choice and remasking rule make of the served rows of the
    pass before (a commit pass's block the tokens the request was
    handed). ``control``: a key of :data:`CONTROLS`, the reference
    computed wrong. Returns ``(ok, details)``."""
    hf, shapes = built["hf"], built["shapes"]
    dtype = hf["torch_dtype"]
    wrong = dict(CONTROLS[control]) if control else {}
    stale = wrong.pop("stale_commit", 0)
    arch = {**hf, **wrong}
    mask, B = hf["mask_token_id"], hf["diffusion_block_length"]
    count = -(-B // ctx.config["deployment"]["denoising_steps"])
    # the engine's own embedding, final norm and head: the same seeded
    # values, and a second copy of the vocabulary (1.2 GB) would not fit
    # beside the engine
    served = built["engine"].model.params
    outer = {"embed_tokens": {"embedding": served["embed"]},
             "norm": {"weight": served["norm"]},
             "lm_head": {"kernel": served["lm_head"]}}

    def layer(i):
        name = f"layers_{i}"
        return weights.seeded_tree(shapes, ctx.seed, dtype,
                                   only=(name,))[name]

    named, passes, broken = [], [], []
    for kind, k in sorted(probed.items()):
        req = rows[k]["req"] if k < len(rows) else None
        probes = list(getattr(req, "probes", ()))
        if req is None or req.probe_blocks or \
                len({p.ordinal for p in probes}) != 2:
            return False, {"reason": f"the {kind} request has not both of "
                           f"its blocks' passes to compare (has "
                           f"{[p.ordinal for p in probes]})"}
        whole = list(req.prompt) + list(req.tokens_out)
        for a, b in zip(probes, probes[1:] + [None]):
            context = list(a.context)
            if stale and a.ordinal:
                context[-stale:] = [mask] * stale
            named.append((kind, a))
            passes.append((context, a.block,
                           -(-(len(context) + B) // PAD) * PAD,
                           a.router_in))
            # what the served path made of this pass, by the reference's
            # rule on the served rows
            if mask in a.block:
                chosen, conf = reference.choose(a.rows, mask)
                want = reference.unmask(a.block, chosen, conf, mask, count)
                if b is None or b.ordinal != a.ordinal or \
                        list(b.block) != want or b.context != a.context:
                    broken.append(f"{kind}.{a.ordinal}.{len(named)}")
            elif whole[len(a.context):len(a.context) + B] != \
                    list(a.block)[:len(whole) - len(a.context)]:
                broken.append(f"{kind}.{a.ordinal}.tokens")
    refs = reference.blocks_logits(passes, arch, outer, layer)
    details, gaps = {}, []
    for (kind, probe), ref in zip(named, refs):
        rows_gap = reference.row_gaps(probe.rows, ref)
        gaps += rows_gap
        entry = details.setdefault(f"{kind}.{probe.ordinal}", {
            "context_tokens": len(probe.context), "masked": [],
            "row_gaps": []})
        entry["masked"].append(sum(1 for t in probe.block if t == mask))
        entry["row_gaps"] += [round(g, 5) for g in rows_gap]
    details["rows"] = len(gaps)
    details["largest"] = round(max(gaps, default=float("nan")), 5)
    details["median"] = round(float(np.median(gaps)), 5)
    details["broken_chains"] = broken
    ok = bool(gaps and np.isfinite(gaps).all()) and \
        max(gaps) <= LOGIT_TOL and not broken
    long_context = max((len(probe.context) for kind, probe in named
                        if kind == "long"), default=0)
    floor = LONG_SLICES * ctx.config["deployment"]["prefill_chunk"]
    if long_context <= floor:
        return False, {"reason": f"the long request's context is "
                       f"{long_context} tokens, not past {floor}",
                       **details}
    return ok, details


def kernel_calls(steps, cfg):
    """The kernels' calls in ``steps`` as keyword arguments of the
    counting functions (``flops_moe.py``): per step one block dispatch
    over the lanes and one call a prompt slice, each once a layer. The
    grouped products of a block dispatch are counted with the experts
    the program found touched, summed over its layers; those of a slice
    with the experts its rows touch when they fall evenly."""
    B, L, E = cfg.diffusion_block_length, cfg.n_layer, cfg.num_experts
    paged = dict(n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
                 head_dim=cfg.head_dim, itemsize=2, block=B)
    ffn = dict(hidden=cfg.hidden_size, width=cfg.intermediate_size,
               itemsize=2)
    calls = {"paged_block_calls": [], "expert_gemm_calls": []}
    for step in steps:
        if step["block_ctx"]:
            n = len(step["block_ctx"])
            calls["paged_block_calls"] += [dict(
                paged, context_lens=step["block_ctx"], q_lens=[B] * n)] * L
            calls["expert_gemm_calls"].append(dict(
                ffn, rows=n * B * cfg.top_k * L, touched=step["touched"]))
        for q, end in step["slices"]:
            calls["paged_block_calls"] += [dict(
                paged, context_lens=[end], q_lens=[q])] * L
            rows = q * cfg.top_k
            calls["expert_gemm_calls"].append(dict(
                ffn, rows=rows * L,
                touched=L * flops_moe.touched_experts(rows, E)))
    return calls


def run(ctx, check=check_passes):
    import jax

    from hcache_deepspeed_tpu.telemetry.tracer import get_tracer

    dep = ctx.config["deployment"]
    fallbacks_before = fallback_count()     # this run's, not the process's
    built = build(ctx)
    engine, server = built["engine"], built["server"]
    with ctx.phase("warm"):
        warmed = warm_engine(engine, ctx.traffic, dep, built["vocab"])
    gen = contract.load_kind("generators", ctx.traffic["kind"])
    arrivals = gen.schedule(ctx.traffic, ctx.seed, ctx.seconds,
                            built["vocab"], dep["max_context"])
    probed = pick_probed(arrivals,
                         ctx.traffic["ramp_s"] + ctx.seconds / 2.0)
    setup_compiles = ctx.meter.take()
    tracer = get_tracer()
    if ctx.trace:
        tracer.configure(enabled=True, capacity=TRACER_EVENTS)
    passes_before = engine.diffusion_stats()
    picks_before = engine.moe_stats()["picks"]

    server.start()
    t0 = time.monotonic()
    t_open = t0 + ctx.traffic["ramp_s"]
    t_close = t_open + ctx.seconds
    stretch = None
    if ctx.trace:
        stretch = TracedStretch(ctx.root, ctx.cell["name"])
        stretch.run(t_open + 1.0, t_open + 1.0 + min(TRACE_S,
                                                     ctx.seconds - 1.0))
    ctx.phases["ramp"] = round(ctx.traffic["ramp_s"], 3)
    ctx.phases["setup_s"] = t_open - ctx.t_start
    try:
        rows = offer(server, arrivals, t0, set(probed.values()))
        time.sleep(max(0.0, t_close - time.monotonic()))
        time.sleep(GRACE_S)
        t_grace = time.monotonic()
        for row in rows:
            if not row["req"].finished:
                server.cancel(row["req"].uid)
    finally:
        server.stop(drain=True, timeout=60.0)
    if server.error is not None:
        raise server.error
    if stretch is not None:
        stretch.join()

    nums = window_numbers(rows, built["tokens"].stamps, t_open, t_close,
                          t_grace)
    leaked = engine.state.allocator.num_blocks - 1 - engine.free_blocks
    faults = server.scheduler.fault_summary()
    passes = {k: v - passes_before[k]
              for k, v in engine.diffusion_stats().items()}
    picks = engine.moe_stats()["picks"] - (
        0 if picks_before is None else picks_before)
    t_check = time.monotonic()
    logits_ok, logit_details = check(ctx, built, rows, probed)
    t_check = time.monotonic() - t_check
    in_window = [s for s in built["steps"].steps
                 if t_open <= s["t"] < t_close]
    compiles = {"ramp": ctx.meter.between(t0, t_open),
                "window": ctx.meter.between(t_open, t_close)}
    fallbacks = fallback_count() - fallbacks_before
    finished = [r["req"] for r in rows
                if r["req"].state == RequestState.DONE and
                not r["req"].cancelled]
    # beside what ``runners/serve.py`` asks: the window ran the kernels
    # and built no program, and every request that ran to its end got
    # exactly the tokens it asked for (seeded weights never emit EOS)
    correct = bool(logits_ok and leaked == 0 and
                   faults["total_faults"] == 0 and
                   nums["attempted"] == sum(1 for a in arrivals
                                            if a.in_window) and
                   fallbacks == 0 and compiles["window"] == 0 and
                   all(len(r.tokens_out) == r.max_new_tokens
                       for r in finished))
    print(f"check: logits {logit_details} in {t_check:.1f} s, "
          f"leaked_blocks {leaked}, "
          f"faults {faults['total_faults']}, fallbacks {fallbacks}, "
          f"programs built or fetched {compiles}, finished "
          f"{len(finished)}, passes {passes}, preempted "
          f"{sum(s['preempted'] for s in in_window)}, steps in window "
          f"{len(in_window)}, longest "
          f"""{max((b['t'] - a['t'] for a, b in
                    zip(in_window, in_window[1:])), default=0.0):.3f} s""",
          flush=True)

    devices = jax.devices()
    result = {"correct": correct, "attempted": nums["attempted"],
              "failed": nums["failed"],
              "device": device_line(devices, ctx.cell["chips"])}
    end_to_end = {
        "ttft_p90_s": (percentile(nums["ttft"], 90), "s"),
        "itl_mean_s": (mean(nums["gaps"]), "s"),
        "serve_tok_s": (nums["tokens_in_window"] / ctx.seconds, "tokens/s"),
        "setup_s": (ctx.phases["setup_s"], "s")}
    ctx.phases.update(warmed, programs=setup_compiles["programs"],
                      cache_hits=setup_compiles["cache_hits"],
                      compile_or_fetch_s=setup_compiles["seconds"])
    if not ctx.trace:
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (v, u) in end_to_end.items()
                             if v is not None}
        return result

    traced = [s for s in built["steps"].steps
              if stretch.t_begin <= s["t"] < stretch.t_end]
    reduction = xplane.reduce_file(stretch.path)
    cfg = built["model_config"]
    # what every dispatch fetches; the check's probed passes (logits
    # rows, 2.4 MB each) are spans of their own, told apart by ``probe``
    fetched = sum(ev.get("args", {}).get("bytes", 0)
                  for ev in tracer.events() if ev["name"] == "serve.fetch"
                  and not ev.get("args", {}).get("probe"))
    latents = engine.latent_stats()
    evidence = {
        "series": {
            "gen_late_s": nums["late"], "submit_wait_s": nums["submit_wait"],
            "itl_s": nums["gaps"], "ttft_s": nums["ttft"],
            "queue_wait_s": [r["req"].queue_wait() for r in rows
                             if t_open <= r["due"] < t_close and
                             r["req"].queue_wait() is not None],
            "decode_lanes": [s["lanes"] for s in in_window if s["lanes"]],
            "block_lanes": [s["lanes"] for s in in_window if s["lanes"]]},
        "counters": {
            "preemptions": sum(s["preempted"] for s in in_window),
            "compiles_in_window": compiles["window"],
            "restores": engine.restore_stats["restores"],
            "restore_mb": engine.restore_stats["bytes_shipped"] / 1e6,
            "fallbacks": fallbacks},
        "memory": {"peak_bytes": result["device"]["memory_peak_bytes"]},
        "trace": reduction,
        "device_kind": devices[0].device_kind,
        "arch": built["hf"],
        "placeholders": {
            "kv_pool": f"{dep['num_blocks'] * dep['block_size']}_"
                       f"{cfg.head_dim}_",
            "kv_blocks": f"{cfg.n_kv_head},{dep['num_blocks']},"
                         f"{dep['block_size']},{cfg.head_dim}"},
    }
    evidence.update(kernel_calls(traced, cfg))
    counters = evidence["counters"]
    if passes["lane_passes"]:
        counters["tokens_per_forward"] = \
            passes["tokens_committed"] / passes["lane_passes"]
    if passes["tokens_committed"] and tracer.dropped == 0:
        counters["fetch_bytes_per_token"] = \
            fetched / passes["tokens_committed"]
    if picks.sum():
        counters["expert_load_imbalance"] = picks.max() / picks.mean()
    if latents.get("captured_tokens"):
        counters["latent_bytes_per_token"] = \
            latents["captured_bytes"] / latents["captured_tokens"]
    # the serve cells' metrics (files that name their cells by the kind
    # "serve"), undeclared for this cell, and this kind's own
    result["metrics"] = {
        **layer_metrics.compute(ctx.cell, "serve", evidence),
        **layer_metrics.compute(ctx.cell, ctx.config["runner"], evidence)}
    result["device"].update(busy_s=reduction.busy_s,
                            window_s=reduction.window_s)
    result["breakdown"] = reduction.breakdown()
    return result
