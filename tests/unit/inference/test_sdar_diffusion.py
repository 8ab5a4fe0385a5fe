"""SDAR-MoE (``model_type`` ``sdar_moe``) on the normal serving path, at
a small size on the CPU: generation by diffusion over blocks of four
through ``build_hf_engine`` -> ``InferenceEngineV2`` ->
``ServingServer``, a sparse-expert trunk with a per-head q/k norm and a
head width of its own, seeded random weights in float32. The reference
is the benchmark's plain one (``benchmarks/reference/sdar_moe.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sdar_moe as reference
from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
from hcache_deepspeed_tpu.inference.engine_v2 import DiffusionUnsupported
from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                    build_hf_engine)
from hcache_deepspeed_tpu.inference.scheduling import BlockPass
from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM, llama_tiny
from hcache_deepspeed_tpu.models.sdar_moe import SdarMoeForCausalLM
from hcache_deepspeed_tpu.moe.dropless import routed_expert_ffn
from hcache_deepspeed_tpu.runtime.config import HDSConfigError
from hcache_deepspeed_tpu.serving import ServerConfig, ServingServer
from hcache_deepspeed_tpu.serving.request import OpenBlock, RequestState
from hcache_deepspeed_tpu.serving.spec import SpeculationConfig

MASK = 255
HF = {
    "model_type": "sdar_moe", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 192, "moe_intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32,
    "max_position_embeddings": 128, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "torch_dtype": "float32",
    "diffusion_block_length": 4, "mask_token_id": MASK}

#: Engine and reference both compute in float32 from the same weights;
#: they differ in the order of their sums (slices and block passes over
#: the pool against one full forward): a few float32 roundings a layer.
#: A causal mask inside the block or a missing q/k norm reads 1e-1.
TOL = 2e-4


@pytest.fixture(scope="module")
def params():
    cfg = MODEL_FAMILIES["sdar_moe"](HF)
    tree = SdarMoeForCausalLM(cfg).init(
        jax.random.PRNGKey(1),
        {"input_ids": np.zeros((1, 16), np.int32)}, train=False)["params"]
    # norm scales away from one, so that a missing norm shows; a head
    # scaled up, so that margins between logits are wide and the served
    # tokens are the reference's whatever the order of the sums
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))
    tree = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.3 * jax.random.normal(
            next(keys), x.shape)) if "norm" in str(path[-2]) else x, tree)
    tree["lm_head"]["kernel"] = tree["lm_head"]["kernel"] * 8.0
    return tree


def make_engine(params, num_blocks=32, latents=True, hf=HF):
    return build_hf_engine(hf, params, RaggedInferenceEngineConfig(
        state_manager=dict(
            max_tracked_sequences=8, max_ragged_sequence_count=8,
            max_ragged_batch_size=64, max_context=128, prefill_chunk=16),
        kv_cache=dict(block_size=16, num_blocks=num_blocks,
                      cache_dtype="float32"),
        hcache={"enable_latents": latents}))


def outer_and_layer(params):
    return ({k: params[k] for k in ("embed_tokens", "norm", "lm_head")},
            lambda i: params[f"layers_{i}"])


def reference_rows(params, context, block, **arch):
    outer, layer = outer_and_layer(params)
    return reference.block_logits(context, block, {**HF, **arch}, outer,
                                  layer)


def prompt_of(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 250, n)]


def prefill(engine, uid, prompt, latents=None):
    """The prompt's whole blocks through 16-token slices (their latents
    appended to ``latents``); returns the context in the cache and the
    first open block."""
    whole = len(prompt) // 4 * 4
    for at in range(0, whole, 16):
        out, (lat,) = engine.put([uid], [prompt[at:min(at + 16, whole)]])
        assert out == [None]            # a slice fetches no logits
        if latents is not None:
            latents.append(np.asarray(lat))
    carried = prompt[whole:]
    return prompt[:whole], carried + [MASK] * (4 - len(carried))


def test_every_pass_through_the_cache_equals_the_full_forward(params):
    engine = make_engine(params)
    context, block = prefill(engine, 7, prompt_of(38))    # 36 + 2 carried
    worst = 0.0
    for _ in range(3):                                    # three blocks
        while True:
            commit = MASK not in block
            (choice,), (lat,) = engine.put(
                [7], [block], blocks={7: BlockPass(commit, probe=True)})
            ref = reference_rows(params, context, block)
            worst = max(worst, reference.logit_gap(choice.logits, ref))
            tokens, conf = reference.choose(ref, MASK)
            assert list(choice.tokens) == list(tokens)
            np.testing.assert_allclose(choice.confidence, conf, rtol=1e-3)
            assert (lat is not None) == commit            # commits only
            if commit:
                break
            block = reference.unmask(block, tokens, conf, MASK, 2)
        context, block = context + block, [MASK] * 4
    assert worst < TOL
    assert engine.state.get_sequence(7).seen_tokens == 36 + 12
    stats = engine.diffusion_stats()
    assert stats["tokens_committed"] == 12
    assert stats["lane_passes"] == 2 + 3 + 3      # 1, 2, 2 denoise passes
    assert stats["positions_masked"] == 2 + (4 + 2) * 2


@pytest.mark.parametrize("variant", [
    {"diffusion_block_length": 1},        # a causal mask inside the block
    {"qk_norm": False}])                  # no per-head norm of q and k
def test_the_comparison_sees_a_wrong_mask_and_a_missing_norm(params,
                                                             variant):
    engine = make_engine(params)
    context, block = prefill(engine, 1, prompt_of(34, seed=3))
    (choice,), _ = engine.put([1], [block],
                              blocks={1: BlockPass(probe=True)})
    good = reference.logit_gap(choice.logits,
                               reference_rows(params, context, block))
    bad = reference.logit_gap(
        choice.logits, reference_rows(params, context, block, **variant))
    assert good < TOL and bad > 50 * TOL


@pytest.mark.parametrize("steps", [2, 4])
def test_served_tokens_equal_the_references_generate(params, steps):
    engine = make_engine(params)
    emitted = {}

    def on_token(req, token):
        emitted.setdefault(req.uid, []).append(token)

    server = ServingServer(engine, block_token_fn=on_token,
                           config=ServerConfig(prefill_chunk=16,
                                               denoising_steps=steps))
    prompts = [prompt_of(n, seed=n) for n in (22, 3, 40, 17)]
    budgets = (10, 9, 8, 13)
    reqs = [server.submit(prompt=p, max_new_tokens=m)
            for p, m in zip(prompts, budgets)]
    reports = []
    for _ in range(100):
        reports.append(server.step())
        if all(r.finished for r in reqs):
            break
    outer, layer = outer_and_layer(params)
    for prompt, req in zip(prompts, reqs):
        assert req.state == RequestState.DONE
        want = reference.generate(
            prompt, req.max_new_tokens, HF, outer, layer,
            denoising_steps=steps)
        assert req.tokens_out == want == emitted[req.uid]
    assert engine.free_blocks == 31             # all but the scratch block
    stats = engine.diffusion_stats()
    assert sum(r.block_lanes for r in reports) == stats["lane_passes"]
    assert sum(r.tokens_committed for r in reports) == \
        stats["tokens_committed"] == 4 * sum(r.commit_lanes
                                             for r in reports)
    assert sum(r.tokens_unmasked for r in reports) == sum(
        -(-(len(p) % 4 + m) // 4) * 4 - len(p) % 4
        for p, m in zip(prompts, budgets))      # every mask was filled once
    assert server.metrics.gauges["tokens_per_forward"] == pytest.approx(
        stats["tokens_committed"] / stats["lane_passes"])
    # a whole block takes ``steps`` passes and a commit
    assert 4 / (steps + 1) <= \
        server.metrics.gauges["tokens_per_forward"] <= 2
    picks = engine.moe_stats()["picks"]
    # two picks a position a layer, over every position fed
    assert picks.sum() == stats["positions_fed"] * 2 * 2


def test_a_request_stops_at_eos_inside_a_block(params):
    engine = make_engine(params)
    outer, layer = outer_and_layer(params)
    prompt = prompt_of(21, seed=5)
    free = reference.generate(prompt, 12, HF, outer, layer)
    eos = free[5]
    server = ServingServer(engine, config=ServerConfig(prefill_chunk=16))
    req = server.submit(prompt=prompt, max_new_tokens=12, eos_token_id=eos)
    for _ in range(60):
        server.step()
    assert req.tokens_out == free[:free.index(eos) + 1]


def test_latents_of_commits_restore_the_uninterrupted_logits(params):
    prompt = prompt_of(27, seed=9)                  # 24 + 3 carried
    straight = make_engine(params)
    evicted = make_engine(params)
    rows = {}
    for name, engine in (("straight", straight), ("evicted", evicted)):
        held = []       # what the engine hands out: slices, then commits
        context, block = prefill(engine, 4, prompt, held)
        for _ in range(2):
            while True:
                commit = MASK not in block
                (choice,), (lat,) = engine.put(
                    [4], [block], blocks={4: BlockPass(commit, probe=True)})
                if commit:
                    held.append(np.asarray(lat))
                    break
                block = reference.unmask(block, choice.tokens,
                                         choice.confidence, MASK, 2)
            context, block = context + block, [MASK] * 4
        if name == "evicted":
            # one denoise pass into the third block, then out and back:
            # the open block is dropped, the committed ones replayed
            engine.put([4], [block], blocks={4: BlockPass()})
            engine.flush(4)
            latents = np.concatenate(held, axis=1)
            assert latents.shape[1] == len(context) == 32
            engine.restore_kv([4], [context], [latents])
        (choice,), _ = engine.put([4], [block],
                                  blocks={4: BlockPass(probe=True)})
        rows[name] = choice.logits
    assert reference.logit_gap(rows["evicted"], rows["straight"]) < TOL
    assert reference.logit_gap(
        rows["evicted"], reference_rows(params, context, block)) < TOL


def test_scheduler_preempts_between_blocks_and_resumes_at_the_commit(
        params):
    outer, layer = outer_and_layer(params)
    prompts = [prompt_of(n, seed=n) for n in (30, 26, 29)]
    want = [reference.generate(p, 24, HF, outer, layer) for p in prompts]
    # 11 usable blocks of 16: three sequences of up to 56 tokens need 12
    engine = make_engine(params, num_blocks=12)
    server = ServingServer(engine, config=ServerConfig(prefill_chunk=16))
    reqs = [server.submit(prompt=p, max_new_tokens=24, priority=i)
            for i, p in enumerate(prompts)]
    preempted = restored = 0
    for _ in range(400):
        report = server.step()
        preempted += len(report.preempted)
        restored += len(report.restored) + len(report.recomputed)
        if all(r.finished for r in reqs):
            break
    assert preempted and restored
    for req, tokens in zip(reqs, want):
        assert req.state == RequestState.DONE and req.tokens_out == tokens
    assert engine.free_blocks == 11


def test_paged_walk_stats_count_each_lanes_own_blocks(params):
    """``engine.paged_walk_stats()`` over hand-built dispatches: a
    prompt slice that starts inside its second block, a decode bucket
    of eight with five padded lanes, a block pass of four lanes with
    one padded. ``table_slots`` is what a grid over the table stepped
    through, ``blocks_walked`` what the lanes hold."""
    engine = make_engine(params)
    model, cache, NB = engine.model, engine.cache, engine.max_blocks_per_seq
    assert (NB, engine.block_size) == (8, 16)
    assert engine.paged_walk_stats() == {
        "dispatches": 0, "table_slots": 0, "blocks_walked": 0}
    tables = np.arange(1, 1 + 8 * NB, dtype=np.int32).reshape(8, NB) % 32

    def lanes(T, start, t_len):
        start, t_len = np.asarray(start), np.asarray(t_len)
        return (np.zeros((len(start), T), np.int32), start,
                tables[:len(start)], t_len)

    # positions 20..35: blocks 0, 1 and 2 of the lane
    model.forward_chunk(cache, *lanes(16, [20], [16]))
    assert engine.paged_walk_stats() == {
        "dispatches": 1, "table_slots": NB, "blocks_walked": 3}
    # contexts of 1, 16 and 17 tokens: 1 + 1 + 2 blocks; padding: none
    model.forward_chunk(cache, *lanes(1, [0, 15, 16, 0, 0, 0, 0, 0],
                                      [1, 1, 1, 0, 0, 0, 0, 0]))
    assert engine.paged_walk_stats() == {
        "dispatches": 2, "table_slots": 9 * NB, "blocks_walked": 7}
    # blocks of four at 12 (ends on a block's edge), 16 and 124 (the
    # table's last positions)
    model.forward_block(cache, *lanes(4, [12, 16, 124, 0], [4, 4, 4, 0]),
                        np.zeros((4,), np.int32))
    assert engine.paged_walk_stats() == {
        "dispatches": 3, "table_slots": 13 * NB,
        "blocks_walked": 7 + 1 + 2 + 8}
    assert engine.dispatch_stats()["dispatches"] == 3


@pytest.mark.parametrize("call", [
    lambda e: e.put_spec([1], [[5, 6]]),
    lambda e: e.generate([[1, 2, 3, 4]], max_new_tokens=4),
    lambda e: e.generate_fused([[1, 2, 3, 4]], max_new_tokens=4),
    lambda e: e.generate_lookup([[1, 2, 3, 4]], max_new_tokens=4),
    lambda e: e.generate_lookup_fused([[1, 2, 3, 4]], max_new_tokens=4)],
    ids=["put_spec", "generate", "generate_fused", "generate_lookup",
         "generate_lookup_fused"])
def test_what_assumes_one_token_a_step_refuses_by_name(params, call):
    engine = make_engine(params)
    with pytest.raises(DiffusionUnsupported,
                       match="diffusion over blocks"):
        call(engine)
    assert engine.state.n_tracked_sequences == 0 and \
        engine.free_blocks == 31


def test_unsupported_settings_are_refused_where_they_are_made(params):
    with pytest.raises(DiffusionUnsupported, match="prefix_caching"):
        build_hf_engine(HF, params, RaggedInferenceEngineConfig(
            state_manager={"prefix_caching": True, "max_context": 128},
            kv_cache={"block_size": 16, "num_blocks": 8},
            hcache={"enable_latents": False}))
    with pytest.raises(ValueError, match="must divide"):
        build_hf_engine(HF, params, RaggedInferenceEngineConfig(
            state_manager={"prefill_chunk": 18, "max_context": 128},
            kv_cache={"block_size": 16, "num_blocks": 8}))
    engine = make_engine(params)
    with pytest.raises(HDSConfigError, match="diffusion over blocks"):
        ServingServer(engine, config=ServerConfig(
            speculation=SpeculationConfig(enabled=True)))
    with pytest.raises(ValueError, match="whole blocks"):
        engine.put([1], [[1, 2, 3]])
    with pytest.raises(ValueError, match="feeds 4 positions"):
        engine.put([1], [[1, 2, 3, 4, 5, 6, 7, 8]],
                   blocks={1: BlockPass()})
    for key, value in (("decoder_sparse_step", 2), ("mlp_only_layers", [0])):
        with pytest.raises(NotImplementedError, match="dense layers"):
            MODEL_FAMILIES["sdar_moe"]({**HF, key: value})


def test_without_the_key_the_family_is_causal(params):
    hf = {k: v for k, v in HF.items() if k != "diffusion_block_length"}
    assert MODEL_FAMILIES["sdar_moe"](hf).diffusion_block_length == 1
    engine = make_engine(params, hf=hf)
    assert not engine.diffusion and engine.model.mask_block == 1
    prompt = prompt_of(21)
    logits, _ = engine.put([1], [prompt])
    outer, layer = outer_and_layer(params)
    ref = reference.logits(prompt, {**hf, "diffusion_block_length": 1},
                           outer, layer)[-1]
    assert reference.logit_gap(logits[0], ref) < TOL


def test_the_router_stays_float32_under_a_bfloat16_model(params):
    """Routing logits, softmax and top-k in float32 whatever the compute
    dtype."""
    engine = make_engine(params, hf={**HF, "torch_dtype": "bfloat16"})
    moe = engine.model.params["layers"]["mlp"]["moe"]
    assert moe["wg"].dtype == jnp.float32
    assert moe["experts"]["w1"].dtype == jnp.bfloat16
    jaxpr = jax.make_jaxpr(lambda lp, h: engine.model._routed(lp, h)[1])(
        jax.tree.map(lambda x: x[0], engine.model.params["layers"]),
        jnp.zeros((1, 4, 64), jnp.bfloat16))
    tops = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "top_k"]
    assert tops and all(e.invars[0].aval.dtype == jnp.float32 for e in tops)


def test_the_device_chooses_greedy_and_never_the_mask(params):
    engine = make_engine(params)
    context, block = prefill(engine, 2, prompt_of(16))
    (first,), _ = engine.put([2], [block], blocks={2: BlockPass()})
    engine.flush(2)
    # a head that likes the mask token best where it liked ``first``'s
    # choice (a positive logit, doubled): it is still not chosen
    head = params["lm_head"]["kernel"]
    engine = make_engine(dict(params, lm_head={
        "kernel": head.at[:, MASK].set(2.0 * head[:, first.tokens[0]])}))
    context, block = prefill(engine, 2, prompt_of(16))
    (choice,), _ = engine.put([2], [block],
                              blocks={2: BlockPass(probe=True)})
    tokens, confidence = reference.choose(choice.logits, MASK)
    assert np.argmax(choice.logits[0]) == MASK
    assert list(choice.tokens) == list(tokens)
    np.testing.assert_allclose(choice.confidence, confidence, rtol=1e-3)
    # what each layer's router read, for the probed lane alone
    assert choice.router_in.shape == (2, 4, 64)
    (plain,), _ = engine.put([2], [block], blocks={2: BlockPass()})
    assert plain.logits is None and plain.router_in is None
    assert list(plain.tokens) == list(tokens)


def test_the_remasking_rules():
    block = OpenBlock([7, MASK, MASK, MASK], carried=1)
    conf = [0.9, 0.2, 0.5, 0.5]
    assert block.unmask([1, 2, 3, 4], conf, MASK, 1) == 1
    assert block.tokens == [7, MASK, 3, MASK]   # a tie: the lower position
    assert reference.unmask(block.tokens, [1, 2, 3, 4], conf, MASK, 1) == \
        [7, MASK, 3, 4]
    # the dynamic rule (the reference's alone: on seeded weights no
    # confidence passes a threshold, and the server has no such setting)
    assert reference.unmask(block.tokens, [1, 2, 3, 4], conf, MASK, 1,
                            "dynamic", 0.1) == [7, 2, 3, 4]
    assert block.unmask([1, 2, 3, 4], conf, MASK, 2) == 2
    assert block.tokens == [7, 2, 3, 4] and block.passes == 2


def test_probed_blocks_come_whole_and_route_the_reference(params):
    """Two requests ask for the same blocks in the same dispatches; a
    dispatch probes one lane, so one of them takes later blocks. Every
    probed block is whole: each pass follows from the one before by the
    reference's rule on the served rows, and the rows are the
    reference's where it routes by what the served routers read."""
    engine = make_engine(params)
    server = ServingServer(engine, config=ServerConfig(prefill_chunk=16))
    prompts = [prompt_of(16, seed=1), prompt_of(16, seed=2)]
    reqs = [server.submit(prompt=p, max_new_tokens=30, probe_blocks=[0, 2])
            for p in prompts]
    for _ in range(60):
        server.step()
    outer, layer = outer_and_layer(params)
    assert sorted(p.ordinal for p in reqs[0].probes) == [0] * 3 + [2] * 3
    assert sorted(p.ordinal for p in reqs[1].probes) == [1] * 3 + [3] * 3
    for req in reqs:
        assert req.finished and not req.probe_blocks
        for a, b in zip(req.probes, req.probes[1:]):
            if a.ordinal != b.ordinal:
                n = len(a.context)      # the commit pass's block stands
                assert MASK not in a.block and \
                    b.context[:n + 4] == a.context + a.block
                continue
            tokens, conf = reference.choose(a.rows, MASK)
            assert reference.unmask(a.block, tokens, conf, MASK, 2) == \
                b.block and a.context == b.context
        for probe in req.probes:
            ref = reference.block_logits(
                probe.context, probe.block, HF, outer, layer,
                route_from=probe.router_in)
            assert reference.logit_gap(probe.rows, ref) < TOL
            wrong = reference.block_logits(
                probe.context, probe.block, HF, outer, layer,
                route_from=np.roll(probe.router_in, 1, axis=1))
            assert reference.logit_gap(probe.rows, wrong) > 50 * TOL


def test_a_published_head_width_on_the_llama_trunk():
    """hidden 64 over 4 heads is 16 a head; the family says 32, and norms
    q and k a head: projections, rotary tables, pools and the restore
    replay all follow ``head_dim``."""
    cfg = llama_tiny(head_width=32, qk_norm=True, n_kv_head=2)
    assert cfg.head_dim == 32
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)},
                        train=False)["params"]
    attn = params["layers_0"]["self_attn"]
    assert attn["q_proj"]["kernel"].shape == (64, 128)
    assert attn["o_proj"]["kernel"].shape == (128, 64)
    assert attn["k_norm"]["weight"].shape == (32,)
    from hcache_deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    config = RaggedInferenceEngineConfig(
        state_manager=dict(max_tracked_sequences=4, max_context=128,
                           prefill_chunk=16),
        kv_cache=dict(block_size=16, num_blocks=16, cache_dtype="float32"))
    engine = InferenceEngineV2(cfg, params, config)
    assert engine.cache.k.shape == (2, 2, 256, 32)
    prompt = prompt_of(40)
    want = model.apply({"params": params},
                       {"input_ids": np.asarray([prompt + [9, 3]])},
                       return_logits=True)[0]
    logits, latents = engine.put([1], [prompt])
    np.testing.assert_allclose(logits[0], want[39], atol=2e-4)
    logits, _ = engine.put([1], [[9]])
    np.testing.assert_allclose(logits[0], want[40], atol=2e-4)
    # the replay norms k too: restore, then the same next row
    again = InferenceEngineV2(cfg, params, config)
    _, more = engine.put([1], [[3]])
    payload = np.concatenate([np.asarray(latents[0])], axis=1)
    again.restore_kv([5], [prompt], [payload])
    logits, _ = again.put([5], [[9]])
    np.testing.assert_allclose(logits[0], want[40], atol=2e-4)


def test_128_experts_top_8_against_the_plain_loop():
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    N, d, f, E, k = 24, 32, 16, 128, 8
    x = jax.random.normal(keys[0], (N, d))
    wg = jax.random.normal(keys[1], (d, E))
    w1, w3 = (jax.random.normal(key, (E, d, f)) / np.sqrt(d)
              for key in keys[2:4])
    w2 = jax.random.normal(keys[4], (E, f, d)) / np.sqrt(f)
    out, _, experts = routed_expert_ffn(x, wg, w1, w3, w2, k, True)
    probs = jax.nn.softmax(x @ wg, axis=-1)
    want = np.zeros((N, d), np.float32)
    for n in range(N):
        top = np.argsort(-np.asarray(probs[n]))[:k]
        assert sorted(top) == sorted(np.asarray(experts[n]))
        for e in top:
            gate = probs[n, e] / probs[n, top].sum()
            want[n] += gate * np.asarray(
                (jax.nn.silu(x[n] @ w1[e]) * (x[n] @ w3[e])) @ w2[e])
    np.testing.assert_allclose(out, want, atol=1e-4)
