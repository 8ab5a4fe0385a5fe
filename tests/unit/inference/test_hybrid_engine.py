"""The hybrid trunk (gated-delta-rule layers beside full attention) on
the normal serving path, at a small size on the CPU: hidden 64, two
periods of (3 linear, 1 full), 4 heads, d_k 8, d_v 16, block 8, prefill
chunk 16, seeded random weights in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import olmo_hybrid as reference
from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                    build_hf_engine)
from hcache_deepspeed_tpu.inference.model_hybrid import (
    PagedHybridModel, RecurrentStateUnsupported)
from hcache_deepspeed_tpu.inference.ragged.kv_cache import \
    pool_sized_copies
from hcache_deepspeed_tpu.inference.ragged.lanes import lanes_width
from hcache_deepspeed_tpu.inference.scheduling import SchedulingResult
from hcache_deepspeed_tpu.models.olmo_hybrid import OlmoHybridForCausalLM
from hcache_deepspeed_tpu.serving import ServerConfig, ServingServer

HF = {
    "model_type": "olmo_hybrid", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "torch_dtype": "float32"}

#: Engine and reference both compute in float32 from the same weights;
#: they differ in the order of their sums (slices and one-token steps
#: over pools against one pass token by token), a few float32 roundings
#: a layer: under 1e-4 of the row's scale at 8 layers. A dropped layer
#: or a state started from another sequence's moves the row by 1e-1.
TOL = 2e-4


@pytest.fixture(scope="module")
def params():
    cfg = MODEL_FAMILIES["olmo_hybrid"](HF)
    tree = OlmoHybridForCausalLM(cfg).init(
        jax.random.PRNGKey(1),
        {"input_ids": np.zeros((1, 16), np.int32)})["params"]
    # norm scales away from one, so that a missing norm shows
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 200))
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.2 * jax.random.normal(
            next(keys), x.shape)) if "norm" in str(path[-2]) else x, tree)


def make_engine(params, num_blocks=64, tracked=8, **overrides):
    config = {"state_manager": dict(
        max_tracked_sequences=tracked, max_ragged_sequence_count=8,
        max_ragged_batch_size=64, max_context=128, prefill_chunk=16),
        "kv_cache": dict(block_size=8, num_blocks=num_blocks,
                         cache_dtype="float32")}
    config.update(overrides)
    return build_hf_engine(HF, params, RaggedInferenceEngineConfig(**config))


def reference_row(params, tokens):
    outer = {k: params[k] for k in ("embed_tokens", "norm", "lm_head")}
    return reference.next_token_logits(
        np.asarray(tokens, np.int32), len(tokens), HF, outer,
        lambda i: params[f"layers_{i}"])


def decode(engine, uids, logits, steps, toks):
    for _ in range(steps):
        nxt = [int(np.argmax(row)) for row in logits]
        for seq, n in zip(toks, nxt):
            seq.append(n)
        logits, _ = engine.put(uids, [[n] for n in nxt])
    return logits


def test_engine_matches_the_token_by_token_reference(params):
    """A ragged batch of mixed lengths, prefilled in 16-token slices,
    then decoded through both pools."""
    engine = make_engine(params)
    rng = np.random.default_rng(0)
    toks = [list(rng.integers(0, 256, n)) for n in (37, 5, 16, 50)]
    uids = [0, 1, 2, 3]
    logits, latents = engine.put(uids, toks)
    # latents leave the program for the full layers only
    assert np.asarray(latents[0]).shape == (2, 37, 64)
    logits = decode(engine, uids, logits, 4, toks)
    for row, seq in zip(logits, toks):
        assert reference.logit_gap(row, reference_row(params, seq)) < TOL
    assert engine.latent_stats()["captured_bytes"] == \
        engine.latent_stats()["captured_tokens"] * 2 * 64 * 4


def test_evict_restore_and_go_on_equals_never_evicted(params):
    rng = np.random.default_rng(1)
    prompt = list(rng.integers(0, 256, 41))
    stayed, evicted = make_engine(params), make_engine(params)
    rows = {}
    for name, engine in (("stayed", stayed), ("evicted", evicted)):
        toks = list(prompt)
        logits, lat = engine.put([7], [toks])
        chunks = [np.asarray(lat[0])]
        for step in range(6):
            if name == "evicted" and step == 3:
                state = engine.snapshot_state(7)
                engine.flush(7)
                assert engine.state.free_state_slots == 8
                # another sequence takes the slot meanwhile
                engine.put([8], [list(rng.integers(0, 256, 20))])
                engine.restore_kv([7], [toks],
                                  [np.concatenate(chunks, axis=1)],
                                  states=[state])
            nxt = int(np.argmax(logits[0]))
            toks.append(nxt)
            logits, lat = engine.put([7], [[nxt]])
            chunks.append(np.asarray(lat[0]))
        rows[name] = logits[0]
    np.testing.assert_allclose(rows["evicted"], rows["stayed"], atol=1e-5)
    assert evicted.state_stats["snapshots"] == 1
    assert evicted.state_stats["bytes_in"] == \
        evicted.state_stats["bytes_out"] == evicted.cache.slot_bytes


def test_a_slot_freed_and_taken_again_starts_from_zero(params):
    rng = np.random.default_rng(2)
    first, second = (list(rng.integers(0, 256, n)) for n in (30, 23))
    engine = make_engine(params)
    engine.put([0], [first])
    slot = engine.state.get_sequence(0).state_slot
    engine.flush(0)
    logits, _ = engine.put([1], [second])
    assert engine.state.get_sequence(1).state_slot == slot
    fresh, _ = make_engine(params).put([1], [second])
    np.testing.assert_array_equal(logits, fresh)
    assert reference.logit_gap(logits[0],
                               reference_row(params, second)) < TOL


def test_the_recurrent_state_is_float32_whatever_the_cache_dtype(params):
    engine = make_engine(params, kv_cache=dict(
        block_size=8, num_blocks=16, cache_dtype="bfloat16"))
    assert engine.cache.state.dtype == jnp.float32
    assert engine.cache.conv.dtype == engine.cache.k.dtype == jnp.bfloat16
    assert engine.cache.state.shape == (6, 8 + 1, 4, 8, 16)
    assert engine.cache.conv.shape == (6, 8 + 1, 3 * (2 * 32 + 64))


def test_slots_bound_admission_as_blocks_do(params):
    engine = make_engine(params, tracked=2)
    engine.put([0, 1], [[1, 2, 3], [4, 5]])
    assert engine.state.free_state_slots == 0
    assert engine.can_schedule([0, 1, 2], [1, 1, 4]) == \
        SchedulingResult.EngineSequenceLimitExceeded
    assert engine.query(2, 16, 4) == (0, 0)
    engine.flush(0)
    assert engine.can_schedule([1, 2], [1, 4]) == SchedulingResult.Success
    assert engine.state.state_slots_in_use == 1


def test_scheduler_preempts_and_restores_through_both_pools(params):
    """The server on a pool too small for its load evicts (latents and
    state rows to the host) and restores; tokens equal those of a pool
    that never evicts, and nothing leaks."""
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, 256, n)) for n in (37, 5, 16, 50, 20)]
    outs, preemptions, slots_seen = {}, {}, {}
    for blocks in (64, 14):
        engine = make_engine(params, num_blocks=blocks, tracked=4)
        server = ServingServer(engine, config=ServerConfig(prefill_chunk=16))
        seen = []
        report = server.metrics.on_step
        server.metrics.on_step = lambda r, s: (seen.append(r.state_slots),
                                               report(r, s))[1]
        server.start()
        reqs = [server.submit(prompt=p, max_new_tokens=10) for p in prompts]
        for req in reqs:
            server.wait(req, timeout=300)
        server.stop(drain=True, timeout=60)
        outs[blocks] = [list(r.tokens_out) for r in reqs]
        preemptions[blocks] = sum(r.n_preemptions for r in reqs)
        slots_seen[blocks] = max(seen)
        assert engine.state.state_slots_in_use == 0
        assert engine.free_blocks == blocks - 1
        assert engine.state_stats["snapshots"] == \
            engine.state_stats["restores"] == preemptions[blocks]
        assert "state_slots_in_use" in server.metrics.gauges
    assert preemptions[64] == 0 and preemptions[14] > 0
    assert outs[14] == outs[64]
    assert 1 <= slots_seen[14] <= 4


class _TensorTwo:
    tensor_size = 2


REFUSED = {
    "prefix_caching": lambda p: make_engine(
        p, state_manager=dict(max_tracked_sequences=8, prefix_caching=True),
        hcache=dict(enable_latents=False)),
    "put_spec": lambda p: make_engine(p).put_spec([0], [[1, 2]]),
    "generate_lookup": lambda p: make_engine(
        p, hcache=dict(enable_latents=False)).generate_lookup([[1, 2, 3]]),
    "generate_lookup_fused": lambda p: make_engine(
        p, hcache=dict(enable_latents=False)).generate_lookup_fused(
            [[1, 2, 3]]),
    "generate_fused": lambda p: make_engine(p).generate_fused([[1, 2, 3]]),
    "tensor_parallelism": lambda p: PagedHybridModel(
        MODEL_FAMILIES["olmo_hybrid"](HF), p, block_size=8,
        max_blocks_per_seq=16, topology=_TensorTwo()),
    "quantisation": lambda p: make_engine(
        p, quantization=dict(enabled=True)),
    "restore_without_state": lambda p: make_engine(p).restore_kv(
        [0], [[1, 2, 3]], [np.zeros((2, 3, 64), np.float32)]),
    "tail_forward": lambda p: make_engine(p).model.forward_chunk_tail(),
    "decode_loop": lambda p: make_engine(p).model.decode_loop(),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_needs_a_state_snapshot_refuses_by_name(params, feature):
    with pytest.raises(RecurrentStateUnsupported,
                       match="recurrent") as err:
        REFUSED[feature](params)
    assert "would need" in str(err.value)


def test_a_refused_call_leaves_the_engine_as_it_was(params):
    engine = make_engine(params)
    with pytest.raises(RecurrentStateUnsupported):
        engine.generate_fused([[1, 2, 3]])
    assert engine.state.n_tracked_sequences == 0
    assert engine.state.free_state_slots == 8


@pytest.mark.parametrize("B,T", [(8, 1), (2, 16)], ids=["decode", "slice"])
def test_compiled_program_holds_every_pool_in_place(params, B, T):
    """Both KV pools and both slot pools are the program's donated
    inputs and its outputs, and nothing the size of a pool, or of one
    layer of one, is copied or sliced."""
    # 96 blocks: no lane gather of the jnp paged reference has the
    # element count of a pool or of a layer of one
    engine = make_engine(params, num_blocks=96)
    model, cache = engine.model, engine.cache
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    compiled = model._fwd.lower(
        model.params, cache.k, cache.v, cache.state, cache.conv,
        i32(B, lanes_width(T, 16, slot=True))).compile()
    text = compiled.as_text()
    header = text.split("\n", 1)[0]
    n_leaves = len(jax.tree.leaves(model.params))
    for out in range(4):
        assert f"{{{out}}}: ({n_leaves + out}, {{}}" in header, header
    for pool in (cache.k, cache.state):
        assert pool_sized_copies(text, pool.shape) == []
    # the convolution tail's scatter is an in-place update of its rows:
    # no copy of that pool either
    assert [c for c in pool_sized_copies(text, cache.conv.shape)
            if c.startswith("copy")] == []
    pools = sum(p.nbytes for p in (cache.k, cache.v, cache.state,
                                   cache.conv))
    assert compiled.memory_analysis().alias_size_in_bytes >= pools


def test_period_is_read_from_layer_types(params):
    cfg = MODEL_FAMILIES["olmo_hybrid"](HF)
    assert cfg.period == ("linear_attention",) * 3 + ("full_attention",)
    other = dict(HF, num_hidden_layers=6,
                 layer_types=["linear_attention", "full_attention"] * 3)
    cfg2 = MODEL_FAMILIES["olmo_hybrid"](other)
    assert cfg2.period == ("linear_attention", "full_attention")
    tree = OlmoHybridForCausalLM(cfg2).init(
        jax.random.PRNGKey(0),
        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    engine = build_hf_engine(other, tree, RaggedInferenceEngineConfig(
        state_manager=dict(max_tracked_sequences=4, max_context=64,
                           max_ragged_batch_size=64),
        kv_cache=dict(block_size=8, num_blocks=16, cache_dtype="float32")))
    assert engine.cache.k.shape[0] == 3 and engine.cache.state.shape[0] == 3
    toks = list(range(1, 20))
    logits, _ = engine.put([0], [toks])
    outer = {k: tree[k] for k in ("embed_tokens", "norm", "lm_head")}
    ref = reference.next_token_logits(
        np.asarray(toks, np.int32), len(toks), other, outer,
        lambda i: tree[f"layers_{i}"])
    assert reference.logit_gap(logits[0], ref) < TOL
