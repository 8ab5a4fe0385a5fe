"""GLM-4-MoE-Lite (``model_type`` ``glm4_moe_lite``) on the normal serving
path, at a small size on the CPU: latent attention over a paged pool of
compressed KV rows, a dense layer before two sparse ones with a sigmoid
router, a selection bias and an ungated shared expert, through
``build_hf_engine`` -> ``InferenceEngineV2`` -> ``ServingServer``; seeded
random weights in float32. The reference is the benchmark's plain one
(``benchmarks/reference/glm4_moe_lite.py``: the published, up-projected
form)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm4_moe_lite as reference
from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                    build_hf_engine)
from hcache_deepspeed_tpu.inference.model_latent import (
    LatentAttentionUnsupported, PagedLatentModel)
from hcache_deepspeed_tpu.models.glm4_moe_lite import (correction_bias,
                                                       seeded_params)
from hcache_deepspeed_tpu.moe.dropless import dropless_route
from hcache_deepspeed_tpu.ops.latent_attention import \
    reference_latent_attention
from hcache_deepspeed_tpu.serving import ServerConfig, ServingServer

HF = {
    "model_type": "glm4_moe_lite", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "rope_scaling": None,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1.8, "first_k_dense_replace": 1,
    "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "attention_bias": False,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "tie_word_embeddings": False, "torch_dtype": "float32"}
#: the saved state of a layer and a token: ``[c | r]``
ROW = HF["kv_lora_rank"] + HF["qk_rope_head_dim"]

#: Engine and reference both compute in float32 from the same weights;
#: they differ in the form of the attention (absorbed over the pool
#: against up-projected over the whole sequence) and in the order of
#: their sums. A wrong scale, a missing norm or rotary step, the bias in
#: the weights or a dropped shared expert read 1e-2 or more
#: (``test_the_reference_computed_wrong_is_told_apart``).
TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    cfg = MODEL_FAMILIES["glm4_moe_lite"](HF)
    tree = seeded_params(cfg, seed=3)
    # norm scales away from one, so that a missing norm shows
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.3 * jax.random.normal(
            next(keys), x.shape)) if "norm" in str(path[-2]) else x, tree)


def _engine(params, latents=True, chunk=16, **state):
    return build_hf_engine(HF, params, RaggedInferenceEngineConfig(
        state_manager={"max_tracked_sequences": 8,
                       "max_ragged_sequence_count": 8,
                       "max_ragged_batch_size": 64, "max_context": 128,
                       "prefill_chunk": chunk, **state},
        kv_cache={"block_size": 8, "num_blocks": 64,
                  "cache_dtype": "float32"},
        hcache={"enable_latents": latents, "restore_chunk_layers": 1}))


def _reference_row(params, tokens, arch=HF, route_from=None):
    outer = {k: params[k] for k in ("embed_tokens", "norm", "lm_head")}
    return np.asarray(reference.logits(
        tokens, arch, outer, lambda i: params[f"layers_{i}"],
        [len(tokens) - 1], route_from))[0]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, HF["vocab_size"], n)))
            for n in lengths]


# ------------------------------------------------------------------ #
# the served path against the reference's full forward
# ------------------------------------------------------------------ #
def test_prefill_and_decode_through_the_pool_match_the_reference(params):
    """Three lanes, the longest chunked over three slices, then four
    decode steps through the cache: every lane's last row against the
    published form's full forward of the same tokens."""
    eng = _engine(params)
    assert isinstance(eng.model, PagedLatentModel)
    seqs = _prompts((40, 7, 23))
    logits, latents = eng.put([0, 1, 2], seqs)
    for row, seq, lat in zip(logits, seqs, latents):
        assert reference.logit_gap(row, _reference_row(params, seq)) < TOL
        # what goes to the host is the cache row of every layer
        assert np.asarray(lat).shape == (3, len(seq), ROW)
    for _ in range(4):
        fed = [int(np.argmax(row)) for row in logits]
        seqs = [seq + [t] for seq, t in zip(seqs, fed)]
        logits, _ = eng.put([0, 1, 2], [[t] for t in fed])
    for row, seq in zip(logits, seqs):
        assert reference.logit_gap(row, _reference_row(params, seq)) < TOL
    stats = eng.latent_stats()
    assert stats["saved_state"] == "cache_row"
    assert stats["captured_bytes"] == stats["captured_tokens"] * 3 * ROW * 4
    # a position's write is one c row and one r row a layer
    writes = eng.kv_write_stats()
    assert writes["run_rows"] == (40 + 7 + 23) * 2 * 3
    assert writes["row_rows"] == 4 * 3 * 2 * 3
    walk = eng.paged_walk_stats()
    assert 0 < walk["blocks_walked"] < walk["table_slots"]


@pytest.mark.parametrize("wrong", [
    {"rope_r": False}, {"norm_c": False},
    {"softmax_scale_dim": HF["qk_nope_head_dim"]},
    {"scoring_func": "softmax"}, {"bias_in_weights": True},
    {"routed_scaling_factor": 1.0}, {"n_shared_experts": 0},
    {"num_experts_per_tok": 1}], ids=lambda w: next(iter(w)))
def test_the_reference_computed_wrong_is_told_apart(params, wrong):
    """Each of the family's mechanisms moves the logits by far more than
    the tolerance: the served row fails against the reference with that
    mechanism left out or done wrong."""
    eng = _engine(params)
    (seq,) = _prompts((37,), seed=1)
    logits, _ = eng.put([0], [seq])
    assert reference.logit_gap(
        logits[0], _reference_row(params, seq, {**HF, **wrong})) > 100 * TOL


def test_routing_the_reference_from_the_served_routers_input(params):
    """``engine.router_inputs`` hands out what each sparse layer's router
    read for a probed lane's last row; the reference routed by it agrees
    as the reference routed by its own (float32 both: the same picks)."""
    eng = _engine(params)
    eng.router_probe_uids = {0}
    seqs = _prompts((21, 9), seed=2)
    logits, _ = eng.put([0, 1], seqs)
    read = eng.router_inputs(0)
    assert read.shape == (2, HF["hidden_size"])
    assert eng.router_inputs(1) is None             # not probed
    row = _reference_row(params, seqs[0], route_from={len(seqs[0]) - 1:
                                                      read})
    assert reference.logit_gap(logits[0], row) < TOL
    # and a wrong input there moves the row: the override is read
    off = _reference_row(params, seqs[0],
                         route_from={len(seqs[0]) - 1: -read})
    assert reference.logit_gap(logits[0], off) > 100 * TOL
    eng.flush(0)
    assert eng.router_inputs(0) is None


# ------------------------------------------------------------------ #
# absorbed against up-projected, on the reference's own tensors
# ------------------------------------------------------------------ #
def test_absorbed_attention_equals_up_projected(params):
    """``q~ . c + q_rope . r`` and ``W_uv sum_s p_s c_s`` over a pool of
    rows give what the published form gives from the same ``c`` and
    ``r``: the kernel's reference, fed the absorbed query, against plain
    attention over the up-projected keys and values."""
    cfg = MODEL_FAMILIES["glm4_moe_lite"](HF)
    H, nope, rope, vd = cfg.n_head, 24, 8, 16
    C, T, BS = cfg.kv_lora_rank, 19, 8
    rng = np.random.default_rng(5)
    c = rng.standard_normal((T, C)).astype(np.float32)
    r = rng.standard_normal((T, rope)).astype(np.float32)
    q = rng.standard_normal((T, H, nope + rope)).astype(np.float32)
    w_kvb = np.asarray(params["layers_1"]["self_attn"]["kv_b_proj"]
                       ["kernel"]).reshape(C, H, nope + vd)
    scale = 1.0 / np.sqrt(nope + rope)
    # published form
    kv = np.einsum("tc,chd->thd", c, w_kvb)
    k = np.concatenate([kv[..., :nope],
                        np.broadcast_to(r[:, None], (T, H, rope))], -1)
    s = np.einsum("thd,shd->hts", q, k) * scale
    s = np.where(np.tril(np.ones((T, T), bool))[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hts,shd->thd", p, kv[..., nope:])
    # absorbed form over the two pools (r padded to its pool's width)
    R = 128
    q_abs = np.einsum("thd,chd->thc", q[..., :nope], w_kvb[..., :nope])
    q_in = np.zeros((1, T, H, C + R), np.float32)
    q_in[0, ..., :C], q_in[0, ..., C:C + rope] = q_abs, q[..., nope:]
    tables = np.array([[3, 1, 2]], np.int32)
    c_pool = np.zeros((2, 1, 4 * BS, C), np.float32)
    r_pool = np.zeros((2, 1, 4 * BS, R), np.float32)
    slots = tables[0, np.arange(T) // BS] * BS + np.arange(T) % BS
    c_pool[1, 0, slots], r_pool[1, 0, slots, :rope] = c, r
    u = reference_latent_attention(
        jnp.asarray(q_in), jnp.asarray(c_pool), jnp.asarray(r_pool), 1,
        jnp.asarray(tables), jnp.zeros((1,), jnp.int32),
        jnp.full((1,), T, jnp.int32), BS, scale)
    got = np.einsum("thc,chd->thd", np.asarray(u)[0], w_kvb[..., nope:])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------ #
# the router
# ------------------------------------------------------------------ #
def test_the_bias_moves_the_choice_and_not_the_weights():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]])
    score = jax.nn.sigmoid(logits)[0]
    plain_w, plain_e, _ = dropless_route(logits, 2, True, score="sigmoid")
    assert sorted(np.asarray(plain_e)[0]) == [0, 1]
    bias = jnp.asarray([0.0, -0.5, 0.0, 0.9])       # lifts 3 over 1
    w, e, _ = dropless_route(logits, 2, True, score="sigmoid", bias=bias,
                             scale=1.8)
    assert sorted(np.asarray(e)[0]) == [0, 3]
    # the weights are the unbiased scores of the chosen, renormalised,
    # times the scaling factor
    picked = np.asarray(score)[np.asarray(e)[0]]
    np.testing.assert_allclose(np.asarray(w)[0],
                               1.8 * picked / picked.sum(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(), 1.8, rtol=1e-6)
    # softmax without bias or scale: what every other family computes
    soft_w, soft_e, _ = dropless_route(logits, 2, True)
    probs = np.asarray(jax.nn.softmax(logits))[0]
    np.testing.assert_allclose(np.asarray(soft_w)[0],
                               probs[:2] / probs[:2].sum(), rtol=1e-6)
    with pytest.raises(ValueError, match="score function"):
        dropless_route(logits, 2, True, score="tanh")


def test_the_expert_layer_against_the_reference(params):
    """One sparse layer of the served model on rows of its own, against
    the reference's expert layer: the seeded bias changes some row's
    picks, and the shared expert is counted once."""
    eng = _engine(params)
    layer = 1
    lp = jax.tree.map(lambda p: p[layer - 1], eng.model.params["layers"])
    rng = np.random.default_rng(7)
    h2 = jnp.asarray(rng.standard_normal((1, 24, HF["hidden_size"])),
                     jnp.float32)
    got, experts = eng.model._routed(lp, h2)
    mlp = params[f"layers_{layer}"]["mlp"]
    bias = np.asarray(mlp["gate"]["e_score_correction_bias"])
    np.testing.assert_array_equal(
        bias, correction_bias(3, layer, HF["n_routed_experts"]))
    score = jax.nn.sigmoid(h2[0] @ mlp["gate"]["weight"])
    unbiased = np.sort(np.asarray(jax.lax.top_k(score, 2)[1]), -1)
    assert (np.sort(np.asarray(experts), -1) != unbiased).any()
    # the reference's layer without its residual and norm: x + mlp(x)
    ones = {"weight": jnp.ones((HF["hidden_size"],))}
    for shared in (True, False):
        want = reference._sparse_mlp(
            h2[0], {"post_attention_layernorm": ones, "mlp": mlp},
            jnp.asarray([24]), jnp.zeros((1, HF["hidden_size"])), eps=0.0,
            top_k=2, norm_topk=True, scaling=1.8, shared=shared) - h2[0]
        if shared:
            rms = jnp.sqrt(jnp.mean(h2[0] ** 2, -1, keepdims=True))
            np.testing.assert_allclose(rms, 1.0, atol=0.3)
        else:
            dropped = want
    # (the reference norms its input: hand the served layer the same)
    normed = h2 / jnp.sqrt(jnp.mean(h2 ** 2, -1, keepdims=True))
    got, _ = eng.model._routed(lp, normed)
    want = reference._sparse_mlp(
        h2[0], {"post_attention_layernorm": ones, "mlp": mlp},
        jnp.asarray([24]), jnp.zeros((1, HF["hidden_size"])), eps=0.0,
        top_k=2, norm_topk=True, scaling=1.8, shared=True) - h2[0]
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    shared_part = eng.model._swiglu(lp["mlp"]["shared_experts"], normed)
    np.testing.assert_allclose(want - dropped, shared_part[0], rtol=2e-4,
                               atol=2e-5)


# ------------------------------------------------------------------ #
# HCache: evict -> host -> restore
# ------------------------------------------------------------------ #
def test_restore_ships_cache_rows_and_gives_the_uninterrupted_logits(
        params):
    eng = _engine(params)
    p0, p1 = _prompts((12, 37), seed=3)
    logits, latents = eng.put([0, 1], [p0, p1])
    fed = int(np.argmax(logits[1]))
    uninterrupted, _ = eng.put([1], [[fed]])
    rows = np.asarray(latents[1])
    assert rows.shape == (3, 37, ROW)
    eng.flush(1)
    before = dict(eng.restore_stats)
    eng.restore_kv([1], [p1], [rows])
    # a ship and a write: three chunks of one layer, no projection
    assert eng.restore_stats["chunks_issued"] - before["chunks_issued"] == 3
    shipped = eng.restore_stats["bytes_shipped"] - before["bytes_shipped"]
    assert shipped == 3 * 64 * ROW * 4          # 37 tokens in a bucket of 64
    restored, _ = eng.put([1], [[fed]])
    np.testing.assert_array_equal(restored, uninterrupted)
    profile = eng.restore_profile()
    assert profile["saved_state"] == "cache_row"
    assert profile["latent_bytes_per_token"] == 3 * ROW * 4
    assert profile["replay_flops_frac"] == 0.0


def test_server_preempts_to_host_rows_and_restores(params):
    """Through ``ServingServer``: a pool too small for both requests
    evicts one to host cache rows and brings it back; its tokens are the
    ones it gets alone."""
    prompts = _prompts((40, 44), seed=4)

    def serve(num_blocks, both):
        engine = build_hf_engine(HF, params, RaggedInferenceEngineConfig(
            state_manager={"max_tracked_sequences": 4,
                           "max_ragged_sequence_count": 4,
                           "max_ragged_batch_size": 64, "max_context": 128,
                           "prefill_chunk": 16},
            kv_cache={"block_size": 8, "num_blocks": num_blocks,
                      "cache_dtype": "float32"}))
        server = ServingServer(engine, config=ServerConfig(
            prefill_chunk=16))
        server.start()
        try:
            reqs = [server.submit(prompt=p, max_new_tokens=24,
                                  priority=i)
                    for i, p in enumerate(prompts if both
                                          else prompts[:1])]
            for req in reqs:
                server.wait(req, timeout=120)
        finally:
            server.stop(drain=True, timeout=30.0)
        assert server.error is None
        return engine, server, reqs

    _, _, (alone,) = serve(40, both=False)
    engine, server, (first, second) = serve(13, both=True)
    assert first.tokens_out == alone.tokens_out
    assert len(second.tokens_out) == 24
    assert engine.restore_stats["restores"] >= 1
    assert engine.latent_stats()["saved_state"] == "cache_row"


# ------------------------------------------------------------------ #
# what else reaches the family
# ------------------------------------------------------------------ #
def test_fused_and_lookup_loops_give_the_host_loops_tokens(params):
    head = dict(params, lm_head={"kernel": params["lm_head"]["kernel"] * 8})
    eng = _engine(head, latents=False, chunk=0)
    prompts = _prompts((12, 37), seed=5)
    want = eng.generate(prompts, max_new_tokens=8)
    assert eng.generate_fused(prompts, max_new_tokens=8)[0] == want
    assert eng.generate_lookup(prompts, max_new_tokens=8)[0] == want
    assert eng.generate_lookup_fused(prompts, max_new_tokens=8)[0] == want


def test_shared_prefixes_and_speculative_verification(params):
    eng = _engine(params, latents=False, prefix_caching=True)
    (shared,) = _prompts((24,), seed=6)
    first, _ = eng.put([0], [shared + [5, 6, 7]])
    second, _ = eng.put([1], [shared + [5, 6, 7]])
    assert eng.prefix_stats == {"hits": 1, "shared_tokens": 24}
    assert reference.logit_gap(second[0], first[0]) < TOL
    spec = _engine(params)
    (prompt,) = _prompts((37,), seed=7)
    logits, _ = spec.put([0], [prompt])
    fed = int(np.argmax(logits[0]))
    emitted, latents = spec.put_spec([0], [[fed, 1, 2, 3]])
    nxt = int(np.argmax(_reference_row(params, prompt + [fed])))
    assert emitted[0][0] == nxt
    assert np.asarray(latents[0]).shape == (3, len(emitted[0]), ROW)


# ------------------------------------------------------------------ #
# the factory
# ------------------------------------------------------------------ #
def _catalog_row():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "GLM-4.7-Flash":
                return row
    pytest.skip("no catalog beside the model-configs guide")


def test_the_catalog_rows_config_builds_the_family(params):
    try:
        published = _catalog_row()["config"]
    except OSError:
        pytest.skip("no catalog beside the model-configs guide")
    cfg = MODEL_FAMILIES[published["model_type"]](published)
    assert (cfg.hidden_size, cfg.n_head, cfg.n_layer) == (2048, 20, 47)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank) == (768, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.head_dim) == (192, 64, 256, 256)
    assert (cfg.num_experts, cfg.top_k, cfg.intermediate_size,
            cfg.dense_intermediate_size) == (64, 4, 1536, 10240)
    assert (cfg.first_k_dense_replace, cfg.n_shared_experts,
            cfg.routed_scaling_factor) == (1, 1, 1.8)
    assert cfg.cache_row_widths == (512, 128)
    # and the same keys at a small size build and serve
    small = {**published, **{k: HF[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers",
        "num_attention_heads", "n_routed_experts", "num_experts_per_tok",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "max_position_embeddings")},
             "torch_dtype": "float32"}
    engine = build_hf_engine(small, params, RaggedInferenceEngineConfig(
        state_manager={"max_context": 64},
        kv_cache={"block_size": 8, "num_blocks": 16,
                  "cache_dtype": "float32"}))
    assert engine.cache.k.shape[1:] == (1, 16 * 8, 32)
    assert engine.cache.v.shape[1:] == (1, 16 * 8, 128)
    assert engine.cache.per_token_bytes == 3 * (32 + 128) * 4


@pytest.mark.parametrize("key,value,named", [
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("n_group", 2, "n_group"), ("topk_group", 2, "topk_group"),
    ("attention_bias", True, "attention_bias"),
    ("topk_method", "greedy", "topk_method"),
    ("partial_rotary_factor", 0.5, "partial_rotary_factor")])
def test_what_is_not_built_is_refused_by_name(key, value, named):
    with pytest.raises(NotImplementedError, match=named):
        MODEL_FAMILIES["glm4_moe_lite"]({**HF, key: value})


def test_tensor_parallelism_and_quantization_are_refused_by_name(params):
    from hcache_deepspeed_tpu.parallel.topology import (MeshTopology,
                                                        TopologySpec)
    topo = MeshTopology(TopologySpec(tensor=2), devices=jax.devices()[:2])
    with pytest.raises(LatentAttentionUnsupported,
                       match="tensor parallelism"):
        build_hf_engine(HF, params, RaggedInferenceEngineConfig(
            kv_cache={"block_size": 8, "num_blocks": 16}), topology=topo)
    with pytest.raises(LatentAttentionUnsupported,
                       match="weight quantization"):
        build_hf_engine(HF, params, RaggedInferenceEngineConfig(
            kv_cache={"block_size": 8, "num_blocks": 16},
            quantization={"enabled": True}))
