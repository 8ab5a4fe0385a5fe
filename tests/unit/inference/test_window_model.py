"""A sparse trunk with window and global attention layers through the
normal path (``model_type`` ``cohere2_moe``): ``build_hf_engine`` ->
``InferenceEngineV2`` -> ``ServingServer``; seeded random weights in
float32. The reference is the benchmark's plain one
(``benchmarks/reference/cohere2_moe.py``: the published form, the window
as a dense mask, the interleaved rotary pairing, four separate shared
experts)."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import cohere2_moe as reference
from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                    build_hf_engine)
from hcache_deepspeed_tpu.inference.model_window import (
    HELD_LOG, PagedWindowModel, WindowedCacheUnsupported, half_split_columns,
    serving_layout)
from hcache_deepspeed_tpu.inference.ragged.kv_cache import (
    StateManager, pool_scatters, pool_sized_copies, stacked_layer_copies)
from hcache_deepspeed_tpu.inference.ragged.lanes import lanes_width
from hcache_deepspeed_tpu.inference.scheduling import (SchedulingError,
                                                       SchedulingResult)
from hcache_deepspeed_tpu.models.cohere2_moe import (held_share,
                                                     param_shapes,
                                                     seeded_params,
                                                     shared_expert)
from hcache_deepspeed_tpu.moe.dropless import routed_expert_ffn
from hcache_deepspeed_tpu.ops.rope import rope_at
from hcache_deepspeed_tpu.serving import ServerConfig, ServingServer

WINDOW = 64         # 8 blocks of 8: slices of 32 cross it
HF = {
    "model_type": "cohere2_moe", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 32, "num_hidden_layers": 8,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 512, "layer_norm_eps": 1e-5,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": WINDOW, "rope_theta": 50000.0,
    "rope_parameters": {"rope_theta": 50000.0, "rope_type": "default"},
    "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 2,
    "norm_topk_prob": True, "expert_selection_fn": "sigmoid",
    "shared_expert_combination_strategy": "average",
    "first_k_dense_replace": 0, "use_parallel_block": True,
    "use_qk_norm": False, "attention_bias": False, "logit_scale": 1,
    "position_embedding_type": "rope_gptj", "rotary_pct": 1,
    "tie_word_embeddings": True, "torch_dtype": "float32"}
#: the saved state of a layer and a token: its K and V rows
ROW = 2 * HF["num_key_value_heads"] * HF["head_dim"]
SHARE = (2, 4)

#: engine and reference both compute in float32 from the same weights;
#: they differ in the form of the attention (blocks of two pools against
#: a dense mask over the whole sequence), in the rotary pairing and in
#: the order of their sums. Every fault of
#: ``test_the_reference_computed_wrong_is_told_apart`` reads 1e-3 or more
TOL = 2e-5


@pytest.fixture(scope="module", autouse=True)
def _small_reference_blocks():
    """The reference's steps at the size of these contexts (a few
    hundred tokens): it pads a context to whole blocks of queries, 1,024
    at the benchmark's sizes."""
    saved = reference._Q_BLOCK
    reference._Q_BLOCK = 256
    yield
    reference._Q_BLOCK = saved


@pytest.fixture(scope="module")
def params():
    cfg = MODEL_FAMILIES["cohere2_moe"](HF)
    tree = seeded_params(cfg, seed=3)
    # norm scales away from one, so that a missing norm shows
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.3 * jax.random.normal(
            next(keys), x.shape)) if "norm" in str(path[-2]) else x, tree)


def _engine(hf, params, latents=True, chunk=32, blocks=128,
            window_blocks=64, **state):
    return build_hf_engine(hf, params, RaggedInferenceEngineConfig(
        state_manager={"max_tracked_sequences": 8,
                       "max_ragged_sequence_count": 8,
                       "max_ragged_batch_size": 128, "max_context": 256,
                       "prefill_chunk": chunk, **state},
        kv_cache={"block_size": 8, "num_blocks": blocks,
                  "num_window_blocks": window_blocks,
                  "cache_dtype": "float32"},
        hcache={"enable_latents": latents}))


def _prompts(lengths, seed):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, HF["vocab_size"], n)]
            for n in lengths]


def _reference_rows(hf, params, seqs, **wrong):
    outer = {"embed_tokens": params["embed_tokens"],
             "norm": params["norm"]}
    return [np.asarray(reference.logits(
        s, {**hf, **wrong}, outer, lambda i: params[f"layers_{i}"],
        [len(s) - 1]))[0] for s in seqs]


def _served(hf, params, lengths=(150, 40, 97), steps=12):
    """Several lanes through chunked prefill (the longest over five
    slices, past the window, blocks freed on the way) and ``steps``
    decode steps: ``(engine, sequences, the rows behind their last
    tokens, the rows behind their prompts)``."""
    eng = _engine(hf, params)
    seqs = _prompts(lengths, seed=1)
    uids = list(range(len(seqs)))
    logits, _ = eng.put(uids, seqs)
    first = logits.copy()
    prompts = [list(s) for s in seqs]
    for _ in range(steps):
        toks = [[int(np.argmax(row))] for row in logits]
        for s, tok in zip(seqs, toks):
            s.append(tok[0])
        logits, _ = eng.put(uids, toks)
    return eng, seqs, logits, (prompts, first)


@pytest.mark.parametrize("held", [None, SHARE], ids=["all", "share"])
def test_prefill_and_decode_through_both_pools_match_the_reference(
        params, held):
    hf = {**HF, "experts_held": held}
    tree = held_share(params, MODEL_FAMILIES["cohere2_moe"](HF), *held) \
        if held else params
    eng, seqs, logits, (prompts, first) = _served(hf, tree)
    for got, want in zip(first, _reference_rows(hf, tree, prompts)):
        assert reference.logit_gap(got, want) < TOL
    for got, want in zip(logits, _reference_rows(hf, tree, seqs)):
        assert reference.logit_gap(got, want) < TOL
    pools = eng.kv_pool_stats()
    assert pools["window"]["released"] > 0          # freed mid-prompt
    # a sequence past the window holds the window's blocks and the one
    # being filled, whatever its length
    assert pools["window"]["in_use"] - 1 <= sum(
        min(-(-len(s) // 8), WINDOW // 8 + 2) for s in seqs)
    assert pools["global"]["in_use"] - 1 == sum(-(-len(s) // 8)
                                                for s in seqs)
    for uid in range(len(seqs)):
        eng.flush(uid)
    pools = eng.kv_pool_stats()
    assert pools["window"]["in_use"] == pools["global"]["in_use"] == 1
    moe = eng.moe_stats()
    first_e, count = held or (0, HF["num_experts"])
    assert moe["picks"].sum() == sum(len(s) for s in seqs) * 2 * 8
    assert moe["picks_held"] == moe["picks"][first_e:first_e + count].sum()
    # every forward left its rows on the held experts and the held
    # experts those touched (8 layers of ``count`` at most)
    rows, touched = moe["held_log"].T
    assert len(rows) == moe["dispatches"] and rows.sum() == moe["picks_held"]
    assert np.all(touched <= np.minimum(rows, 8 * count))
    assert np.all((touched > 0) == (rows > 0))
    assert moe["touched"] == touched.sum()


@pytest.mark.parametrize("wrong", [
    {"sliding_window": None}, {"sliding_window": WINDOW // 2},
    {"rope_on_global": True}, {"rope_pairing": "half_split"},
    {"use_parallel_block": False}, {"expert_selection_fn": "softmax"},
    {"norm_topk_prob": False},
    {"shared_expert_combination_strategy": "sum"},
    {"num_shared_experts": 0}, {"num_experts_per_tok": 1},
    {"layer_norm_eps": 1e-1}], ids=lambda w: next(iter(w)))
def test_the_reference_computed_wrong_is_told_apart(params, wrong):
    (seq,) = _prompts((150,), seed=7)
    eng = _engine(HF, params)
    logits, _ = eng.put([0], [seq])
    (right,) = _reference_rows(HF, params, [seq])
    (off,) = _reference_rows(HF, params, [seq], **wrong)
    assert reference.logit_gap(logits[0], right) < TOL
    assert reference.logit_gap(logits[0], off) > 1e-3


# ------------------------------------------------------------------ #
# an expert layer that holds a share
# ------------------------------------------------------------------ #
def test_the_shares_add_up(params):
    """Four chips' shares of a layer (two of its eight experts each):
    the routed parts summed, with the shared experts counted once, are
    the uncut reference's whole expert layer; the served layer's share
    is the reference's."""
    lp = params["layers_0"]
    x = jax.random.normal(jax.random.PRNGKey(5), (256, 64), jnp.float32)
    h = reference.layer_norm(x, lp["input_layernorm"]["weight"], 1e-5)
    nowhere = jnp.asarray([len(h)], jnp.int32)
    kw = dict(top_k=2, norm_topk=True, combination="average",
              scoring="sigmoid")

    def expert_layer(mlp, held, n_shared):
        return reference._experts(h, mlp, nowhere, h[:1], held=held,
                                  n_shared=n_shared, **kw)

    with jax.default_matmul_precision("highest"):
        whole = expert_layer(lp["mlp"], (0, 8), 2)
        routed = expert_layer(lp["mlp"], (0, 8), 0)
        parts, served = [], []
        for first in range(0, 8, 2):
            share = {k: v[first:first + 2]
                     for k, v in lp["mlp"]["experts"].items()}
            parts.append(expert_layer({**lp["mlp"], "experts": share},
                                      (first, 2), 0))
            served.append(routed_expert_ffn(
                h, lp["mlp"]["gate"]["weight"], share["w1"], share["w3"],
                share["w2"], 2, True, held=(first, 2),
                score="sigmoid")[0])
    once = whole - routed               # the shared experts, once
    assert float(jnp.max(jnp.abs(sum(parts) + once - whole))) < 1e-5
    assert float(jnp.max(jnp.abs(sum(served) + once - whole))) < 1e-5
    for part, got in zip(parts, served):
        assert float(jnp.max(jnp.abs(part - got))) < 1e-5
    # a share alone is a part, not the whole
    assert float(jnp.max(jnp.abs(parts[0] - routed))) > 1e-2


@pytest.mark.parametrize("site", ["mixtral", "sdar", "glm"])
def test_holding_every_expert_is_todays_arithmetic_bit_for_bit(site):
    """``held`` covering every expert at the three call sites' arguments
    (``model_moe.py``: Mixtral and qwen2_moe, SDAR with a layer of a
    stacked leaf; ``model_latent.py``: GLM's sigmoid router under a
    bias and a scale)."""
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    N, d, f, E, L = 48, 32, 16, 8, 3
    tokens = jax.random.normal(keys[0], (N, d), jnp.float32)
    wg = jax.random.normal(keys[1], (d, E), jnp.float32)
    w1, w3 = (jax.random.normal(k, (L, E, d, f), jnp.float32)
              for k in keys[2:4])
    w2 = jax.random.normal(keys[4], (L, E, f, d), jnp.float32)
    kw = {"mixtral": dict(layer=None),
          "sdar": dict(layer=jnp.int32(1)),
          "glm": dict(layer=jnp.int32(2), score="sigmoid",
                      bias=jax.random.normal(keys[5], (E,)) * 0.1,
                      scale=1.8)}[site]
    if kw["layer"] is None:
        w1, w3, w2 = w1[0], w3[0], w2[0]
    k = 2
    renorm = site != "mixtral"
    want = routed_expert_ffn(tokens, wg, w1, w3, w2, k, renorm, **kw)
    got = routed_expert_ffn(tokens, wg, w1, w3, w2, k, renorm,
                            held=(0, E), **kw)
    for a, b in zip(want, got):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    same = jax.make_jaxpr(lambda *a: routed_expert_ffn(
        *a, k, renorm, **kw))(tokens, wg, w1, w3, w2)
    held = jax.make_jaxpr(lambda *a: routed_expert_ffn(
        *a, k, renorm, held=(0, E), **kw))(tokens, wg, w1, w3, w2)
    assert str(same) == str(held)


def test_the_fused_shared_expert_is_the_mean_of_the_separate_ones(params):
    fused = params["layers_2"]["mlp"]["shared_experts"]
    h = jax.random.normal(jax.random.PRNGKey(9), (40, 64), jnp.float32)

    def swiglu(p):
        return (jax.nn.silu(h @ p["gate_proj"]["kernel"]) *
                (h @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]

    separate = [swiglu(shared_expert(fused, j, 32)) for j in range(2)]
    assert float(jnp.max(jnp.abs(
        swiglu(fused) * 0.5 - sum(separate) / 2))) < 1e-5
    assert float(jnp.max(jnp.abs(separate[0] - separate[1]))) > 1e-2


def test_half_split_rotary_on_permuted_columns_is_the_published_pairing():
    """``rope_at`` on q and k columns permuted once at load gives the
    scores the interleaved pairing gives on the published columns."""
    D, H, T = 16, 4, 12
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(keys[0], (T, 32), jnp.float32)
    wq, wk = (jax.random.normal(k, (32, H * D), jnp.float32)
              for k in keys[1:])
    pos = jnp.arange(T)[None, :]
    served = [rope_at((x @ half_split_columns(w, D)).reshape(1, T, H, D),
                      pos, 50000.0)[0] for w in (wq, wk)]
    published = [jnp.stack([reference.rope(
        (x @ w).reshape(T, H, D)[:, i], 50000.0) for i in range(H)], 1)
        for w in (wq, wk)]
    scores = [jnp.einsum("thd,shd->hts", q, k)
              for q, k in (served, published)]
    assert float(jnp.max(jnp.abs(scores[0] - scores[1]))) < 1e-4
    assert np.array_equal(half_split_columns(np.asarray(wq), D),
                          np.asarray(half_split_columns(wq, D)))


# ------------------------------------------------------------------ #
# two pools, two block lifetimes
# ------------------------------------------------------------------ #
def test_window_blocks_return_while_the_sequence_lives():
    state = StateManager(4, 64, 8, 512, window_blocks=16, window=32)
    seq = state.get_or_create_sequence(7)
    most = 0
    for _ in range(20):                     # 20 slices of 16 tokens
        assert state.has_room([(seq, 16)])
        state.maybe_allocate_kv(seq, 16)
        most = max(most, len(seq.window_blocks))
        seq.pre_forward(16)
        seq.post_forward()
        state.release_behind_window(seq)
        # never a block that a later query could see
        assert seq.window_first * 8 <= max(seq.seen_tokens - 32, 0)
    assert seq.seen_tokens == 320 and len(seq.blocks) == 40
    assert most <= -(-(32 + 16) // 8) + 1   # the bound a sequence holds
    assert len(seq.window_blocks) == 40 - seq.window_first <= 5
    assert state.window_blocks_released == seq.window_first == 36
    table = state.block_table(seq, 64)
    assert table.shape == (128,)
    assert list(table[:40]) == seq.blocks
    assert list(table[64 + 36:64 + 40]) == seq.window_blocks
    assert not table[64:64 + 36].any()      # behind the window: unread
    state.flush_sequence(7)                 # the global ones at flush
    assert state.free_blocks == 64 and state.free_window_blocks == 16
    assert state.pool_stats()["window"]["peak_in_use"] == most


def test_admission_refuses_when_either_pool_is_short(params):
    eng = _engine(HF, params, blocks=64, window_blocks=8)
    # the window pool holds 7 blocks beside its scratch: a 32-token
    # slice asks 4, and two sequences' slices 8
    two = _prompts((32, 32), seed=2)
    assert eng.can_schedule([0, 1], [32, 32]) == \
        SchedulingResult.KVCacheLimitExceeded
    assert eng.query(0, 32, 100)[0] == 32
    eng.put([0], two[:1])
    assert eng.can_schedule([1], [32]) == \
        SchedulingResult.KVCacheLimitExceeded
    assert eng.query(1, 32, 100) == (0, 0)
    with pytest.raises(SchedulingError):
        eng.put([1], two[1:])
    eng.flush(0)
    # and the global pool, with a window pool that has room
    eng = _engine(HF, params, blocks=9, window_blocks=64)
    assert eng.can_schedule([0], [60]) == SchedulingResult.Success
    assert eng.can_schedule([0], [70]) == \
        SchedulingResult.KVCacheLimitExceeded


def test_a_long_prompt_fits_a_window_pool_sized_by_the_window(params):
    """Chunked prefill of a prompt four windows long through a window
    pool that holds a window and a slice: the blocks behind the window
    are back before the next slice asks."""
    (prompt,) = _prompts((250,), seed=3)
    eng = _engine(HF, params, window_blocks=(WINDOW + 32) // 8 + 2 + 1)
    logits, _ = eng.put([0], [prompt])
    (want,) = _reference_rows(HF, params, [prompt])
    assert reference.logit_gap(logits[0], want) < TOL
    assert eng.kv_pool_stats()["window"]["released"] >= 250 // 8 - 9


# ------------------------------------------------------------------ #
# the kernel under a window
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("T,starts", [(1, (300, 70, 20, 0)),
                                      (32, (288, 64, 0, 40))],
                         ids=["decode", "slice"])
def test_the_kernel_under_a_window_matches_the_masked_oracle(T, starts):
    """Interpret mode, every buffer NaN until written and a read outside
    an operand an error: lanes whose walk starts past block 0 (their
    table entries behind the window name no block at all), a lane inside
    the window, and a blank one."""
    from jax.experimental.pallas import tpu as pltpu
    from hcache_deepspeed_tpu.ops.paged_attention import (
        pallas_paged_attention, reference_paged_attention)
    BS, W, KV, G, D, NB = 16, 64, 2, 4, 128, 24
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    pool_k = jax.random.normal(keys[0], (2, KV, 128 * BS, D), jnp.float32)
    pool_v = jax.random.normal(keys[1], (2, KV, 128 * BS, D), jnp.float32)
    B = len(starts)
    q = jax.random.normal(keys[2], (B, T, KV * G, D), jnp.float32)
    start = np.asarray(starts, np.int32)
    t_len = np.asarray([T, T, T // 2 or 1, 0], np.int32)
    kv_len = start + t_len
    rng = np.random.default_rng(0)
    tables = rng.permutation(128)[:B * NB].reshape(B, NB).astype(np.int32)
    oracle_tables = tables.copy()
    for b in range(B):      # behind the window: gone, and never read
        tables[b, :max(int(start[b]) - (W - 1), 0) // BS] = 1 << 20
        oracle_tables[b, :max(int(start[b]) - (W - 1), 0) // BS] = 0
    want = reference_paged_attention(
        q, pool_k, pool_v, 1, jnp.asarray(oracle_tables), jnp.asarray(start),
        jnp.asarray(kv_len), BS, 1, W)
    got = pallas_paged_attention(
        q, pool_k, pool_v, jnp.int32(1), jnp.asarray(tables),
        jnp.asarray(start), jnp.asarray(kv_len), BS, window=W,
        interpret=pltpu.InterpretParams(
            detect_races=True, uninitialized_memory="nan",
            out_of_bounds_reads="raise"))
    for b in range(B - 1):          # the last lane is blank
        n = int(t_len[b])
        assert np.isfinite(np.asarray(got[b, :n])).all()
        assert float(jnp.max(jnp.abs(got[b, :n] - want[b, :n]))) < 2e-3, b
    # the mask does something: without it the far rows differ
    full = reference_paged_attention(
        q, pool_k, pool_v, 1, jnp.asarray(oracle_tables), jnp.asarray(start),
        jnp.asarray(kv_len), BS)
    assert float(jnp.max(jnp.abs(full[0, :1] - want[0, :1]))) > 1e-2


# ------------------------------------------------------------------ #
# HCache: evict -> host -> restore
# ------------------------------------------------------------------ #
def test_restore_ships_kv_rows_into_both_pools(params):
    prompts = _prompts((150, 45), seed=8)
    uninterrupted = _engine(HF, params)
    logits, _ = uninterrupted.put([0, 1], prompts)
    tokens = [[int(np.argmax(row))] for row in logits]
    want, _ = uninterrupted.put([0, 1], tokens)

    eng = _engine(HF, params)
    _, latents = eng.put([0, 1], prompts)
    rows = [np.asarray(lat) for lat in latents]
    assert [r.shape for r in rows] == [(8, 150, ROW), (8, 45, ROW)]
    assert eng.latent_stats()["saved_state"] == "cache_row"
    profile = eng.restore_profile()
    assert profile["saved_state"] == "cache_row"
    assert profile["latent_bytes_per_token"] == 8 * ROW * 4
    assert profile["replay_flops_frac"] == 0.0
    assert (profile["window_layers"], profile["window"]) == (6, WINDOW)
    for uid in (0, 1):
        eng.flush(uid)
    assert eng.kv_pool_stats()["window"]["in_use"] == 1
    before = dict(eng.kv_write_stats()["window"])
    eng.restore_kv([0, 1], prompts, rows)
    pools = eng.kv_pool_stats()
    # of the window layers only the rows still inside the window: the
    # long sequence gets the blocks from position 150 - 64 on
    assert pools["window"]["in_use"] - 1 == \
        (-(-150 // 8) - (150 - WINDOW) // 8) + -(-45 // 8)
    assert pools["global"]["in_use"] - 1 == -(-150 // 8) + -(-45 // 8)
    wrote = eng.kv_write_stats()["window"]["run_rows"] - before["run_rows"]
    assert wrote == (150 - (150 - WINDOW) // 8 * 8 + 45) * 2 * 2 * 6
    got, _ = eng.put([0, 1], tokens)
    assert float(np.max(np.abs(got - want))) < 1e-4
    for uid in (0, 1):
        eng.flush(uid)
    pools = eng.kv_pool_stats()
    assert pools["window"]["in_use"] == pools["global"]["in_use"] == 1


def test_server_preempts_to_host_rows_and_restores(params):
    """Through ``ServingServer``: a global pool too small for both
    requests evicts one to host K/V rows and brings it back through both
    pools; its tokens are the ones it gets alone."""
    prompts = _prompts((90, 100), seed=4)

    def serve(num_blocks, both):
        engine = _engine(HF, params, blocks=num_blocks,
                         max_tracked_sequences=4,
                         max_ragged_sequence_count=4,
                         max_ragged_batch_size=64)
        server = ServingServer(engine, config=ServerConfig(
            prefill_chunk=32))
        server.start()
        try:
            reqs = [server.submit(prompt=p, max_new_tokens=24,
                                  priority=i)
                    for i, p in enumerate(prompts if both
                                          else prompts[:1])]
            for req in reqs:
                server.wait(req, timeout=180)
        finally:
            server.stop(drain=True, timeout=30.0)
        assert server.error is None
        return engine, server, reqs

    _, _, (alone,) = serve(64, both=False)
    engine, server, (first, second) = serve(29, both=True)
    assert first.tokens_out == alone.tokens_out
    assert len(second.tokens_out) == 24
    assert engine.restore_stats["restores"] >= 1
    pools = engine.kv_pool_stats()
    assert pools["window"]["in_use"] == pools["global"]["in_use"] == 1
    assert pools["window"]["released"] > 0


def test_a_steps_decode_lanes_ride_the_slices_program(params):
    """A step of decode lanes and one prompt slice is one program over
    both pools (``engine._launch_step``), and gives the rows the two
    programs give."""
    first, second = _prompts((70, 32), seed=12)
    eng = _engine(HF, params)
    logits, _ = eng.put([0], [first])
    tok = int(np.argmax(logits[0]))
    logits, latents = eng.put([0, 1], [[tok], second])
    assert eng.dispatch_stats()["fused"] == 1
    assert [np.asarray(lat).shape for lat in latents] == \
        [(8, 1, ROW), (8, 32, ROW)]
    for got, want in zip(logits, _reference_rows(
            HF, params, [first + [tok], second])):
        assert reference.logit_gap(got, want) < TOL


# ------------------------------------------------------------------ #
# what else reaches the family
# ------------------------------------------------------------------ #
def test_what_needs_a_freed_block_is_refused_by_name(params):
    eng = _engine(HF, params, latents=False, chunk=0)
    (prompt,) = _prompts((20,), seed=6)
    with pytest.raises(WindowedCacheUnsupported, match="prefix_caching"):
        _engine(HF, params, latents=False, prefix_caching=True)
    with pytest.raises(WindowedCacheUnsupported, match="generate_fused"):
        eng.generate_fused([prompt], max_new_tokens=4)
    with pytest.raises(WindowedCacheUnsupported, match="generate_lookup"):
        eng.generate_lookup([prompt], max_new_tokens=4)
    with pytest.raises(WindowedCacheUnsupported,
                       match="generate_lookup_fused"):
        eng.generate_lookup_fused([prompt], max_new_tokens=4)
    eng.put([0], [prompt])
    with pytest.raises(WindowedCacheUnsupported, match="put_spec"):
        eng.put_spec([0], [[1, 2, 3]])
    with pytest.raises(WindowedCacheUnsupported, match="suspend_sequence"):
        eng.suspend_sequence(0)
    assert eng.generate([prompt], max_new_tokens=4)     # the host loop


def test_tensor_parallelism_and_quantization_are_refused_by_name(params):
    from hcache_deepspeed_tpu.inference.config import QuantizationConfig
    from hcache_deepspeed_tpu.parallel.topology import (MeshTopology,
                                                        TopologySpec)
    cfg = MODEL_FAMILIES["cohere2_moe"](HF)
    topo = MeshTopology(TopologySpec(tensor=2), devices=jax.devices()[:2])
    with pytest.raises(WindowedCacheUnsupported, match="tensor parallel"):
        PagedWindowModel(cfg, params, block_size=8, max_blocks_per_seq=8,
                         topology=topo)
    with pytest.raises(WindowedCacheUnsupported, match="quantisation"):
        PagedWindowModel(cfg, params, block_size=8, max_blocks_per_seq=8,
                         quantization=QuantizationConfig(enabled=True))


def _catalog_config():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "command-a-plus-05-2026":
                return row["config"]
    pytest.skip("the catalog has no command-a-plus-05-2026 row here")


def test_the_catalog_rows_config_builds_the_family(params):
    published = _catalog_config()
    cfg = MODEL_FAMILIES["cohere2_moe"]({**published,
                                         "experts_held": [16, 16]})
    assert (cfg.hidden_size, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == \
        (4096, 128, 8, 128)
    assert (cfg.num_experts, cfg.top_k, cfg.num_shared_experts,
            cfg.intermediate_size) == (128, 8, 4, 4096)
    assert cfg.period == ("sliding_attention",) * 3 + ("full_attention",)
    assert (cfg.sliding_window, cfg.rope_theta, cfg.held) == \
        (4096, 50000, (16, 16))
    shapes = param_shapes(cfg)["layers_31"]["mlp"]
    assert shapes["experts"]["w1"].shape == (16, 4096, 4096)
    assert shapes["gate"]["weight"].shape == (4096, 128)
    assert shapes["shared_experts"]["down_proj"]["kernel"].shape == \
        (16384, 4096)
    # the published keys at a small size serve through the engine
    small = {**published, **{k: HF[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "max_position_embeddings", "layer_types",
        "sliding_window", "num_experts", "num_experts_per_tok",
        "num_shared_experts", "torch_dtype")}}
    eng = _engine(small, params)
    assert isinstance(eng.model, PagedWindowModel)
    (prompt,) = _prompts((70,), seed=9)
    logits, _ = eng.put([0], [prompt])
    (want,) = _reference_rows(HF, params, [prompt])
    assert reference.logit_gap(logits[0], want) < TOL


@pytest.mark.parametrize("key,value,named", [
    ("use_qk_norm", True, "use_qk_norm"),
    ("attention_bias", True, "attention_bias"),
    ("first_k_dense_replace", 2, "first_k_dense_replace"),
    ("rope_parameters", {"rope_type": "yarn"}, "rope_type"),
    ("use_parallel_block", False, "use_parallel_block"),
    ("expert_selection_fn", "softmax", "expert_selection_fn"),
    ("shared_expert_combination_strategy", "sum", "combination"),
    ("tie_word_embeddings", False, "tie_word_embeddings"),
    ("position_embedding_type", "rope_llama", "position_embedding")])
def test_what_is_not_built_is_refused_by_name(key, value, named):
    with pytest.raises(NotImplementedError, match=named):
        MODEL_FAMILIES["cohere2_moe"]({**HF, key: value})


def test_logit_scale_is_a_multiply(params):
    (prompt,) = _prompts((20,), seed=10)
    plain, _ = _engine(HF, params).put([0], [prompt])
    scaled, _ = _engine({**HF, "logit_scale": 0.25}, params).put(
        [0], [prompt])
    assert np.allclose(scaled, plain * 0.25, atol=1e-6)


# ------------------------------------------------------------------ #
# both pools in place, read off the programs compiled for a v5e
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _v5e_program(one_chip, shapes):
    """The published widths, one period, the cell's pools (6,144 and
    2,336 blocks of 64), compiled for the described chip: the program of
    lane groups ``shapes``. Returns ``(compiled, pools, params)``."""
    from jax.experimental.compilation_cache import compilation_cache
    from hcache_deepspeed_tpu import platform
    from hcache_deepspeed_tpu.models.cohere2_moe import Cohere2MoeConfig

    class ShapesOnly(PagedWindowModel):
        def load_params(self, params):
            self.params = params

    platform.set_platform("tpu")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        cfg = Cohere2MoeConfig(
            vocab_size=32768, hidden_size=4096, intermediate_size=4096,
            n_layer=4, n_head=128, n_kv_head=8, head_width=128,
            max_positions=32768, num_experts=128, top_k=8,
            experts_held=(0, 16), dtype="bfloat16")

        def on_chip(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        params = jax.tree_util.tree_map_with_path(
            lambda path, x: on_chip(
                x.shape, jnp.float32 if PagedWindowModel._keep_fp32(path)
                else jnp.bfloat16),
            jax.eval_shape(lambda p: serving_layout(cfg, p),
                           param_shapes(cfg)))
        NB = 512
        model = ShapesOnly(cfg, params, block_size=64,
                           max_blocks_per_seq=NB)
        pools = {"global": on_chip((1, 8, 6144 * 64, 128), jnp.bfloat16),
                 "window": on_chip((3, 8, 2336 * 64, 128), jnp.bfloat16)}
        width = sum(B * lanes_width(T, 2 * NB) for B, T in shapes)
        if len(shapes) == 1:
            (B, T), = shapes
            program, lanes = model._fwd, (B, lanes_width(T, 2 * NB))
        else:
            program, lanes = model.step_program(shapes), (width,)
        compiled = program.lower(
            params, pools["global"], pools["global"], pools["window"],
            pools["window"],
            jax.eval_shape(lambda: model._blank_counts(128)).update(
                sharding=one_chip),
            on_chip(lanes, jnp.int32)).compile()
    finally:
        platform._platform = None
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    return compiled, pools, params


@pytest.mark.parametrize("shapes", [((8, 1),), ((1, 512),),
                                    ((8, 1), (1, 512))],
                         ids=["decode", "slice", "step"])
def test_v5e_program_holds_both_pools_and_the_weights_in_place(one_chip,
                                                               shapes):
    """The optimised v5e program at the cell's sizes: nothing of either
    pool's extent (or of a layer of it) copied, sliced or updated by
    slice; rows scattered only by decode lanes, a slice going by block
    runs; no layer of a stacked weight copied (the expert stacks read in
    place by the grouped products); no kernel given up for its
    reference; and the two masks under their two names."""
    from hcache_deepspeed_tpu import ops
    ops.reset_fallback_report()
    compiled, pools, params = _v5e_program(one_chip, shapes)
    assert ops.fallback_report() == {}
    text = compiled.as_text()
    decode = any(T == 1 for _, T in shapes)
    for name, pool in pools.items():
        assert pool_sized_copies(text, pool.shape) == [], name
        layers = pool.shape[0]
        assert len(pool_scatters(text, pool.shape)) == \
            (2 * layers if decode else 0), name
    assert ("hds_kv_write" in text) == any(T > 1 for _, T in shapes)
    stacks = [leaf.shape for leaf in jax.tree.leaves(params)
              if len(leaf.shape) >= 3]
    assert stacked_layer_copies(text, stacks) == []

    def calls(kernel):
        return [ins for ins in re.split(r"\n(?=\s*(?:ROOT )?%)", text)
                if re.search(rf"hds_kernel\W+{kernel}", ins)
                and "custom_call_target" in ins]
    assert len(calls("window_attention")) == 3 * len(shapes)
    assert len(calls("paged_attention")) == len(shapes)
    assert len(calls("expert_gemm")) == 3 * 4       # once over all rows
    # the routing's counts are written where they lie
    counts = HELD_LOG * 2 + 128 + 1
    assert not re.search(rf"s32\[{counts}\]\S* copy\(", text)
    # the weights and both pools as operands: 12.9 GB, and little beside
    memory = compiled.memory_analysis()
    assert 12.8e9 < memory.argument_size_in_bytes < 13.0e9
    assert memory.temp_size_in_bytes < 0.3e9
