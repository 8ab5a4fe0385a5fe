"""Prefix caching: full KV blocks shared by refcount across sequences
with identical prompt prefixes (no reference analog — FastGen lacks
prefix caching; this is a beyond-parity feature of the TPU engine)."""

import jax
import numpy as np
import pytest

from hcache_deepspeed_tpu.inference import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM, llama_tiny

BS = 16


@pytest.fixture(scope="module")
def tiny():
    cfg = llama_tiny(max_positions=128, use_flash=False)
    model = LlamaForCausalLM(cfg)
    batch = {"input_ids": np.zeros((1, 8), np.int32)}
    params = model.init(jax.random.PRNGKey(0), batch, train=False)["params"]
    return cfg, model, params


def make_engine(cfg, params, prefix_caching=True, blocks=24):
    return InferenceEngineV2(
        cfg, params,
        config=RaggedInferenceEngineConfig(
            state_manager={"max_tracked_sequences": 8,
                           "max_ragged_batch_size": 256,
                           "max_ragged_sequence_count": 4,
                           "max_context": 128,
                           "prefix_caching": prefix_caching},
            kv_cache={"block_size": BS, "num_blocks": blocks,
                      "cache_dtype": "float32"},
            hcache={"enable_latents": False}))


def full_logits(model, params, tokens):
    out = model.apply({"params": params},
                      {"input_ids": np.asarray(tokens, np.int32)[None]},
                      train=False, return_logits=True)
    return np.asarray(out)[0]


class TestPrefixCaching:

    def test_latents_incompatible(self, tiny):
        cfg, _, params = tiny
        with pytest.raises(ValueError, match="prefix_caching"):
            InferenceEngineV2(
                cfg, params,
                config=RaggedInferenceEngineConfig(
                    state_manager={"prefix_caching": True},
                    kv_cache={"block_size": BS, "num_blocks": 8},
                    hcache={"enable_latents": True}))

    def test_identical_prompts_share_blocks(self, tiny):
        cfg, model, params = tiny
        engine = make_engine(cfg, params)
        rng = np.random.default_rng(0)
        prompt = list(rng.integers(0, cfg.vocab_size, (3 * BS + 5,)))

        engine.put([1], [prompt])
        free_after_first = engine.state.free_blocks
        logits2, _ = engine.put([2], [prompt])
        # second sequence allocates only the tail block
        assert free_after_first - engine.state.free_blocks == 1
        s1 = engine.state.get_sequence(1)
        s2 = engine.state.get_sequence(2)
        assert s2.blocks[:3] == s1.blocks[:3]      # shared by reference
        assert s2.blocks[3] != s1.blocks[3]
        # logits are exact: same cache content, same math
        ref = full_logits(model, params, prompt)
        np.testing.assert_allclose(logits2[0], ref[-1], atol=2e-2)

        # decode continues correctly on the shared cache
        nxt = int(np.argmax(logits2[0]))
        out, _ = engine.put([2], [[nxt]])
        ref2 = full_logits(model, params, prompt + [nxt])
        np.testing.assert_allclose(out[0], ref2[-1], atol=2e-2)

    def test_flush_order_refcounts(self, tiny):
        cfg, _, params = tiny
        engine = make_engine(cfg, params)
        rng = np.random.default_rng(1)
        prompt = list(rng.integers(0, cfg.vocab_size, (2 * BS + 3,)))
        engine.put([1], [prompt])
        engine.put([2], [prompt])
        shared = engine.state.get_sequence(2).blocks[:2]
        engine.flush(1)            # owner leaves; sharer keeps blocks
        for b in shared:
            assert engine.state.allocator.refcount(b) == 1
        logits, _ = engine.put([2], [[5]])    # sharer still decodes
        assert np.all(np.isfinite(logits))
        engine.flush(2)
        for b in shared:
            assert engine.state.allocator.refcount(b) == 0

    def test_divergent_prompts_share_common_prefix_only(self, tiny):
        cfg, model, params = tiny
        engine = make_engine(cfg, params)
        rng = np.random.default_rng(2)
        common = list(rng.integers(0, cfg.vocab_size, (2 * BS,)))
        a = common + list(rng.integers(0, cfg.vocab_size, (BS,)))
        b = common + list(rng.integers(0, cfg.vocab_size, (BS,)))
        engine.put([1], [a])
        logits, _ = engine.put([2], [b])
        s1, s2 = engine.state.get_sequence(1), engine.state.get_sequence(2)
        assert s2.blocks[:2] == s1.blocks[:2]
        assert s2.blocks[2] != s1.blocks[2]
        ref = full_logits(model, params, b)
        np.testing.assert_allclose(logits[0], ref[-1], atol=2e-2)

    def test_whole_prompt_match_still_runs_one_token(self, tiny):
        cfg, model, params = tiny
        engine = make_engine(cfg, params)
        rng = np.random.default_rng(3)
        prompt = list(rng.integers(0, cfg.vocab_size, (2 * BS,)))
        engine.put([1], [prompt])
        # identical prompt of exactly 2 full blocks: only 1 block may be
        # shared (the last token must produce logits)
        logits, _ = engine.put([2], [prompt])
        s2 = engine.state.get_sequence(2)
        assert s2.blocks[0] == engine.state.get_sequence(1).blocks[0]
        assert s2.blocks[1] != engine.state.get_sequence(1).blocks[1]
        ref = full_logits(model, params, prompt)
        np.testing.assert_allclose(logits[0], ref[-1], atol=2e-2)

    def test_index_purged_after_all_flushed(self, tiny):
        cfg, _, params = tiny
        engine = make_engine(cfg, params)
        rng = np.random.default_rng(4)
        prompt = list(rng.integers(0, cfg.vocab_size, (2 * BS + 1,)))
        engine.put([1], [prompt])
        assert engine._prefix_index
        engine.flush(1)
        assert not engine._prefix_index
        assert not engine._block_prefix

    def test_decode_grown_blocks_become_sharable(self, tiny):
        cfg, model, params = tiny
        engine = make_engine(cfg, params)
        rng = np.random.default_rng(5)
        prompt = list(rng.integers(0, cfg.vocab_size, (BS - 1,)))
        logits, _ = engine.put([1], [prompt])
        toks = list(prompt)
        for _ in range(BS + 2):   # decode past a block boundary
            nxt = int(np.argmax(logits[0]))
            toks.append(nxt)
            logits, _ = engine.put([1], [[nxt]])
        # a new prompt equal to (prompt + generated) shares the full
        # blocks the decode filled
        n_shared_possible = (len(toks) - 1) // BS
        free_before = engine.state.free_blocks
        engine.put([2], [toks])
        used = free_before - engine.state.free_blocks
        assert used == -(-len(toks) // BS) - n_shared_possible
        ref = full_logits(model, params, toks)
        # engine logits for uid 2 come from the shared + fresh cache
        out, _ = engine.put([2], [[int(np.argmax(ref[-1]))]])
        assert np.all(np.isfinite(out))

    def test_in_batch_duplicates_share_via_second_wave(self, tiny):
        cfg, model, params = tiny
        engine = make_engine(cfg, params)
        rng = np.random.default_rng(6)
        prompt = list(rng.integers(0, cfg.vocab_size, (2 * BS + 4,)))
        free0 = engine.state.free_blocks
        logits, _ = engine.put([1, 2], [prompt, prompt])
        # one full set (3 blocks) + one tail block, not 2 full sets
        assert free0 - engine.state.free_blocks == 4
        s1, s2 = engine.state.get_sequence(1), engine.state.get_sequence(2)
        assert s2.blocks[:2] == s1.blocks[:2]
        ref = full_logits(model, params, prompt)
        np.testing.assert_allclose(logits[0], ref[-1], atol=2e-2)
        np.testing.assert_allclose(logits[1], ref[-1], atol=2e-2)
        # both sequences decode independently afterwards
        nxt = int(np.argmax(ref[-1]))
        out, _ = engine.put([1, 2], [[nxt], [nxt]])
        ref2 = full_logits(model, params, prompt + [nxt])
        np.testing.assert_allclose(out[0], ref2[-1], atol=2e-2)
        np.testing.assert_allclose(out[1], ref2[-1], atol=2e-2)

    def test_restored_sequences_never_register(self, tiny):
        """A restore_kv-built sequence has history only for post-restore
        decodes; indexing its blocks under that history would share
        wrong KV (the blocks hold the PROMPT's cache)."""
        cfg, model, params = tiny
        # latents from a capture-enabled twin
        lat_engine = InferenceEngineV2(
            cfg, params,
            config=RaggedInferenceEngineConfig(
                state_manager={"max_tracked_sequences": 8,
                               "max_context": 128},
                kv_cache={"block_size": BS, "num_blocks": 24,
                          "cache_dtype": "float32"}))
        rng = np.random.default_rng(7)
        prompt = list(rng.integers(0, cfg.vocab_size, (2 * BS,)))
        logits, latents = lat_engine.put([1], [prompt])

        engine = make_engine(cfg, params)
        engine.restore_kv([1], [prompt], [latents[0]])
        cur = int(np.argmax(logits[0]))
        for _ in range(BS + 1):   # decode past a block boundary
            out, _ = engine.put([1], [[cur]])
            cur = int(np.argmax(out[0]))
        # nothing registered: history (decodes only) != seen_tokens
        assert not engine._prefix_index

    def test_unindex_survives_deep_chain(self, tiny):
        """A ~64k-token shared prefix at block_size 16 is a 4000-level
        chain; purging it must not hit the Python recursion limit
        (advisor finding: the old recursive walk died at ~1000)."""
        cfg, _, params = tiny
        engine = make_engine(cfg, params)
        depth = 4000          # >> default recursionlimit
        parent = -1
        for i in range(depth):
            key = (parent, i)
            bid = 10_000 + i   # synthetic ids, never touch the allocator
            engine._prefix_index[key] = bid
            engine._block_prefix[bid] = key
            if parent != -1:
                engine._chain_children.setdefault(parent, set()).add(key)
            parent = bid
        engine._unindex_subtree(10_000)
        # everything below the root is gone; the root itself is the
        # caller's (purge loop's) responsibility
        assert len(engine._prefix_index) == 1
        assert len(engine._block_prefix) == 1
        assert not engine._chain_children


class TestPrefixCachingFuzz:
    """Randomized interleavings of shared-prefix admissions, decodes,
    flushes and suspend/resume under pool pressure; every decode's
    logits check against a full-context recompute, so refcount bugs,
    stale chain entries after purge, or cross-sequence block corruption
    surface at the exact op that broke them."""

    def test_random_interleavings(self, tiny):
        cfg, model, params = tiny
        engine = make_engine(cfg, params, blocks=30)
        rng = np.random.default_rng(99)
        bases = [list(rng.integers(0, cfg.vocab_size, (2 * BS,)))
                 for _ in range(3)]
        shadows = {}     # uid -> list of tokens whose KV is cached
        suspended = set()
        next_uid = 0

        def check(uid, logits):
            ref = full_logits(model, params, shadows[uid])
            np.testing.assert_allclose(logits, ref[-1], atol=2e-2)

        for _ in range(70):
            op = rng.choice(["new", "new", "decode", "decode", "decode",
                             "flush", "suspend", "resume"])
            live = [u for u in shadows if u not in suspended]
            if op == "new" and len(shadows) < 4:
                base = bases[int(rng.integers(len(bases)))]
                tail = list(rng.integers(0, cfg.vocab_size,
                                         (int(rng.integers(1, 20)),)))
                prompt = base + tail
                from hcache_deepspeed_tpu.inference import SchedulingResult
                if engine.can_schedule([next_uid], [len(prompt)]) != \
                        SchedulingResult.Success:
                    continue
                logits, _ = engine.put([next_uid], [prompt])
                shadows[next_uid] = list(prompt)
                check(next_uid, logits[0])
                next_uid += 1
            elif op == "decode" and live:
                uid = int(rng.choice(live))
                if len(shadows[uid]) + 1 > 128:
                    continue
                tok = int(rng.integers(0, cfg.vocab_size))
                shadows[uid].append(tok)
                logits, _ = engine.put([uid], [[tok]])
                check(uid, logits[0])
            elif op == "flush" and shadows:
                uid = int(rng.choice(list(shadows)))
                engine.flush(uid)
                del shadows[uid]
                suspended.discard(uid)
            elif op == "suspend" and live:
                uid = int(rng.choice(live))
                engine.suspend_sequence(uid)
                suspended.add(uid)
            elif op == "resume" and suspended:
                from hcache_deepspeed_tpu.inference import SchedulingError
                uid = int(rng.choice(list(suspended)))
                try:
                    engine.resume_sequence(uid)
                except SchedulingError:
                    continue    # pool too full right now — legal
                suspended.remove(uid)

        # teardown invariant: freeing everything empties the index
        for uid in list(shadows):
            engine.flush(uid)
        assert not engine._prefix_index
        assert not engine._block_prefix
        assert all(not v for v in engine._chain_children.values())
