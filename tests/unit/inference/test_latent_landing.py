"""Deferred latent landing in the engine: ``put`` starts the copy of a
dispatch's latents and hands out pending chunks; their bytes are the
program's (decode, a bucketed prefill with a padded lane, the
engine-level chunked prefill); the next ``put`` lands them into the
stores that adopted them while its own program runs; chunks nobody
keeps are dropped unread; latents on their way hold a bounded amount of
device memory; and a restore from a store that still has pending chunks
is the restore from the synchronous payload."""

import jax
import numpy as np
import pytest

from hcache_deepspeed_tpu.inference import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
from hcache_deepspeed_tpu.inference.ragged.latents import (HostLatentStore,
                                                           PendingLatents)
from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM, llama_tiny


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama_tiny(max_positions=128, use_flash=False)
    params = LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)},
        train=False)["params"]
    return cfg, params


def build_engine(cfg, params, prefill_chunk=0):
    return InferenceEngineV2(
        cfg, params,
        config=RaggedInferenceEngineConfig(
            state_manager={"max_tracked_sequences": 8,
                           "max_ragged_batch_size": 128,
                           "max_ragged_sequence_count": 4,
                           "max_context": 128,
                           "prefill_chunk": prefill_chunk},
            kv_cache={"block_size": 8, "num_blocks": 33,
                      "cache_dtype": "float32"},
            hcache={"enable_latents": True, "restore_chunk_layers": 1}))


def record_programs(eng):
    """Every ``forward_chunk`` result of ``eng``, as the synchronous
    fetch read it: ``np.asarray`` of the program's whole latents."""
    seen = []
    forward = eng.model.forward_chunk

    def recording(*args, **kwargs):
        logits, latents = forward(*args, **kwargs)
        seen.append(latents)
        return logits, latents

    eng.model.forward_chunk = recording
    step = eng.model.forward_step

    def recording_step(*args, **kwargs):
        # a slice of the chunk's bucket takes the step program (alone:
        # on blank decode lanes); its latents are the last group's
        logits, latents = step(*args, **kwargs)
        seen.append(latents[-1])
        return logits, latents

    eng.model.forward_step = recording_step
    return seen


class Ready:
    """Stands in for the logits of the program in flight."""

    def __init__(self, ready):
        self.is_ready = lambda: ready


def steer(eng, ready):
    """Tiny CPU programs finish when they like; a test says whether
    the landing pass finds the program in flight still running."""
    land = eng._land_pending
    eng._land_pending = lambda in_flight, program: land(Ready(ready),
                                                        program)


def prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, cfg.vocab_size, n)))
            for n in lengths]


@pytest.mark.parametrize("case", ["decode", "padded_prefill", "chunked"])
def test_handles_hold_the_programs_bytes(tiny_model, case):
    cfg, params = tiny_model
    eng = build_engine(cfg, params, prefill_chunk=8 if case == "chunked"
                       else 0)
    seen = record_programs(eng)
    if case == "chunked":
        (prompt,) = prompts(cfg, [20])
        _, (got,) = eng.put([0], [prompt])
        assert len(seen) == 3 and len(got.parts) == 3   # 8 + 8 + 4
        want = [np.concatenate(
            [np.asarray(seen[0])[:, 0, :8], np.asarray(seen[1])[:, 0, :8],
             np.asarray(seen[2])[:, 0, :4]], axis=1)]
        got = [got]
    else:
        batch = prompts(cfg, [5, 7, 6])
        _, got = eng.put([0, 1, 2], batch)
        assert seen[-1].shape[1:3] == (4, 8)            # one padded lane
        lengths = [5, 7, 6]
        if case == "decode":
            _, got = eng.put([0, 1, 2], [[3], [4], [5]])
            assert seen[-1].shape[1:3] == (8, 1)
            lengths = [1, 1, 1]
        want = [np.asarray(seen[-1])[:, j, :n]
                for j, n in enumerate(lengths)]
    for g, w in zip(got, want):
        assert isinstance(g, PendingLatents)
        assert g.shape == w.shape and g.dtype == w.dtype == \
            np.dtype(eng.model.latent_dtype)
        assert g.nbytes == w.nbytes
        np.testing.assert_array_equal(np.asarray(g), w)
    stats = eng.latent_stats()
    assert stats["landed_forced_bytes"] == sum(w.nbytes for w in want)
    assert stats["landed_hidden_bytes"] == 0


def test_the_next_put_lands_what_the_last_left_pending(tiny_model):
    cfg, params = tiny_model
    eng = build_engine(cfg, params)
    seen = record_programs(eng)
    steer(eng, ready=False)
    batch = prompts(cfg, [5, 7, 6])
    stores = [HostLatentStore(capacity=16) for _ in batch]
    _, lat = eng.put([0, 1, 2], batch)
    for store, chunk in zip(stores, lat):
        store.append(chunk)
    first = sum(c.nbytes for c in lat)
    assert eng.latent_stats() == {
        "saved_state": "hidden",
        "captured_bytes": first, "captured_tokens": 5 + 7 + 6,
        "landed_hidden_bytes": 0, "landed_forced_bytes": 0,
        "dropped_bytes": 0, "pending_bytes": first,
        "pending_peak_bytes": seen[0].nbytes}
    del lat
    _, lat = eng.put([0, 1, 2], [[3], [4], [5]])
    stats = eng.latent_stats()
    assert stats["landed_hidden_bytes"] == first
    assert stats["landed_forced_bytes"] == 0
    assert stats["pending_bytes"] == sum(c.nbytes for c in lat)
    assert not any(store._pending for store in stores)
    for j, (store, n) in enumerate(zip(stores, [5, 7, 6])):
        store.append(lat[j])
        np.testing.assert_array_equal(
            store.view(), np.concatenate(
                [np.asarray(seen[0])[:, j, :n],
                 np.asarray(seen[1])[:, j, :1]], axis=1))
    # the reader above landed the second put's chunks itself
    assert eng.latent_stats()["landed_forced_bytes"] == \
        sum(c.nbytes for c in lat)


def test_a_program_still_running_leaves_the_landing_for_later(tiny_model):
    cfg, params = tiny_model
    eng = build_engine(cfg, params)
    steer(eng, ready=True)              # done before the pass looks
    (prompt,) = prompts(cfg, [6])
    store = HostLatentStore(capacity=16)
    _, lat = eng.put([0], [prompt])
    store.append(lat[0])
    _, lat = eng.put([0], [[3]])
    assert eng.latent_stats()["landed_hidden_bytes"] == 0
    assert store.pending_bytes == store.nbytes


def test_chunks_nobody_keeps_are_dropped_unread(tiny_model):
    cfg, params = tiny_model
    eng = build_engine(cfg, params)
    batch = prompts(cfg, [5, 7])
    _, lat = eng.put([0, 1], batch)
    captured = sum(c.nbytes for c in lat)
    eng.flush(0)
    eng.flush(1)
    del lat
    assert eng.latent_stats() == {
        "saved_state": "hidden",
        "captured_bytes": captured, "captured_tokens": 5 + 7,
        "landed_hidden_bytes": 0, "landed_forced_bytes": 0,
        "dropped_bytes": captured, "pending_bytes": 0,
        "pending_peak_bytes": eng.latent_stats()["pending_peak_bytes"]}
    assert not eng._latent_parts
    # generate() never looks at its latents: no copy is waited for
    outs = eng.generate(batch, max_new_tokens=3)
    assert [len(o) for o in outs] == [3, 3]
    stats = eng.latent_stats()
    assert stats["landed_hidden_bytes"] == stats["landed_forced_bytes"] \
        == stats["pending_bytes"] == 0
    assert stats["dropped_bytes"] > captured and not eng._latent_parts
    assert eng.free_blocks == 32


@pytest.mark.parametrize("adopted", [False, True])
def test_latents_on_their_way_hold_bounded_device_memory(tiny_model,
                                                         adopted):
    """Past ``_PENDING_PROGRAMS`` of the largest program the oldest
    copies are waited for: handles a caller sits on give up their
    device buffers, chunks in a store land (forced)."""
    cfg, params = tiny_model
    eng = build_engine(cfg, params)
    steer(eng, ready=True)              # nothing ever lands hidden
    (prompt,) = prompts(cfg, [20])
    store = HostLatentStore(capacity=32)
    kept = []
    _, lat = eng.put([0], [prompt])
    for step in range(5):
        kept.append(lat[0])
        if adopted:
            store.append(lat[0])
        _, lat = eng.put([0], [[step]])
    decode = kept[-1].parts[0].program.nbytes
    largest = kept[0].parts[0].program.nbytes
    assert largest > decode
    held = [c.parts[0].program.device_bytes for c in kept]
    # the prefill's copy was waited for; the rest are within the bound
    assert held[0] == 0 and sum(held) + decode <= 2 * largest
    stats = eng.latent_stats()
    assert 2 * largest < stats["pending_peak_bytes"] <= \
        2 * largest + decode
    assert stats["landed_hidden_bytes"] == 0
    assert (stats["landed_forced_bytes"] > 0) == adopted
    if adopted:
        assert store.pending_bytes < store.nbytes


def test_restore_from_a_store_with_pending_chunks(tiny_model):
    """``test_restore_pipeline``'s parity case, with the victim's
    payload still pending in its store when ``restore_kv`` reads it:
    the restored sequence and the resident decode to the same logits
    as from the payload read at once."""
    cfg, params = tiny_model
    p0, p1 = prompts(cfg, [12, 20], seed=3)

    def run(pending):
        eng = build_engine(cfg, params)
        steer(eng, ready=True)
        _, lat = eng.put([0, 1], [p0, p1])
        payload = HostLatentStore(capacity=32)
        payload.append(lat[1] if pending else np.asarray(lat[1]))
        assert bool(payload.pending_bytes) == pending
        eng.flush(1)
        eng.restore_kv([1], [p1], [payload])
        assert payload.pending_bytes == 0
        l0, _ = eng.put([0], [[5]])
        l1, _ = eng.put([1], [[6]])
        return l0[0], l1[0], eng.latent_stats()["landed_forced_bytes"]

    a0, a1, forced_a = run(pending=True)
    b0, b1, forced_b = run(pending=False)
    np.testing.assert_array_equal(a0, b0)
    np.testing.assert_array_equal(a1, b1)
    assert forced_a == forced_b > 0
