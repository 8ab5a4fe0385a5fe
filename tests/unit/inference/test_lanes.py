"""The lanes of a dispatch as one packed operand
(``inference/ragged/lanes.py``): the pack/unpack pair over the engine's
buckets, one host array a dispatch on both trunks, and the packed
program's results against the forward over separate arrays, bit for
bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hcache_deepspeed_tpu.inference.ragged.lanes import (LaneGroup, Lanes,
                                                         lanes_width,
                                                         pack_lanes,
                                                         pack_step,
                                                         unpack_lanes,
                                                         unpack_step)
from hcache_deepspeed_tpu.telemetry.tracer import get_tracer

from . import test_hybrid_engine as hybrid
from .test_engine_v2 import make_engine, tiny_model  # noqa: F401
from .test_hybrid_engine import params  # noqa: F401


def _lanes(B, T, n_blocks, slot, seed=0):
    rng = np.random.default_rng(seed)
    arrays = [rng.integers(0, 50_000, (B, T)), rng.integers(0, 8192, (B,)),
              rng.integers(0, 2560, (B, n_blocks)),
              rng.integers(0, T + 1, (B,))]
    if slot:
        arrays.append(rng.integers(0, 65, (B,)))
    return [a.astype(np.int32) for a in arrays]


@pytest.mark.parametrize("slot", [False, True], ids=["kv", "kv+slot"])
@pytest.mark.parametrize("T", [1, 64, 512])
@pytest.mark.parametrize("B", [1, 8])
def test_pack_and_unpack_round_trip(B, T, slot):
    """What goes in comes out, on the host (views) and inside a program
    (static slices); the layout is ``tokens | start | t_len | tables |
    (slot)``."""
    n_blocks = 32
    arrays = _lanes(B, T, n_blocks, slot)
    lanes = pack_lanes(*arrays)
    assert lanes.dtype == np.int32 and lanes.flags.c_contiguous
    assert lanes.shape == (B, lanes_width(T, n_blocks, slot))
    assert lanes.shape[1] == T + 2 + n_blocks + slot
    tokens, start, tables, t_len = arrays[:4]
    np.testing.assert_array_equal(lanes[:, :T], tokens)
    np.testing.assert_array_equal(lanes[:, T], start)
    np.testing.assert_array_equal(lanes[:, T + 1], t_len)
    np.testing.assert_array_equal(lanes[:, T + 2:T + 2 + n_blocks], tables)
    if slot:
        np.testing.assert_array_equal(lanes[:, -1], arrays[4])
    on_host = unpack_lanes(lanes, n_blocks, slot)
    in_program = jax.jit(
        lambda x: unpack_lanes(x, n_blocks, slot))(lanes)
    assert len(on_host) == len(in_program) == len(arrays)
    for given, view, cut in zip(arrays, on_host, in_program):
        assert view.base is lanes                   # no copy on the host
        np.testing.assert_array_equal(view, given)
        assert cut.dtype == jnp.int32 and cut.shape == given.shape
        np.testing.assert_array_equal(np.asarray(cut), given)


@pytest.mark.parametrize("slot", [False, True], ids=["kv", "kv+slot"])
@pytest.mark.parametrize("shapes", [((8, 1), (1, 512)), ((16, 1), (1, 16)),
                                    ((8, 1),)])
def test_a_steps_groups_pack_flat_and_unpack_by_their_shapes(shapes, slot):
    """``pack_step``: the groups' packed lanes flat, one after the other,
    one host array; ``unpack_step`` cuts every group's columns out again
    by the static shapes, on the host and inside a program, and refuses
    an array that is not of those shapes."""
    n_blocks = 32
    groups = [_lanes(B, T, n_blocks, slot, seed=i)
              for i, (B, T) in enumerate(shapes)]
    flat = pack_step(groups)
    assert flat.dtype == np.int32 and flat.ndim == 1
    assert flat.size == sum(B * lanes_width(T, n_blocks, slot)
                            for B, T in shapes)
    np.testing.assert_array_equal(flat, np.concatenate(
        [pack_lanes(*g).ravel() for g in groups]))
    on_host = unpack_step(flat, shapes, n_blocks, slot)
    in_program = jax.jit(
        lambda x: unpack_step(x, shapes, n_blocks, slot))(flat)
    for given, views, cuts in zip(groups, on_host, in_program):
        assert len(given) == len(views) == len(cuts)
        for a, view, cut in zip(given, views, cuts):
            np.testing.assert_array_equal(view, a)
            np.testing.assert_array_equal(np.asarray(cut), a)
    with pytest.raises(ValueError, match="not lane groups"):
        unpack_step(flat[:-1], shapes, n_blocks, slot)


def test_lanes_cut_rows_into_groups_and_put_them_together_again():
    """``Lanes`` over two groups: ``split`` cuts ``[.., 1, N, ..]`` rows
    into each group's ``[.., B, T, ..]``, ``join`` is its inverse,
    ``rows`` lays a per-group attribute over all rows and ``last_rows``
    takes each lane's last valid position; over one group every one of
    them hands back what it got."""
    decode = LaneGroup(*(jnp.asarray(a) for a in _lanes(8, 1, 4, False)))
    slice_ = LaneGroup(*(jnp.asarray(a) for a in _lanes(2, 16, 4, False, 1)))
    both = Lanes([decode, slice_])
    assert (decode.shape, slice_.shape) == ((8, 1), (2, 16))
    x = jnp.arange(3 * 40 * 5, dtype=jnp.float32).reshape(3, 1, 40, 5)
    a, b = both.split(x, lead=1)
    assert (a.shape, b.shape) == ((3, 8, 1, 5), (3, 2, 16, 5))
    np.testing.assert_array_equal(a[:, :, 0], x[:, 0, :8])
    np.testing.assert_array_equal(b[:, 1], x[:, 0, 24:])
    np.testing.assert_array_equal(both.join([a, b], lead=1), x)
    tokens = both.rows("tokens")
    assert tokens.shape == (1, 40)
    np.testing.assert_array_equal(tokens[0, :8], decode.tokens[:, 0])
    np.testing.assert_array_equal(tokens[0, 8:], slice_.tokens.reshape(-1))
    last = both.last_rows(x[0])
    assert last.shape == (10, 5)
    np.testing.assert_array_equal(last[:8], x[0, 0, :8])
    for j in range(2):
        at = max(int(slice_.t_len[j]) - 1, 0)
        np.testing.assert_array_equal(last[8 + j], x[0, 0, 8 + 16 * j + at])
    one = Lanes([slice_])
    y = x[0, 0, :32].reshape(2, 16, 5)
    assert one.split(y)[0] is y and one.join([y]) is y
    assert one.rows("tokens") is slice_.tokens
    assert len(Lanes.of(list(_lanes(8, 1, 4, True)) +
                        list(_lanes(1, 16, 4, True)), slot=True).groups) == 2


def test_pack_casts_what_callers_hand_it():
    """Lists, int64 arrays and device arrays pack as ``int32``, as the
    ``jnp.asarray(x, jnp.int32)`` they replace did."""
    tokens, start, tables, t_len = _lanes(2, 4, 3, False)
    want = pack_lanes(tokens, start, tables, t_len)
    got = pack_lanes(tokens.tolist(), start.astype(np.int64),
                     jnp.asarray(tables), list(t_len))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="no token"):
        unpack_lanes(want, 3 + 4)


@pytest.mark.parametrize("trunk", ["llama", "hybrid"])
def test_every_dispatch_hands_over_one_array(trunk, request):
    """``engine.dispatch_stats()`` after a chunked prompt beside decode
    lanes, and a full slice beside them (one program for both groups):
    as many host arrays as dispatches, of the packed lanes' bytes; the
    two enqueue spans carry the same as attributes."""
    if trunk == "hybrid":
        engine = hybrid.make_engine(request.getfixturevalue("params"))
        vocab = hybrid.HF["vocab_size"]
    else:
        cfg, _, weights = request.getfixturevalue("tiny_model")
        vocab = cfg.vocab_size
        engine = make_engine(cfg, weights, state_manager={
            "max_tracked_sequences": 8, "max_ragged_batch_size": 128,
            "max_ragged_sequence_count": 4, "max_context": 128,
            "prefill_chunk": 16})
    slot = engine.recurrent
    n_blocks = engine.max_blocks_per_seq
    assert engine.dispatch_stats() == {"dispatches": 0, "h2d_arrays": 0,
                                       "h2d_bytes": 0, "chained": 0,
                                       "fused": 0}
    rng = np.random.default_rng(0)
    tracer = get_tracer()
    tracer.clear()
    tracer.configure(enabled=True)
    try:
        logits, _ = engine.put([1, 2], [rng.integers(0, vocab, 5),
                                        rng.integers(0, vocab, 9)])
        # a 37-token prompt in 16-token slices beside two decode lanes
        engine.put([1, 2, 3], [[int(np.argmax(logits[0]))],
                               [int(np.argmax(logits[1]))],
                               rng.integers(0, vocab, 37)])
        engine.put([1, 2, 3], [[5], [6], [7]])
        # a full slice beside three decode lanes: one program
        engine.put([1, 2, 3, 4], [[5], [6], [7], rng.integers(0, vocab, 16)])
    finally:
        tracer.configure(enabled=False)
    stats = engine.dispatch_stats()
    writes = engine.kv_write_stats()
    assert stats["fused"] == 1      # which wrote rows and runs
    assert stats["dispatches"] + stats["fused"] == \
        writes["run_dispatches"] + writes["row_dispatches"]
    assert writes["row_dispatches"] == 3 and writes["run_dispatches"] >= 5
    assert stats["h2d_arrays"] == stats["dispatches"]
    spans = [e for e in tracer.events()
             if e["name"] in ("serve.decode_dispatch",
                              "serve.prefill_dispatch")]
    assert len(spans) == stats["dispatches"]
    assert {e["name"] for e in spans} == {"serve.decode_dispatch",
                                          "serve.prefill_dispatch"}
    for event in spans:
        args = event["args"]
        assert args["h2d_arrays"] == 1
        T = args.get("bucket_T", 1)
        # a step program's span (``decode_lanes``: a chunk-bucket slice
        # with the decode lanes, or alone on blank ones) carries both
        # groups' bytes
        decode = 8 * lanes_width(1, n_blocks, slot) \
            if "decode_lanes" in args else 0
        assert args["h2d_bytes"] == \
            4 * (decode + args["bucket"] * lanes_width(T, n_blocks, slot))
    assert stats["h2d_bytes"] == sum(e["args"]["h2d_bytes"] for e in spans)
    tracer.clear()


@pytest.mark.parametrize("B,T", [(8, 1), (1, 16), (2, 8)],
                         ids=["decode", "slice", "two-lanes"])
def test_the_packed_program_computes_what_separate_arrays_do(tiny_model,
                                                             B, T):
    """``forward_chunk`` (packed lanes, a NumPy operand) against
    ``_fwd_inner`` called with the four arrays: logits, latents and both
    pools bit for bit."""
    cfg, _, weights = tiny_model
    engine = make_engine(cfg, weights, kv_cache={
        "block_size": 16, "num_blocks": 40, "cache_dtype": "float32"})
    model, cache = engine.model, engine.cache
    rng = np.random.default_rng(B * 100 + T)
    # a prompt first, so the pools the programs start from are not blank
    engine.put([9], [rng.integers(0, cfg.vocab_size, 20)])
    tok, start, t_len, tables = engine._blank_lanes(B, T)
    live = max(1, B - 1)                    # the last lane stays blank
    tok[:live] = rng.integers(0, cfg.vocab_size, (live, T))
    start[:live] = 16 * np.arange(live) % 48
    t_len[:live] = rng.integers(1, T + 1, live)
    free = np.setdiff1d(np.arange(1, 40), engine._tables([0], [9])[0])
    tables[:live, :4] = rng.permutation(free)[:live * 4].reshape(live, 4)
    before = (jnp.array(cache.k), jnp.array(cache.v))
    want_k, want_v, want_logits, want_latents = jax.jit(model._fwd_inner)(
        model.params, *before, jnp.asarray(tok), jnp.asarray(start),
        jnp.asarray(tables), jnp.asarray(t_len))
    logits, latents = model.forward_chunk(cache, tok, start, tables, t_len)
    for got, want in ((logits, want_logits), (latents, want_latents),
                      (cache.k, want_k), (cache.v, want_v)):
        got, want = np.asarray(got), np.asarray(want)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))
    assert not np.array_equal(np.asarray(cache.k), np.asarray(before[0]))
