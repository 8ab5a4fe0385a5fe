"""A step that holds decode lanes and a prompt slice of the chunk's
bucket is one program (``InferenceEngineV2._launch_step``,
``PagedInferenceModel.forward_step``): on the llama, hybrid, latent and
MoE trunks the fused launch against ``_one_by_one`` over the two
launches it replaces (results, pools, counters, what a fault leaves);
which shapes take it and which keep two programs; who builds its
programs; and that a model that generates by diffusion over blocks
takes none of it: its put records the spans and counters recorded on
the parent of the PR that brought the fused launch."""

import jax
import numpy as np
import pytest

from hcache_deepspeed_tpu.inference.ragged.lanes import lanes_width
from hcache_deepspeed_tpu.telemetry.metrics import ENQUEUE_SPANS

from .test_chained_dispatch import (SHORT_TAIL, Injected, _tokens,
                                    assert_same_results, build, decode_put,
                                    fail_nth_call, mixed_put, pools,
                                    recorded_put, warm_up)

CAUSAL = ["llama", "hybrid", "latent", "moe"]
#: a full slice of the builders' ``prefill_chunk``
CHUNK = 16
#: the largest difference between the fused program and the two it
#: replaces on the CPU backend: none on the llama, latent and MoE
#: trunks, bit for bit; the hybrid trunk's float32 products over 8 + 16
#: rows and over 8 and 16 rows are blocked differently by the CPU's
#: matmul and round apart by up to 6e-4 in a pool row (logits 5e-5)
ATOL = {"hybrid": 2e-3}


def two_programs(trunk):
    """The engine that launches the decode lanes and the slice as it did
    before a step could be one program, each collected before the next
    is built: the reference."""
    engine = build(trunk, one_by_one=True)
    engine._step_T = 0
    return engine


def step_put(engine, done):
    return mixed_put(engine, done, slice_len=CHUNK)


@pytest.mark.parametrize("trunk", CAUSAL)
def test_fused_step_equals_its_two_programs(trunk):
    """Logits rows, latents (cache rows), both pools (and the state pool
    and the convolution tails), the sequences and the latents' ledger of
    a fused step and of the decode put after it are those of the two
    programs one by one; the step counts ``fused`` and not ``chained``,
    one dispatch and one host array."""
    fused, plain = build(trunk), two_programs(trunk)
    done = []
    for engine in (fused, plain):
        puts = warm_up(engine)
        before = engine.dispatch_stats()
        puts.append(step_put(engine, puts))
        after = engine.dispatch_stats()
        puts.append(decode_put(engine))
        done.append(puts)
        moved = {k: after[k] - before[k] for k in after}
        if engine is fused:
            assert moved == {"dispatches": 1, "h2d_arrays": 1, "fused": 1,
                             "chained": 0, "h2d_bytes": moved["h2d_bytes"]}
            nbytes = moved["h2d_bytes"]
        else:
            assert moved == {"dispatches": 2, "h2d_arrays": 2, "fused": 0,
                             "chained": 0, "h2d_bytes": nbytes}
    atol = ATOL.get(trunk, 0.0)
    for got, want in zip(*done):
        assert_same_results(got, want, atol)
    for mine, theirs in zip(pools(fused), pools(plain)):
        np.testing.assert_allclose(mine, theirs, rtol=0.0, atol=atol)
    for uid in (1, 2, 3):
        a = fused.state.get_sequence(uid)
        b = plain.state.get_sequence(uid)
        assert (a.seen_tokens, a.blocks, a.state_slot) == \
            (b.seen_tokens, b.blocks, b.state_slot)
    mine, theirs = fused.latent_stats(), plain.latent_stats()
    peak, peak_plain = (s.pop("pending_peak_bytes") for s in (mine, theirs))
    assert mine == theirs
    assert peak_plain <= peak <= peak_plain + plain._latent_program_max
    for name in ("kv_write_stats", "paged_walk_stats"):
        assert getattr(fused, name)() == getattr(plain, name)(), name


def test_fused_step_hands_out_both_groups_router_inputs():
    """What the latent trunk's routers read for a probed decode lane and
    for the probed slice's last row, out of the one program: the two
    programs' rows."""
    read = []
    for engine in (build("latent"), two_programs("latent")):
        engine.router_probe_uids = {2, 3}
        step_put(engine, warm_up(engine))
        read.append([engine.router_inputs(uid) for uid in (1, 2, 3)])
    assert read[0][0] is None and read[1][0] is None
    for mine, theirs in zip(read[0][1:], read[1][1:]):
        np.testing.assert_array_equal(mine, theirs)
    assert not np.array_equal(read[0][1], read[0][2])


@pytest.mark.parametrize("trunk", CAUSAL)
def test_fused_step_is_one_enqueue_span_one_wait_one_fetch(trunk):
    """The step's one program is enqueued inside the slice's span, which
    says so, and is waited for and fetched once."""
    engine = build(trunk)
    done = warm_up(engine)
    spans = recorded_put(lambda: step_put(engine, done))
    names = [e["name"] for e in spans if e["name"] != "serve.latents.force"]
    assert names == [
        "serve.put.admit", "serve.put.admit", "serve.batch_build",
        "serve.prefill_dispatch", "serve.latents.land",
        "serve.device_wait", "serve.fetch", "serve.scatter",
        "serve.scatter"]
    (enqueue,) = (e for e in spans if e["name"] in ENQUEUE_SPANS)
    args = enqueue["args"]
    assert (args["fused"], args["decode_lanes"], args["lanes"],
            args["bucket"], args["bucket_T"], args["tokens"],
            args["h2d_arrays"]) == (1, 2, 1, 1, CHUNK, CHUNK, 1)
    assert "chained" not in args


def two_slices(engine, done):
    logits = done[-1][0]
    return engine.put([1, 2, 3, 4], [
        [int(np.argmax(logits[0]))], [int(np.argmax(logits[1]))],
        _tokens(CHUNK, 3), _tokens(CHUNK, 4)])


def two_groups(engine, done):
    logits = done[-1][0]
    return engine.put([1, 2, 3, 4], [
        [int(np.argmax(logits[0]))], [int(np.argmax(logits[1]))],
        _tokens(CHUNK, 3), _tokens(SHORT_TAIL, 4)])


def handed_shapes(engine):
    """The shapes of the host arrays handed to programs from now on."""
    handed, hand_over = [], engine.model._hand_over

    def recording(program, pools, lanes):
        handed.append(lanes.shape)
        return hand_over(program, pools, lanes)
    engine.model._hand_over = recording
    return handed


def slice_only(engine):
    """The host array of the slice program of one lane of ``CHUNK``."""
    return (1, lanes_width(CHUNK, engine.max_blocks_per_seq,
                           engine.recurrent))


@pytest.mark.parametrize("put,programs,fused", [
    (lambda engine, done: mixed_put(engine, done, SHORT_TAIL), 2, 0),
    (two_slices, 2, 0), (two_groups, 2, 1),
    (lambda engine, done: decode_put(engine), 1, 0),
    (lambda engine, done: engine.put([3], [_tokens(CHUNK, 3)]), 1, 0)],
    ids=["short-tail", "two-slices", "two-groups", "decode-only",
         "slice-only"])
@pytest.mark.parametrize("trunk", CAUSAL)
def test_other_shapes_keep_a_program_a_group(trunk, put, programs, fused):
    """A short tail beside the decode lanes, two prompts' slices in one
    put, and a put of one group: a program a group, chained, none
    fused. Of two prefill groups the chunk's one lane rides the decode
    lanes' program and the short tail keeps its own, chained behind: no
    put runs the slice-only program of that shape, which a warm-up of
    each shape alone no longer builds."""
    engine = build(trunk)
    done = warm_up(engine)
    if put is two_slices or programs == 1:
        done.append(step_put(engine, done))     # sequence 3 exists
    before = engine.dispatch_stats()
    handed = handed_shapes(engine)
    put(engine, done)
    after = engine.dispatch_stats()
    assert after["fused"] - before["fused"] == fused
    assert after["dispatches"] - before["dispatches"] == programs
    assert after["chained"] - before["chained"] == programs - 1
    assert len(handed) == programs and slice_only(engine) not in handed


def test_defer_fetch_keeps_a_program_a_group():
    """``defer_fetch`` is the plain path's (no chunked prefill, so no
    slice shape to ride the decode lanes): two programs, logits left on
    the device."""
    from hcache_deepspeed_tpu.models.llama import (LlamaForCausalLM,
                                                   llama_tiny)
    from . import test_engine_v2 as llama
    cfg = llama_tiny(max_positions=128, use_flash=False)
    weights = LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)},
        train=False)["params"]
    engine = llama.make_engine(cfg, weights,
                               hcache={"enable_latents": False})
    engine.put([1, 2], [_tokens(5, 1), _tokens(12, 2)])
    before = engine.dispatch_stats()
    out, _ = engine.put([1, 2, 3], [[5], [6], _tokens(CHUNK, 3)],
                        defer_fetch=True)
    after = engine.dispatch_stats()
    assert (after["fused"], after["dispatches"] - before["dispatches"]) \
        == (0, 2)
    assert all(isinstance(row[0], jax.Array) for row in out)
    with pytest.raises(ValueError, match="defer_fetch"):
        build("llama").put([9], [[1, 2, 3]], defer_fetch=True)


def test_more_lanes_than_the_step_program_holds_keep_their_program():
    """The step program carries 8 decode lanes. A step of nine and a
    slice keeps the decode program at the lanes' own bucket and chains
    the step program behind it on blank decode lanes: no second step
    program, no ``fused``, and what the two programs give, bit for bit."""
    from hcache_deepspeed_tpu.models.llama import (LlamaForCausalLM,
                                                   llama_tiny)
    from . import test_engine_v2 as llama
    cfg = llama_tiny(max_positions=128, use_flash=False)
    weights = LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)},
        train=False)["params"]
    done = []
    for step in (True, False):
        engine = llama.make_engine(
            cfg, weights, state_manager={
                "max_tracked_sequences": 32, "max_ragged_batch_size": 128,
                "max_ragged_sequence_count": 16, "max_context": 128,
                "prefill_chunk": CHUNK},
            kv_cache={"block_size": 16, "num_blocks": 48,
                      "cache_dtype": "float32"})
        assert engine._step_B == 8
        if not step:
            engine._step_T, engine._dispatch = 0, engine._one_by_one
        uids = list(range(1, 10))
        engine.put(uids, [_tokens(3 + u, u) for u in uids])
        engine.put(uids, [[u] for u in uids])       # a decode bucket of 16
        before = engine.dispatch_stats()
        done.append(engine.put(uids + [10],
                               [[u + 1] for u in uids] + [_tokens(CHUNK, 10)]))
        after = engine.dispatch_stats()
        assert (after["fused"], after["dispatches"] - before["dispatches"],
                after["chained"] - before["chained"]) == (0, 2, int(step))
        assert list(engine.model._fwd_step_cache) == (
            [((8, 1), (1, CHUNK))] if step else [])
    assert_same_results(*done)


class _Programs:
    """Programs built or fetched, as ``benchmarks/compile_meter.py``
    counts them."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


_PROGRAMS = _Programs()


@pytest.mark.parametrize("trunk", CAUSAL)
def test_the_benchmarks_warm_up_order_builds_the_step_program(trunk):
    """``benchmarks/runners/serve.py warm_engine`` puts each slice shape
    alone and then each decode bucket alone, never both. The slice shape
    of the chunk's bucket alone already takes the narrow step program
    (on blank decode lanes: that shape has no program of its own), and
    it is the engine's only step program: so the first mixed put after
    such a warm-up builds none."""
    engine = build(trunk)
    engine.put([2], [_tokens(SHORT_TAIL, 2)])        # a short slice alone
    assert not engine.model._fwd_step_cache
    engine.put([1], [_tokens(CHUNK, 1)])             # the chunk's, alone
    (shapes,) = engine.model._fwd_step_cache
    assert shapes == ((8, 1), (1, CHUNK))
    stats = engine.dispatch_stats()
    assert (stats["dispatches"], stats["fused"]) == (2, 0)
    engine.put([1, 2], [[5], [6]])                   # the decode bucket alone
    after = engine.dispatch_stats()
    assert (after["dispatches"], after["fused"]) == (3, 0)
    built = _PROGRAMS.count
    out, _ = engine.put([1, 2, 3], [[5], [6], _tokens(CHUNK, 3)])
    assert _PROGRAMS.count == built
    assert engine.dispatch_stats()["fused"] == 1
    assert np.isfinite(out).all()
    # and once a bucket: a second decode put builds nothing either
    engine.put([1, 2, 3], [[5], [6], [7]])
    assert _PROGRAMS.count == built
    assert list(engine.model._fwd_step_cache) == [shapes]


@pytest.mark.parametrize("trunk", CAUSAL)
def test_a_slice_alone_on_blank_decode_lanes_equals_its_own_program(trunk):
    """A slice of the chunk's bucket with no decode lane beside it runs
    the narrow step program on blank decode lanes: the slice program's
    logits row, latents and pools, and no ``fused`` counted."""
    step, plain = build(trunk), two_programs(trunk)
    done = []
    for engine in (step, plain):
        puts = warm_up(engine)
        puts.append(engine.put([3], [_tokens(CHUNK, 3)]))
        done.append(puts)
    assert step.dispatch_stats()["fused"] == 0
    assert list(step.model._fwd_step_cache) == [((8, 1), (1, CHUNK))]
    assert not plain.model._fwd_step_cache
    atol = ATOL.get(trunk, 0.0)
    assert_same_results(done[0][-1], done[1][-1], atol)
    spare = step.state.state_slots
    for name, mine, theirs in zip(("k", "v", "state", "conv"),
                                  pools(step), pools(plain)):
        if name in ("state", "conv"):       # blank lanes: the spare slot
            mine, theirs = np.delete(mine, spare, 1), \
                np.delete(theirs, spare, 1)
        np.testing.assert_allclose(mine, theirs, rtol=0.0, atol=atol)


@pytest.mark.parametrize("trunk", CAUSAL)
def test_a_long_prompts_lead_rounds_take_the_step_program(trunk):
    """A prompt longer than ``prefill_chunk`` put whole: each leading
    chunk runs the step program on blank decode lanes, not a slice-only
    program of the same shape, and the put gives what the slice programs
    give (logits row, the joined latents, the pools)."""
    step, plain = build(trunk), two_programs(trunk)
    done, handed = [], {}
    for engine in (step, plain):
        engine.put([2], [_tokens(12, 2)])
        handed[engine] = handed_shapes(engine)
        done.append(engine.put([1], [_tokens(2 * CHUNK + SHORT_TAIL, 1)]))
    stats = step.dispatch_stats()
    assert (stats["dispatches"], stats["fused"], stats["chained"]) == \
        (4, 0, 0)
    assert list(step.model._fwd_step_cache) == [((8, 1), (1, CHUNK))]
    assert handed[plain].count(slice_only(plain)) == 2
    assert len(handed[step]) == 3 and slice_only(step) not in handed[step]
    atol = ATOL.get(trunk, 0.0)
    assert_same_results(done[0], done[1], atol)
    spare = step.state.state_slots
    for name, mine, theirs in zip(("k", "v", "state", "conv"),
                                  pools(step), pools(plain)):
        if name in ("state", "conv"):       # blank lanes: the spare slot
            mine, theirs = np.delete(mine, spare, 1), \
                np.delete(theirs, spare, 1)
        np.testing.assert_allclose(mine, theirs, rtol=0.0, atol=atol)


def fail_the_launch(engine):
    """The first program the put enqueues: the fused launch's only one,
    the two programs' decode program."""
    fail_nth_call(engine.model, "_hand_over", 1)


def fail_the_collect(engine):
    fail_nth_call(engine, "_fetch", 1)


@pytest.mark.parametrize("fault", [fail_the_launch, fail_the_collect],
                         ids=["launch", "collect"])
@pytest.mark.parametrize("trunk", CAUSAL)
def test_a_fault_in_the_fused_launch_leaves_what_one_by_one_leaves(trunk,
                                                                   fault):
    """An exception out of the fused launch or its collect leaves every
    sequence's counts, its blocks, its state slot and the allocator as
    the same fault leaves them with the two programs one by one, and the
    engine takes the next put."""
    left = []
    for engine in (build(trunk), two_programs(trunk)):
        done = warm_up(engine)
        fault(engine)
        with pytest.raises(Injected):
            step_put(engine, done)
        seqs = [engine.state.get_sequence(uid) for uid in (1, 2, 3)]
        left.append(([(s.seen_tokens, s.in_flight_tokens, list(s.blocks),
                       s.state_slot) for s in seqs],
                     engine.state.free_blocks,
                     engine.state.n_tracked_sequences))
        engine.flush(3)         # as the scheduler's quarantine does
        out, _ = engine.put([1, 2], [[5], [6]])
        assert out.shape[0] == 2 and np.isfinite(out).all()
    assert left[0] == left[1]


# ------------------------------------------------------------------ #
# a model that generates by diffusion over blocks takes none of it
# ------------------------------------------------------------------ #
#: the leaf spans, with the names of their attributes, of the diffusion
#: trunk's mixed put (block lanes of which one is probed and one
#: commits, and a prompt slice) and of its put of block lanes alone, and
#: every counter after them: recorded on the parent of the PR that
#: brought the fused launch (58af4b9) by ``recorded_put`` over
#: ``test_chained_dispatch``'s puts
_BLOCK_DISPATCH = ["block", "bucket", "commit_lanes", "h2d_arrays",
                   "h2d_bytes", "lanes", "masked"]
_PARENT_DIFFUSION = {
    "mixed": [
        ("serve.put.admit", []), ("serve.put.admit", []),
        ("serve.batch_build", ["bucket"]),
        ("serve.decode_dispatch", _BLOCK_DISPATCH),
        ("serve.batch_build", ["bucket"]),
        ("serve.prefill_dispatch", ["bucket", "bucket_T", "chained",
                                    "h2d_arrays", "h2d_bytes", "lanes",
                                    "tokens"]),
        ("serve.latents.land", ["bytes", "chunks"]),
        ("serve.device_wait", []), ("serve.fetch", ["bytes"]),
        ("serve.fetch", ["bytes", "probe"]), ("serve.scatter", []),
        ("serve.latents.land", ["bytes", "chunks"]),
        ("serve.latents.force", ["bytes"]),
        ("serve.device_wait", []), ("serve.scatter", []),
        ("serve.scatter", [])],
    "blocks": [
        ("serve.put.admit", []), ("serve.put.admit", []),
        ("serve.batch_build", ["bucket"]),
        ("serve.decode_dispatch", _BLOCK_DISPATCH),
        ("serve.latents.land", ["bytes", "chunks"]),
        ("serve.device_wait", []), ("serve.fetch", ["bytes"]),
        ("serve.scatter", []), ("serve.scatter", [])],
    "dispatch_stats": {"dispatches": 5, "h2d_arrays": 5, "h2d_bytes": 1272,
                       "chained": 1},
    "kv_write_stats": {"run_dispatches": 5, "run_rows": 480,
                       "row_dispatches": 0, "row_rows": 0},
    "paged_walk_stats": {"dispatches": 5, "table_slots": 152,
                         "blocks_walked": 10},
    "diffusion_stats": {"lane_passes": 4, "positions_fed": 16,
                        "positions_masked": 7, "tokens_committed": 4}}


def test_a_diffusion_engines_put_is_the_parents():
    """``fused`` stays 0, no step program is built or held, and the
    puts' spans, their attributes and every counter are the parent's."""
    engine = build("diffusion")
    assert engine._step_T == 0
    done = warm_up(engine)
    for name, put in (("mixed", lambda: mixed_put(engine, done)),
                      ("blocks", lambda: decode_put(engine))):
        spans = recorded_put(put)
        assert [(e["name"], sorted(e.get("args", {}))) for e in spans] == \
            _PARENT_DIFFUSION[name], name
    stats = engine.dispatch_stats()
    assert stats.pop("fused") == 0
    assert stats == _PARENT_DIFFUSION["dispatch_stats"]
    for name in ("kv_write_stats", "paged_walk_stats", "diffusion_stats"):
        assert getattr(engine, name)() == _PARENT_DIFFUSION[name], name
    assert not engine.model._fwd_step_cache
