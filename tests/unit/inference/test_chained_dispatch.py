"""A put's programs are enqueued back to back and collected afterwards
(``InferenceEngineV2._dispatch``): on the llama, hybrid, latent and
diffusion trunks, a put that holds decode (or block) lanes and a prompt
slice against the same put with each program collected before the next
is built (``_one_by_one``, the order before launches were chained):
results bit for bit, the order of the spans, the ``chained`` counter and
attribute, and what a fault leaves behind.

The slice of a causal trunk's put here is a short tail (5 tokens beside
a ``prefill_chunk`` of 16), which stays a program of its own; a slice in
the chunk's bucket rides the decode lanes' program
(``test_fused_step.py``)."""

import functools

import jax
import numpy as np
import pytest

from hcache_deepspeed_tpu.inference.factory import MODEL_FAMILIES
from hcache_deepspeed_tpu.inference.scheduling import BlockPass
from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM, llama_tiny
from hcache_deepspeed_tpu.telemetry.metrics import ENQUEUE_SPANS
from hcache_deepspeed_tpu.telemetry.tracer import get_tracer

from . import test_engine_v2 as llama
from . import test_hybrid_engine as hybrid
from . import test_latent_family as latent
from . import test_sdar_diffusion as diffusion

TRUNKS = ["llama", "hybrid", "latent", "diffusion"]
MASK = diffusion.MASK


def _norms_off_one(tree, n_keys):
    keys = iter(jax.random.split(jax.random.PRNGKey(2), n_keys))
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.2 * jax.random.normal(
            next(keys), x.shape)) if "norm" in str(path[-2]) else x, tree)


@functools.lru_cache(maxsize=None)
def _builder(trunk):
    """``() -> engine`` over one set of seeded weights a trunk, at the
    sizes of that trunk's own tests (16-token slices)."""
    if trunk == "llama":
        cfg = llama_tiny(max_positions=128, use_flash=False)
        weights = LlamaForCausalLM(cfg).init(
            jax.random.PRNGKey(0),
            {"input_ids": np.zeros((1, 8), np.int32)},
            train=False)["params"]
        return lambda: llama.make_engine(cfg, weights, state_manager={
            "max_tracked_sequences": 8, "max_ragged_batch_size": 128,
            "max_ragged_sequence_count": 4, "max_context": 128,
            "prefill_chunk": 16})
    if trunk == "hybrid":
        from hcache_deepspeed_tpu.models.olmo_hybrid import \
            OlmoHybridForCausalLM
        weights = _norms_off_one(OlmoHybridForCausalLM(
            MODEL_FAMILIES["olmo_hybrid"](hybrid.HF)).init(
                jax.random.PRNGKey(1),
                {"input_ids": np.zeros((1, 16), np.int32)})["params"], 200)
        return lambda: hybrid.make_engine(weights)
    if trunk == "moe":
        from hcache_deepspeed_tpu.models.mixtral import (MixtralForCausalLM,
                                                         mixtral_tiny)
        cfg = mixtral_tiny(max_positions=128, use_flash=False,
                           dropless=True)
        weights = MixtralForCausalLM(cfg).init(
            jax.random.PRNGKey(0),
            {"input_ids": np.zeros((1, 8), np.int32)},
            train=False)["params"]
        return lambda: llama.make_engine(cfg, weights, state_manager={
            "max_tracked_sequences": 8, "max_ragged_batch_size": 128,
            "max_ragged_sequence_count": 4, "max_context": 128,
            "prefill_chunk": 16})
    if trunk == "latent":
        from hcache_deepspeed_tpu.models.glm4_moe_lite import seeded_params
        weights = _norms_off_one(seeded_params(
            MODEL_FAMILIES["glm4_moe_lite"](latent.HF), seed=3), 64)
        return lambda: latent._engine(weights)
    from hcache_deepspeed_tpu.models.sdar_moe import SdarMoeForCausalLM
    weights = _norms_off_one(SdarMoeForCausalLM(
        MODEL_FAMILIES["sdar_moe"](diffusion.HF)).init(
            jax.random.PRNGKey(1),
            {"input_ids": np.zeros((1, 16), np.int32)},
            train=False)["params"], 64)
    return lambda: diffusion.make_engine(weights)


def build(trunk, one_by_one=False):
    engine = _builder(trunk)()
    if one_by_one:
        engine._dispatch = engine._one_by_one
    return engine


def _tokens(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 250, n)]


def warm_up(engine):
    """Two sequences with their prompts in the cache; the puts so far,
    results and all."""
    if engine.diffusion:
        return [engine.put([1], [_tokens(16, 1)]),
                engine.put([2], [_tokens(12, 2)])]
    return [engine.put([1, 2], [_tokens(5, 1), _tokens(12, 2)])]


#: a causal trunk's slice under half a ``prefill_chunk`` (16 in every
#: builder): its bucket is not the chunk's, so it stays a program
SHORT_TAIL = 5


def mixed_put(engine, done, slice_len=None):
    """The put of a serving step that moves a prompt forward: the two
    sequences' decode lanes (their open blocks, of which one commits) and
    a slice of a third (default: 16 tokens of a model that generates by
    diffusion, else ``SHORT_TAIL``): two programs."""
    if slice_len is None:
        slice_len = 16 if engine.diffusion else SHORT_TAIL
    slice_ = _tokens(slice_len, 3)
    if engine.diffusion:
        blocks = {1: BlockPass(commit=False, probe=True),
                  2: BlockPass(commit=True, probe=False)}
        return engine.put([1, 2, 3],
                          [[5, MASK, 7, MASK], [9, 10, 11, 12], slice_],
                          blocks=blocks)
    logits = done[-1][0]
    return engine.put([1, 2, 3], [[int(np.argmax(logits[0]))],
                                  [int(np.argmax(logits[1]))], slice_])


def decode_put(engine):
    """One program: every sequence a decode (block) lane."""
    if engine.diffusion:
        return engine.put(
            [1, 3], [[5, 6, 7, MASK], [MASK] * 4],
            blocks={1: BlockPass(False, False), 3: BlockPass(False, False)})
    return engine.put([1, 2, 3], [[5], [6], [7]])


def pools(engine):
    cache = engine.cache
    names = ("k", "v", "state", "conv") if engine.recurrent else ("k", "v")
    return [np.asarray(getattr(cache, name)) for name in names]


def assert_same_results(got, want, atol=0.0):
    """Two puts' ``(logits, latents)``, bit for bit (``atol`` 0)."""
    def same(a, b):
        if atol and b is not None:
            np.testing.assert_allclose(a, b, rtol=0.0, atol=atol)
        else:
            np.testing.assert_array_equal(a, b)

    for mine, theirs in zip(got[0], want[0]):
        if theirs is None or isinstance(theirs, np.ndarray):
            same(mine, theirs)
            continue
        for field in ("tokens", "confidence", "logits", "router_in"):
            a, b = getattr(mine, field), getattr(theirs, field)
            assert (a is None) == (b is None)
            if b is not None:
                same(a, b)
    assert len(got[1]) == len(want[1])
    for mine, theirs in zip(got[1], want[1]):
        assert (mine is None) == (theirs is None)
        if theirs is not None:
            same(np.asarray(mine), np.asarray(theirs))


@pytest.mark.parametrize("trunk", TRUNKS)
def test_launched_together_equals_one_by_one_bit_for_bit(trunk):
    """Logits (a block lane's choice, its probed rows and routers'
    inputs), latents and every pool of a put of two programs, and of
    the decode put after it, are those of the same lanes with each
    program collected before the next is built."""
    chained, plain = build(trunk), build(trunk, one_by_one=True)
    done = []
    for engine in (chained, plain):
        puts = warm_up(engine)
        puts.append(mixed_put(engine, puts))
        puts.append(decode_put(engine))
        done.append(puts)
    assert len(done[0]) == len(done[1])
    for got, want in zip(*done):
        assert_same_results(got, want)
    for mine, theirs in zip(pools(chained), pools(plain)):
        np.testing.assert_array_equal(mine, theirs)
    for uid in (1, 2, 3):
        a = chained.state.get_sequence(uid)
        b = plain.state.get_sequence(uid)
        assert (a.seen_tokens, a.blocks) == (b.seen_tokens, b.blocks)
    mine, theirs = chained.latent_stats(), plain.latent_stats()
    peak, peak_plain = (s.pop("pending_peak_bytes") for s in (mine, theirs))
    assert mine == theirs
    assert peak_plain <= peak <= peak_plain + plain._latent_program_max
    assert plain.dispatch_stats()["chained"] == 0
    assert chained.dispatch_stats()["chained"] >= 1


def recorded_put(put):
    """The leaf spans of one put (those inside ``hds.serve.put``), in
    time order."""
    tracer = get_tracer()
    tracer.clear()
    tracer.configure(enabled=True, xla=False)
    try:
        put()
        return sorted((e for e in tracer.events() if e["ph"] == "X"
                       and e["name"] != "hds.serve.put"),
                      key=lambda e: e["ts"])
    finally:
        tracer.configure(enabled=False)
        tracer.clear()


@pytest.mark.parametrize("trunk", TRUNKS)
def test_every_enqueue_span_closes_before_the_first_wait_opens(trunk):
    """In a put of two programs both are built and enqueued before
    either is waited for, and each is then waited for, fetched and
    scattered in the order of its launch; a put of one program records
    the spans it recorded before launches were chained."""
    chained, plain = build(trunk), build(trunk, one_by_one=True)
    names = {}
    for engine in (chained, plain):
        done = warm_up(engine)
        spans = recorded_put(lambda: mixed_put(engine, done))
        single = recorded_put(lambda: decode_put(engine))
        # (the test keeps every put's latents unread, so the engine's
        # bound on pending device memory forces some, on both orders)
        names[engine] = [[e["name"] for e in group
                          if e["name"] != "serve.latents.force"]
                         for group in (spans, single)]
        if engine is plain:
            continue
        enqueues = [e for e in spans if e["name"] in ENQUEUE_SPANS]
        waits = [e for e in spans if e["name"] == "serve.device_wait"]
        assert [e["name"] for e in enqueues] == [
            "serve.decode_dispatch", "serve.prefill_dispatch"]
        assert len(waits) == 2
        assert max(e["ts"] + e["dur"] for e in enqueues) <= waits[0]["ts"]
        # the first program's results are on the host and scattered
        # before the second is waited for
        between = [e["name"] for e in spans
                   if waits[0]["ts"] < e["ts"] < waits[1]["ts"]]
        assert "serve.scatter" in between
        if not engine.diffusion:
            assert "serve.fetch" in between
    mixed, single = names[chained]
    mixed_plain, single_plain = names[plain]
    assert single == single_plain
    assert single.count("serve.device_wait") == 1
    assert sorted(mixed) == sorted(mixed_plain) and mixed != mixed_plain
    if trunk == "llama":
        assert single == [
            "serve.put.admit", "serve.put.admit", "serve.batch_build",
            "serve.decode_dispatch", "serve.latents.land",
            "serve.device_wait", "serve.fetch", "serve.scatter",
            "serve.scatter"]
        assert mixed == [
            "serve.put.admit", "serve.put.admit", "serve.batch_build",
            "serve.decode_dispatch", "serve.batch_build",
            "serve.prefill_dispatch", "serve.latents.land",
            "serve.device_wait", "serve.fetch", "serve.scatter",
            "serve.latents.land", "serve.device_wait", "serve.fetch",
            "serve.scatter", "serve.scatter"]


@pytest.mark.parametrize("trunk", TRUNKS)
def test_chained_counts_every_program_after_a_puts_first(trunk):
    """``dispatch_stats()["chained"]``: one a program enqueued behind an
    uncollected one of the same put; its enqueue span carries
    ``chained=1``, the first program's span of every put does not."""
    engine = build(trunk)
    assert engine.dispatch_stats()["chained"] == 0
    tracer = get_tracer()
    tracer.clear()
    tracer.configure(enabled=True, xla=False)
    try:
        done = warm_up(engine)
        mixed_put(engine, done)
        decode_put(engine)
        events = [e for e in tracer.events() if e["ph"] == "X"]
    finally:
        tracer.configure(enabled=False)
        tracer.clear()
    puts = [e for e in events if e["name"] == "hds.serve.put"]
    want = 0
    for put in puts:
        mine = sorted((e for e in events if e["name"] in ENQUEUE_SPANS and
                       put["ts"] <= e["ts"] <= put["ts"] + put["dur"]),
                      key=lambda e: e["ts"])
        assert "chained" not in mine[0]["args"]
        assert all(e["args"]["chained"] == 1 for e in mine[1:])
        want += len(mine) - 1
    stats = engine.dispatch_stats()
    # the mixed put at the least; the causal trunks' two prompts of the
    # warm-up fall into two length buckets: two programs as well
    assert stats["chained"] == want >= 1
    assert stats["chained"] == (1 if engine.diffusion else 2)
    assert stats["dispatches"] == sum(
        1 for e in events if e["name"] in ENQUEUE_SPANS)


class Injected(RuntimeError):
    pass


def fail_nth_call(owner, name, nth):
    real, calls = getattr(owner, name), []

    def faulty(*args, **kwargs):
        calls.append(1)
        if len(calls) == nth:
            raise Injected(name)
        return real(*args, **kwargs)
    setattr(owner, name, faulty)


def fail_second_launch(engine):
    fail_nth_call(engine.model, "_enqueue", 2)


def fail_first_collect(engine):
    fail_nth_call(engine, "_fetch", 1)


@pytest.mark.parametrize("fault", [fail_second_launch, fail_first_collect],
                         ids=["second-launch", "first-collect"])
@pytest.mark.parametrize("trunk", TRUNKS)
def test_a_fault_leaves_what_it_left_one_by_one(trunk, fault):
    """An exception out of the second launch or the first collect of a
    two-program put leaves every sequence's counts, its blocks and the
    allocator as the same fault left them with each program collected
    before the next was built, and the engine takes the next put."""
    left = []
    for engine in (build(trunk), build(trunk, one_by_one=True)):
        done = warm_up(engine)
        fault(engine)
        with pytest.raises(Injected):
            mixed_put(engine, done)
        seqs = [engine.state.get_sequence(uid) for uid in (1, 2, 3)]
        left.append(([(s.seen_tokens, s.in_flight_tokens, list(s.blocks),
                       s.state_slot) for s in seqs],
                     engine.state.free_blocks,
                     engine.state.n_tracked_sequences,
                     engine.dispatch_stats()["dispatches"]))
        engine.flush(3)         # as the scheduler's quarantine does
        if engine.diffusion:
            out, _ = engine.put([1], [[5, 6, 7, MASK]],
                                blocks={1: BlockPass(False, False)})
            assert len(out) == 1
        else:
            out, _ = engine.put([1, 2], [[5], [6]])
            assert out.shape[0] == 2 and np.isfinite(out).all()
    chained, plain = left
    assert chained[:3] == plain[:3]
    # the chained put had enqueued the slice when the first collect
    # raised; one by one it never came to it
    assert chained[3] - plain[3] == (1 if fault is fail_first_collect
                                     else 0)
