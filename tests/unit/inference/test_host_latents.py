"""Coalesced layer-major host latent store (the restore payload
buffer): ndarray-contract parity with the np.concatenate accumulation
it replaces, a buffer sized once (amortized growth as the fallback),
dtype preservation (fp8 capture), drop-in use as a ``restore_kv``
payload — each for ndarray chunks, for pending chunks (the engine's
deferred landing: recorded on ``append``, copied later) and for the
two mixed in one store."""

import jax.numpy as jnp
import numpy as np
import pytest

from hcache_deepspeed_tpu.inference.ragged.latents import (
    LAND_PIECE_BYTES, HostLatentStore, HostLink, LatentProgram)
from hcache_deepspeed_tpu.resilience.faults import (FaultPlan, FaultRule,
                                                    InjectedFault, injected)

KINDS = ["ndarray", "pending", "mixed"]


def chunks(rng, n, L=2, H=4, dtype=np.float32):
    return [rng.standard_normal((L, t, H)).astype(dtype)
            for t in [5] + [1] * (n - 1)]       # prefill then decodes


def pending(chunk, link=None, lanes=2, pad=3):
    """``chunk`` as the engine hands it out: lane 1 of a padded
    ``[L, lanes, t + pad, H]`` program whose copy to the host has been
    started and not waited for."""
    L, t, H = chunk.shape
    program = np.full((L, lanes, t + pad, H), 7, chunk.dtype)
    program[:, 1, :t] = chunk
    return LatentProgram(jnp.asarray(program), link or HostLink()).chunk(1, t)


def as_kind(parts, kind, link=None):
    return [pending(p, link) if kind == "pending" or
            (kind == "mixed" and i % 2 == 0) else p
            for i, p in enumerate(parts)]


@pytest.mark.parametrize("kind", KINDS)
def test_matches_concatenate_accumulation(kind):
    rng = np.random.default_rng(0)
    parts = chunks(rng, 40)
    link = HostLink()
    store = HostLatentStore()
    for p in as_kind(parts, kind, link):
        store.append(p)
    ref = np.concatenate(parts, axis=1)
    # counted at once: nothing has been copied for a pending chunk yet
    assert store.shape == ref.shape
    assert len(store) == ref.shape[1]
    assert store.nbytes == ref.nbytes
    if kind != "ndarray":
        assert store.pending_bytes > 0
        assert link.landed_forced_bytes == link.landed_hidden_bytes == 0
    np.testing.assert_array_equal(np.asarray(store), ref)
    np.testing.assert_array_equal(store.view(), ref)
    assert store.pending_bytes == 0
    if kind == "pending":
        assert link.landed_forced_bytes == link.captured_bytes == ref.nbytes


@pytest.mark.parametrize("kind", KINDS)
def test_pending_chunk_reads_like_its_array(kind):
    """What ``put`` returns is array-like either way: shape, dtype,
    nbytes, ``np.asarray`` and indexing give the chunk's bytes."""
    rng = np.random.default_rng(1)
    ref = chunks(rng, 1)[0]
    got = as_kind([ref], kind)[0]
    assert got.shape == ref.shape and got.dtype == ref.dtype
    assert got.nbytes == ref.nbytes and got.ndim == 3
    np.testing.assert_array_equal(np.asarray(got), ref)
    np.testing.assert_array_equal(got[:, :2], ref[:, :2])
    np.testing.assert_array_equal(
        np.concatenate([got, ref], axis=1),
        np.concatenate([ref, ref], axis=1))


def test_layer_major_contiguous_buffer():
    """The backing buffer is ONE C-contiguous [L, cap, H] array — a
    per-layer-chunk slice walks memory in shipping order."""
    store = HostLatentStore(np.ones((3, 4, 8), np.float32))
    store.append(np.ones((3, 1, 8), np.float32))
    assert store._buf.flags["C_CONTIGUOUS"]
    v = store.view()
    assert v.base is store._buf and v.shape == (3, 5, 8)


@pytest.mark.parametrize("kind", KINDS)
def test_growth_is_amortized_doubling(kind):
    rng = np.random.default_rng(2)
    parts = [rng.standard_normal((2, t, 4)).astype(np.float32)
             for t in [3] + [1] * 200]
    store = HostLatentStore()
    caps = set()
    for p in as_kind(parts, kind):
        store.append(p)
        caps.add(store._buf.shape[1])
    # 203 tokens via doubling from 16: few distinct capacities, not 200
    assert len(caps) <= 6 and len(store) == 203
    # pending chunks recorded before a growth land in the grown buffer
    np.testing.assert_array_equal(store.view(),
                                  np.concatenate(parts, axis=1))


@pytest.mark.parametrize("kind", KINDS)
def test_a_store_with_capacity_never_reallocates(kind):
    """Sized once from what its owner can ever cache
    (``Request.absorb_latents``: prompt + max_new_tokens)."""
    rng = np.random.default_rng(3)
    parts = [rng.standard_normal((2, 1, 4)).astype(np.float32)
             for _ in range(2048)]
    store = HostLatentStore(capacity=2048)
    store.append(as_kind(parts[:1], kind)[0])
    buf = store._buf
    assert buf.shape == (2, 2048, 4)
    link = HostLink()
    for p in as_kind(parts[1:], kind, link):
        store.append(p)
        assert store._buf is buf
    np.testing.assert_array_equal(store.view(),
                                  np.concatenate(parts, axis=1))
    assert store._buf is buf and len(store) == 2048


@pytest.mark.parametrize("kind", KINDS)
def test_dtype_preserved_and_mismatch_rejected(kind):
    dt = np.dtype(jnp.float8_e4m3fn)
    first, second, wrong_l = as_kind(
        [np.zeros((2, 2, 4), dt), np.zeros((2, 1, 4), dt),
         np.zeros((3, 1, 4), dt)], "ndarray" if kind == "ndarray"
        else "pending")
    store = HostLatentStore(first)
    store.append(second)
    assert store.dtype == dt and store.shape == (2, 3, 4)
    with pytest.raises(ValueError, match="does not match"):
        store.append(wrong_l)                       # wrong L
    with pytest.raises(ValueError, match="L, t, H"):
        store.append(np.zeros((4,), dt))
    with pytest.raises(ValueError, match="no view"):
        HostLatentStore().view()
    assert store.view().dtype == dt and store.shape == (2, 3, 4)


def test_landing_goes_piece_by_piece_and_counts_hidden_bytes():
    """``land`` copies one piece a call — whole layers while they fit
    ``LAND_PIECE_BYTES``, else a run of one layer's tokens — so the
    engine can look at the program in flight in between."""
    rng = np.random.default_rng(4)
    H = 256
    tokens = 3 * LAND_PIECE_BYTES // (2 * H * 4)     # 1.5 pieces a layer
    big = rng.standard_normal((2, tokens, H)).astype(np.float32)
    small = rng.standard_normal((2, 1, H)).astype(np.float32)
    link = HostLink()
    store = HostLatentStore(capacity=tokens + 1)
    store.append(pending(big, link))
    store.append(pending(small, link))
    big_part, small_part = store._pending
    sizes = []
    while not big_part.landed:
        sizes.append(store.land(big_part, hidden=True))
    assert len(sizes) == 4 and max(sizes) <= LAND_PIECE_BYTES
    assert sum(sizes) == big.nbytes
    assert store.land(small_part, hidden=True) == small.nbytes  # 1 piece
    assert not store._pending
    assert link.landed_hidden_bytes == big.nbytes + small.nbytes
    assert link.landed_forced_bytes == 0
    np.testing.assert_array_equal(store.view(),
                                  np.concatenate([big, small], axis=1))


def test_a_failed_landing_truncates_to_the_last_landed_token():
    """A dead buffer under a pending chunk: the store keeps what had
    landed before it, drops the rest, and stays shorter than its
    owner's token count (the scheduler's partial-payload branches)."""
    rng = np.random.default_rng(5)
    parts = chunks(rng, 4)
    store = HostLatentStore(capacity=16)
    store.append(parts[0])
    store.append(pending(parts[1]))
    dead = pending(parts[2])
    store.append(dead)
    store.append(parts[3])
    assert len(store) == 8

    def boom():
        raise RuntimeError("Array has been deleted.")

    dead.parts[0].program._device = type("Dead", (), {
        "__array__": lambda self, *a, **k: boom()})()
    with pytest.raises(RuntimeError, match="deleted"):
        store.view()
    assert len(store) == 6 and store.pending_bytes == 0
    np.testing.assert_array_equal(
        store.view(), np.concatenate(parts[:2], axis=1))
    store.append(parts[3])                      # still one short
    assert len(store) == 7


@pytest.mark.parametrize("kind", KINDS)
def test_host_latents_fault_leaves_the_span_untouched(kind):
    """The ``host.latents`` site fires in ``append`` before any state
    changes, whatever the chunk is."""
    rng = np.random.default_rng(6)
    parts = as_kind(chunks(rng, 3), kind)
    store = HostLatentStore(parts[0])
    buf, pend = store._buf, len(store._pending)
    with injected(FaultPlan(rules=[FaultRule("host.latents",
                                             at_hits=(1,))])):
        with pytest.raises(InjectedFault):
            store.append(parts[1])
        assert len(store) == 5 and store._buf is buf
        assert len(store._pending) == pend
        store.append(parts[2])
    assert len(store) == 6


def test_the_link_learns_from_the_copies_it_waited_for():
    """No way to ask jax whether a copy has arrived, so the engine asks
    a model: cost per byte from a wait (exact), eased when a copy had
    arrived (a bound), later programs behind earlier ones."""

    class Program:
        nbytes = 10 * LAND_PIECE_BYTES
        start_at = due_at = None

    link = HostLink()
    first, second = Program(), Program()
    link.enqueue(first, now=100.0)
    assert link.due(first, 100.0)               # nothing learned yet
    link.arrived(first, asked_at=100.0, got_at=100.5)    # waited 0.5 s
    assert link.seconds_per_byte == pytest.approx(0.5 / Program.nbytes)
    link.enqueue(second, now=100.6)
    third = Program()
    link.enqueue(third, now=100.7)              # behind the second
    assert not link.due(second, 101.0) and link.due(second, 101.11)
    assert not link.due(third, 101.5) and link.due(third, 101.61)
    link.arrived(second, asked_at=101.2, got_at=101.2)   # had arrived
    assert link.seconds_per_byte == pytest.approx(
        0.95 * 0.5 / Program.nbytes)
    link.arrived(third, asked_at=101.7, got_at=101.9)    # 0.3 s late
    assert link.seconds_per_byte == pytest.approx(0.8 / Program.nbytes)
    fourth = Program()
    link.enqueue(fourth, now=101.9)             # what is behind moves too
    assert not link.due(fourth, 102.6) and link.due(fourth, 102.71)


def test_restore_payload_contract_with_sim_engine():
    """np.asarray(store) satisfies the [L, T, H] restore contract the
    engines check (shape[1] vs token count)."""
    from hcache_deepspeed_tpu.serving import SimulatedEngine
    eng = SimulatedEngine()
    tokens = list(range(10))
    _, lat = eng.put([7], [tokens])
    store = HostLatentStore(lat[0])
    eng.flush(7)
    eng.restore_kv([7], [tokens], [store])
    assert eng.state.get_sequence(7).seen_tokens == len(tokens)
