"""The paced, stratified generator gives every seed the same work."""

import json
import os

import numpy as np
import pytest

from benchmarks import contract
from benchmarks.generators import packed_stream, paced

SEEDS = [0, 1, 2, 3, 17, 1234, 99991, 2 ** 31 - 1, 2 ** 31 + 7, 2 ** 31 + 12345]
CHAT = contract.load_traffic("chat-steady")
RUN_SECONDS = contract.load_benchmark()["run_seconds"]


def _schedule(seed, seconds=RUN_SECONDS):
    return paced.schedule(CHAT, seed, seconds, 32000, 2048)


def _blocks(arrivals, block):
    out = {}
    for a in arrivals:
        out.setdefault(a.index // block, []).append(a)
    return out


REFERENCE = _schedule(SEEDS[0])


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_every_seed_brings_the_same_work(seed):
    got = _schedule(seed)
    assert len(got) == len(REFERENCE)
    win, ref_win = ([a for a in s if a.in_window] for s in (got, REFERENCE))
    assert len(win) == len(ref_win) == int(CHAT["rate"] * RUN_SECONDS)
    assert sum(len(a.prompt) for a in win) == \
        sum(len(a.prompt) for a in ref_win)
    assert sum(a.max_new_tokens for a in win) == \
        sum(a.max_new_tokens for a in ref_win)
    block = CHAT["block"]
    mine, theirs = _blocks(got, block), _blocks(REFERENCE, block)
    assert mine.keys() == theirs.keys()
    for key in mine:
        if len(theirs[key]) < block:
            continue            # the ramp's first block may be cut
        assert sorted(len(a.prompt) for a in mine[key]) == \
            sorted(len(a.prompt) for a in theirs[key])
        assert sorted(a.max_new_tokens for a in mine[key]) == \
            sorted(a.max_new_tokens for a in theirs[key])


@pytest.mark.parametrize("seed", SEEDS[1:4])
def test_seeds_differ_in_order_and_token_ids(seed):
    got = _schedule(seed)
    # the prompt lengths come in one order for every seed (it sets the
    # tail of the time to first token); the outputs in the seed's own
    assert [len(a.prompt) for a in got] == \
        [len(a.prompt) for a in REFERENCE]
    assert [a.max_new_tokens for a in got] != \
        [a.max_new_tokens for a in REFERENCE]
    moved = [abs(i - j) for i, a in enumerate(got)
             for j, b in enumerate(REFERENCE)
             if a.index // CHAT["block"] == b.index // CHAT["block"]
             and a.max_new_tokens == b.max_new_tokens]
    assert max(moved) < CHAT["shuffle"]
    assert got[0].prompt != REFERENCE[0].prompt or \
        len(got[0].prompt) != len(REFERENCE[0].prompt)
    assert [a.due_s for a in got] != [a.due_s for a in REFERENCE]


def test_same_seed_same_schedule():
    assert _schedule(41) == _schedule(41)


def test_window_holds_whole_blocks():
    n = CHAT["rate"] * RUN_SECONDS
    assert n == int(n) and int(n) % CHAT["block"] == 0


def test_arrivals_are_paced_inside_their_slots():
    rate, ramp = CHAT["rate"], CHAT["ramp_s"]
    for a in _schedule(5):
        slot = ramp + a.index / rate
        assert slot < a.due_s < slot + 1.0 / rate
    due = [a.due_s for a in _schedule(5)]
    assert due == sorted(due)
    first_in = next(a for a in _schedule(5) if a.in_window)
    assert first_in.due_s >= ramp


def test_lengths_keep_the_stated_distribution():
    spec = CHAT["prompt_tokens"]
    lengths = paced.quantile_lengths(spec, CHAT["block"])
    assert lengths == sorted(lengths)
    assert min(lengths) >= spec["min"] and max(lengths) <= spec["max"]
    middle = lengths[len(lengths) // 2]
    assert abs(middle - spec["median"]) <= 0.1 * spec["median"]
    for a in REFERENCE:
        assert len(a.prompt) + a.max_new_tokens <= 2048
        assert all(0 <= t < 32000 for t in a.prompt[:8])


def test_prefill_slices_cover_every_prompt():
    slices = set(paced.prefill_slices(CHAT, 512))
    for n in paced.quantile_lengths(CHAT["prompt_tokens"], CHAT["block"]):
        while n > 512:
            assert 512 in slices
            n -= 512
        assert n in slices or n == 0


def test_jitter_must_keep_a_request_in_its_slot():
    with pytest.raises(ValueError):
        paced.schedule(dict(CHAT, jitter=0.5), 1, 10, 32000, 2048)


def test_packed_stream_is_seeded_and_full():
    pre = contract.load_traffic("pretrain-4k")
    small = dict(pre, seq_len=128, global_batch=4)
    a = packed_stream.batches(small, 7, 32000)
    b = packed_stream.batches(small, 7, 32000)
    c = packed_stream.batches(small, 8, 32000)
    first, again, other = next(a), next(b), next(c)
    assert first["input_ids"].shape == (4, 128)
    assert first["input_ids"].dtype == np.int32
    assert np.array_equal(first["input_ids"], again["input_ids"])
    assert not np.array_equal(first["input_ids"], other["input_ids"])
    assert not np.array_equal(first["input_ids"], next(a)["input_ids"])
    assert (first["input_ids"] == small["eod_id"]).any()
