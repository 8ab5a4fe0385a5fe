"""A forward's K and V into the pools, a block run at a time
(``ops/kv_write.py``): the kernel path (Pallas, interpreted here) and the
jnp row path leave the pools as a plain row-by-row write does, bit for
bit: every slot of ``[start, start + t_len)`` of each lane holds the new
row and every other slot of the pool keeps what it held.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hcache_deepspeed_tpu import ops
from hcache_deepspeed_tpu.ops import kv_write as kw

L, LAYER = 2, 1


def _row_write(pools, new, tables, start, t_len, BS):
    """The plain reference: today's write, a row at a time."""
    pools = [np.array(p) for p in pools]
    for pool, x in zip(pools, new):
        x = np.asarray(x.astype(pool.dtype))
        for b in range(len(start)):
            for t in range(int(t_len[b])):
                p = int(start[b]) + t
                pool[LAYER, :, tables[b, p // BS] * BS + p % BS] = x[b, t]
    return pools


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _far_apart(B, NB, NBLK):
    """Disjoint tables whose consecutive entries lie far apart in the
    pool: the blocks 0, NBLK-1, 1, NBLK-2, ... dealt out a lane at a
    time."""
    ends = np.stack([np.arange(NBLK), NBLK - 1 - np.arange(NBLK)], 1)
    table = ends.reshape(-1)[:B * NB].reshape(B, NB)
    assert len(np.unique(table)) == B * NB
    return table.astype(np.int32)


def case(T, KV, start, t_len, *, earlier=None, blank=None, D=32, BS=64,
         NB=12, dtype="bfloat16", id):
    return pytest.param(dict(T=T, KV=KV, start=start, t_len=t_len,
                             earlier=earlier, blank=blank, D=D, BS=BS,
                             NB=NB, dtype=dtype), id=id)


CASES = [
    case(8, 8, [0], [8], id="T8-on-a-block-edge"),
    case(8, 8, [60], [8], id="T8-across-a-block-edge"),
    case(8, 30, [61], [3], id="T8-30heads-ends-on-the-edge"),
    case(64, 8, [64], [64], id="T64-one-whole-block"),
    case(64, 30, [37], [20], id="T64-30heads-inside-one-block"),
    case(64, 8, [100], [64], earlier=[100], id="T64-after-an-earlier-slice"),
    case(64, 8, [5], [1], id="T64-one-row"),
    case(64, 8, [704], [8], id="T64-frames-past-the-table"),
    case(512, 8, [0], [512], D=128, id="T512-whole-blocks"),
    case(512, 8, [128], [300], D=128, id="T512-short-last-slice"),
    case(512, 30, [549], [512], earlier=[549], D=128, NB=20,
         id="T512-30heads-starts-inside-a-part-filled-block"),
    case(512, 30, [63], [1], id="T512-30heads-one-row-before-an-edge"),
    case(512, 8, [200], [0], id="T512-nothing-to-write"),
    case(64, 8, [64, 31], [64, 17], id="2-lanes"),
    case(8, 8, [0, 60, 127, 500], [8, 8, 0, 3], id="4-lanes-one-padded"),
    case(64, 30, [100, 0, 640, 17], [64, 1, 50, 64], earlier=[100, 0, 0, 17],
         id="4-lanes-30heads"),
    case(512, 8, [0, 512], [512, 100], NB=20, id="2-lanes-T512"),
    case(16, 8, [3, 0], [16, 0], blank=1, id="padded-lane-names-block-0"),
    case(64, 4, [40], [50], BS=16, dtype="float32",
         id="float32-blocks-of-16"),
    case(32, 4, [33, 7], [32, 9], BS=32, dtype="float32",
         id="float32-2-lanes"),
]


@pytest.mark.parametrize("c", CASES)
def test_run_path_leaves_the_pools_as_the_row_write_does(c):
    T, KV, D, BS, NB = c["T"], c["KV"], c["D"], c["BS"], c["NB"]
    start = np.asarray(c["start"], np.int32)
    t_len = np.asarray(c["t_len"], np.int32)
    B = len(start)
    NBLK = B * NB + 3
    rng = np.random.default_rng(T * KV + B)
    dtype = jnp.dtype(c["dtype"])
    tables = _far_apart(B, NB, NBLK)
    if c["blank"] is not None:
        # the padded lane's table is blank: all its entries name block 0,
        # which lane 0 owns and writes
        tables[c["blank"]] = 0
        assert tables[0, 0] == 0
    shape = (L, KV, NBLK * BS, D)
    pools = [jnp.asarray(rng.standard_normal(shape), dtype)
             for _ in range(2)]
    if c["earlier"] is not None:
        # an earlier slice filled positions [0, start): the block the new
        # slice starts in is part filled with rows that must survive
        before = np.asarray(c["earlier"], np.int32)
        old = [jnp.asarray(rng.standard_normal((B, int(before.max()), KV,
                                                D)), jnp.float32)
               for _ in range(2)]
        pools = [jnp.asarray(p) for p in _row_write(
            pools, old, tables, np.zeros_like(before), before, BS)]
    new = [jnp.asarray(rng.standard_normal((B, T, KV, D)), jnp.float32)
           for _ in range(2)]
    want = _row_write(pools, new, tables, start, t_len, BS)
    args = (*pools, *new, jnp.int32(LAYER), jnp.asarray(tables),
            jnp.asarray(start), jnp.asarray(start + t_len))

    runs = jax.jit(lambda *a: kw.pallas_kv_write(*a, BS, interpret=True))
    rows = jax.jit(lambda *a: kw.reference_kv_write(*a, BS))
    for path in (runs, rows):
        for got, ref, held in zip(path(*args), want, pools):
            assert np.array_equal(_bits(got), _bits(ref))
            # and the other layer is the one that came in
            assert np.array_equal(_bits(got[0]), _bits(held[0]))


@pytest.mark.parametrize("T,BS,frames", [(8, 64, 2), (64, 64, 2),
                                         (65, 64, 2), (66, 64, 3),
                                         (512, 64, 9), (2, 16, 2),
                                         (512, 16, 33)])
def test_frames_cover_every_offset_of_a_run(T, BS, frames):
    """``T`` positions from any offset inside a block touch at most
    ``n_frames`` blocks, and some offset touches that many."""
    assert kw.n_frames(T, BS) == frames
    touched = [(off + T - 1) // BS + 1 for off in range(BS)]
    assert max(touched) == frames


@pytest.mark.parametrize("KV,BS,D,itemsize,tile",
                         [(8, 64, 128, 2, 8), (30, 64, 128, 2, 30),
                          (32, 512, 128, 2, 8), (30, 512, 128, 4, 6),
                          (7, 4096, 128, 4, 1)])
def test_head_tile_divides_the_heads_and_fits_vmem(KV, BS, D, itemsize,
                                                   tile):
    got = kw.head_tile(KV, BS, D, itemsize)
    assert got == tile and KV % got == 0
    assert got == 1 or 6 * got * BS * D * itemsize <= kw._VMEM_BUDGET


def test_a_block_that_is_not_whole_tiles_takes_the_row_path_and_says_so():
    """bf16 blocks of 8 rows are half a tile: the dispatcher that runs
    where the platform has the kernel gives way to the rows and counts
    it, as every other kernel's dispatcher does."""
    ops.reset_fallback_report()
    rng = np.random.default_rng(0)
    BS, KV, D = 8, 2, 32
    pools = [jnp.asarray(rng.standard_normal((L, KV, 40 * BS, D)),
                         jnp.bfloat16) for _ in range(2)]
    new = [jnp.asarray(rng.standard_normal((1, 16, KV, D)), jnp.float32)
           for _ in range(2)]
    tables = _far_apart(1, 8, 40)
    start, t_len = np.asarray([5], np.int32), np.asarray([16], np.int32)
    got = kw._dispatch_kv_write(*pools, *new, LAYER, jnp.asarray(tables),
                                jnp.asarray(start),
                                jnp.asarray(start + t_len), BS)
    assert ops.fallback_report() == {"kv_write": {"block_misaligned": 1}}
    ops.reset_fallback_report()
    for g, ref in zip(got, _row_write(pools, new, tables, start, t_len,
                                      BS)):
        assert np.array_equal(_bits(g), _bits(ref))


def test_the_forward_picks_the_write_by_its_shape(monkeypatch):
    """``_scatter_kv`` reads ``k.shape``: one position a lane is the row
    write, more are the run path; no caller chooses."""
    from hcache_deepspeed_tpu.inference import model as model_mod
    from hcache_deepspeed_tpu.inference.model import PagedInferenceModel
    taken = []
    monkeypatch.setattr(model_mod, "kv_write",
                        lambda *a: taken.append("runs") or a[:2])
    monkeypatch.setattr(model_mod, "write_rows",
                        lambda *a: taken.append("rows") or a[:2])
    from hcache_deepspeed_tpu.inference.ragged.lanes import LaneGroup, Lanes
    self = type("M", (), {"block_size": 16})()
    pool = jnp.zeros((1, 2, 64, 8))

    def group(B, T):
        g = LaneGroup(jnp.zeros((B, T), jnp.int32), None, None, None)
        g.positions = jnp.zeros((B, T), jnp.int32)
        return g

    for T in (1, 8, 1, 512):
        k = jnp.zeros((2, T, 2, 8))
        PagedInferenceModel._scatter_kv(self, pool, pool, 0, k, k,
                                        Lanes([group(2, T)]))
    assert taken == ["rows", "runs", "rows", "runs"]
    # a step's two lane groups: each its own write, in the groups' order
    del taken[:]
    k = jnp.zeros((1, 8 + 512, 2, 8))
    PagedInferenceModel._scatter_kv(self, pool, pool, 0, k, k,
                                    Lanes([group(8, 1), group(1, 512)]))
    assert taken == ["rows", "runs"]


def test_the_engine_counts_dispatches_and_rows_by_the_write_they_took():
    """``engine.kv_write_stats()``: a prompt's dispatch is a run
    dispatch of ``tokens x 2 x KV heads x layers`` rows, a decode step a
    row dispatch of one position a lane, a restore replay a run dispatch
    a layer chunk."""
    from hcache_deepspeed_tpu.inference import (InferenceEngineV2,
                                                RaggedInferenceEngineConfig)
    from hcache_deepspeed_tpu.models.llama import (LlamaForCausalLM,
                                                   llama_tiny)
    cfg = llama_tiny(max_positions=128, use_flash=False)
    params = LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)},
        train=False)["params"]
    engine = InferenceEngineV2(cfg, params, config=RaggedInferenceEngineConfig(
        state_manager={"max_tracked_sequences": 8,
                       "max_ragged_batch_size": 128,
                       "max_ragged_sequence_count": 4, "max_context": 128},
        kv_cache={"block_size": 16, "num_blocks": 24,
                  "cache_dtype": "float32"}))
    a_row = 2 * cfg.n_kv_head * cfg.n_layer
    assert engine.kv_write_stats() == {
        "run_dispatches": 0, "run_rows": 0,
        "row_dispatches": 0, "row_rows": 0}
    rng = np.random.default_rng(0)
    _, latents = engine.put([1, 2], [rng.integers(0, cfg.vocab_size, 13),
                                     rng.integers(0, cfg.vocab_size, 5)])
    after_prompts = engine.kv_write_stats()
    assert after_prompts["run_rows"] == 18 * a_row
    assert after_prompts["run_dispatches"] >= 1
    assert after_prompts["row_dispatches"] == 0
    engine.put([1, 2], [[3], [4]])
    after_decode = engine.kv_write_stats()
    assert after_decode["row_dispatches"] == 1
    assert after_decode["row_rows"] == 2 * a_row
    assert after_decode["run_rows"] == after_prompts["run_rows"]
    # a sequence comes back from its latents: the replays write its 13
    # positions into every layer, a chunk of layers a dispatch
    engine.flush(1)
    engine.restore_kv([1], [list(range(13))],
                      [np.asarray(latents[0])[:, :13]])
    after_restore = engine.kv_write_stats()
    assert after_restore["run_rows"] == after_prompts["run_rows"] + \
        13 * a_row
    assert after_restore["run_dispatches"] > after_prompts["run_dispatches"]
    assert after_restore["row_rows"] == after_decode["row_rows"]
