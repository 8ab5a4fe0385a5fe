"""Ragged paged-attention kernel vs the dense-gather oracle
(reference analog: tests for inference/v2 kernels/ragged_ops/blocked_flash)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hcache_deepspeed_tpu.ops.paged_attention import (
    pallas_paged_attention, reference_paged_attention)


def _case(B, T, Hq, KV, D, BS, NBLK, NB, starts, lens, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((KV, NBLK * BS, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((KV, NBLK * BS, D)), jnp.float32)
    perm = rng.permutation(NBLK)
    tables = perm[:B * NB].reshape(B, NB).astype(np.int32)
    start = jnp.asarray(starts, jnp.int32)
    kvl = jnp.asarray(lens, jnp.int32)
    ref = reference_paged_attention(q, kp[None], vp[None], 0, tables,
                                    start, kvl, BS)
    pal = pallas_paged_attention(q, kp[None], vp[None], 0, tables, start,
                                 kvl, BS, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=3e-5)


def _dense_oracle(q, kp, vp, tables, start, kvl, BS):
    """Plain numpy attention, one sequence and one head at a time, over
    the rows of a ONE-layer [KV, P, D] pool that the table names."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    B, T, Hq, D = q.shape
    G = Hq // kp.shape[0]
    out = np.zeros_like(q)
    for b in range(B):
        n = int(kvl[b])
        rows = [int(tables[b, p // BS]) * BS + p % BS for p in range(n)]
        for h in range(Hq):
            k, v = kp[h // G, rows], vp[h // G, rows]       # [n, D]
            for t in range(T):
                seen = min(int(start[b]) + t + 1, n)
                s = k[:seen] @ q[b, t, h] / np.sqrt(D)
                w = np.exp(s - s.max())
                out[b, t, h] = (w / w.sum()) @ v[:seen]
    return out


#: (B, T, Hq, KV, D, BS, NBLK, NB, starts, lens): a ragged decode batch
#: and a continuation prefill chunk
_POOL_SHAPES = {
    "decode": (3, 1, 8, 2, 64, 16, 32, 8, [0, 40, 99], [1, 41, 100]),
    "prefill": (1, 16, 4, 2, 32, 8, 32, 8, [24], [40]),
}


class TestLayerOfAWholePool:
    """The kernel and the reference read layer ``layer`` of the whole
    [L, KV, P, D] pool: the same sequences sit in one layer of a 4-layer
    pool whose other layers hold other data, and the answer is that of
    the layer alone."""

    @pytest.mark.parametrize("layer", [0, 2, 3])
    @pytest.mark.parametrize("shape", sorted(_POOL_SHAPES))
    def test_pallas_reference_and_dense_oracle_agree(self, shape, layer):
        B, T, Hq, KV, D, BS, NBLK, NB, starts, lens = _POOL_SHAPES[shape]
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((4, KV, NBLK * BS, D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((4, KV, NBLK * BS, D)),
                         jnp.float32)
        tables = rng.permutation(NBLK)[:B * NB].reshape(B, NB).astype(
            np.int32)
        start = jnp.asarray(starts, jnp.int32)
        kvl = jnp.asarray(lens, jnp.int32)
        want = _dense_oracle(q, kp[layer], vp[layer], tables, starts, lens,
                             BS)
        # ``layer`` is traced, as it is under the model's layer scan
        ref = jax.jit(lambda l: reference_paged_attention(
            q, kp, vp, l, tables, start, kvl, BS))(layer)
        pal = jax.jit(lambda l: pallas_paged_attention(
            q, kp, vp, l, tables, start, kvl, BS, interpret=True))(layer)
        np.testing.assert_allclose(np.asarray(ref), want, atol=3e-5)
        np.testing.assert_allclose(np.asarray(pal), want, atol=3e-5)
        # and another layer's data gives another answer
        other = reference_paged_attention(q, kp, vp, (layer + 1) % 4,
                                          tables, start, kvl, BS)
        assert np.max(np.abs(np.asarray(other) - want)) > 1e-2


class TestPagedAttentionParity:
    def test_ragged_decode_batch(self):
        # T=1 rows, wildly different context lengths in one batch
        _case(4, 1, 8, 2, 64, 16, 64, 8,
              starts=[0, 5, 33, 100], lens=[1, 6, 34, 101])

    def test_prefill_from_scratch(self):
        _case(1, 32, 8, 8, 64, 16, 16, 4, starts=[0], lens=[32])

    def test_chunked_prefill_continuation(self):
        # start > 0: continuation chunk attends to earlier cache blocks
        _case(1, 16, 4, 2, 32, 8, 32, 8, starts=[24], lens=[40])

    def test_prefill_walks_row_tiles(self):
        # T*G = 1152 rows per kv head: three 512-row tiles (the last one
        # padded), continuation chunk so the causal frontier differs
        # per tile
        _case(1, 288, 8, 2, 32, 16, 40, 24, starts=[70], lens=[358])

    def test_mha_no_gqa(self):
        _case(2, 1, 4, 4, 128, 16, 32, 4, starts=[7, 0], lens=[8, 1])

    def test_single_token_context(self):
        _case(1, 1, 2, 2, 32, 8, 8, 2, starts=[0], lens=[1])

    def test_bf16(self):
        rng = np.random.default_rng(3)
        B, T, Hq, KV, D, BS, NBLK, NB = 2, 1, 4, 2, 64, 16, 16, 4
        q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.bfloat16)
        kp = jnp.asarray(rng.standard_normal((KV, NBLK * BS, D)),
                         jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal((KV, NBLK * BS, D)),
                         jnp.bfloat16)
        tables = rng.permutation(NBLK)[:B * NB].reshape(B, NB).astype(
            np.int32)
        start = jnp.asarray([3, 17], jnp.int32)
        kvl = jnp.asarray([4, 18], jnp.int32)
        ref = reference_paged_attention(q, kp[None], vp[None], 0, tables,
                                        start, kvl, BS)
        pal = pallas_paged_attention(q, kp[None], vp[None], 0, tables,
                                     start, kvl, BS, interpret=True)
        np.testing.assert_allclose(
            np.asarray(pal, np.float32), np.asarray(ref, np.float32),
            atol=3e-2)

    def test_garbage_in_dead_table_slots_ignored(self):
        # past each lane's own count the table names blocks that are not
        # in the pool at all, and the pool's last block is NaN: the walk
        # must end at the lane's count, never forming their address (the
        # TPU interpreter raises on a read out of bounds, starts every
        # buffer as NaN and reports two copies that race)
        from jax.experimental.pallas import tpu as pltpu
        rng = np.random.default_rng(4)
        B, T, Hq, KV, D, BS, NBLK, NB = 4, 1, 8, 2, 32, 8, 64, 12
        q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
        kp = rng.standard_normal((1, KV, NBLK * BS, D)).astype(np.float32)
        vp = rng.standard_normal((1, KV, NBLK * BS, D)).astype(np.float32)
        kp[:, :, (NBLK - 1) * BS:] = np.nan
        vp[:, :, (NBLK - 1) * BS:] = np.nan
        lens = np.array([0, 3, 5 * BS + 1, NB * BS])
        dead = np.arange(NB)[None] >= -(-lens // BS)[:, None]
        live = rng.permutation(NBLK - 1)[:B * NB].reshape(B, NB)
        # half the dead slots past the pool, half on the NaN block
        poison = np.where(np.arange(NB)[None] % 2, NBLK + 100, NBLK - 1)
        tables = np.where(dead, poison, live).astype(np.int32)
        start = jnp.asarray(np.maximum(lens - 1, 0), jnp.int32)
        kvl = jnp.asarray(lens, jnp.int32)
        want = reference_paged_attention(
            q, jnp.asarray(kp), jnp.asarray(vp), 0,
            np.where(dead, 0, live).astype(np.int32), start, kvl, BS)
        for interpret in (True, pltpu.InterpretParams(
                detect_races=True, uninitialized_memory="nan",
                out_of_bounds_reads="raise")):
            pal = np.asarray(pallas_paged_attention(
                q, jnp.asarray(kp), jnp.asarray(vp), 0, tables, start, kvl,
                BS, interpret=interpret))
            assert np.all(pal[0] == 0.0)        # the lane with no context
            np.testing.assert_allclose(pal[1:], np.asarray(want)[1:],
                                       atol=3e-5)
        from jax._src.pallas.mosaic.interpret import interpret_pallas_call
        assert not interpret_pallas_call.races.races_found


# ------------------------------------------------------------------ #
# ragged lanes in one dispatch: every lane walks its own blocks
# ------------------------------------------------------------------ #
_BS, _NB = 64, 18                          # a table of 1,152 positions


def _ragged(shape, G):
    """``(T, mask_block, start, kv_len)`` of one dispatch."""
    whole = _NB * _BS
    if shape == "decode":       # none, one, around a block's edge, all
        lens = np.array([0, 1, _BS - 1, _BS, _BS + 1, whole])
        return 1, 1, np.maximum(lens - 1, 0), lens
    if shape == "block":        # a block of 4: inside a cache block, on
        # its edge (the new block holds these 4 alone), ending on it,
        # padding, the table's last positions
        start = np.array([_BS + 8, 2 * _BS, _BS - 4, 0, whole - 4])
        return 4, 4, start, np.where(start == 0, 0, start + 4)
    # "tiles": 1,024 rows a kv head, two row tiles with frontiers of
    # their own; the second lane's slice is half padding
    T = 1024 // G
    return T, 1, np.array([70, 0]), np.array([70 + T, T // 2 + 3])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("G", [1, 4, 8])
@pytest.mark.parametrize("shape", ["decode", "block", "tiles"])
def test_ragged_lanes_walk_their_own_blocks(shape, G, dtype):
    T, MB, start, lens = _ragged(shape, G)
    B, KV, D, NBLK = len(lens), 2, 32, 48
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((B, T, KV * G, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((2, KV, NBLK * _BS, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((2, KV, NBLK * _BS, D)), dtype)
    # every lane's blocks its own, scattered over the pool
    tables = np.stack([rng.permutation(NBLK)[:_NB]
                       for _ in range(B)]).astype(np.int32)
    args = (1, tables, jnp.asarray(start, jnp.int32),
            jnp.asarray(lens, jnp.int32), _BS)
    ref = np.asarray(reference_paged_attention(q, kp, vp, *args,
                                               mask_block=MB), np.float32)
    pal = np.asarray(pallas_paged_attention(
        q, kp, vp, *args, interpret=True, mask_block=MB), np.float32)
    live = lens > 0
    assert live.sum() >= B - 1
    np.testing.assert_allclose(
        pal[live], ref[live], atol=3e-5 if dtype == "float32" else 3e-2)
    assert np.all(pal[~live] == 0.0)       # a padded lane writes zeros


class TestHeadTiling:
    """KVT kv heads per grid step (the decode-shape grid-count fix) must
    be invisible to results for every tile size."""

    @pytest.mark.parametrize("head_tile", [1, 2, 4, 0])   # 0 = adaptive
    def test_tile_sizes_agree(self, head_tile):
        rng = np.random.default_rng(5)
        B, T, Hq, KV, D, BS, NBLK, NB = 3, 1, 8, 4, 64, 16, 32, 8
        q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((KV, NBLK * BS, D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((KV, NBLK * BS, D)),
                         jnp.float32)
        tables = rng.permutation(NBLK)[:B * NB].reshape(B, NB).astype(
            np.int32)
        start = jnp.asarray([0, 40, 99], jnp.int32)
        kvl = jnp.asarray([1, 41, 100], jnp.int32)
        ref = reference_paged_attention(q, kp[None], vp[None], 0, tables,
                                        start, kvl, BS)
        pal = pallas_paged_attention(q, kp[None], vp[None], 0, tables,
                                     start, kvl, BS, interpret=True,
                                     head_tile=head_tile)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   atol=3e-5)

    def test_pick_tiles(self):
        from hcache_deepspeed_tpu.ops.paged_attention import (
            _MAX_ROW_TILE, _VMEM_BUDGET, _step_bytes, pick_tiles)
        # decode shapes: one 8-row tile, every head in one step
        assert pick_tiles(32, 1, 64, 64, 2) == (8, 8, 32)
        # the head tile must divide KV
        assert 24 % pick_tiles(24, 8, 64, 64, 2)[2] == 0
        # a 2048-token GQA 32/8 x 128 prefill: rows walk in tiles, the
        # footprint is that of one tile however long the dispatch
        tq, tgp, kvt = pick_tiles(8, 2048 * 4, 128, 64, 2)
        assert tq == _MAX_ROW_TILE and tgp == 2048 * 4 and 8 % kvt == 0
        assert kvt * _step_bytes(tq, 128, 64, 2) <= _VMEM_BUDGET
        # ragged row counts pad up to whole tiles
        assert pick_tiles(2, 515, 64, 16, 4)[:2] == (512, 1024)

    def test_pick_blocks(self):
        """Blocks a loop iteration: from the shapes under the budget, at
        the tiles ``pick_tiles`` chose; the serve cells' shapes."""
        from hcache_deepspeed_tpu.ops.paged_attention import (
            _MAX_COL_TILE, _VMEM_BUDGET, _step_bytes, pick_blocks,
            pick_tiles)
        # (KV, rows a head, D, BS, NB) -> blocks
        for (KV, TG, D, BS, NB), want in {
                (4, 4 * 8, 128, 64, 36): 8,        # sparse block pass
                (8, 1 * 4, 128, 64, 32): 8,        # Mistral decode
                (8, 512 * 4, 128, 64, 32): 4,      # Mistral slice
                (30, 1, 128, 64, 128): 4,          # hybrid decode
                (30, 512, 128, 64, 128): 1,        # hybrid slice
                (2, 1, 128, 16, 4): 4,             # a table of 4 slots
                (2, 1, 128, 1024, 8): 1}.items():  # a block a tile
            TQ, _, KVT = pick_tiles(KV, TG, D, BS, 2)
            P = pick_blocks(KVT, TQ, D, BS, NB, 2)
            assert P == want, (KV, TG, D, BS, NB, P)
            assert P <= NB and (P == 1 or P * BS <= _MAX_COL_TILE)
            assert KVT * _step_bytes(TQ, D, P * BS, 2) <= _VMEM_BUDGET

    def test_over_budget_layout_is_a_typed_error(self):
        """One head at the row tile already over the VMEM budget is
        refused by name with the numbers, never handed to the compiler
        (the old picker clamped it to one head and Mosaic died with
        RESOURCE_EXHAUSTED)."""
        from hcache_deepspeed_tpu.ops.paged_attention import (
            PagedAttentionBudgetError, pick_tiles)
        with pytest.raises(PagedAttentionBudgetError) as exc:
            pick_tiles(8, 2048 * 4, 256, 2048, 2)
        msg = str(exc.value)
        assert "512 query rows" in msg and "block_size=2048" in msg
        assert "bytes" in msg and "budget" in msg

    def test_non_divisor_head_tile_rejected(self):
        rng = np.random.default_rng(6)
        B, T, Hq, KV, D, BS, NBLK, NB = 1, 1, 4, 4, 32, 8, 8, 2
        q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((KV, NBLK * BS, D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((KV, NBLK * BS, D)),
                         jnp.float32)
        tables = np.zeros((B, NB), np.int32)
        with pytest.raises(ValueError, match="head_tile"):
            pallas_paged_attention(q, kp[None], vp[None], 0, tables,
                                   jnp.asarray([0], jnp.int32),
                                   jnp.asarray([1], jnp.int32), BS,
                                   interpret=True, head_tile=3)
