"""Ragged paged-attention kernel vs the dense-gather oracle
(reference analog: tests for inference/v2 kernels/ragged_ops/blocked_flash)."""

import numpy as np
import pytest

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
    ref = reference_paged_attention(q, kp, vp, tables, start, kvl, BS)
    pal = pallas_paged_attention(q, kp, vp, tables, start, kvl, BS,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=3e-5)


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
        ref = reference_paged_attention(q, kp, vp, tables, start, kvl, BS)
        pal = pallas_paged_attention(q, kp, vp, tables, start, kvl, BS,
                                     interpret=True)
        np.testing.assert_allclose(
            np.asarray(pal, np.float32), np.asarray(ref, np.float32),
            atol=3e-2)

    def test_garbage_in_dead_table_slots_ignored(self):
        # dead table slots point at blocks full of huge values; the
        # clamped index_map + masking must never read them into the result
        rng = np.random.default_rng(4)
        B, T, Hq, KV, D, BS, NBLK, NB = 1, 1, 2, 2, 32, 8, 16, 8
        q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
        kp = rng.standard_normal((KV, NBLK * BS, D)).astype(np.float32)
        vp = rng.standard_normal((KV, NBLK * BS, D)).astype(np.float32)
        kp[:, BS * 2:], vp[:, BS * 2:] = 1e9, 1e9  # poison all but blocks 0-1
        tables = np.zeros((B, NB), np.int32)
        tables[0, 0], tables[0, 1] = 0, 1
        tables[0, 2:] = 9  # dead slots point at poison
        start = jnp.asarray([11], jnp.int32)
        kvl = jnp.asarray([12], jnp.int32)  # only blocks 0-1 valid
        pal = pallas_paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), tables,
            start, kvl, BS, interpret=True)
        assert np.all(np.isfinite(np.asarray(pal)))
        assert np.max(np.abs(np.asarray(pal))) < 1e3


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
        ref = reference_paged_attention(q, kp, vp, tables, start, kvl, BS)
        pal = pallas_paged_attention(q, kp, vp, tables, start, kvl, BS,
                                     interpret=True, head_tile=head_tile)
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
            pallas_paged_attention(q, kp, vp, tables,
                                   jnp.asarray([0], jnp.int32),
                                   jnp.asarray([1], jnp.int32), BS,
                                   interpret=True, head_tile=3)
