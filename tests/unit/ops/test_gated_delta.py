"""The gated-delta-rule kernels (``ops/gated_delta.py``), run in interpret
mode, against the recurrence itself written here token by token in
numpy: pad tokens, ``beta`` up to 2, state carried across slices, lanes
of unequal length, slots started from zero inside the program."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hcache_deepspeed_tpu import ops
from hcache_deepspeed_tpu.ops import gated_delta as gd

H, DK, DV, L, SLOTS = 4, 8, 16, 2, 5


def recurrence(q, k, v, g, beta, s0):
    """One lane, one head at a time, one token at a time, float64."""
    T, n_head, d_k = q.shape
    out = np.zeros((T, n_head, v.shape[-1]))
    s = s0.astype(np.float64).copy()
    for t in range(T):
        for h in range(n_head):
            s[h] *= np.exp(g[t, h])
            u = beta[t, h] * (v[t, h] - s[h].T @ k[t, h])
            s[h] += np.outer(k[t, h], u)
            out[t, h] = s[h].T @ q[t, h] / np.sqrt(d_k)
    return out, s


def draw(rng, B, T):
    q = rng.normal(size=(B, T, H, DK))
    k = rng.normal(size=(B, T, H, DK))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(B, T, H, DV))
    g = -np.abs(rng.normal(size=(B, T, H))) * 0.5
    beta = 2.0 * rng.uniform(size=(B, T, H))        # up to 2
    beta[:, 0] = 2.0
    return [x.astype(np.float32) for x in (q, k, v, g, beta)]


def expected(args, pool, layer, slots, start, t_len):
    q, k, v, g, beta = args
    pool = pool.copy()
    outs = []
    for b, slot in enumerate(slots):
        s0 = pool[layer, slot] if start[b] else np.zeros_like(pool[0, 0])
        n = t_len[b]
        o, s = recurrence(q[b, :n], k[b, :n], v[b, :n], g[b, :n],
                          beta[b, :n], s0)
        pool[layer, slot] = s
        outs.append(o)
    return outs, pool


def run(fn, args, pool, layer, slots, start, t_len, **kw):
    o, new_pool = fn(*(jnp.asarray(a) for a in args), jnp.asarray(pool),
                     jnp.int32(layer), jnp.asarray(slots, jnp.int32),
                     jnp.asarray(start, jnp.int32),
                     jnp.asarray(t_len, jnp.int32), **kw)
    return np.asarray(o), np.asarray(new_pool)


def masked(fn):
    """``fn`` behind the mask ``gated_delta_rule`` puts on pads."""
    def call(q, k, v, g, beta, pool, layer, slots, start, t_len):
        valid = (jnp.arange(q.shape[1])[None] < t_len[:, None])[..., None]
        return fn(q, k, v, jnp.where(valid, g, 0.0),
                  jnp.where(valid, beta, 0.0), pool, layer, slots, start,
                  interpret=True)
    return call


CASES = {
    # B, T, slots, start, t_len: a short bucket, several chunks of 64
    # with a ragged tail, lanes of unequal length
    "one_chunk": (2, 16, [3, 1], [0, 5], [16, 11]),
    "chunks_and_pads": (3, 128, [0, 4, 2], [7, 0, 64], [128, 70, 1]),
    "blank_lane": (2, 64, [2, SLOTS], [9, 0], [64, 0]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_rule_is_the_recurrence(case):
    B, T, slots, start, t_len = CASES[case]
    rng = np.random.default_rng(len(case))
    args = draw(rng, B, T)
    pool = rng.normal(size=(L, SLOTS + 1, H, DK, DV)).astype(np.float32)
    want_o, want_pool = expected(args, pool, 1, slots, start, t_len)
    o, new_pool = run(masked(gd.pallas_gated_delta_chunk), args, pool, 1,
                      slots, start, t_len)
    for b, n in enumerate(t_len):
        np.testing.assert_allclose(o[b, :n], want_o[b], atol=2e-5)
    # written slots hold the new state; every other row of the pool,
    # the other layer's too, is as it was (pads left their lane's alone)
    np.testing.assert_allclose(new_pool, want_pool, atol=2e-5)


def test_step_kernel_is_the_recurrence_at_one_token():
    B, slots, start, t_len = 4, [3, 1, 0, SLOTS], [0, 5, 7, 0], [1, 1, 1, 0]
    rng = np.random.default_rng(7)
    args = draw(rng, B, 1)
    pool = rng.normal(size=(L, SLOTS + 1, H, DK, DV)).astype(np.float32)
    want_o, want_pool = expected(args, pool, 0, slots, start, t_len)

    def step(q, k, v, g, beta, pool, layer, slots, start, t_len):
        live = (t_len > 0)[:, None, None]
        return gd.pallas_gated_delta_step(
            q, k, v, jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0),
            pool, layer, slots, start, interpret=True)

    o, new_pool = run(step, args, pool, 0, slots, start, t_len)
    for b in range(3):
        np.testing.assert_allclose(o[b, :1], want_o[b], atol=2e-5)
    want_pool[0, SLOTS] = 0.0       # the blank lane "starts" at 0
    np.testing.assert_allclose(new_pool, want_pool, atol=2e-5)


def test_state_is_carried_from_slice_to_slice_and_into_decode():
    """A 150-token sequence as slices of 64, 64 and 22 (padded to 32)
    and then two decode steps equals the recurrence run once over all of
    it."""
    rng = np.random.default_rng(11)
    args = draw(rng, 1, 152)
    pool = rng.normal(size=(L, SLOTS + 1, H, DK, DV)).astype(np.float32)
    want_o, want_pool = expected(args, pool, 1, [2], [0], [152])
    got, at = [], 0
    for T, n in ((64, 64), (64, 64), (32, 22), (1, 1), (1, 1)):
        part = [np.zeros((1, T) + a.shape[2:], np.float32) for a in args]
        for dst, src in zip(part, args):
            dst[:, :n] = src[:, at:at + n]
        o, pool = run(gd.gated_delta_rule, part, pool, 1, [2], [at], [n])
        got.append(o[0, :n])
        at += n
    np.testing.assert_allclose(np.concatenate(got), want_o[0], atol=5e-5)
    np.testing.assert_allclose(pool, want_pool, atol=5e-5)


def test_reference_fallback_is_the_recurrence_too():
    B, T, slots, start, t_len = CASES["chunks_and_pads"]
    rng = np.random.default_rng(3)
    args = draw(rng, B, T)
    pool = rng.normal(size=(L, SLOTS + 1, H, DK, DV)).astype(np.float32)
    want_o, want_pool = expected(args, pool, 0, slots, start, t_len)
    # on the CPU the registry hands out the jnp reference
    o, new_pool = run(gd.gated_delta_rule, args, pool, 0, slots, start,
                      t_len)
    for b, n in enumerate(t_len):
        np.testing.assert_allclose(o[b, :n], want_o[b], atol=2e-5)
    np.testing.assert_allclose(new_pool, want_pool, atol=2e-5)


def test_misaligned_slice_falls_back_and_is_counted():
    ops.reset_fallback_report()
    rng = np.random.default_rng(5)
    args = draw(rng, 1, 12)                  # not a multiple of 8
    pool = np.zeros((L, SLOTS + 1, H, DK, DV), np.float32)
    gd._dispatch_chunk(*(jnp.asarray(a) for a in args), jnp.asarray(pool),
                       jnp.int32(0), jnp.asarray([1]), jnp.asarray([0]))
    assert ops.fallback_report() == {
        "gated_delta_chunk": {"slice_misaligned": 1}}
    ops.reset_fallback_report()


@pytest.mark.parametrize("kernel,T", [("gated_delta_chunk", 128),
                                      ("gated_delta_step", 1)])
def test_lowered_kernels_carry_their_names_and_alias_the_pool(kernel, T):
    """Lowered for the TPU (nothing compiled here): the custom call is
    named through ``ops.kernel_name`` and its pool operand is its pool
    result."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    fn = gd.pallas_gated_delta_step if T == 1 else \
        gd.pallas_gated_delta_chunk
    text = jax.jit(lambda *a: fn(*a, interpret=False)).trace(
        f32(2, T, H, DK), f32(2, T, H, DK), f32(2, T, H, DV),
        f32(2, T, H), f32(2, T, H), f32(L, SLOTS + 1, H, DK, DV), i32(),
        i32(2), i32(2)).lower(lowering_platforms=("tpu",)).as_text()
    assert f'kernel_name = "hds_{kernel}"' in text
    # the metadata is JSON inside an attribute string: quotes are \22
    assert re.search(rf"hds_kernel\W+22:\W+22{kernel}\W", text)
    assert "output_operand_aliases" in text or "operand_index = 8" in text


def test_head_tile_divides_the_heads_and_fits_the_budget():
    assert gd.head_tile(30, 96, 192, 64) in (1, 2, 3, 5, 6, 10, 15, 30)
    assert 30 % gd.head_tile(30, 96, 192, 64) == 0
    assert gd.head_tile(4, 8, 16, 16) == 4
