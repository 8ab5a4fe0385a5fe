"""The main path's Pallas kernels carry their names into the compiled
program: lowered for the TPU (on this host, nothing compiled), each
custom call is ``kernel_name = "hds_<kernel>"`` with
``kernel_metadata`` ``{"hds_kernel": "<kernel>"}``, which is what a
device trace's reader finds them by (benchmarks/metrics/
kernel_share.*.json) whatever their operands' shapes."""

import re

import jax
import jax.numpy as jnp
import pytest

from hcache_deepspeed_tpu.ops import kernel_name
from hcache_deepspeed_tpu.ops.flash_attention import pallas_attention
from hcache_deepspeed_tpu.ops.paged_attention import pallas_paged_attention
from hcache_deepspeed_tpu.ops.rms_norm import pallas_rms_norm


def bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def flash_loss(q, k, v):
    return pallas_attention(q, k, v, interpret=False).astype(
        jnp.float32).sum()


CASES = {
    "rms_norm": (
        lambda x, w: pallas_rms_norm(x, w, interpret=False),
        (bf16(2, 256, 512), bf16(512)), ["rms_norm"]),
    "paged_attention": (
        lambda q, k, v, t, s, n: pallas_paged_attention(
            q, k, v, t, s, n, 16, interpret=False),
        (bf16(2, 1, 8, 128), bf16(2, 64 * 16, 128), bf16(2, 64 * 16, 128),
         i32(2, 8), i32(2), i32(2)), ["paged_attention"]),
    "flash_attention": (
        jax.grad(flash_loss, argnums=(0, 1, 2)),
        (bf16(1, 256, 4, 128),) * 3,
        ["flash_attention_fwd", "flash_attention_bwd_dq",
         "flash_attention_bwd_dkv"]),
}


def test_kernel_name_is_name_and_metadata():
    assert kernel_name("paged_attention") == {
        "name": "hds_paged_attention",
        "metadata": {"hds_kernel": "paged_attention"}}


@pytest.mark.parametrize("case", sorted(CASES))
def test_lowered_custom_calls_carry_the_kernels_name(case):
    fn, shapes, kernels = CASES[case]
    text = jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = re.findall(r'kernel_name = "(\w+)"', text)
    assert sorted(set(calls)) == sorted(f"hds_{k}" for k in kernels)
    assert len(calls) == text.count("tpu_custom_call")
    for kernel in kernels:
        assert re.search(rf"hds_kernel\W+22:\W+22{kernel}\W", text), kernel
