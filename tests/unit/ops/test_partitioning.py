"""Pallas kernels inside partitioned programs (``ops/partitioning.py``).

On the chip Mosaic refuses a kernel wherever XLA would still partition
it: under ``jit`` over several devices and inside a ``shard_map`` that
leaves any mesh axis automatic. ``per_shard`` wraps the kernel in a
``shard_map`` over the axes still automatic, using the framework's
layout convention; the engine puts its mesh in scope with
``kernel_mesh``. The refusal itself only shows on a TPU (chip_smoke.py's
four-chip phases); here the placement is checked on the virtual CPU
mesh in interpret mode: same values, the convention's shardings kept,
and nothing gathered.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from hcache_deepspeed_tpu.ops.flash_attention import (pallas_attention,
                                                      reference_attention)
from hcache_deepspeed_tpu.ops.partitioning import (BATCH, HEADS,
                                                   kernel_mesh, per_shard)
from hcache_deepspeed_tpu.ops.rms_norm import (pallas_rms_norm,
                                               reference_rms_norm)
from hcache_deepspeed_tpu.parallel.topology import (MeshTopology,
                                                    TopologySpec)


@pytest.fixture
def topo(eight_devices):
    return MeshTopology(TopologySpec(data=4, tensor=2),
                        devices=eight_devices)


def test_model_kernels_keep_the_engine_layout_under_jit(topo):
    """RMSNorm and GQA flash attention, forward and backward, under
    plain ``jit`` on a data=4 x tensor=2 mesh: values match the
    references, batch and heads stay split, no all-gather."""
    mesh = topo.mesh
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 128, 64)), jnp.float32)
    w = jnp.full((64,), 1.5, jnp.float32)
    q = jnp.asarray(rng.standard_normal((8, 128, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((8, 128, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((8, 128, 2, 16)), jnp.float32)

    def loss(x, w, q, k, v):
        with kernel_mesh(mesh):
            y = pallas_rms_norm(x, w, 1e-5, interpret=True)
            a = pallas_attention(q, k, v, causal=True, interpret=True,
                                 block_q=128, block_k=128)
        return (y ** 2).sum() + (a ** 2).sum()

    def ref(x, w, q, k, v):
        return (reference_rms_norm(x, w, 1e-5) ** 2).sum() + \
            (reference_attention(q, k, v, causal=True) ** 2).sum()

    heads = NamedSharding(mesh, P("data", None, "tensor", None))
    placed = (jax.device_put(x, topo.batch_sharding()), w,
              jax.device_put(q, heads), jax.device_put(k, heads),
              jax.device_put(v, heads))
    fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
    val, grads = fn(*placed)
    rval, rgrads = jax.value_and_grad(ref, argnums=(0, 1, 2, 3, 4))(
        x, w, q, k, v)
    np.testing.assert_allclose(float(val), float(rval), rtol=1e-5)
    for got, want in zip(grads, rgrads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=1e-4)
    assert grads[0].sharding.spec[0] == "data"
    assert tuple(grads[2].sharding.spec)[:3] == ("data", None, "tensor")
    assert "all-gather" not in fn.lower(*placed).compile().as_text()


def test_roles_share_axes_only_where_every_dimension_divides(topo):
    """A role's axes are chosen once for all arrays that name it: three
    KV heads do not split two ways, so the query heads stay whole too
    (splitting one side alone would mis-pair the GQA groups)."""
    seen = {}

    def kernel(q, k):
        seen["q"], seen["k"] = q.shape, k.shape
        return q

    q = jnp.zeros((8, 4, 6, 16))
    k = jnp.zeros((8, 4, 3, 16))
    roles = (BATCH, None, HEADS, None)

    def run(q, k):
        with kernel_mesh(topo.mesh):
            return per_shard(kernel, (q, k), in_roles=(roles, roles),
                             out_roles=roles)

    rep = NamedSharding(topo.mesh, P())
    jax.jit(run)(jax.device_put(q, rep), jax.device_put(k, rep))
    assert seen == {"q": (2, 4, 6, 16), "k": (2, 4, 3, 16)}


def test_roleless_kernel_runs_on_the_local_block_of_a_manual_body(topo):
    """The quantized wire's kernels run inside a ``shard_map`` that is
    manual over ``data`` only: ``per_shard`` makes the remaining axes
    manual around the kernel and leaves the local block alone."""
    from hcache_deepspeed_tpu.ops.quantizer import (pallas_quantize,
                                                    reference_quantize)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (8, 64, 256)), jnp.float32)

    def body(local):
        assert local.shape == (2, 64, 256)
        q, s, _, _ = pallas_quantize(local, group_size=256,
                                     interpret=True)
        return q.astype(jnp.float32) * s

    got = jax.jit(jax.shard_map(
        body, mesh=topo.mesh, in_specs=P("data"), out_specs=P("data"),
        axis_names={"data"}, check_vma=False))(x)
    q, s, _, _ = reference_quantize(x, 256, 8)
    np.testing.assert_allclose(
        np.asarray(got).reshape(-1),
        np.asarray(q.astype(jnp.float32) * s).reshape(-1), atol=1e-6)


def test_direct_call_where_nothing_is_left_to_partition(topo):
    """No mesh in scope, one device, or every axis manual already: the
    kernel is called as is (one-chip programs do not change)."""
    calls = []

    def kernel(x):
        calls.append(jax.sharding.get_abstract_mesh().manual_axes)
        return x * 2

    x = jnp.ones((8, 4))
    per_shard(kernel, (x,))                               # no mesh
    one = MeshTopology(TopologySpec(data=1),
                       devices=topo.mesh.devices.flat[:1]).mesh
    assert isinstance(kernel_mesh(one), type(kernel_mesh(None)))
    jax.jit(jax.shard_map(                                # all manual
        lambda x: per_shard(kernel, (x,)), mesh=topo.mesh,
        in_specs=P("data"), out_specs=P("data"), check_vma=False))(x)
    assert calls[0] == () and set(calls[-1]) == set(topo.mesh.axis_names)
    assert len(calls) == 2      # eval_shape never ran: no wrapping
