"""RMSNorm / LayerNorm ops.

Reference analog: ``csrc/transformer/inference/csrc/rms_norm.cu`` /
``layer_norm.cu`` and the v2 core ops ``cuda_rms_norm`` — fused residual-add
+ normalisation kernels. On TPU a Pallas kernel fuses the reduction and
scale in VMEM; backward is analytic jnp (XLA fuses it into neighbours).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_name, note_fallback, register_op
from .partitioning import BATCH, SEQ, per_shard


def reference_rms_norm(x, weight, eps=1e-6):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps) *
                w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _block_rows(n, most=256):
    """Rows a grid step: ``most`` or all ``n`` where that divides them,
    else the most sublane tiles (8 rows) that do (the 520 rows of a
    step's decode lanes and prompt slice: 104); ``most`` where nothing
    does, which the caller refuses."""
    if n % min(most, n) == 0:
        return min(most, n)
    return next((rows for rows in range(most - most % 8, 0, -8)
                 if n % rows == 0), most)


def _rms_fwd_pallas(x, weight, eps, interpret):
    orig_shape = x.shape
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    block_rows = _block_rows(n)
    if n % block_rows:
        note_fallback("rms_norm", "rows_not_block_multiple",
                      f"rows={n} block_rows={block_rows}")
        return reference_rms_norm(x, weight, eps)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=interpret,
        **kernel_name("rms_norm"),
    )(x2, weight)
    return out.reshape(orig_shape)


def _rms_fwd_placed(x, weight, eps, interpret):
    # rows are independent: [batch, seq, d] activations keep their
    # batch/sequence split, the feature dim and the scale are whole
    rows = (BATCH, SEQ, None) if x.ndim == 3 else \
        (BATCH,) + (None,) * (x.ndim - 1)
    return per_shard(
        lambda x, w: _rms_fwd_pallas(x, w, eps, interpret), (x, weight),
        in_roles=(rows, (None,)), out_roles=rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms(x, weight, eps, interpret):
    return _rms_fwd_placed(x, weight, eps, interpret)


def _rms_fwd(x, weight, eps, interpret):
    return _rms_fwd_placed(x, weight, eps, interpret), (x, weight)


def _rms_bwd(eps, interpret, res, g):
    x, weight = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = weight.astype(jnp.float32)
    d = x.shape[-1]
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xf * rstd
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    gw = gf * wf
    dx = rstd * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dw.astype(weight.dtype)


_rms.defvjp(_rms_fwd, _rms_bwd)


def pallas_rms_norm(x, weight, eps=1e-6, interpret=None):
    if interpret is None:
        from ..platform import get_platform
        interpret = not get_platform().supports_pallas()
    return _rms(x, weight, eps, interpret)


def rms_norm(x, weight, eps=1e-6):
    from . import get_op
    return get_op("rms_norm")(x, weight, eps=eps)


register_op("rms_norm", reference_rms_norm, pallas_rms_norm)
