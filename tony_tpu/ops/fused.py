"""Small fused pallas kernels: RMSNorm and residual-add-norm.

HBM-bandwidth ops the XLA fuser usually handles; kept as pallas kernels
both as the pattern reference for this repo and for the cases XLA splits
(norm feeding multiple consumers). ``interpret=None`` auto-selects:
compiled on TPU, the pallas interpreter elsewhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tony_tpu.ops.platform import interpret_mode as _interp


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps) * scale_ref[:].astype(jnp.float32)
                ).astype(o_ref.dtype)


def _add_rmsnorm_kernel(x_ref, res_ref, scale_ref, o_ref, sum_ref, *, eps: float):
    s = x_ref[:].astype(jnp.float32) + res_ref[:].astype(jnp.float32)
    sum_ref[:] = s.astype(sum_ref.dtype)
    var = jnp.mean(s * s, axis=-1, keepdims=True)
    o_ref[:] = (s * jax.lax.rsqrt(var + eps) * scale_ref[:].astype(jnp.float32)
                ).astype(o_ref.dtype)


def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool | None = None):
    """x: [..., D], scale: [D]."""
    shape = x.shape
    d = shape[-1]
    rows = math.prod(shape[:-1])
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    if rows % br:
        br = rows  # fall back to one block for awkward sizes
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        interpret=_interp() if interpret is None else interpret,
    )(x2, scale)
    return out.reshape(shape)


def add_rmsnorm(x, residual, scale, *, eps: float = 1e-6,
                block_rows: int = 256, interpret: bool | None = None):
    """Fused (x + residual) -> (normed, sum). Returns the residual stream sum
    too, as transformer blocks need it."""
    shape = x.shape
    d = shape[-1]
    rows = math.prod(shape[:-1])
    x2 = x.reshape(rows, d)
    r2 = residual.reshape(rows, d)
    br = min(block_rows, rows)
    if rows % br:
        br = rows
    normed, summed = pl.pallas_call(
        functools.partial(_add_rmsnorm_kernel, eps=eps),
        out_shape=(
            jax.ShapeDtypeStruct((rows, d), x.dtype),
            jax.ShapeDtypeStruct((rows, d), x.dtype),
        ),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=(
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ),
        interpret=_interp() if interpret is None else interpret,
    )(x2, r2, scale)
    return normed.reshape(shape), summed.reshape(shape)
