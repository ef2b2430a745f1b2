"""Weight-only int8 quantization + pallas dequant-matmul kernel.

Decode roofline (docs/PERF.md): generation is HBM-bound — every token
re-reads the weights — so storing kernels as int8 with per-output-channel
scales HALVES the bytes per decode step vs bf16. The pallas kernel
dequantizes tiles in VMEM right at the MXU: HBM traffic stays int8, the
matmul runs at full precision, and the scale multiply fuses into the
output epilogue. A plain ``int8.astype(bf16) * scale`` in jax would be
hoisted out of the decode scan as a loop invariant and materialize full
bf16 weights — exactly the traffic the format exists to avoid.

Quantization is symmetric per OUTPUT channel (absmax / 127), the
standard weight-only recipe: activations stay bf16/fp32, so there is no
calibration step and no accuracy cliff for serving-sized models.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tony_tpu.ops.platform import interpret_mode as _interp


def quantize_q8(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """w: [in, out] float -> (w_q int8 [in, out], scale fp32 [out]).
    Symmetric absmax per output channel; dequant is ``w_q * scale``."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127) \
        .astype(jnp.int8)
    return w_q, scale


def dequantize_q8(w_q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return w_q.astype(jnp.float32) * scale[None, :]


def _q8_matmul_kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)  # int8 tile dequant happens IN VMEM
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)
    # s_ref is deliberately [1, bn] (2-D): Mosaic rejects 1-D blocks
    # whose lane count disagrees with XLA's vector tiling (seen on-chip:
    # f32[4096] laid out T(1024) vs a (256,) block); [1, bn] broadcasts
    # over the [bm, bn] accumulator as-is
    o_ref[:] = (acc * s_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "out_dtype", "interpret"))
def q8_matmul(x, w_q, scale, *, block_m: int = 128, block_n: int = 256,
              out_dtype=None, interpret: bool | None = None):
    """x: [m, k] float @ int8 weights [k, n] (+ scale [n]) -> [m, k]·W.

    Grid tiles (m, n); each block reads an int8 [k, bn] weight tile from
    HBM and dequantizes in VMEM. K is kept whole per block (serving dims
    k<=8192 fit comfortably: bm·k fp32 + k·bn int8 < VMEM)."""
    m, k = x.shape
    k2, n = w_q.shape
    if k != k2 or scale.shape != (n,):
        raise ValueError(f"shape mismatch: x{x.shape} w{w_q.shape} "
                         f"scale{scale.shape}")
    out_dtype = out_dtype or x.dtype
    # n (a WEIGHT dim): largest divisor <= block_n — padding weights per
    # call would re-copy k*n bytes and forfeit the bandwidth win. Dense
    # dims are MXU-sized in practice; if only a tiny divisor exists the
    # kernel would degenerate (per-column dispatches), so fall back to
    # the XLA dequant matmul — correct, merely without the int8 traffic
    # saving for that pathological shape.
    bn = min(block_n, n)
    while n % bn:
        bn -= 1
    if bn < 64 and n > 64:
        return (jnp.dot(x.astype(jnp.float32), dequantize_q8(w_q, scale))
                ).astype(out_dtype)
    # m (the ACTIVATION dim): pad rows up to a block multiple and slice —
    # cheap (activations are small), and it avoids the prime-length
    # cliff where a divisor search would collapse to 1-row blocks that
    # each re-read the whole weight tile.
    bm = min(block_m, m)
    m_pad = -(-m // bm) * bm
    x_in = x if m_pad == m else jnp.pad(x, ((0, m_pad - m), (0, 0)))
    out = pl.pallas_call(
        _q8_matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((m_pad, n), out_dtype),
        grid=(m_pad // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=_interp() if interpret is None else interpret,
    )(x_in, w_q, scale.reshape(1, n))
    return out if m_pad == m else out[:m]

# Tensor parallelism note: GSPMD cannot see inside a pallas_call (an
# opaque custom call), so a tensor-sharded int8 kernel fed to q8_matmul
# under bare pjit would be silently ALL-GATHERED before the kernel ran —
# the opposite of the bandwidth win. The serving path therefore runs the
# kernel under shard_map with explicit column/row-parallel specs: see
# models.transformer.QuantDense (a custom_partitioning route was tried
# and dropped — jax 0.9's Shardy glue hands the callbacks sub-axis
# shardings it cannot convert mid-model).
