"""Flash-decode: single-query KV-cache attention as a pallas TPU kernel.

The decode hot loop is HBM-bound (docs/PERF.md "Decode roofline"): every
generated token re-reads the whole KV cache once. This kernel is the
cache-side counterpart of the int8 weight path (ops/quant.py):

- one grid step per (batch, kv-head block, kv block): K/V tiles are
  DMA'd HBM->VMEM once — sliced straight out of the cache's NATIVE
  [B, S, KVH, D] layout by the BlockSpec index maps as
  [block_k, hb, D] tiles (no transposed copy of the cache is ever
  materialized; only the int8 path's scale tensors, 4/D of the cache
  bytes, are pre-transposed) — and consumed by an online-softmax
  accumulation held in VMEM scratch: no [S] score tensor round-trips
  to HBM, and the softmax/weighted-sum fuse into the tile pass (XLA's
  decode attention materializes scores + probabilities in HBM at
  small batch);
- the cache may be stored **int8 with per-(position, head) scales**
  (quantize-on-write in models/transformer._decode_attention): tiles
  cross HBM as int8 — HALF the cache traffic of bf16, the dominant
  decode bytes at long context — and dequantize in VMEM right before
  the MXU, exactly the ops/quant.py recipe for weights;
- GQA: the q-head group [G, D] of each kv head contracts against that
  head's tile inside the instance, so cache tiles are read ONCE per kv
  head (never repeated to n_heads), preserving the GQA bandwidth
  saving end-to-end;
- cache positions at/after ``length`` (and behind the sliding window)
  are masked; blocks entirely outside [start, length) skip their FLOPs
  via ``@pl.when`` predication.

No reference analog (TonY ships no kernels; SURVEY.md section 2.5 —
the data plane is delegated). Falls back to the pallas interpreter
off-TPU so CPU tests pin exactness against the jax reference path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tony_tpu.ops.platform import interpret_mode

NEG_INF = -1e30


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[B, L, H, D] float -> (int8 values, fp32 scales [B, L, H]).
    Symmetric absmax per (batch, position, head) — the KV analog of
    ops/quant.quantize_q8's per-output-channel recipe; dequant is
    ``q * scale[..., None]``."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale[..., None]


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, *rest,
                   block_k: int, scale: float, window: int,
                   quant: bool, hb: int, group: int):
    """Grid (batch, kv-head block, kv block). ``hb`` kv heads of one
    batch row ride one instance: K/V arrive in their NATIVE
    [B, S, KVH, D] cache layout as one [block_k, hb, D] DMA, and the
    ``hb * group`` query rows of those heads (q heads are laid out
    kv-head-major, so they are contiguous) fill the sublanes. All rows
    share the batch, so ONE SMEM length serves the whole instance.
    Per-kv-head score/value contractions are statically unrolled plain
    2-D dots ([group, D] x [D, block_k]) — no batched dot_general, no
    in-VMEM transpose, Mosaic-safe by construction.

    Tile legality is the caller's job (``_head_block``): Mosaic wants
    the last two block dims — here (hb, D) over (KVH, D) — to be the
    full array dims or multiples of (8, 128). A block of ONE head over
    a KVH > 1 cache is what the chip's compiler refuses, which is why
    there is no per-head grid axis. int8 scales arrive pre-transposed
    ``[B, KVH, S]`` as ``(1, hb, bk)`` tiles (sublane hb, lane bk —
    legal by the same rule) and FOLD onto the score/probability rows
    instead of dequantizing tiles: the per-(position, head) scale
    distributes over the d-contraction, exactly the einsum path's
    trick (models/transformer._decode_attention)."""
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # lengths live whole in SMEM (scalars don't tile: a (1, 1) VMEM
    # block of an [B, 1] array fails Mosaic's sublane rule on-chip);
    # indexed dynamically per grid row instead of via BlockSpec
    length = len_ref[pl.program_id(0), 0]
    start = jnp.maximum(length - window, 0) if window > 0 else 0

    def _body():
        q = q_ref[0].astype(jnp.float32)  # [hb * group, D]
        k = k_ref[0]                      # [block_k, hb, D]
        v = v_ref[0]
        rows = []
        for hh in range(hb):
            s_h = jax.lax.dot_general(
                q[hh * group:(hh + 1) * group, :],
                k[:, hh, :].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [group, block_k]
            if quant:
                # fold the K scale onto the lane-major score rows (it
                # distributes over the d-contraction) — no
                # sublane-major scale column is ever needed
                s_h = s_h * ks_ref[0, hh:hh + 1, :]
            rows.append(s_h)
        s = jnp.concatenate(rows, axis=0) * scale  # [hb * group, block_k]
        pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        visible = pos < length
        if window > 0:
            visible = visible & (pos >= start)
        s = jnp.where(visible, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_new
        pv = []
        for hh in range(hb):
            p_h = p[hh * group:(hh + 1) * group, :]
            if quant:
                # likewise fold the V scale into the probabilities
                p_h = p_h * vs_ref[0, hh:hh + 1, :]
            pv.append(jax.lax.dot_general(
                p_h, v[:, hh, :].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        acc_scr[:] = acc_scr[:] * corr + jnp.concatenate(pv, axis=0)

    # skip FLOPs for blocks wholly past `length` or behind the window
    # (their DMA is already issued by BlockSpec — static grid — so this
    # saves compute, not traffic; the traffic win comes from int8 tiles)
    in_range = ki * block_k < length
    if window > 0:
        in_range = in_range & (ki * block_k + block_k > start)

    @pl.when(in_range)
    def _run():
        _body()

    @pl.when(ki == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[:], 1e-30)
        # a length-0 batch (an empty continuous-batching slot) never
        # runs _body: m stays NEG_INF and the mask pins its rows to
        # the exact zeros the reference path emits
        valid = m_scr[:] > NEG_INF * 0.5
        o_ref[0] = jnp.where(valid, acc_scr[:] / l_safe,
                             0.0).astype(o_ref.dtype)


def _head_block(kvh: int) -> int:
    """kv heads per kernel instance: 8 (a sublane multiple) when it
    divides ``kvh``, else the FULL kv-head dim (a full-dim block is
    always tile-legal)."""
    return 8 if kvh % 8 == 0 else kvh


def _pick_block_k(limit: int, s: int) -> int:
    """Largest multiple-of-8 divisor of ``s`` within ``limit``; a whole-
    length single block is legal too (mosaic pads a full-dim block). Any
    other non-8-multiple would be a sublane-misaligned TPU tile that only
    the CPU interpreter accepts, so it is an error, not a fallback."""
    if s <= limit:
        return s
    b = limit
    for cand in range(b - b % 8, 7, -8):
        if s % cand == 0:
            return cand
    raise ValueError(
        f"no usable flash-decode block for cache length {s} (need a "
        f"divisor <= {limit} that is a multiple of 8, or the whole "
        f"length; pad max_seq_len to a multiple of 8)")


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "interpret"))
def flash_decode(q, k, v, length, *, window: int = 0, block_k: int = 512,
                 k_scale=None, v_scale=None, interpret: bool | None = None):
    """Single-step decode attention over a static KV cache.

    q: [B, H, D] — the one new query per sequence (head-grouped GQA ok).
    k/v: [B, S, KVH, D] cache buffers — float, or int8 with
      ``k_scale``/``v_scale`` [B, S, KVH] fp32 per-(position, head)
      scales (quantize-on-write; see models/quantize.quantize_kv).
    length: [B] int32 — valid cache length per sequence (query sits at
      position ``length - 1``); positions >= length are masked. Lengths
      are PER-SLOT state: a serving batch may mix any lengths, and a
      length of 0 marks an EMPTY continuous-batching slot — its output
      row is exact zeros (see _finalize), never NaN, so
      empty slots ride a live batch for free.
    window: sliding window (key visible iff 0 <= q_pos - k_pos < window).
    Returns [B, H, D] in q's dtype.
    """
    b, h, d = q.shape
    bs, s, kvh, dk = k.shape
    if bs != b or dk != d or v.shape != k.shape:
        raise ValueError(f"shape mismatch: q{q.shape} k{k.shape} v{v.shape}")
    if h % kvh:
        raise ValueError(f"q heads {h} not divisible by kv heads {kvh}")
    quant = k.dtype == jnp.int8
    if quant != (v.dtype == jnp.int8):
        raise ValueError("k and v must both be int8 or both float")
    if quant and (k_scale is None or v_scale is None):
        raise ValueError("int8 cache needs k_scale and v_scale")
    group = h // kvh
    scale = d ** -0.5
    if interpret is None:
        interpret = interpret_mode()
    bk = _pick_block_k(block_k, s)
    hb = _head_block(kvh)
    rows = hb * group  # query rows per instance

    from jax.experimental.pallas import tpu as pltpu

    len2 = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1, 1),
                            (b, 1))  # scalar length broadcasts per batch
    kernel = functools.partial(
        _decode_kernel, block_k=bk, scale=scale, window=window,
        quant=quant, hb=hb, group=group)
    # q heads are kv-head-major ([B, H, D] == [B, KVH, G, D] flattened),
    # so the q rows of kv-head block hi are the contiguous rows
    # [hi * rows, (hi + 1) * rows): q and the output need no reshape
    q_spec = pl.BlockSpec((1, rows, d), lambda bi, hi, ki: (bi, hi, 0))
    kv_spec = pl.BlockSpec((1, bk, hb, d),
                           lambda bi, hi, ki: (bi, ki, hi, 0))
    in_specs = [q_spec, kv_spec, kv_spec,
                pl.BlockSpec(memory_space=pltpu.SMEM)]
    operands = [q, k, v, len2]
    if quant:
        # the ONE relayout, scales only (tiny — 4/D of the cache
        # bytes): [B, S, KVH] -> [B, KVH, S] so the tile is
        # (1, hb, bk) — sublane hb, lane bk, Mosaic-legal wherever the
        # K/V tile is. The kernel folds them onto scores/probabilities.
        sc_spec = pl.BlockSpec((1, hb, bk), lambda bi, hi, ki: (bi, hi, ki))
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale.transpose(0, 2, 1),
                     v_scale.transpose(0, 2, 1)]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        grid=(b, kvh // hb, s // bk),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[_vmem((rows, 1)), _vmem((rows, 1)),
                        _vmem((rows, d))],
        interpret=interpret,
    )(*operands)
