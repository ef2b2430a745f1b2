"""Fused flash attention as a pallas TPU kernel.

The hot op of the transformer stack (no reference analog — TonY has no
kernels; this is the TPU-first replacement for what torch users get from
SDPA/FlashAttention-CUDA). Design per the pallas TPU playbook:

- grid = (batch*heads, q_blocks, kv_blocks); kv is the innermost
  "arbitrary" (sequential) dimension so VMEM scratch carries the online-
  softmax running state (m, l) and the fp32 output accumulator across kv
  steps
- q/k/v blocks are DMA'd HBM->VMEM by BlockSpec; matmuls run in the
  input dtype (bf16 in production) with fp32 MXU accumulation; block
  sizes default to 512 (measured ~2x faster than 128 on v5-class chips:
  the kernel is grid-overhead-bound below that), clamped to a divisor of
  the sequence length
- causal masking prunes fully-masked kv blocks via @pl.when

Falls back to the interpreter off-TPU (tests run it on CPU), and exposes a
custom_vjp with a pallas backward in the FlashAttention-2 scheme: the
forward saves the per-row logsumexp and the backward recomputes
P = exp(S - lse) blockwise, so no [L, L] tensor is ever materialized in
either direction and GQA K/V are never repeated in HBM (block-indexed per
q-head group). The shapes choose one of two backward paths:

- fused (kernel ``flash_bwd_fused``) where the q-head group's f32 dQ fits
  the VMEM budget (``_fused_bwd_vmem_bytes``): for each kv block, one
  kernel walks the q blocks, recomputes P ONCE a tile and accumulates
  dK, dV and the whole sequence's dQ from it (5 tile products a tile);
- split (``flash_bwd_dq`` and ``flash_bwd_dkv``) where it does not (long
  sequences, wide GQA groups): each kernel recomputes P for its own
  gradients (7 tile products a tile, two exp passes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from tony_tpu.ops.platform import on_tpu as _on_tpu

NEG_INF = -1e30


def _causal_mask(qi, ki, block_q, block_k, window: int = 0):
    """Causal visibility for one (q block, kv block) tile; window > 0 also
    hides keys further than ``window`` behind the query (sliding window,
    key visible iff 0 <= q_pos - k_pos < window)."""
    pos_q = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    pos_k = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = pos_q >= pos_k
    if window > 0:
        mask = mask & (pos_q - pos_k < window)
    return mask


def _block_interior(qi, ki, block_q, block_k, window: int):
    """Grid predicate: is this tile FULLY visible (every q sees every k)?
    Interior tiles skip the iota mask build + where entirely — at these
    head dims the kernels are VPU-bound, and for causal seq/block ratios
    around 4 most visible tiles are interior, so the saved elementwise
    passes are a real fraction of kernel time."""
    pred = qi * block_q >= ki * block_k + block_k - 1
    if window > 0:
        pred = pred & (qi * block_q + block_q - 1 - ki * block_k < window)
    return pred


def _dispatch_body(body, causal: bool, has_seg: bool, qi, ki,
                   block_q: int, block_k: int, window: int):
    """Shared tile dispatch for the three flash kernels: skip invisible
    tiles, and run fully-visible (interior) tiles without the mask build.
    ``body(masked)`` does the tile's work; segment ids are data-dependent
    so they always mask."""
    if not causal:
        body(False)
        return
    vis = _block_visible(qi, ki, block_q, block_k, window)
    if has_seg:
        pl.when(vis)(lambda: body(True))
        return
    interior = _block_interior(qi, ki, block_q, block_k, window)
    pl.when(vis & interior)(lambda: body(False))
    pl.when(vis & jnp.logical_not(interior))(lambda: body(True))


def _block_visible(qi, ki, block_q, block_k, window: int):
    """Grid predicate: does this (q block, kv block) tile contain ANY
    visible entry? Upper side: the tile's newest query must not precede
    the tile's oldest key (causal). Lower side (window only): the tile's
    oldest query must be nearer than ``window`` to the tile's newest key —
    tiles wholly behind the window are skipped, making windowed compute
    O(L*window) instead of O(L^2/2)."""
    pred = ki * block_k <= qi * block_q + block_q - 1
    if window > 0:
        pred = pred & (qi * block_q - (ki * block_k + block_k - 1) < window)
    return pred


def _kv_band(window: int, block_q: int, block_k: int, nk: int) -> int:
    """Grid width (in kv blocks) of the visible band for one q block under
    a sliding window. The band [q_first - window + 1, q_last] spans at most
    window + block_q - 1 keys, i.e. this many kv tiles (+1 for alignment
    slack). Shrinking the GRID — not just @pl.when-skipping the body —
    means invisible kv tiles are never DMA'd, so windowed attention is
    O(L*window) in HBM traffic too, which is what actually pays on a
    bandwidth-bound chip."""
    if window <= 0:
        return nk
    return min(nk, (window + block_q - 2) // block_k + 2)


def _banded_ki(qi, ki_local, nkb, block_q: int, block_k: int, nk: int):
    """Real kv block index for banded grids: the band ends at this q
    block's diagonal tile; local index 0 is ``nkb - 1`` tiles before it
    (clamped at 0 — early q blocks just re-scan the first tiles and rely
    on the visibility predicate). With a full band (nkb == nk) this is the
    identity, so the same formula serves the unwindowed causal path.

    ``nk`` is the TOTAL kv-block count: for causal cross-attention with
    lq > lk the diagonal lies past the kv grid, so it is clamped to the
    last real tile — every block is then scanned and the position mask
    alone decides visibility (the pre-band full-scan behavior)."""
    diag = jnp.minimum((qi * block_q + block_q - 1) // block_k, nk - 1)
    return jnp.maximum(diag - (nkb - 1), 0) + ki_local


def _q_band(window: int, block_q: int, block_k: int, nq: int) -> int:
    """Grid width (in q blocks) of the band of queries that can see one kv
    block under a sliding window (the dK/dV mirror of _kv_band)."""
    if window <= 0:
        return nq
    return min(nq, (window + block_k - 2) // block_q + 2)


def _banded_qi(ki, qi_local, nqb, nq, block_q: int, block_k: int):
    """Real q block index for the dK/dV banded grid: the band starts at
    the first q tile that can see this kv block (its diagonal), clamped so
    the band stays inside [0, nq)."""
    first = (ki * block_k) // block_q
    return jnp.minimum(first, nq - nqb) + qi_local


def _flash_kernel(q_ref, k_ref, v_ref, *rest,
                  causal: bool, block_q: int, block_k: int, scale: float,
                  nk_total: int, window: int = 0, has_seg: bool = False):
    if has_seg:
        qseg_ref, kseg_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki_local = pl.program_id(2)
    nk = pl.num_programs(2)  # band width (= all kv blocks when unwindowed)
    if causal:
        ki = _banded_ki(qi, ki_local, nk, block_q, block_k, nk_total)
    else:
        ki = ki_local

    @pl.when(ki_local == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _body(masked: bool):
        # inputs stay in their native dtype (bf16 in production): the MXU
        # runs bf16 x bf16 -> fp32 accumulation at full rate; casting the
        # operands to fp32 first would halve matmul throughput
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]
        # RAW scores: the softmax scale is folded into the exp (max
        # commutes with positive scaling), so no [block_q, block_k]
        # scaling pass ever runs — at d=64 the kernel is VPU-bound and
        # every elementwise pass over the scores tile is ~a third of the
        # matmul time
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            mask = _causal_mask(qi, ki, block_q, block_k, window)
            if has_seg:
                mask = mask & (qseg_ref[0, 0][:, None]
                               == kseg_ref[0, 0][None, :])
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp((s - m_new) * scale)  # one fused sub-mul-exp pass
        corr = jnp.exp((m_prev - m_new) * scale)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_new
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch_body(_body, causal, has_seg, qi, ki, block_q, block_k,
                   window)

    @pl.when(ki_local == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # logsumexp per q row ([block_q, 1], same layout as the scratch),
        # saved for the backward's softmax recompute. m_scr holds the RAW
        # running max, so it re-enters scaled space here.
        lse_ref[0] = m_scr[:] * scale + jnp.log(l_safe)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *rest, causal: bool, block_q: int,
                         block_k: int, scale: float, nk_total: int,
                         window: int = 0, has_seg: bool = False):
    if has_seg:
        qseg_ref, kseg_ref, dq_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest
    """dQ: grid (bh, nq, nk); for each q block, scan kv blocks.

    FlashAttention-2 backward math with the normalized P recomputed from
    the saved logsumexp: P = exp(S - lse); dP = dO V^T;
    dS = P * (dP - delta) * scale; dQ = sum_k dS K.
    """
    qi = pl.program_id(1)
    ki_local = pl.program_id(2)
    nk = pl.num_programs(2)  # band width
    if causal:
        ki = _banded_ki(qi, ki_local, nk, block_q, block_k, nk_total)
    else:
        ki = ki_local

    @pl.when(ki_local == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _body(masked: bool):
        q = q_ref[0]  # native dtype: full-rate MXU, fp32 accumulation
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        # raw scores; scale folds into the fused exp below, and the dS
        # scale is applied once to the [block_q, d] accumulator at
        # finalize instead of per-body on the [block_q, block_k] tile
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            mask = _causal_mask(qi, ki, block_q, block_k, window)
            if has_seg:
                mask = mask & (qseg_ref[0, 0][:, None]
                               == kseg_ref[0, 0][None, :])
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s * scale - lse_ref[0])  # lse: [block_q, 1] broadcast
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch_body(_body, causal, has_seg, qi, ki, block_q, block_k,
                   window)

    @pl.when(ki_local == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          *rest, causal: bool,
                          block_q: int, block_k: int, scale: float,
                          nq: int, nqb: int, window: int = 0,
                          has_seg: bool = False, with_dq: bool = False):
    """dK/dV: grid (b*kvh, nk, group*nqb); for each KV-HEAD block, the
    innermost scan walks every q block of every q head in this kv group
    (step s: head g = s // nqb, q block qi = s % nqb), accumulating into one
    [block_k, d] scratch pair — so dK/dV are written at their true
    [b*kvh, lk, d] size with no group-factor HBM amplification.

    dV = sum_{g,q} P^T dO; dK = sum_{g,q} dS^T Q (dS as in the dQ kernel).

    ``with_dq`` is the fused backward: the same tile also adds dS K into
    an f32 scratch holding dQ for the whole sequence of every q head in
    the group, so P is recomputed once a tile for all three gradients
    (five tile products and one exp pass, against seven and two beside
    the dQ kernel). That scratch carries across the kv axis, which is
    then sequential too; dQ is rounded once, after the last kv block.

    ``nq`` is the TOTAL q-block count; ``nqb`` the banded width actually
    walked per head (== nq when unwindowed)."""
    if has_seg:
        qseg_ref, kseg_ref, *rest = rest
    if with_dq:
        dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
    ki = pl.program_id(1)
    s_idx = pl.program_id(2)
    ns = pl.num_programs(2)
    if causal:
        qi = _banded_qi(ki, s_idx % nqb, nqb, nq, block_q, block_k)
    else:
        qi = s_idx % nqb

    @pl.when(s_idx == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if with_dq:
        @pl.when((ki == 0) & (s_idx == 0))
        def _init_dq():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    def _body(masked: bool):
        q = q_ref[0]  # native dtype: full-rate MXU, fp32 accumulation
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        # raw scores (see the dQ kernel): scale folds into the exp; the
        # dS scale lands on the dK and dQ accumulators at finalize
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            mask = _causal_mask(qi, ki, block_q, block_k, window)
            if has_seg:
                mask = mask & (qseg_ref[0, 0][:, None]
                               == kseg_ref[0, 0][None, :])
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s * scale - lse_ref[0])  # [block_q, block_k]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if with_dq:
            rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            dq_scr[s_idx // nqb, rows, :] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _dispatch_body(_body, causal, has_seg, qi, ki, block_q, block_k,
                   window)

    @pl.when(s_idx == ns - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    if with_dq:
        @pl.when((ki == pl.num_programs(1) - 1) & (s_idx == ns - 1))
        def _finalize_dq():
            for g in range(dq_ref.shape[0]):
                dq_ref[g] = (dq_scr[g] * scale).astype(dq_ref.dtype)


def _flash_forward(q, k, v, *, causal: bool, block_q: int, block_k: int,
                   interpret: bool, window: int = 0, segments=None):
    b, lq, h, d = q.shape
    lk = k.shape[1]
    kvh = k.shape[2]
    if lq % block_q or lk % block_k:
        raise ValueError(
            f"seq lens ({lq},{lk}) must divide block sizes ({block_q},{block_k})")
    if h % kvh:
        raise ValueError(f"q heads {h} not divisible by kv heads {kvh}")
    group = h // kvh
    scale = d ** -0.5
    # [B, L, H, D] -> [B*H, L, D]
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kvh, lk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kvh, lk, d)
    nk = lk // block_k
    # windowed: the kv grid axis covers only the visible band per q block,
    # so out-of-window kv tiles are never DMA'd (O(L*window) HBM traffic)
    nkb = _kv_band(window, block_q, block_k, nk) if causal else nk
    grid = (b * h, lq // block_q, nkb)

    def kv_index(bh, qi, ki):
        # GQA: q head -> its kv group's row; the same kv block is DMA'd for
        # each of the `group` q heads instead of materializing a repeat
        row = (bh // h) * kvh + (bh % h) // group
        if causal:
            return row, _banded_ki(qi, ki, nkb, block_q, block_k, nk), 0
        return row, ki, 0

    kernel = functools.partial(_flash_kernel, causal=causal, block_q=block_q,
                               block_k=block_k, scale=scale, nk_total=nk,
                               window=window, has_seg=segments is not None)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d), kv_index),
    ]
    operands = [qr, kr, vr]
    if segments is not None:
        seg3 = segments[:, None, :]  # [B, 1, L]: legal TPU tile shape
        in_specs += [
            pl.BlockSpec((1, 1, block_q),
                         lambda bh, qi, ki: (bh // h, 0, qi)),
            pl.BlockSpec(
                (1, 1, block_k),
                (lambda bh, qi, ki:
                 (bh // h, 0, _banded_ki(qi, ki, nkb, block_q, block_k, nk)))
                if causal else (lambda bh, qi, ki: (bh // h, 0, ki))),
        ]
        operands += [seg3, seg3]
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
            # [bh, lq, 1]: lane-dim 1 keeps the (block_q, 1) block a legal
            # TPU tile and matches the m/l scratch layout
            jax.ShapeDtypeStruct((b * h, lq, 1), jnp.float32),
        ],
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        scratch_shapes=[
            _vmem((block_q, 1)),
            _vmem((block_q, 1)),
            _vmem((block_q, d)),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, h, lq, d).transpose(0, 2, 1, 3), lse


# VMEM the fused backward may plan to hold: a quarter of a v5e's 128 MiB,
# leaving the compiler room for its own internal scratch
_FUSED_BWD_VMEM = 32 << 20


def _fused_bwd_vmem_bytes(group: int, lq: int, d: int, block_q: int,
                          block_k: int, itemsize: int) -> int:
    """VMEM the fused backward holds live at once, from its shapes: the
    group's f32 dQ scratch with its double-buffered output block; the
    double-buffered q, dO, k, v, dK and dV blocks and the lse and delta
    rows (a [block_q, 1] f32 block pads to 128 lanes); the dK/dV scratch;
    and a tile's four f32 temporaries (S, P, dP, dS) with the two casts
    that feed the MXU."""
    dq = group * lq * d * (4 + 2 * itemsize)
    blocks = (2 * itemsize * d * (2 * block_q + 4 * block_k)
              + 2 * 2 * block_q * 128 * 4)
    dkv_scratch = 2 * block_k * d * 4
    tile = block_q * block_k * (4 * 4 + 2 * itemsize)
    return dq + blocks + dkv_scratch + tile


def _flash_backward(q, k, v, o, lse, g, *, causal: bool, block_q: int,
                    block_k: int, interpret: bool, window: int = 0,
                    segments=None):
    """Pallas dQ/dK/dV (FlashAttention-2 scheme), by one of two paths
    that the shapes choose:

    - fused (kernel ``flash_bwd_fused``), where ``_fused_bwd_vmem_bytes``
      fits ``_FUSED_BWD_VMEM``: one kernel over the per-KV-head grid that
      recomputes P once a tile and accumulates all three gradients;
    - split (``flash_bwd_dq``, then ``flash_bwd_dkv``) where the group's
      f32 dQ does not fit (long sequences, wide GQA groups): each kernel
      recomputes P for its own gradients.

    GQA: the kv BlockSpec indexes each q head's group row (as in the
    forward), so K/V are never repeated in HBM, and the dK/dV (and fused)
    kernels accumulate the whole q-head group in VMEM scratch so dK/dV
    are written at the true [b*kvh] size (no group-factor HBM
    amplification)."""
    b, lq, h, d = q.shape
    lk, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    scale = d ** -0.5
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kvh, lk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kvh, lk, d)
    dor = g.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    # delta_i = rowsum(dO * O): one cheap bandwidth pass, done by XLA;
    # [bh, lq, 1] to match the lse layout
    delta = jnp.sum(dor.astype(jnp.float32)
                    * o.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
                    .astype(jnp.float32), axis=-1, keepdims=True)
    has_seg = segments is not None
    seg3 = segments[:, None, :] if has_seg else None
    kw = dict(causal=causal, block_q=block_q, block_k=block_k, scale=scale,
              window=window, has_seg=has_seg)
    dq_shape = jax.ShapeDtypeStruct((b * h, lq, d), q.dtype)
    vmem = _fused_bwd_vmem_bytes(group, lq, d, block_q, block_k,
                                 q.dtype.itemsize)
    fused = vmem <= _FUSED_BWD_VMEM

    nk = lk // block_k
    if not fused:
        nkb = _kv_band(window, block_q, block_k, nk) if causal else nk

        def kv_index_dq(bh, qi, ki):
            row = (bh // h) * kvh + (bh % h) // group
            if causal:
                return row, _banded_ki(qi, ki, nkb, block_q, block_k, nk), 0
            return row, ki, 0

        q_spec_dq = pl.BlockSpec((1, block_q, d),
                                 lambda bh, qi, ki: (bh, qi, 0))
        row_spec_dq = pl.BlockSpec((1, block_q, 1),
                                   lambda bh, qi, ki: (bh, qi, 0))
        in_specs_dq = [
            q_spec_dq,
            pl.BlockSpec((1, block_k, d), kv_index_dq),
            pl.BlockSpec((1, block_k, d), kv_index_dq),
            q_spec_dq,
            row_spec_dq,
            row_spec_dq,
        ]
        operands_dq = [qr, kr, vr, dor, lse, delta]
        if has_seg:
            in_specs_dq += [
                pl.BlockSpec((1, 1, block_q),
                             lambda bh, qi, ki: (bh // h, 0, qi)),
                pl.BlockSpec(
                    (1, 1, block_k),
                    (lambda bh, qi, ki:
                     (bh // h, 0,
                      _banded_ki(qi, ki, nkb, block_q, block_k, nk)))
                    if causal else (lambda bh, qi, ki: (bh // h, 0, ki))),
            ]
            operands_dq += [seg3, seg3]
        dq = pl.pallas_call(
            functools.partial(_flash_bwd_dq_kernel, nk_total=nk, **kw),
            out_shape=dq_shape,
            grid=(b * h, lq // block_q, nkb),
            in_specs=in_specs_dq,
            out_specs=q_spec_dq,
            scratch_shapes=[_vmem((block_q, d))],
            compiler_params=_compiler_params(),
            interpret=interpret,
            name="flash_bwd_dq",
        )(*operands_dq)

    # dK/dV grid is per KV head: the innermost axis walks group*nqb steps
    # (the banded q blocks of all q heads in this group), so outputs are
    # written at [b*kvh, lk, d] directly — no group-factor HBM
    # amplification, and out-of-window q tiles are never DMA'd
    nq = lq // block_q
    nqb = _q_band(window, block_q, block_k, nq) if causal else nq

    def q_row_dkv(bkv, ki, s):
        row = (bkv // kvh) * h + (bkv % kvh) * group + s // nqb
        if causal:
            return row, _banded_qi(ki, s % nqb, nqb, nq, block_q, block_k), 0
        return row, s % nqb, 0

    q_spec_dkv = pl.BlockSpec((1, block_q, d), q_row_dkv)
    row_spec_dkv = pl.BlockSpec((1, block_q, 1), q_row_dkv)
    kv_spec_dkv = pl.BlockSpec((1, block_k, d), lambda bkv, ki, s: (bkv, ki, 0))
    in_specs_dkv = [
        q_spec_dkv,
        kv_spec_dkv,
        kv_spec_dkv,
        q_spec_dkv,
        row_spec_dkv,
        row_spec_dkv,
    ]
    operands_dkv = [qr, kr, vr, dor, lse, delta]
    if has_seg:
        in_specs_dkv += [
            pl.BlockSpec(
                (1, 1, block_q),
                (lambda bkv, ki, s:
                 (bkv // kvh, 0, _banded_qi(ki, s % nqb, nqb, nq,
                                            block_q, block_k)))
                if causal else
                (lambda bkv, ki, s: (bkv // kvh, 0, s % nqb))),
            pl.BlockSpec((1, 1, block_k),
                         lambda bkv, ki, s: (bkv // kvh, 0, ki)),
        ]
        operands_dkv += [seg3, seg3]
    out_shapes = [jax.ShapeDtypeStruct((b * kvh, lk, d), k.dtype),
                  jax.ShapeDtypeStruct((b * kvh, lk, d), v.dtype)]
    out_specs = [kv_spec_dkv, kv_spec_dkv]
    scratch = [_vmem((block_k, d)), _vmem((block_k, d))]
    params = _compiler_params()
    if fused:
        # the q heads of one kv head are consecutive rows of dq, so the
        # group's whole dQ is one output block, resident across both
        # inner axes and written back once
        out_shapes.insert(0, dq_shape)
        out_specs.insert(0, pl.BlockSpec((group, lq, d),
                                         lambda bkv, ki, s: (bkv, 0, 0)))
        scratch.insert(0, _vmem((group, lq, d)))
        params = _compiler_params("arbitrary", vmem_limit_bytes=vmem)
    outs = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, nq=nq, nqb=nqb,
                          with_dq=fused, **kw),
        out_shape=out_shapes,
        grid=(b * kvh, nk, group * nqb),
        in_specs=in_specs_dkv,
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_fused" if fused else "flash_bwd_dkv",
    )(*operands_dkv)
    if fused:
        dq, dk, dv = outs
    else:
        dk, dv = outs

    dq = dq.reshape(b, h, lq, d).transpose(0, 2, 1, 3)
    dk = dk.reshape(b, kvh, lk, d).transpose(0, 2, 1, 3)
    dv = dv.reshape(b, kvh, lk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params(second: str = "parallel",
                     vmem_limit_bytes: int | None = None):
    """The grid's first axis is parallel and its last sequential; the
    middle axis is sequential too where scratch carries across it."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", second, "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes)


def _pick_block(limit: int, length: int) -> int:
    """Largest block <= limit that divides the sequence length and keeps a
    legal TPU tile (multiple of 8, or the whole length). Degenerate tiny
    blocks would be silently 10-100x slower than XLA attention, so a
    length with no usable divisor is an error, not a fallback."""
    b = min(limit, length)
    if length % b == 0:
        return b
    for cand in range(b - b % 8, 7, -8):  # multiples of 8, descending
        if length % cand == 0:
            return cand
    raise ValueError(
        f"no usable flash-attention block for seq len {length} (need a "
        f"divisor <= {limit} that is a multiple of 8); pad the sequence "
        f"or use the blockwise backend")


def _blocks(block_q, block_k, q, k):
    return _pick_block(block_q, q.shape[1]), _pick_block(block_k, k.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention_core(q, k, v, segments, causal: bool, block_q: int,
                          block_k: int, interpret: bool | None,
                          window: int = 0):
    """custom_vjp core; sequence lengths must have a usable block.
    ``segments`` is an int operand (or None): zero-cotangent in the vjp."""
    if interpret is None:
        interpret = not _on_tpu()
    bq, bk = _blocks(block_q, block_k, q, k)
    out, _ = _flash_forward(q, k, v, causal=causal, block_q=bq, block_k=bk,
                            interpret=interpret, window=window,
                            segments=segments)
    return out


def _fwd(q, k, v, segments, causal, block_q, block_k, interpret, window=0):
    if interpret is None:
        interpret = not _on_tpu()
    bq, bk = _blocks(block_q, block_k, q, k)
    out, lse = _flash_forward(q, k, v, causal=causal, block_q=bq, block_k=bk,
                              interpret=interpret, window=window,
                              segments=segments)
    return out, (q, k, v, segments, out, lse)


def _bwd(causal, block_q, block_k, interpret, window, res, g):
    """Pallas FlashAttention-2 backward: recomputes P blockwise from the
    saved logsumexp — O(L) memory, no [L, L] tensor, no K/V repeat."""
    q, k, v, segments, o, lse = res
    if interpret is None:
        interpret = not _on_tpu()
    bq, bk = _blocks(block_q, block_k, q, k)
    dq, dk, dv = _flash_backward(q, k, v, o, lse, g, causal=causal,
                                 block_q=bq, block_k=bk, interpret=interpret,
                                 window=window, segments=segments)
    # int segments carry the symbolic-zero float0 cotangent
    dseg = None if segments is None else np.zeros(segments.shape,
                                                  jax.dtypes.float0)
    return dq, dk, dv, dseg


_flash_attention_core.defvjp(_fwd, _bwd)


def _padded_len(length: int, limit: int) -> int:
    """Sequence length after padding so a usable block exists (unchanged
    if one already does). Only lengths > limit can need padding: a length
    <= limit is always its own legal whole-length block."""
    try:
        _pick_block(limit, length)
        return length
    except ValueError:
        return -(-length // limit) * limit


def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool | None = None,
                    window: int = 0, segment_ids=None):
    """Fused attention. q: [B, L, H, D]; k/v: [B, L, KVH, D] with
    H % KVH == 0 (GQA: the kernel indexes each q head's kv group directly —
    no repeated K/V is ever materialized). Returns [B, L, H, D].

    window > 0 adds sliding-window masking (key visible iff
    0 <= q_pos - k_pos < window, HF Mistral semantics; requires causal)
    with block-level pruning, so compute scales O(L*window) not O(L^2).

    Awkward sequence lengths (e.g. the L-1 of a shifted LM batch) are
    zero-padded up to a blockable length and sliced back — safe for causal
    attention because padded K rows sit beyond every real query's causal
    horizon and padded-row dO is zero in the backward. Non-causal calls
    with an unblockable length raise instead (padded K rows would receive
    real attention mass).

    interpret=None auto-selects: compiled on TPU, interpreter elsewhere.
    """
    lq, lk = q.shape[1], k.shape[1]
    if window > 0 and not causal:
        raise ValueError("window > 0 requires causal=True (the sliding "
                         "window is defined over past keys)")
    if window > 0 and lq != lk:
        raise ValueError("window > 0 needs self-attention shapes (lq == "
                         f"lk): the banded grid width is derived from lk, "
                         f"got ({lq}, {lk})")
    if segment_ids is not None:
        if not causal:
            raise ValueError("segment_ids require causal=True (packed-LM "
                             "masking)")
        if lq != lk:
            raise ValueError("segment_ids need self-attention shapes "
                             f"(lq == lk), got ({lq}, {lk})")
        segment_ids = segment_ids.astype(jnp.int32)
    plq, plk = _padded_len(lq, block_q), _padded_len(lk, block_k)
    if plq == lq and plk == lk:
        return _flash_attention_core(q, k, v, segment_ids, causal, block_q,
                                     block_k, interpret, window)
    if not causal:
        raise ValueError(
            f"non-causal flash attention needs blockable seq lens, got "
            f"({lq}, {lk}); pad the sequence or use the blockwise backend")
    if lq != lk:
        # causal cross-attention with lq > lk would let real queries past
        # lk attend zero-padded keys (score 0 > negative real scores =
        # silent mass leak); the pad path is only sound for self-attention
        raise ValueError(
            f"causal flash attention with unblockable UNEQUAL seq lens "
            f"({lq}, {lk}) cannot be zero-padded safely; pad the inputs "
            f"yourself or use the blockwise backend")
    # pad BOTH sides to one common blockable length: with block_q !=
    # block_k, plq != plk would let q-side blocks (and the banded kv
    # index) run past the shorter array
    pm = max(plq, plk)
    pad_q = [(0, 0), (0, pm - lq), (0, 0), (0, 0)]
    pad_k = [(0, 0), (0, pm - lk), (0, 0), (0, 0)]
    seg_p = None
    if segment_ids is not None:
        # padded positions get segment -1: real queries never attend them
        seg_p = jnp.pad(segment_ids, [(0, 0), (0, pm - lk)],
                        constant_values=-1)
    out = _flash_attention_core(
        jnp.pad(q, pad_q), jnp.pad(k, pad_k), jnp.pad(v, pad_k), seg_p,
        causal, block_q, block_k, interpret, window)
    return out[:, :lq]
