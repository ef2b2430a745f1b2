"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

Absent from the reference entirely (SURVEY.md section 5.7: TonY scales
workers, never sequence length) — first-class here. Each device holds a
sequence shard of Q/K/V; K/V blocks rotate around the ring via
``lax.ppermute`` (XLA collective-permute over ICI neighbors) while every
device accumulates its queries' attention with an online-softmax running
state, so peak memory is O(L/n) and comm overlaps compute around the ring
(Liu et al., Ring Attention with Blockwise Transformers; public pattern,
re-implemented for shard_map).

Differentiable end-to-end: the scan + ppermute compose with jax autodiff
(ppermute's transpose is the reverse permute).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from tony_tpu.parallel.mesh import SEQ

NEG_INF = -1e30


def _block_attn(q, k, v, m, l, o, mask):
    """One online-softmax accumulation step.

    q: [B, Lq, H, D]; k/v: [B, Lk, H, D]; m/l: [B, H, Lq]; o like q.
    mask: boolean (True = attend), [Lq, Lk] shared across the batch or
    [B, Lq, Lk] per-example (segment masking), or None.
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        bmask = mask[None, None] if mask.ndim == 2 else mask[:, None]
        s = jnp.where(bmask, s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (all NEG_INF): exp underflows to 0 safely
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None].transpose(0, 2, 1, 3) + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v)
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, segments, *, axis_name: str, causal: bool,
                          window: int):
    """Per-shard body under shard_map. Shapes are the local shards:
    q/k/v: [B, L_local, H, D]; segments: [B, L_local] int or None.

    ``window``/``segments`` masking is positional, and every ring step
    knows the global positions of the visiting K/V block from its source
    shard index — so the sliding-window cut and packed-document masks are
    exact across shard boundaries. Segment ids rotate around the ring
    with their K/V block (one extra int ppermute per step, negligible
    next to the K/V traffic).
    """
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    m = jnp.full((b, h, lq), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((b, h, lq), dtype=jnp.float32)
    o = jnp.zeros((b, lq, h, d), dtype=jnp.float32)
    q32 = q.astype(jnp.float32)

    pos_q = my_idx * lq + jnp.arange(lq)
    perm = [(j, (j - 1) % n) for j in range(n)]
    seg_blk0 = segments if segments is not None else jnp.zeros((b, 0),
                                                               jnp.int32)

    def step(carry, i):
        k_blk, v_blk, seg_blk, m, l, o = carry
        src_idx = (my_idx + i) % n  # which shard this k/v block came from
        pos_k = src_idx * lq + jnp.arange(lq)
        mask = None
        if causal:
            mask = pos_q[:, None] >= pos_k[None, :]
        if window > 0:
            delta = pos_q[:, None] - pos_k[None, :]
            wmask = (delta >= 0) & (delta < window)
            mask = wmask if mask is None else mask & wmask
        if segments is not None:
            same = segments[:, :, None] == seg_blk[:, None, :]
            mask = same if mask is None else mask[None] & same
        m, l, o = _block_attn(q32, k_blk.astype(jnp.float32),
                              v_blk.astype(jnp.float32), m, l, o, mask)
        # rotate k/v to the next ring position (receive from right neighbor)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        if segments is not None:
            seg_blk = lax.ppermute(seg_blk, axis_name, perm)
        return (k_blk, v_blk, seg_blk, m, l, o), None

    (k, v, _, m, l, o), _ = lax.scan(step, (k, v, seg_blk0, m, l, o),
                                     jnp.arange(n))
    out = o / jnp.maximum(l[..., None].transpose(0, 2, 1, 3), 1e-30)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, axis_name: str = SEQ,
                   causal: bool = True,
                   batch_spec: P | None = None,
                   window: int = 0, segment_ids=None):
    """Sequence-parallel attention.

    q/k/v: [B, L, H, D] globally, sharded along L over ``axis_name``.
    Returns [B, L, H, D] with the same sharding. ``window`` > 0 applies
    sliding-window masking (key visible iff 0 <= q_pos - k_pos < window);
    ``segment_ids`` [B, L] (sharded like the sequence) restricts attention
    to keys in the same segment (packed documents).
    """
    qspec = P(batch_spec, axis_name, None, None) if batch_spec else \
        P(None, axis_name, None, None)
    sspec = P(batch_spec, axis_name) if batch_spec else P(None, axis_name)
    local = functools.partial(_ring_attention_local, axis_name=axis_name,
                              causal=causal, window=window)
    if segment_ids is None:
        fn = shard_map(lambda q, k, v: local(q, k, v, None), mesh=mesh,
                       in_specs=(qspec, qspec, qspec), out_specs=qspec,
                       check_vma=False)
        return fn(q, k, v)
    fn = shard_map(local, mesh=mesh, in_specs=(qspec, qspec, qspec, sspec),
                   out_specs=qspec, check_vma=False)
    return fn(q, k, v, segment_ids.astype(jnp.int32))


def blockwise_attention(q, k, v, *, block_size: int = 512, causal: bool = True,
                        window: int = 0, segment_ids=None):
    """Single-device memory-efficient attention: the same online-softmax
    accumulation over K/V chunks without the ring — the long-context path
    when seq fits one device but the full [L, L] score matrix does not.

    window > 0 restricts each query to the last ``window`` keys (sliding
    window, HF Mistral semantics: key visible iff 0 <= q_pos - k_pos <
    window); 0 means full causal/bidirectional.

    segment_ids [B, L] (packed-document training) restricts attention to
    keys in the SAME segment — documents packed into one window never
    attend across their boundaries.
    """
    b, lq, h, d = q.shape
    lk = k.shape[1]
    block = min(block_size, lk)
    n_blocks = (lk + block - 1) // block
    pad = n_blocks * block - lk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if segment_ids is not None:
        seg_q = segment_ids
        # pad with -1: padded keys match no real segment
        seg_k = jnp.pad(segment_ids, ((0, 0), (0, pad)),
                        constant_values=-1) if pad else segment_ids
    q32 = q.astype(jnp.float32)
    m = jnp.full((b, h, lq), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((b, h, lq), dtype=jnp.float32)
    o = jnp.zeros((b, lq, h, d), dtype=jnp.float32)
    pos_q = jnp.arange(lq)

    def step(carry, i):
        m, l, o = carry
        k_blk = lax.dynamic_slice_in_dim(k, i * block, block, axis=1)
        v_blk = lax.dynamic_slice_in_dim(v, i * block, block, axis=1)
        pos_k = i * block + jnp.arange(block)
        mask = pos_k[None, :] < lk  # mask padding
        if causal:
            mask = mask & (pos_q[:, None] >= pos_k[None, :])
        else:
            mask = jnp.broadcast_to(mask, (lq, block))
        if window > 0:
            # documented bound 0 <= q_pos - k_pos < window: the lower half
            # must hold even under causal=False
            delta = pos_q[:, None] - pos_k[None, :]
            mask = mask & (delta >= 0) & (delta < window)
        if segment_ids is not None:
            seg_k_blk = lax.dynamic_slice_in_dim(seg_k, i * block, block,
                                                 axis=1)
            mask = mask[None] & (seg_q[:, :, None] == seg_k_blk[:, None, :])
        m, l, o = _block_attn(q32, k_blk.astype(jnp.float32),
                              v_blk.astype(jnp.float32), m, l, o, mask)
        return (m, l, o), None

    (m, l, o), _ = lax.scan(step, (m, l, o), jnp.arange(n_blocks))
    out = o / jnp.maximum(l[..., None].transpose(0, 2, 1, 3), 1e-30)
    return out.astype(q.dtype)


def reference_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        segment_ids=None):
    """O(L^2)-memory reference for tests. ``window``/``segment_ids`` as in
    blockwise_attention."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    lq, lk = q.shape[1], k.shape[1]
    pos_q, pos_k = jnp.arange(lq)[:, None], jnp.arange(lk)[None, :]
    if causal:
        s = jnp.where((pos_q >= pos_k)[None, None], s, NEG_INF)
    if window > 0:
        visible = (pos_q >= pos_k) & (pos_q - pos_k < window)
        s = jnp.where(visible[None, None], s, NEG_INF)
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]
        s = jnp.where(same[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)
