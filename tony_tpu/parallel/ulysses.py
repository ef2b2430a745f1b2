"""Ulysses-style sequence parallelism: all-to-all head redistribution.

Absent from the reference (SURVEY.md section 5.7). Complements ring
attention as the second SP backend: instead of rotating K/V blocks around
a ring, one ``all_to_all`` swaps the sequence sharding for a head sharding
— each device then holds the FULL sequence for H/n heads and runs plain
(blockwise) attention locally, followed by the inverse all_to_all.

Trade-offs vs ring (public DeepSpeed-Ulysses pattern, re-implemented for
shard_map/TPU):
- comm volume: 2 all-to-alls over activations, independent of #steps —
  cheaper than a ring when heads >= devices and ICI all-to-all is fast;
- constraint: n_heads must be divisible by the seq-axis size (ring has no
  such constraint);
- memory: holds L (full) x H/n activations per device vs ring's L/n x H.
"""

from __future__ import annotations

import functools

from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from tony_tpu.parallel.mesh import SEQ
from tony_tpu.parallel.ring_attention import blockwise_attention


def _ulysses_local(q, k, v, segments, *, axis_name: str, causal: bool,
                   block_size: int, window: int):
    """Per-shard body. Local shapes in: [B, L/n, H, D]; segments
    [B, L/n] int or None (packed-document ids, all-gathered to the full
    sequence so the local full-seq attention can mask exactly)."""
    # seq-shard -> head-shard: split heads (axis 2) n ways, gather seq (1)
    q = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    if segments is not None:
        segments = lax.all_gather(segments, axis_name, axis=1, tiled=True)
    # full-sequence attention over this device's head group
    out = blockwise_attention(q, k, v, block_size=block_size,
                              causal=causal, window=window,
                              segment_ids=segments)
    # head-shard -> seq-shard
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh, *, axis_name: str = SEQ,
                      causal: bool = True, block_size: int = 512,
                      batch_spec: P | None = None, window: int = 0,
                      segment_ids=None):
    """Sequence-parallel attention via all-to-all head redistribution.

    q/k/v: [B, L, H, D] globally, sharded along L over ``axis_name``.
    Requires H % mesh.shape[axis_name] == 0. Returns the same sharding.
    ``window`` adds sliding-window masking and ``segment_ids`` [B, L]
    packed-document masking (each device holds the full sequence
    post-all-to-all, so both cuts are local; segment ids need one cheap
    int all-gather along the seq axis).
    """
    import jax.numpy as jnp

    n = mesh.shape.get(axis_name, 1)
    heads = q.shape[2]
    if heads % n != 0:
        raise ValueError(
            f"ulysses needs n_heads ({heads}) divisible by the {axis_name!r} "
            f"axis size ({n}); use ring attention otherwise")
    qspec = P(batch_spec, axis_name, None, None) if batch_spec else \
        P(None, axis_name, None, None)
    sspec = P(batch_spec, axis_name) if batch_spec else P(None, axis_name)
    local = functools.partial(_ulysses_local, axis_name=axis_name,
                              causal=causal, block_size=block_size,
                              window=window)
    if segment_ids is None:
        fn = shard_map(lambda q, k, v: local(q, k, v, None), mesh=mesh,
                       in_specs=(qspec, qspec, qspec), out_specs=qspec,
                       check_vma=False)
        return fn(q, k, v)
    fn = shard_map(local, mesh=mesh, in_specs=(qspec, qspec, qspec, sspec),
                   out_specs=qspec, check_vma=False)
    return fn(q, k, v, segment_ids.astype(jnp.int32))
