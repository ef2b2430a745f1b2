"""Pipeline parallelism over the ``pipe`` mesh axis.

Absent from the reference (its TaskScheduler DAG sequences *jobs*, not
micro-batches — SURVEY.md section 2.4). Two schedules:

- GPipe (default): each pipe-axis device holds one stage's parameters
  (stacked along a leading "layers" dim sharded on ``pipe``); activations
  flow stage-to-stage via ``lax.ppermute`` inside a ``lax.scan`` bubble
  schedule. Bubble: (n_stages - 1) ticks of one stage's work per tick.
- Interleaved/circular (``circular_repeats=R > 1``, the Megatron-style
  schedule): n_stages * R virtual stages round-robin over the same ring
  (device d holds virtual stages {r*n + d}), microbatches injected in
  groups of n. Same per-device parameter count as stacking R layers into
  one GPipe stage, but the bubble stays (n - 1) ticks of ONE virtual
  stage's work — R times smaller.

Both are differentiable and jit-compatible (static schedule lengths).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from tony_tpu.parallel.mesh import PIPE


def _pipeline_local(stage_params, x_micro, *, stage_fn, axis_name):
    """Body under shard_map.

    stage_params: this stage's param tree (leading stacked dim stripped
      to size 1 by sharding; squeezed before use).
    x_micro: [n_micro, mb, ...] full microbatched input (replicated).
    Returns [n_micro, mb, ...] outputs (valid on every device after psum).
    """
    n_stages = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: p[0], stage_params)  # strip stacked dim
    n_micro = x_micro.shape[0]
    total = n_micro + n_stages - 1
    out_buf = jnp.zeros_like(x_micro)
    carry_act = jnp.zeros_like(x_micro[0])

    def step(state, t):
        carry_act, out_buf = state
        # stage 0 ingests microbatch t (clamped; masked later)
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        inp = jnp.where(stage == 0, x_micro[mb_idx], carry_act)
        y = stage_fn(params, inp)
        # last stage writes finished microbatch t-(n_stages-1)
        out_idx = t - (n_stages - 1)
        valid_out = (stage == n_stages - 1) & (out_idx >= 0) & (out_idx < n_micro)
        out_buf = lax.cond(
            valid_out,
            lambda b: lax.dynamic_update_index_in_dim(b, y, jnp.maximum(out_idx, 0), 0),
            lambda b: b,
            out_buf,
        )
        # shift activations to the next stage
        perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
        carry_act = lax.ppermute(y, axis_name, perm)
        return (carry_act, out_buf), None

    (carry_act, out_buf), _ = lax.scan(step, (carry_act, out_buf),
                                       jnp.arange(total))
    # outputs only live on the last stage; broadcast over the ring
    mask = (stage == n_stages - 1).astype(out_buf.dtype)
    return lax.psum(out_buf * mask, axis_name)


def _circular_local(stage_params, x_micro, *, stage_fn, axis_name,
                    n_stages: int, repeats: int, n_micro: int):
    """Interleaved schedule body under shard_map.

    stage_params: this device's [R, ...] virtual-stage params (device-major
      interleaving done by the caller: local rep r = virtual stage r*n + d).
    x_micro: [n_micro, mb, ...] microbatched input (replicated).

    Schedule: microbatch m enters virtual stage v at tick
      t(m, v) = (m // n) * n * R + (m % n) + v
    (conflict-free: each device runs at most one stage_fn per tick), so a
    microbatch advances one virtual stage — one ring hop — every tick, and
    injections pause between groups while earlier microbatches loop around
    the ring. Total ticks: t(n_micro-1, V-1) + 1.
    """
    d = lax.axis_index(axis_name)
    V = n_stages * repeats
    total = ((n_micro - 1) // n_stages) * n_stages * repeats \
        + ((n_micro - 1) % n_stages) + V
    out_buf = jnp.zeros_like(x_micro)
    # carry slot per device: activation + its virtual stage v + microbatch m
    act0 = jnp.zeros_like(x_micro[0])
    state0 = (act0, jnp.int32(-1), jnp.int32(0), out_buf)

    def step(state, t):
        act, v, m, out_buf = state
        # device 0 injection: tick t carries microbatch m_cand iff the
        # in-group offset (t mod n*R) is < n
        tmod = t % (n_stages * repeats)
        m_cand = (t // (n_stages * repeats)) * n_stages + tmod
        inject = (d == 0) & (tmod < n_stages) & (m_cand < n_micro)
        act = jnp.where(inject, x_micro[jnp.clip(m_cand, 0, n_micro - 1)],
                        act)
        v = jnp.where(inject, 0, v)
        m = jnp.where(inject, m_cand, m)

        active = (v >= 0) & (v < V)
        rep = jnp.clip(v // n_stages, 0, repeats - 1)
        params_r = jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(p, rep, 0, keepdims=False),
            stage_params)

        def run(operand):
            p, a = operand
            return stage_fn(p, a)

        y = lax.cond(active, run, lambda operand: operand[1],
                     (params_r, act))
        # last virtual stage (necessarily device n-1) emits the microbatch
        done = active & (v == V - 1)
        out_buf = lax.cond(
            done,
            lambda b: lax.dynamic_update_index_in_dim(
                b, y, jnp.clip(m, 0, n_micro - 1), 0),
            lambda b: b,
            out_buf,
        )
        v_next = jnp.where(active & ~done, v + 1, -1)
        perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
        act = lax.ppermute(y, axis_name, perm)
        v_next = lax.ppermute(v_next, axis_name, perm)
        m = lax.ppermute(m, axis_name, perm)
        return (act, v_next, m, out_buf), None

    (_, _, _, out_buf), _ = lax.scan(step, state0, jnp.arange(total))
    # each finished microbatch was written on device n-1 only
    mask = (d == n_stages - 1).astype(out_buf.dtype)
    return lax.psum(out_buf * mask, axis_name)


def interleave_stage_params(stacked_params, n_stages: int, repeats: int):
    """Pipeline-order [V, ...] stack -> device-major order for the
    interleaved schedule (device d's contiguous rows become its virtual
    stages [r*n + d]). Do this ONCE at setup and pass
    ``interleaved=True``: the permutation is a cross-device reshuffle of
    every parameter when the stack is pipe-sharded, not something to pay
    per training step."""
    perm = jnp.asarray([r * n_stages + d for d in range(n_stages)
                        for r in range(repeats)])
    return jax.tree.map(lambda p: p[perm], stacked_params)


def pipeline_apply(stage_fn: Callable, stacked_params, x, *, mesh: Mesh,
                   n_microbatches: int, axis_name: str = PIPE,
                   remat: bool = False, circular_repeats: int = 1,
                   interleaved: bool = False, batch_axis: str | None = None,
                   param_specs=None):
    """Run ``x`` through ``n_stages`` pipeline stages.

    stage_fn(params, x_mb) -> y_mb with y_mb.shape == x_mb.shape (uniform
      inter-stage activation shape, standard for decoder stacks).
    stacked_params: pytree whose leaves have leading dim n_stages (sharded
      along ``axis_name``).
    x: [batch, ...]; batch must divide by n_microbatches.
    remat: rematerialize each stage call in the backward pass — activation
      memory per device drops from O(schedule_len x stage_activations) to
      O(schedule_len x microbatch) at the cost of one extra forward, the
      standard trade for deep pipelines on HBM-bound TPUs.
    circular_repeats: R > 1 selects the interleaved (Megatron-style)
      schedule: stacked_params' leading dim must be n_stages * R virtual
      stages in PIPELINE ORDER (stage v runs on device v % n_stages);
      bubble shrinks from (n-1) R-deep ticks to (n-1) 1-deep ticks.
    interleaved: the circular stacked_params are ALREADY device-major
      (pre-permuted once at setup by ``interleave_stage_params``). Without
      it, pipeline_apply permutes per call — a full cross-device reshuffle
      of the parameters every step when the stack lives pipe-sharded, so
      training loops should pre-interleave.
    batch_axis: mesh axis to shard the per-microbatch batch dim over
      (data parallelism composed with the pipeline: each data shard runs
      the same schedule on its slice; grad reduction over the axis is the
      shard_map transpose of the params' replication — automatic).
    param_specs: pytree of PartitionSpecs for stacked_params composing
      OTHER mesh axes into the stage weights (tensor parallelism: e.g.
      ``P(PIPE, None, TENSOR)``; stage_fn is then responsible for the
      matching ``lax.psum`` over the tensor axis, Megatron-style). Every
      leaf spec must lead with ``axis_name``. Default: ``P(axis_name)``.
    """
    n_stages = mesh.shape[axis_name]
    if circular_repeats < 1:
        raise ValueError(f"circular_repeats must be >= 1, "
                         f"got {circular_repeats}")
    lead = jax.tree.leaves(stacked_params)[0].shape[0]
    if lead != n_stages * circular_repeats:
        raise ValueError(
            f"{n_stages} pipe devices x circular_repeats={circular_repeats} "
            f"needs {n_stages * circular_repeats} stacked virtual stages, "
            f"got leading dim {lead}")
    batch = x.shape[0]
    if batch % n_microbatches:
        raise ValueError(f"batch {batch} % n_microbatches {n_microbatches} != 0")
    x_micro = x.reshape(n_microbatches, batch // n_microbatches, *x.shape[1:])

    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    if circular_repeats > 1:
        if not interleaved:
            stacked_params = interleave_stage_params(
                stacked_params, n_stages, circular_repeats)
        local = functools.partial(
            _circular_local, stage_fn=stage_fn, axis_name=axis_name,
            n_stages=n_stages, repeats=circular_repeats,
            n_micro=n_microbatches)
    else:
        local = functools.partial(_pipeline_local, stage_fn=stage_fn,
                                  axis_name=axis_name)

    if param_specs is None:
        params_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    else:
        for leaf in jax.tree.leaves(param_specs,
                                    is_leaf=lambda s: isinstance(s, P)):
            if not leaf or leaf[0] != axis_name:
                raise ValueError(
                    f"param_specs leaves must lead with the pipe axis "
                    f"{axis_name!r}, got {leaf}")
        params_specs = param_specs
    x_spec = P(None, batch_axis) if batch_axis else P()
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(params_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    out = fn(stacked_params, x_micro)
    return out.reshape(batch, *x.shape[1:])


def stack_stage_params(per_stage_params: list) -> dict:
    """Stack per-stage param trees along a new leading dim for pipe sharding."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)
