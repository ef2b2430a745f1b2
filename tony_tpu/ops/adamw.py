"""Fused AdamW: one pallas pass over (grad, param, mu, nu) per step.

The optimizer bucket of the flagship step is pure HBM bandwidth. Measured
on-chip (v5e, 378M-param tree, device-busy trace): XLA already fuses the
optax `scale_by_adam -> add_decayed -> scale -> apply_updates` chain into
elementwise fusions running at ~670 GB/s — the materialized-updates tax
the r4 trace suggested does not exist at this scale, and a straight
pallas transcription only matches it (647 GB/s; with
``input_output_aliases`` it HALVES to ~350 GB/s on this backend, so the
kernel deliberately does not alias). The real win is TRAFFIC, which a
kernel makes natural:

- **grads read in compute dtype** (bf16 halves the g pass),
- **the next step's bf16 compute params are emitted by the same pass**
  (``compute_dtype=...``): the train step's separate master->bf16 cast
  pass disappears, and the backward writes bf16 grad leaves instead of
  fp32,
- **optional bf16 moments** (``moment_dtype``): halves the mu/nu passes
  — an accuracy trade the caller opts into.

Math matches ``optax.adamw`` in fp32 (same moment update, bias
correction by ``count+1``, decoupled weight decay, final ``-lr``
scaling); every input is upcast to fp32 in VMEM before the update.

Sharding: a pallas call is opaque to GSPMD (see ops/quant.py's tensor-
parallel note), so under a sharded param tree the update runs per-leaf
under ``shard_map`` with that leaf's PartitionSpec — elementwise math
needs no collectives; every device updates its local shard. Leaves too
small or oddly shaped for the kernel fall back to plain jnp (XLA fuses
those fine; the bandwidth lives in the big matmul kernels anyway).

Reference parity note: the reference framework has no optimizer at all
(training belongs to the user script, SURVEY.md §2.5) — this is part of
tony-tpu's in-tree compute stack built for the TPU roofline.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

from tony_tpu.ops.platform import interpret_mode as _interp

_LANES = 1024  # flat leaves are viewed (rows, _LANES); fp32 tile-friendly
# 7-8 live tiles x 4 B x rows x lanes, double-buffered by Mosaic:
# 128 rows ~= 8 MB of the 16 MB VMEM budget (256 OOM'd on-chip at
# 17-18 MB); 0.5 MB DMA chunks already stream at the measured HBM rate
_BLOCK_ROWS = 128


def _min_kernel_elems() -> int:
    """Leaves with at least this many (local) elements take the pallas
    kernel; the rest take the jnp path. DEFAULT = never: measured on a
    v5e at flagship scale (before PR 1), the per-pallas-call fixed cost
    (~0.19 ms x 113 leaves) loses to XLA's own elementwise fusions,
    which already run the same 7-pass floor at ~670 GB/s — the fused
    WIN here is the compute-dtype carry + bf16 grads (jnp path), worth
    +1.1 MFU points on the flagship (218.6 vs 223.5 ms/step), while the
    all-pallas variant measured 235.2 ms. Env-tunable for
    experimentation and so dryruns/tests can force the kernel+shard_map
    composition on tiny leaves (interpret mode)."""
    import os

    return int(os.environ.get("TONY_FUSED_ADAMW_MIN_ELEMS",
                              str(1 << 62)))


def _adamw_kernel(hyp_ref, g_ref, p_ref, mu_ref, nu_ref, *out_refs,
                  b1, b2, eps, wd):
    p_out, mu_out, nu_out = out_refs[:3]
    lr = hyp_ref[0, 0]
    c1 = hyp_ref[0, 1]  # 1 / (1 - b1^t)
    c2 = hyp_ref[0, 2]  # 1 / (1 - b2^t)
    g = g_ref[:].astype(jnp.float32)
    mu = b1 * mu_ref[:].astype(jnp.float32) + (1.0 - b1) * g
    nu = b2 * nu_ref[:].astype(jnp.float32) + (1.0 - b2) * g * g
    p = p_ref[:].astype(jnp.float32)
    upd = (mu * c1) / (jnp.sqrt(nu * c2) + eps) + wd * p
    p_new = p - lr * upd
    p_out[:] = p_new.astype(p_out.dtype)
    mu_out[:] = mu.astype(mu_out.dtype)
    nu_out[:] = nu.astype(nu_out.dtype)
    if len(out_refs) == 4:  # fused master->compute cast (bf16 serving of
        out_refs[3][:] = p_new.astype(out_refs[3].dtype)  # the fwd pass)


def _leaf_update_jnp(g, p, mu, nu, lr, c1, c2, *, b1, b2, eps, wd,
                     compute_dtype=None):
    g = g.astype(jnp.float32)
    mu_n = b1 * mu.astype(jnp.float32) + (1.0 - b1) * g
    nu_n = b2 * nu.astype(jnp.float32) + (1.0 - b2) * g * g
    p32 = p.astype(jnp.float32)
    upd = (mu_n * c1) / (jnp.sqrt(nu_n * c2) + eps) + wd * p32
    p_new = p32 - lr * upd
    out = (p_new.astype(p.dtype), mu_n.astype(mu.dtype),
           nu_n.astype(nu.dtype))
    if compute_dtype is not None:
        out += (p_new.astype(compute_dtype),)
    return out


def _leaf_update_kernel(g, p, mu, nu, hyp, *, b1, b2, eps, wd,
                        compute_dtype=None, interpret: bool | None = None):
    n = p.size
    rows = n // _LANES
    br = min(_BLOCK_ROWS, rows)
    while rows % br:
        br -= 1
    view = lambda a: a.reshape(rows, _LANES)  # noqa: E731
    kern = functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd)
    tile = lambda i: (i, 0)  # noqa: E731
    out_shape = [jax.ShapeDtypeStruct((rows, _LANES), p.dtype),
                 jax.ShapeDtypeStruct((rows, _LANES), mu.dtype),
                 jax.ShapeDtypeStruct((rows, _LANES), nu.dtype)]
    if compute_dtype is not None:
        out_shape.append(jax.ShapeDtypeStruct((rows, _LANES),
                                              compute_dtype))
    outs = pl.pallas_call(
        kern,
        out_shape=tuple(out_shape),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((1, 128), lambda i: (0, 0)),
            pl.BlockSpec((br, _LANES), tile),
            pl.BlockSpec((br, _LANES), tile),
            pl.BlockSpec((br, _LANES), tile),
            pl.BlockSpec((br, _LANES), tile),
        ],
        out_specs=(pl.BlockSpec((br, _LANES), tile),) * len(out_shape),
        # NO input_output_aliases: measured on-chip (v5e) aliasing drops
        # the kernel from 647 to ~350 GB/s; buffer liveness is handled by
        # the jit-level donation of the train state instead
        interpret=_interp() if interpret is None else interpret,
    )(hyp, view(g), view(p), view(mu), view(nu))
    shape = p.shape
    return tuple(o.reshape(shape) for o in outs)


class FusedAdamWState(NamedTuple):
    count: jnp.ndarray  # int32 step counter (optax ScaleByAdamState twin)
    mu: Any
    nu: Any
    # bf16 (compute-dtype) copy of the params, emitted by the SAME fused
    # pass that writes the fp32 master — the train step forwards/backs
    # through this copy, so no separate cast pass ever runs and grads
    # arrive (and are read by the next update) in compute dtype.
    # None when the caller runs full-precision.
    compute_params: Any = None


class FusedAdamW(NamedTuple):
    """AdamW config consumed by ``fused_adamw_update`` and recognized by
    ``train.Trainer`` as the fused-optimizer flag (pass it where an optax
    transformation would go). Hyperparameters mirror ``optax.adamw``.

    ``moment_dtype`` (e.g. ``jnp.bfloat16``) stores mu/nu at reduced
    precision — halves the moment HBM passes at an accuracy cost the
    caller opts into; default fp32 matches optax bit-for-bit."""

    learning_rate: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 1e-4
    moment_dtype: Any = None

    def init(self, params, compute_dtype=None) -> FusedAdamWState:
        def zeros():
            return jax.tree.map(
                lambda p: jnp.zeros(p.shape, self.moment_dtype or p.dtype),
                params)

        compute = None
        if compute_dtype is not None:
            compute = jax.tree.map(
                lambda p: p.astype(compute_dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        return FusedAdamWState(count=jnp.zeros((), jnp.int32),
                               mu=zeros(), nu=zeros(),
                               compute_params=compute)


def fused_adamw_update(opt: FusedAdamW, grads, state: FusedAdamWState,
                       params, *, mesh: Mesh | None = None,
                       param_specs=None, compute_dtype=None):
    """One fused AdamW step: returns (new_params, new_state).

    ``param_specs`` (a pytree of PartitionSpec matching ``params``) plus
    ``mesh`` routes sharded leaves through shard_map so the kernel runs
    on local shards; replicated/absent specs run the kernel directly.
    ``compute_dtype`` emits ``state.compute_params`` from the same pass.
    """
    count = state.count + 1
    t = count.astype(jnp.float32)
    c1 = 1.0 / (1.0 - jnp.power(opt.b1, t))
    c2 = 1.0 / (1.0 - jnp.power(opt.b2, t))
    # optax-style schedules drop in: a callable learning_rate is
    # evaluated at the PRE-increment count, matching scale_by_schedule
    lr = opt.learning_rate(state.count) if callable(opt.learning_rate) \
        else opt.learning_rate
    lr = jnp.asarray(lr, jnp.float32)
    # scalars ride in one small VMEM operand: lr may be a traced schedule
    # value and t always is, so they cannot be closed over statically
    hyp = jnp.zeros((1, 128), jnp.float32)
    hyp = hyp.at[0, 0].set(lr).at[0, 1].set(c1).at[0, 2].set(c2)
    static = dict(b1=opt.b1, b2=opt.b2, eps=opt.eps, wd=opt.weight_decay)

    leaves_g, treedef = jax.tree_util.tree_flatten(grads)
    leaves_p = treedef.flatten_up_to(params)
    leaves_mu = treedef.flatten_up_to(state.mu)
    leaves_nu = treedef.flatten_up_to(state.nu)
    if param_specs is None:
        leaves_spec = [None] * len(leaves_g)
    else:
        leaves_spec = treedef.flatten_up_to(param_specs)

    out: list[list] = [[], [], [], []]
    for g, p, mu, nu, spec in zip(leaves_g, leaves_p, leaves_mu,
                                  leaves_nu, leaves_spec):
        cdt = compute_dtype if (
            compute_dtype is not None
            and jnp.issubdtype(p.dtype, jnp.floating)) else None
        sharded = (mesh is not None and spec is not None
                   and any(ax is not None for ax in spec))
        # local (per-shard) element count decides the kernel/jnp split.
        # A spec entry may be a TUPLE of axis names (P(('data','fsdp'))
        # — legal, and what batch_sharding emits on multi-axis meshes):
        # the dim splits over every named axis, so divide by each.
        n_local = p.size
        if sharded:
            for ax in spec:
                if ax is None:
                    continue
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    n_local //= mesh.shape[a]
        if n_local < _min_kernel_elems() or n_local % _LANES:
            new = _leaf_update_jnp(g, p, mu, nu, lr, c1, c2,
                                   compute_dtype=cdt, **static)
        elif sharded:
            fn = functools.partial(_leaf_update_kernel,
                                   compute_dtype=cdt, **static)
            n_out = 3 if cdt is None else 4
            new = shard_map(
                lambda g_, p_, mu_, nu_, h_: fn(g_, p_, mu_, nu_, h_),
                mesh=mesh,
                in_specs=(spec, spec, spec, spec, P(None, None)),
                out_specs=(spec,) * n_out,
                # pallas out_shapes carry no varying-mesh-axes info, so
                # the vma checker cannot type them (same as QuantDense)
                check_vma=False,
            )(g, p, mu, nu, hyp)
        else:
            new = _leaf_update_kernel(g, p, mu, nu, hyp,
                                      compute_dtype=cdt, **static)
        for i, leaf in enumerate(new):
            out[i].append(leaf)
        if cdt is None and compute_dtype is not None:
            # non-float leaf: carry the UPDATED value (new[0]), not the
            # stale input — params and compute_params must never diverge
            # (the train step differentiates through compute_params)
            out[3].append(new[0])

    unflatten = treedef.unflatten
    return unflatten(out[0]), FusedAdamWState(
        count=count, mu=unflatten(out[1]), nu=unflatten(out[2]),
        compute_params=unflatten(out[3]) if compute_dtype is not None
        else None)
