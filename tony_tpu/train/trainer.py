"""Training-step builders: pjit'd SPMD train loops over a named mesh.

The compute-side counterpart of the control plane: where the reference
delegates "training" entirely to the user script + NCCL/Gloo
(SURVEY.md section 2.5), tony-tpu ships an in-tree trainer whose gradient
exchange is XLA collectives inserted by pjit from sharding annotations.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu.ops.adamw import FusedAdamW, fused_adamw_update
from tony_tpu.parallel.sharding import batch_sharding, shard_params_by_size


@dataclass
class TrainState:
    step: jnp.ndarray
    params: Any
    opt_state: Any

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state), None


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.step, s.params, s.opt_state), None),
    lambda _, c: TrainState(*c),
)


def cross_entropy_loss(logits, labels, mask=None):
    """logits: [..., V], labels: [...] int. ``mask`` (same shape as labels,
    0/1 or bool) drops positions from the mean — e.g. packed-document
    training masking the cross-boundary target after each EOS."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@dataclass
class Trainer:
    """Builds a jitted SPMD train step.

    apply_fn(params, batch) -> loss (scalar). Shardings: params via the
    FSDP-by-size heuristic (or replicated), batch sharded on (data, fsdp).
    """

    mesh: Mesh
    apply_fn: Callable[[Any, Any], jnp.ndarray]
    optimizer: optax.GradientTransformation
    fsdp: bool = False
    donate: bool = True
    # mixed precision: keep fp32 master params + optimizer state, run the
    # forward/backward in `compute_dtype` (bf16 on TPU: MXU-native, halves
    # activation HBM). The cast happens inside the differentiated function,
    # so XLA fuses it into the first consumer of each param and autodiff
    # casts gradients back to fp32 before the optimizer — no loss scaling
    # needed on TPU since bf16 keeps fp32's exponent range.
    compute_dtype: Any = None
    # gradient accumulation: the incoming batch's leading dim is split into
    # `accum_steps` microbatches scanned inside the jitted step (grads
    # averaged, ONE optimizer update) — the way to train at a global batch
    # whose activations don't fit HBM without changing the data pipeline
    accum_steps: int = 1
    # opt-in telemetry: global_norm re-reads every grad leaf (an extra
    # full-params HBM pass per step), so the DEFAULT step computes exactly
    # the math the model requires and nothing else — the framework step
    # must cost what a hand-written step costs (BASELINE north star)
    log_grad_norm: bool = False
    # batch input shardings: None = batch dim over (data, fsdp) for every
    # leaf. A pytree (e.g. {"tokens": sh, "segments": sh2}) overrides per
    # leaf — sequence-parallel training lands seq-sharded inputs (packed
    # segment ids, pre-split sequences) without a per-step relayout.
    batch_shardings: Any = None

    def init_state(self, params) -> TrainState:
        if isinstance(self.optimizer, FusedAdamW):
            # compute-dtype carry (under accum the per-micro grads are
            # bf16 but the accumulator stays fp32 — see compile_step)
            opt_state = self.optimizer.init(
                params, compute_dtype=self.compute_dtype)
        else:
            opt_state = self.optimizer.init(params)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
        )

    def state_shardings(self, state: TrainState):
        if self.fsdp:
            p_sh = shard_params_by_size(self.mesh, state.params)
        else:
            p_sh = jax.tree.map(
                lambda _: NamedSharding(self.mesh, P()), state.params)
        o_sh = _opt_shardings_like(self.mesh, state.opt_state, p_sh,
                                   state.params)
        return TrainState(
            step=NamedSharding(self.mesh, P()),
            params=p_sh,
            opt_state=o_sh,
        )

    def compile_step(self, shardings):
        """The jitted step for a given TrainState sharding tree (shardings
        may come from a real or an abstract — jax.eval_shape — state)."""
        b_sh = self.batch_shardings if self.batch_shardings is not None \
            else batch_sharding(self.mesh)
        accum = max(self.accum_steps, 1)

        if self.compute_dtype is not None:
            cdtype = self.compute_dtype

            def to_compute(tree):
                # batch floats must be cast too: one fp32 operand would
                # promote every downstream op back to fp32 and silently
                # undo the bf16 compute/activation savings
                return jax.tree.map(
                    lambda x: x.astype(cdtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x,
                    tree)

            def loss_fn(params, batch):
                # fp32 loss: keeps the logged metric at full precision and
                # matches the accum>1 path's f32 scan carry
                return self.apply_fn(
                    to_compute(params), to_compute(batch)).astype(jnp.float32)
        else:
            loss_fn = self.apply_fn

        def grads_of(params, batch):
            if accum == 1:
                return jax.value_and_grad(loss_fn)(params, batch)

            def micro(x, sh):
                b = x.shape[0]
                if b % accum:
                    raise ValueError(
                        f"batch dim {b} not divisible by accum_steps {accum}")
                # strided split: row i -> microbatch i % accum, so each
                # device contributes an equal local slice to EVERY
                # microbatch and the sharding constraint is a local
                # relayout, not a cross-device reshard (a contiguous split
                # would move ~(accum-1)/accum of the batch over the
                # interconnect each step; row assignment is arbitrary
                # since grads are averaged over all microbatches)
                x = x.reshape(b // accum, accum, *x.shape[1:]).swapaxes(0, 1)
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(self.mesh, P(None, *sh.spec)))

            if isinstance(b_sh, NamedSharding):
                micros = jax.tree.map(lambda x: micro(x, b_sh), batch)
            else:
                # b_sh is a pytree PREFIX of batch (same contract as jit
                # in_shardings): broadcast each sharding over its subtree
                micros = jax.tree.map(
                    lambda sh, sub: jax.tree.map(
                        lambda x: micro(x, sh), sub),
                    b_sh, batch,
                    is_leaf=lambda x: isinstance(x, NamedSharding))

            def body(carry, mb):
                loss_sum, grad_sum = carry
                loss, grads = jax.value_and_grad(loss_fn)(params, mb)
                return (loss_sum + loss,
                        jax.tree.map(jnp.add, grad_sum, grads)), None

            # fp32 accumulator even when the compute carry delivers bf16
            # per-micro grads (jnp.add promotes): bf16 accumulation
            # across micros would compound rounding
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32)
                if jnp.issubdtype(p.dtype, jnp.floating)
                else jnp.zeros_like(p), params)
            (loss_sum, grad_sum), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), micros)
            scale = 1.0 / accum
            return loss_sum * scale, jax.tree.map(
                lambda g: g * scale, grad_sum)

        fused = isinstance(self.optimizer, FusedAdamW)
        # compute-dtype carry: the fused update emits the bf16 copy of
        # the new params from the SAME pass that writes the fp32 master;
        # the next step forwards/backwards through that copy. The
        # separate master->bf16 cast pass disappears, the backward
        # writes bf16 grad leaves, and the update reads them as bf16 —
        # ~3 GB/step less HBM traffic at the 386M flagship.
        carry_compute = fused and self.compute_dtype is not None
        if fused:
            # the fused path needs each param's PartitionSpec so sharded
            # leaves run their pallas update under shard_map (a pallas
            # call is opaque to GSPMD — bare pjit would all-gather)
            param_specs = jax.tree.map(lambda s: s.spec, shardings.params)

        def train_step(state: TrainState, batch):
            # under the carry, forward/backward run through the bf16
            # copy the previous update emitted: per-(micro)batch grads
            # arrive in compute dtype (the one numerics change — one
            # rounding per grad leaf; the products were bf16 with f32
            # accumulation either way) and no master->bf16 cast pass
            # ever materializes. loss_fn is reused as-is: its
            # to_compute on the carried bf16 params is an identity
            # cast XLA elides.
            diff_params = state.opt_state.compute_params \
                if carry_compute else state.params
            loss, grads = grads_of(diff_params, batch)
            if fused:
                # single fused read+write pass over g/p/mu/nu — no
                # materialized updates tree between transforms
                params, opt_state = fused_adamw_update(
                    self.optimizer, grads, state.opt_state, state.params,
                    mesh=self.mesh, param_specs=param_specs,
                    compute_dtype=self.compute_dtype
                    if carry_compute else None)
            else:
                updates, opt_state = self.optimizer.update(
                    grads, state.opt_state, state.params)
                params = optax.apply_updates(state.params, updates)
            metrics = {"loss": loss}
            if self.log_grad_norm:
                # fp32 accumulation even when the carry delivers bf16
                # grads: the metric must stay comparable across the
                # optimizer flag (squares at 8-bit mantissa drift)
                metrics["grad_norm"] = optax.global_norm(
                    jax.tree.map(lambda g_: g_.astype(jnp.float32),
                                 grads))
            new_state = TrainState(step=state.step + 1, params=params,
                                   opt_state=opt_state)
            return new_state, metrics

        metric_sh = {"loss": NamedSharding(self.mesh, P())}
        if self.log_grad_norm:
            metric_sh["grad_norm"] = NamedSharding(self.mesh, P())
        # b_sh is a pytree prefix: one sharding broadcast over the batch tree
        return jax.jit(
            train_step,
            in_shardings=(shardings, b_sh),
            out_shardings=(shardings, metric_sh),
            donate_argnums=(0,) if self.donate else (),
        )

    def build_step(self, state: TrainState):
        """Returns (step_fn, placed_state). step_fn(state, batch) ->
        (state, metrics)."""
        shardings = self.state_shardings(state)
        return self.compile_step(shardings), jax.device_put(state, shardings)


def build_train_step(mesh: Mesh, apply_fn, optimizer, params, fsdp=False):
    """One-call convenience: returns (step_fn, state)."""
    trainer = Trainer(mesh=mesh, apply_fn=apply_fn, optimizer=optimizer,
                      fsdp=fsdp)
    state = trainer.init_state(params)
    return trainer.build_step(state)


def _opt_shardings_like(mesh, opt_state, param_shardings, params):
    """Optimizer-state shardings: leaves shaped like a param get that
    param's sharding (momentum/adam moments); everything else replicated."""
    flat_params, _ = jax.tree_util.tree_flatten(params)
    flat_shard, _ = jax.tree_util.tree_flatten(param_shardings)
    by_shape, by_shape_only = {}, {}
    for p, s in zip(flat_params, flat_shard):
        by_shape.setdefault((p.shape, p.dtype), s)
        # dtype-blind fallback: FusedAdamW's compute_params mirror the
        # params at compute dtype and must shard identically
        by_shape_only.setdefault(p.shape, s)

    def pick(leaf):
        if hasattr(leaf, "shape"):
            s = by_shape.get((leaf.shape, leaf.dtype)) \
                or by_shape_only.get(leaf.shape)
            if s is not None:
                return s
        return NamedSharding(mesh, P())

    return jax.tree.map(pick, opt_state)
