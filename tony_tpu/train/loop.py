"""High-level training loop: loader -> jitted step -> checkpoints/eval/logs.

No reference analog (TonY's "training loop" is the user script it execs,
SURVEY.md section 2.1 Utils.executeShell). tony-tpu ships the loop so a
job script reduces to model + loss + conf: ``fit`` wires the sharded
DataLoader, the pjit'd Trainer step, orbax checkpointing (with
coordinator-retry resume via TONY_CHECKPOINT_DIR), periodic eval, and
metric sinks into one call. Host work (logging, checkpoint scheduling)
stays off the device path: metrics are only fetched when a sink needs
them, so steps dispatch back-to-back and XLA pipelines them.
"""

from __future__ import annotations

import functools
import json
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import optax

from tony_tpu.train.checkpoint import CheckpointManager, job_checkpoint_dir
from tony_tpu.train.trainer import Trainer, TrainState

log = logging.getLogger(__name__)


@dataclass
class FitResult:
    state: TrainState
    steps_run: int
    resumed_from: int | None
    history: list[dict] = field(default_factory=list)
    # exponential moving average of params (None unless fit(ema_decay=...))
    ema_params: Any = None


class JsonlMetricsLogger:
    """Metric sink appending one JSON object per logged step — the same
    jsonl idiom as the event/history pipeline, so the portal can serve it."""

    def __init__(self, path: str):
        self.path = path
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)

    def __call__(self, step: int, metrics: dict) -> None:
        with open(self.path, "a") as f:
            f.write(json.dumps({"step": step, **metrics}) + "\n")


def fit(trainer: Trainer, params: Any, train_data: Iterable, *,
        num_steps: int | None = None,
        total_steps: int | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,
        max_checkpoints: int = 3,
        eval_data: Iterable | None = None,
        eval_fn: Callable[[Any, Any], Any] | None = None,
        eval_every: int = 0,
        log_every: int = 50,
        metric_sinks: list[Callable[[int, dict], None]] | None = None,
        ema_decay: float = 0.0,
        ) -> FitResult:
    """Train until ``train_data`` is exhausted or ``num_steps`` is reached.

    Args:
      trainer: a configured Trainer (mesh/apply_fn/optimizer/fsdp).
      params: initial params pytree (ignored when a checkpoint is restored).
      train_data: iterable of batches (e.g. tony_tpu.data.DataLoader with
        sharding= so batches arrive as global jax.Arrays).
      num_steps: cap on ADDITIONAL steps this call runs (counted from the
        restored step). For retry-resume jobs use total_steps instead.
      total_steps: absolute target step: a resumed attempt completes the
        original budget (trains total_steps - restored_step more) rather
        than a fresh num_steps. Both given -> the earlier bound wins.
      checkpoint_dir: where to save/restore; defaults to the
        coordinator-injected TONY_CHECKPOINT_DIR (tony.application.
        checkpoint-dir), making retry attempts resume automatically.
        None/absent env -> no checkpointing.
      checkpoint_every: save cadence in steps (0 = only the final save,
        which always happens when a checkpoint dir is configured).
      eval_data / eval_fn: eval_fn(params, batch) -> scalar-or-dict, run
        over all of eval_data every ``eval_every`` steps; means are logged
        under "eval/...".
      log_every: host-side logging cadence (each log forces a metrics
        fetch; between logs, steps dispatch without synchronizing).
      metric_sinks: callables (step, metrics-dict) — e.g.
        JsonlMetricsLogger — invoked at the log cadence and after eval.
      ema_decay: > 0 maintains a device-resident exponential moving
        average of params (ema = decay*ema + (1-decay)*params after every
        step; typical 0.999), returned as FitResult.ema_params — the
        standard eval/serving weights for vision and diffusion training.
        The EMA lives alongside params with the same shardings and one
        cheap fused elementwise update per step; it is NOT checkpointed —
        a retry-resumed attempt restarts the average from the restored
        params.

    Returns FitResult (final state, steps run, resume step, logged history).
    """
    resumed_from = None
    manager = None
    placed = None
    # abstract state: shapes/dtypes only, no device allocation — so a
    # resuming attempt never materializes the fresh state it would discard
    abstract = jax.eval_shape(trainer.init_state, params)
    shardings = trainer.state_shardings(abstract)
    ckpt_dir = checkpoint_dir or job_checkpoint_dir()
    if ckpt_dir:
        manager = CheckpointManager(ckpt_dir, max_to_keep=max_checkpoints)
        if manager.latest_step() is not None:
            template = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                abstract, shardings)
            restored = manager.restore(template)
            if restored is not None:
                placed = restored
                resumed_from = int(placed.step)
                log.info("fit: resumed from checkpoint step %d", resumed_from)
    if placed is None:
        placed = jax.device_put(trainer.init_state(params), shardings)
        if trainer.donate:
            # device_put can alias buffers of the CALLER's params (no-op
            # placement, or zero-copy on host platforms), and the first
            # donated step would delete them out from under the caller.
            # That reaches opt_state too when an optimizer's init stores
            # params references (lookahead-style slow weights), so detect
            # aliasing by underlying buffer pointer and copy exactly the
            # aliased leaves — fresh zeros_like opt leaves are never
            # copied, keeping init peak memory flat in the near-HBM
            # regime donation targets.
            def ptrs(x):
                try:
                    return {s.data.unsafe_buffer_pointer()
                            for s in x.addressable_shards}
                except Exception:
                    return None

            caller_bufs: set = set()
            for x in jax.tree.leaves(params):
                p = ptrs(x)
                if p:
                    caller_bufs |= p

            def fresh(x):
                p = ptrs(x)
                # unknown pointers -> copy to be safe
                if p is None or p & caller_bufs:
                    return jnp.copy(x)
                return x

            placed = jax.tree.map(fresh, placed)
    step_fn = trainer.compile_step(shardings)

    # compile the eval step once: shapes are static (drop_remainder
    # contract), and an uncompiled per-batch apply would run eager
    eval_step = jax.jit(eval_fn) if eval_fn else None

    ema_params = None
    ema_step = None
    if ema_decay:
        # deep copy, NOT a reference: step_fn donates its input state
        # (Trainer.donate default), which would delete aliased buffers out
        # from under the first EMA update
        ema_params = jax.tree.map(jnp.copy, placed.params)
        ema_step = jax.jit(functools.partial(
            optax.incremental_update, step_size=1.0 - ema_decay))

    sinks = list(metric_sinks or [])
    history: list[dict] = []
    start_step = int(placed.step)
    target = None if num_steps is None else start_step + num_steps
    if total_steps is not None:
        target = total_steps if target is None else min(target, total_steps)
    steps_run = 0
    last_metrics = None
    t0 = time.monotonic()

    def emit(step: int, metrics: dict) -> None:
        history.append({"step": step, **metrics})
        for sink in sinks:
            sink(step, metrics)

    # Log-boundary metrics are fetched ASYNCHRONOUSLY: a synchronous
    # float() at the boundary parks the host on a device->host round trip
    # while the dispatch queue drains —
    # the measured few-percent fit() overhead of r2 (VERDICT r2 #5). Instead
    # the boundary starts a device->host copy and the values are emitted at
    # the NEXT boundary (or at loop end), by which time the copy long
    # finished and float() costs nothing. Sinks therefore observe each
    # boundary one log period late, with identical (step, metrics) pairs.
    pending: tuple[int, Any, float] | None = None

    def flush_pending() -> None:
        nonlocal pending
        if pending is None:
            return
        p_step, p_metrics, p_rate = pending
        pending = None
        fetched = {k: float(v) for k, v in p_metrics.items()}
        log.info("step %d: %s (%.2f steps/s)", p_step,
                 {k: round(v, 4) for k, v in fetched.items()}, p_rate)
        emit(p_step, {**fetched, "steps_per_sec": p_rate})

    data_iter = None
    if target is None or start_step < target:  # budget not already met
        if resumed_from and hasattr(train_data, "from_step"):
            # resume the data order too: skip the batches already consumed
            data_iter = train_data.from_step(start_step)
        else:
            if resumed_from:
                log.warning(
                    "fit: resumed model state at step %d but train_data has "
                    "no from_step — the iterator restarts from its "
                    "beginning, replaying already-seen batches", resumed_from)
            data_iter = iter(train_data)

    try:
        while data_iter is not None and \
                (target is None or start_step + steps_run < target):
            try:
                batch = next(data_iter)
            except StopIteration:
                break
            placed, last_metrics = step_fn(placed, batch)
            if ema_step is not None:
                ema_params = ema_step(placed.params, ema_params)
            steps_run += 1
            step = start_step + steps_run
            if log_every and steps_run % log_every == 0:
                flush_pending()  # previous boundary's copy is done by now
                for v in last_metrics.values():
                    if hasattr(v, "copy_to_host_async"):
                        v.copy_to_host_async()
                pending = (step, last_metrics,
                           steps_run / (time.monotonic() - t0))
            if manager and checkpoint_every and \
                    steps_run % checkpoint_every == 0:
                manager.save(step, placed)
            if eval_step and eval_data is not None and eval_every and \
                    steps_run % eval_every == 0:
                flush_pending()  # keep history/sinks step-ordered
                ev = _run_eval(eval_step, placed.params, eval_data)
                if ev:
                    emit(step, ev)
    finally:
        # emit the deferred boundary even when the loop dies mid-window —
        # the last logged metrics are exactly what a crash post-mortem
        # needs. A flush failure must not mask the original exception.
        try:
            flush_pending()
        except Exception:
            log.exception("fit: failed to flush pending metrics")
        # release the loader's prefetch thread + staged device batches
        if data_iter is not None and hasattr(data_iter, "close"):
            data_iter.close()

    if manager:
        final = start_step + steps_run
        # the periodic save may already have written this exact step
        # (orbax raises StepAlreadyExists rather than overwriting)
        if manager.latest_step() != final:
            manager.save(final, placed, force=True)
        manager.wait()
        manager.close()
    return FitResult(state=placed, steps_run=steps_run,
                     resumed_from=resumed_from, history=history,
                     ema_params=ema_params)


def _run_eval(eval_fn, params, eval_data) -> dict:
    totals: dict[str, float] = {}
    n = 0
    for batch in eval_data:
        out = eval_fn(params, batch)
        if not isinstance(out, dict):
            out = {"loss": out}
        for k, v in out.items():
            totals[k] = totals.get(k, 0.0) + float(v)
        n += 1
    if n == 0:
        # a one-shot generator passed as eval_data is exhausted after the
        # first eval — surface it instead of silently logging nothing
        log.warning("fit: eval pass saw no batches (eval_data exhausted? "
                    "pass a re-iterable like a DataLoader or a list)")
        return {}
    return {f"eval/{k}": v / n for k, v in totals.items()}
