"""The child that holds the chip for a training cell.

Set-up builds ONE object — the compiled ``Trainer`` step with its state,
as ``train.fit`` makes them (``init_state`` -> ``device_put`` ->
``compile_step``) — drives it from the seed through its first three
steps (every row of every batch different), reading after step 1 the
per-leaf norm of the first gradient out of the optimizer's first moment
(``mu = (1 - b1) g``) and after step 3 the per-leaf norm of the
parameters' change, and hands that same object to the window. Once the
window has closed and the state is dropped, the plain reference follows
the same three steps.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import statistics
import sys
import time

from . import common as C


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``: each step's loss against the
    reference's; the first gradient and the three-step change by the
    WORST leaf — the gap between the program's norm and the reference's
    over the larger of the reference's norm of that leaf and of the
    median leaf. The change is taken over the elements whose reference
    gradient is not nought to rounding (``reference.train_reference``)."""
    out = {f"loss_step{i + 1}_rel": abs(p - r) / abs(r)
           for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]))}
    worst = {}
    for what in ("grad_norms", "delta_norms"):
        med = statistics.median(ref[what].values())
        worst[what] = max(
            (abs(prog[what][n] - r) / max(r, med), n)
            for n, r in ref[what].items())
    # and, by the worst leaf, the norm of the first gradients' DIFFERENCE
    # over the same measure: the one number that is first-order in the
    # arithmetic's rounding (a gap of norms is second-order in it)
    med = statistics.median(ref["grad_norms"].values())
    worst["diff"] = max((d / max(ref["grad_norms"][n], med), n)
                        for n, d in ref["grad_diff_norms"].items())
    out.update(grad_norm_worst_leaf=worst["grad_norms"][0],
               delta_norm_worst_leaf=worst["delta_norms"][0],
               grad_diff_worst_leaf=worst["diff"][0])
    return {"numbers": out, "worst_grad_leaf": worst["grad_norms"][1],
            "worst_delta_leaf": worst["delta_norms"][1],
            "worst_grad_diff_leaf": worst["diff"][1]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--queries", default="{}")
    p.add_argument("--rehearsal", action="store_true")
    p.add_argument("--fault", default="",
                   help="tests only: unchanged | half_batch")
    args = p.parse_args(argv)
    sys.path.insert(0, C.CHECKOUT)

    with open(args.config) as f:
        cfg = json.load(f)
    with open(args.traffic) as f:
        job = json.load(f)
    if args.rehearsal:
        job.update(job.get("rehearsal", {}))
    device = C.devices(args.chips, args.rehearsal)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import adapter, reference as R, weights as W

    cache_dir = C.enable_compile_cache()
    meter = C.CompileMeter()
    a = W.arch(cfg, args.rehearsal)
    rows, seq = job["global_batch"], job["seq_len"]
    opt = job["optimizer"]

    from tony_tpu.models import Transformer
    from tony_tpu.ops import chunked_cross_entropy
    from tony_tpu.parallel import data_parallel_mesh
    from tony_tpu.train import FusedAdamW, Trainer

    mesh = data_parallel_mesh(devices=jax.devices()[:args.chips])
    model = Transformer(adapter.program_config(
        a, jnp.bfloat16, attention_backend=job["attention_backend"],
        attention_block_size=job["block_q"], attention_block_k=job["block_k"],
        remat=True, remat_policy=job["remat_policy"],
        mesh=mesh if args.chips > 1 else None))

    def apply_fn(params, batch):
        hidden = model.apply({"params": params}, batch["tokens"],
                             return_hidden=True)
        return chunked_cross_entropy(
            hidden[:, :-1], params["lm_head"], batch["tokens"][:, 1:],
            chunk_size=job["ce_chunk"], compute_dtype={
                "bfloat16": jnp.bfloat16, "float32": None}[
                    job["ce_compute_dtype"]])

    trainer = Trainer(
        mesh=mesh, apply_fn=apply_fn, donate=True,
        compute_dtype=jnp.bfloat16, optimizer=FusedAdamW(
            opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"]))
    t0 = time.monotonic()
    params = adapter.seeded_params(a, args.seed, jnp.float32)
    shardings = trainer.state_shardings(
        jax.eval_shape(trainer.init_state, params))
    state = jax.device_put(trainer.init_state(params), shardings)
    # init_state keeps the caller's leaves as the master weights and the
    # step donates them: this process holds no other reference
    del params
    step_fn = trainer.compile_step(shardings)
    data_key = W.root_key(args.seed, 1)
    from tony_tpu.parallel.sharding import batch_sharding

    n_pool = job["batch_pool"]
    pool = jax.jit(lambda k: jnp.stack([
        W.token_batch(a, k, i, rows, seq) for i in range(n_pool)]))(data_key)
    b_sh = batch_sharding(mesh)
    batches = [{"tokens": jax.device_put(pool[i], b_sh)}
               for i in range(n_pool)]
    names = adapter.leaf_names(a)
    norms = jax.jit(lambda tree: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        tree))
    def by_name(tree) -> dict:
        return {n: float(v) for n, v in zip(
            jax.tree.leaves(names), jax.tree.leaves(tree))}

    if args.fault and not args.rehearsal:
        raise SystemExit("--fault is for the rehearsal's tests")
    if args.fault == "unchanged":      # a step that returns its state
        real_step = step_fn            # unchanged (the step donates, so
                                       # it is given a copy)
        def step_fn(st, batch):
            _, m = real_step(jax.tree.map(jnp.copy, st), batch)
            return st, m
    elif args.fault == "half_batch":   # half of the batch left out, the
        batches = [{"tokens": jax.device_put(   # mean taken over the rest
            b["tokens"][:rows // 2], b_sh)} for b in batches]

    # the first three steps: through the window's own call and feed
    prog, warm_s = {"losses": []}, []
    for i in range(job["warmup_steps"]):
        t_step = time.monotonic()
        state, metrics = step_fn(state, batches[i % n_pool])
        prog["losses"].append(float(metrics["loss"]))
        warm_s.append(time.monotonic() - t_step)
        if i == 0:   # Adam's first moment after one step is (1 - b1) g
            prog["grad_norms"] = {
                n: v / (1.0 - opt["b1"])
                for n, v in by_name(norms(state.opt_state.mu)).items()}
            first_grads = {n: np.asarray(v) / np.float32(1.0 - opt["b1"])
                           for n, v in zip(jax.tree.leaves(names),
                                           jax.tree.leaves(state.opt_state.mu))}
    # the parameters after the three steps, kept on the HOST for the
    # comparison (the step donates its state, and the device is the
    # reference's once the window has closed)
    after = {n: np.asarray(v) for n, v in zip(
        jax.tree.leaves(names), jax.tree.leaves(state.params))}
    if args.trace:
        C.start_trace("warm")
        C.stop_trace()
    C.emit("ready", device=device, setup={
        "compile_cache": cache_dir, "state_s": round(time.monotonic() - t0, 2),
        **meter.report()})

    # ------------------------------------------------------- the window
    # The host's cores are shared and the machine stands still now and
    # then, for a second or two. So that the chip stays fed meanwhile,
    # steps are dispatched ``dispatch_ahead_s`` of device time ahead of
    # the one waited for (by the warm steps' time: the first compiles or
    # loads); no loss is read in the window. When the time is up nothing
    # more is sent, all that was sent is waited for, and the clock is read
    # after that wait: all of that work counts, over all of that time.
    ahead = max(1, min(job["max_steps_in_flight"], round(
        job["dispatch_ahead_s"] / min(warm_s[1:] or warm_s))))
    compiled = meter.requests
    trace_dir, traced = None, {}
    step_ms, n_steps, sent = [], 0, collections.deque()
    trace_at = (args.seconds * 0.35,
                min(job["trace_seconds"], args.seconds * 0.3))
    t_open = time.monotonic()
    while True:
        t_step = time.monotonic()
        now = t_step - t_open
        if args.trace and trace_dir is None and now >= trace_at[0]:
            jax.block_until_ready(state.step)
            trace_dir = C.start_trace("train")
            traced = {"t0": time.monotonic(), "step0": n_steps}
        if trace_dir and "t1" not in traced \
                and t_step - traced["t0"] >= trace_at[1]:
            jax.block_until_ready(state.step)
            traced.update(t1=time.monotonic(), step1=n_steps)
            C.stop_trace()
        if now >= args.seconds:
            break
        state, metrics = step_fn(state, batches[
            (job["warmup_steps"] + n_steps) % n_pool])
        n_steps += 1
        sent.append(metrics["loss"])
        # inside a traced span one step ahead, so that the span closes on
        # the second: the device is as busy, and a stall there costs no
        # end-to-end metric
        while len(sent) > (1 if trace_dir and "t1" not in traced else ahead):
            jax.block_until_ready(sent.popleft())
        step_ms.append((time.monotonic() - t_step) * 1e3)
    jax.block_until_ready(state.step)
    window_s = time.monotonic() - t_open
    last_loss = float(metrics["loss"])
    window = {
        "steps": n_steps, "window_s": window_s,
        "tokens": n_steps * rows * seq, "last_loss": last_loss,
        "step_host_ms": step_ms, "steps_in_flight": ahead,
        "compiles_in_window":
        meter.requests - compiled, "memory_peak_bytes": C.memory_peak_bytes(),
        "memory_in_use_bytes": C.memory_in_use_bytes()}
    if traced:
        window["traced"] = {
            "span_s": traced["t1"] - traced["t0"],
            "steps": traced["step1"] - traced["step0"],
            "tokens": (traced["step1"] - traced["step0"]) * rows * seq}
    C.emit("window", **window)

    # the program's state goes before the reference runs
    del state, step_fn, batches, pool, trainer, model, metrics, sent
    gc.collect()
    jax.clear_caches()
    gc.collect()
    final = {"memory_in_use_after_free": C.memory_in_use_bytes()}
    if trace_dir:
        final["trace"] = C.reduce_trace(trace_dir, json.loads(args.queries),
                                        device["platform"])
    t1 = time.monotonic()
    ref = R.train_reference(a, args.seed, job, job["warmup_steps"],
                            other_grads=R.stacked(a, first_grads))
    del first_grads
    ref["delta_norms"] = R.change_norms(a, args.seed, ref.pop("params"),
                                        ref["moved"])
    prog["delta_norms"] = R.change_norms(a, args.seed, R.stacked(a, after),
                                         ref.pop("moved"))
    final["check"] = compare(prog, ref)
    final["check"]["reference_step_s"] = ref["step_s"]
    os.makedirs(C.OUT_DIR, exist_ok=True)
    with open(os.path.join(C.OUT_DIR, "leaves-train-%d.json" % args.seed),
              "w") as f:   # every leaf's norms, for whoever sets a limit
        json.dump({k: {n: [prog[k][n], v] for n, v in ref[k].items()}
                   for k in ("grad_norms", "delta_norms")}
                  | {"grad_diff_norms": ref["grad_diff_norms"]}, f)
    final["check"]["reference_s"] = round(time.monotonic() - t1, 2)
    final["check"]["program_losses"] = prog["losses"]
    final["check"]["reference_losses"] = ref["losses"]
    C.emit("final", **final)
    return 0


if __name__ == "__main__":
    sys.exit(main())
