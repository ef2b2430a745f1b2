"""Trace-bucket analysis of the flagship train step (tuning aid).

Runs N steps of bench.bench_transformer's exact step under an xplane
trace and prints device-busy time grouped into buckets (dense fusions,
pallas kernels, optimizer-ish fusions, copies, the rest) plus the
top-K individual ops. This is the tool behind docs/PERF.md's
"where the time goes" tables.

Usage (TPU):  python tools/trace_buckets.py [steps]
Honors the TONY_BENCH_LM_* env knobs bench.py uses.
"""

from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def classify(name: str) -> str:
    from tony_tpu.profiler.xplane import hlo_op_kind

    kind = hlo_op_kind(name).lower()
    if "custom-call" in kind or "custom_call" in kind:
        return "pallas (attention/decode kernels)"
    if kind.startswith(("copy", "bitcast", "transpose", "reshape")):
        return "copies/layout"
    if "dynamic-update-slice" in kind or "dynamic-slice" in kind:
        return "dynamic slices"
    if kind.startswith(("all-reduce", "all-gather", "reduce-scatter",
                        "collective")):
        return "collectives"
    if kind == "fusion":
        return "fusions (dense + elementwise)"
    if kind.startswith(("convolution", "dot")):
        return "bare matmul/conv"
    return f"other ({kind})"


def main() -> None:
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    import jax
    import jax.numpy as jnp

    import bench
    from tony_tpu.parallel.sharding import batch_sharding
    from tony_tpu.profiler import op_totals_ms
    from tony_tpu.utils import compilecache

    compilecache.enable()
    # the EXACT benchmarked step: config/trainer/env knobs live in
    # bench.flagship_lm_setup, shared with bench_transformer
    model, trainer, batch, accum, seq, _ = bench.flagship_lm_setup(True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                model.cfg.vocab_size, jnp.int32)
    params = jax.device_get(model.init(jax.random.PRNGKey(0),
                                       jnp.zeros((1, seq), jnp.int32)))
    state = trainer.init_state(params)
    step_fn, placed = trainer.build_step(state)
    train_batch = {"tokens": jax.device_put(
        tokens, batch_sharding(trainer.mesh))}

    def fw(carry):
        new_state, metrics = step_fn(carry, train_batch)
        return new_state, metrics["loss"]

    _, placed = bench.timed_round(fw, placed, 2)  # compile + prime

    import tempfile

    logdir = tempfile.mkdtemp(prefix="tony_buckets_")
    jax.profiler.start_trace(logdir)
    out = None
    for _ in range(steps):
        placed, out = fw(placed)
    float(jnp.asarray(out).reshape(-1)[0])
    jax.profiler.stop_trace()

    totals = op_totals_ms(logdir)
    if not totals:
        print("no device plane in trace (CPU backend?)")
        return
    buckets: dict[str, float] = {}
    for name, ms in totals.items():
        buckets[classify(name)] = buckets.get(classify(name), 0.0) + ms
    total = sum(totals.values())
    print(f"\n== device-busy {total/steps:.1f} ms/step over {steps} steps "
          f"(batch {batch}, accum {accum}) ==")
    for b, ms in sorted(buckets.items(), key=lambda kv: -kv[1]):
        print(f"  {ms/steps:8.2f} ms  {100*ms/total:5.1f}%  {b}")
    print("\n== top 20 ops ==")
    for name, ms in sorted(totals.items(), key=lambda kv: -kv[1])[:20]:
        short = re.sub(r"[%.\d]+$", "", name)[:84]
        print(f"  {ms/steps:8.2f} ms  {short}")


if __name__ == "__main__":
    main()
