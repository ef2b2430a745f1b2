"""The control of the comparison that decides ``correct``, and the planted
faults: run on the chip at the cell's own size when a limit is set (PERF.md
gives the readings), never by the benchmark's own runs.

    python -m benchmarks.harness.control --workload <cell> --seed <n> [...]

Serving: over the rows a run of that seed saved (``.bench_out/rows-<cell>-
<seed>.json``: its sampled prompts with the tokens it served), the reference
computed in int8 is put in the program's place — at each position the token
IT puts first — and read by the same number: how far that token's logit
lies below the float32 reference's best.

Training: the reference in int8 (straight-through backward) follows the
three steps in the program's place, and so does the reference with half of
the batch left out; each is compared with the float32 reference by the
cell's own numbers. Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

from . import common as C


def serve_control(a, seed: int, rows: list, quant: str = "int8") -> dict:
    import numpy as np

    from . import reference as R
    from .serve_child import check_rows

    low = check_rows(a, seed, rows, quant=quant)
    mask, pos, tokens = low["mask"], low["pos"], low["tokens"]
    best, at, _ = (np.asarray(x) for x in R.serve_logits(
        a, seed, tokens, pos, low["argmax"].astype(np.int32)))
    gap = np.where(mask, best - at, 0.0)
    return {"logit_gap_max": float(gap.max()),
            "logit_gap_mean": float(gap.sum() / mask.sum()),
            "served_tokens": int(mask.sum()),
            "argmax_is_float32_argmax": float(
                ((gap == 0) & mask).sum() / mask.sum())}


def train_readings(a, seed: int, job: dict, **planted) -> dict:
    """The cell's numbers with a planted reference in the program's place."""
    import numpy as np

    import jax

    from . import reference as R
    from .train_child import compare

    n = job["warmup_steps"]
    prog = R.train_reference(a, seed, job, n, keep_grads=True, **planted)
    after = jax.tree.map(np.asarray, prog.pop("params"))
    prog.pop("moved")
    gc.collect()
    ref = R.train_reference(a, seed, job, n, other_grads=prog.pop("grads"))
    ref["delta_norms"] = R.change_norms(a, seed, ref.pop("params"),
                                        ref["moved"])
    prog["delta_norms"] = R.change_norms(a, seed, after, ref.pop("moved"))
    return compare(prog, ref)["numbers"]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, nargs="+", required=True)
    p.add_argument("--rehearsal", action="store_true")
    p.add_argument("--modes", nargs="+", default=["int8", "fp8"],
                   help="int8 fp8 (controls), bf16 (a witness), half_batch")
    args = p.parse_args(argv)
    sys.path.insert(0, C.CHECKOUT)
    from benchmarks.run import resolve

    from . import weights as W

    spec = resolve(args.workload)
    C.devices(spec["cell"]["chips"], args.rehearsal)
    C.enable_compile_cache()
    a = W.arch(spec["config"], args.rehearsal)
    job = dict(spec["traffic"])
    if args.rehearsal:
        job.update(job.get("rehearsal", {}))
    for seed in args.seed:
        out = {"workload": args.workload, "seed": seed}
        if job["kind"] == "serve":
            with open(os.path.join(C.OUT_DIR, "rows-%s-%d.json" % (
                    args.workload, seed))) as f:
                rows = json.load(f)
            for q in args.modes:
                out[q] = serve_control(a, seed, rows, q)
        else:
            for q in args.modes:
                out[q] = train_readings(
                    a, seed, job, rows=job["global_batch"] // 2) \
                    if q == "half_batch" else train_readings(
                        a, seed, job, quant=q)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
