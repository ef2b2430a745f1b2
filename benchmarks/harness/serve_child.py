"""The child that holds the chip for a serving cell.

It builds the model from the seed, runs the real serving stack in this
process (``cli.gateway.build_parser()`` flags, ``cli.gateway.serve()``:
edge -> core -> ``serve.Server`` -> paged cache -> model), warms every
shape the traffic will use over real HTTP, and says ``ready``. The
parent then drives the window and sends commands on this child's stdin:

    {"cmd": "trace_start"} / {"cmd": "trace_stop"}
    {"cmd": "window_open"} / {"cmd": "window_close"}
    {"cmd": "check", "rows": [[prompt, served], ...]}

and ends with a real SIGTERM: the gateway drains, ``serve()`` returns,
the program's state is dropped, and only then does the plain reference
run over the sampled rows (``reference.serve_logits``).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

from . import common as C


def warm_up(url: str, a, mix: dict, batch: int, seed: int) -> dict:
    """One request into every prefill bucket the mix can reach, each
    decoding a few tokens so that decode meets every page-view bucket
    (the view is the power of two above the longest live row), then a
    burst that fills every slot."""
    import random
    import threading as th

    from .loadgen import generate_blocking

    rng = random.Random(seed ^ 0x5EED)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    top = min(hi + mix["output_tokens"]["max"], a.max_len)
    lengths, n = [], lo
    while n < hi:
        lengths.append(n)
        n *= 2
    lengths.append(hi)
    t0 = time.monotonic()
    for n in lengths:
        prompt = [rng.randrange(1, a.vocab) for _ in range(n)]
        generate_blocking(url, prompt, min(8, top - n))
    errors = []

    def one(n):
        try:
            generate_blocking(
                url, [rng.randrange(1, a.vocab) for _ in range(n)], 12)
        except Exception as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    burst = [th.Thread(target=one, args=(lengths[i % len(lengths)],))
             for i in range(2 * batch)]
    for t in burst:
        t.start()
    for t in burst:
        t.join()
    if errors:
        raise errors[0]
    return {"warmup_requests": len(lengths) + len(burst),
            "warmup_s": round(time.monotonic() - t0, 2)}


def check_rows(a, seed: int, rows: list, quant: str = "") -> dict:
    """The reference over each sampled request's prompt and served
    tokens: by how much a served token's logit lies below the
    reference's best, the widest over all served tokens."""
    import numpy as np

    from . import reference as R

    n = len(rows)
    width = a.max_len
    p_max = max(len(served) for _, served in rows)
    p_max = -(-p_max // 64) * 64
    tokens = np.zeros((n, width), np.int32)
    pos = np.zeros((n, p_max), np.int32)
    target = np.zeros((n, p_max), np.int32)
    mask = np.zeros((n, p_max), bool)
    for i, (prompt, served) in enumerate(rows):
        seq = (list(prompt) + list(served))[:width]
        tokens[i, :len(seq)] = seq
        k = len(served)
        pos[i, :k] = np.arange(len(prompt) - 1, len(prompt) - 1 + k)
        target[i, :k] = served
        mask[i, :k] = True
    best, at, argmax = (np.asarray(x) for x in R.serve_logits(
        a, seed, tokens, pos, target, quant=quant))
    gap = np.where(mask, best - at, 0.0)
    return {"rows": n, "served_tokens": int(mask.sum()),
            "logit_gap_max": float(gap.max()),
            "logit_gap_mean": float(gap.sum() / mask.sum()),
            "argmax_agree": float(((argmax == target) & mask).sum()
                                  / mask.sum()),
            "best_logit_mean": float((best * mask).sum() / mask.sum()),
            "argmax": argmax, "mask": mask, "pos": pos, "tokens": tokens}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--queries", default="{}")
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args(argv)
    sys.path.insert(0, C.CHECKOUT)

    with open(args.config) as f:
        cfg = json.load(f)
    with open(args.traffic) as f:
        mix = json.load(f)
    if args.rehearsal:
        mix.update(mix.get("rehearsal", {}))
    device = C.devices(args.chips, args.rehearsal)
    import jax
    import jax.numpy as jnp

    from . import adapter, weights as W

    cache_dir = C.enable_compile_cache()
    meter = C.CompileMeter()
    a = W.arch(cfg, args.rehearsal)
    flags = (cfg["rehearsal"] if args.rehearsal else cfg)["serve_flags"]

    from tony_tpu.cli.gateway import build_parser, serve
    from tony_tpu.models import Transformer

    gw_args = build_parser().parse_args(["--port", "0"] + flags)
    t0 = time.monotonic()
    model = Transformer(adapter.program_config(a, jnp.bfloat16))
    params = adapter.seeded_params(a, args.seed, jnp.bfloat16)
    jax.block_until_ready(params)
    weights_s = time.monotonic() - t0
    state: dict = {"rows": None, "trace_dir": None, "error": None}

    def control(http):
        try:
            url = f"http://{http.host}:{http.port}"
            setup = warm_up(url, a, mix, gw_args.serve_batch, args.seed)
            if args.trace:  # a throw-away start/stop: the plug-in's
                C.start_trace("warm")  # first start blocks for seconds
                C.stop_trace()
            C.emit("ready", host=http.host, port=http.port, device=device,
                   setup={**setup, "weights_s": round(weights_s, 2),
                          "compile_cache": cache_dir,
                          "serve_batch": gw_args.serve_batch,
                          **meter.report()})
            mark = None
            for line in sys.stdin:
                cmd = json.loads(line)
                if cmd["cmd"] == "window_open":
                    mark = (meter.requests, meter.hits, meter.seconds)
                elif cmd["cmd"] == "trace_start":
                    state["trace_dir"] = C.start_trace("serve")
                    C.emit("trace_started")
                elif cmd["cmd"] == "trace_stop":
                    C.stop_trace()
                    C.emit("trace_stopped")
                elif cmd["cmd"] == "window_close":
                    C.emit("window",
                           compiles_in_window=meter.requests - mark[0],
                           compile_s_in_window=meter.seconds - mark[2],
                           memory_peak_bytes=C.memory_peak_bytes(),
                           memory_in_use_bytes=C.memory_in_use_bytes())
                elif cmd["cmd"] == "check":
                    state["rows"] = cmd["rows"]
                    C.emit("check_received")
                    return
        except BaseException as e:  # noqa: BLE001 — reported below
            state["error"] = e
            import traceback

            traceback.print_exc()
            C.emit("failed", error=f"{type(e).__name__}: {e}")
            os.kill(os.getpid(), 15)

    def on_ready(http):
        threading.Thread(target=control, args=(http,), daemon=True).start()

    rc = serve(gw_args, model, params, [], on_ready=on_ready)
    if state["error"] is not None:
        return 3
    # the program's state goes before the reference runs
    del params, model
    gc.collect()
    jax.clear_caches()
    gc.collect()
    final = {"drain_exit_code": rc,
             "memory_in_use_after_free": C.memory_in_use_bytes()}
    if state["trace_dir"]:
        t1 = time.monotonic()
        final["trace"] = C.reduce_trace(
            state["trace_dir"], json.loads(args.queries), device["platform"])
        final["trace_reduce_s"] = round(time.monotonic() - t1, 2)
    if state["rows"]:
        t1 = time.monotonic()
        res = check_rows(a, args.seed, state["rows"])
        final["check"] = {k: v for k, v in res.items()
                          if not hasattr(v, "shape")}
        final["check"]["reference_s"] = round(time.monotonic() - t1, 2)
    C.emit("final", **final)
    return 0


if __name__ == "__main__":
    sys.exit(main())
