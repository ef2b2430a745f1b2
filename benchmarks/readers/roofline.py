"""Shares of the chip's peaks: the least time the chip could take for
the work (the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s, both from shapes by ``harness/work.py``) over the device time
the trace shows. Returns None — never 0 — when the trace holds nothing
to divide by, or the device has no published peaks (the rehearsal).

  work=decode_step      one decode step: weights + the live K/V of the
                        batch (time-average over the traced span, from
                        the client's token stamps); time = per call of
                        ``program``
  work=flash_attention  the causal attention of the traced steps; time =
                        the events of the metric's ``trace_query``
  work=serve_step       model FLOPs of every prompt and output token the
                        traced span processed; time = the span
  work=train_step       forward+backward FLOPs of the traced steps'
                        tokens; time = the span
"""

from __future__ import annotations

from benchmarks.harness import work as K
from benchmarks.harness.loadgen import span_work
from benchmarks.readers.trace_program_ms import program_time


def read(ctx: dict, work: str, program: str = "", query: str = ""):
    trace, pk, a = ctx.get("trace"), ctx.get("peaks"), ctx["arch"]
    if not trace or not pk or not trace.get("window_s"):
        return None
    if work == "decode_step":
        calls, secs = program_time(trace, program)
        lo, hi = ctx["trace_span"]
        if not calls or lo is None or hi is None:
            return None
        seen = span_work(ctx["records"], lo, hi)
        live = seen["live_tokens_mean"]
        batch = min(ctx["serve_batch"], seen["out_tokens"] / max(calls, 1))
        least = max(K.decode_step_bytes(a, live) / pk["hbm_bytes_per_s"],
                    K.decode_step_flops(a, batch, live) / pk["bf16_flops"])
        return 100.0 * least / (secs / calls)
    if work == "flash_attention":
        found = (trace.get("queries") or {}).get(query)
        steps = (ctx["window"].get("traced") or {}).get("steps")
        if not found or not found["total_s"] or not steps:
            return None
        job = ctx["job"]
        flops = steps * K.flash_flops_per_step(a, job["global_batch"],
                                               job["seq_len"])
        return 100.0 * flops / pk["bf16_flops"] / found["total_s"]
    if work == "serve_step":
        lo, hi = ctx["trace_span"]
        if lo is None or hi is None:
            return None
        seen = span_work(ctx["records"], lo, hi)
        flops = sum(K.serve_token_flops(a, p, s) for p, s in seen["positions"])
        return 100.0 * flops / pk["bf16_flops"] / (hi - lo)
    if work == "train_step":
        traced = ctx["window"].get("traced")
        if not traced or not traced["steps"]:
            return None
        flops = traced["tokens"] * K.train_flops_per_token(
            a, ctx["job"]["seq_len"])
        return 100.0 * flops / pk["bf16_flops"] / traced["span_s"]
    raise ValueError(f"roofline: unknown work {work!r}")
