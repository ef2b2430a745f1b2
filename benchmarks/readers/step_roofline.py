"""A share of the chip's peaks for ONE part of the decode step: the least
time the chip could take for that part's work (the larger of its FLOPs
over peak FLOP/s and its bytes over peak bytes/s) over the device time
its ops take in a call of ``program``, from the metric's ``trace_query``.

  query=<metric>   the key of the metric's own ``trace_query``
  program=<regex>  the decode program (one call is one step where the
                   cell serves at ``--chunk-steps 1``)
  count=<name>     the function of the cell's family file that counts
                   the part: ``count(a, batch, live_tokens, counters)
                   -> (flops, bytes)``; batch and live positions are
                   the traced span's, from the client's token stamps

``counters`` is None, or what the program's own counters say of the
WINDOW (``/stats`` close minus before): ``pairs_per_step`` and
``hit_per_step``, token-expert pairs computed here and held experts hit,
a decode step (``engine.moe_tokens_held``, ``engine.moe_experts_hit``
over ``engine.decode_steps``). Returns None — never 0 — where the trace
holds nothing the query matches, the family has no such count, or the
device has no published peaks (the rehearsal).
"""

from __future__ import annotations

from benchmarks.harness import weights as W
from benchmarks.harness.loadgen import span_work
from benchmarks.readers.stats_path import dig
from benchmarks.readers.trace_program_ms import program_time


def window_counters(ctx: dict) -> dict | None:
    before, after = ctx.get("stats_before"), ctx.get("stats_at_close")
    if before is None or after is None:
        return None

    def delta(key):
        a, b = dig(after, "engine." + key), dig(before, "engine." + key)
        return None if a is None else a - (b or 0)

    steps, pairs, hit = (delta(k) for k in (
        "decode_steps", "moe_tokens_held", "moe_experts_hit"))
    if not steps or pairs is None or hit is None:
        return None
    return {"pairs_per_step": pairs / steps, "hit_per_step": hit / steps}


def read(ctx: dict, query: str, program: str, count: str):
    trace, pk, a = ctx.get("trace"), ctx.get("peaks"), ctx["arch"]
    if not trace or not pk:
        return None
    found = (trace.get("queries") or {}).get(query)
    calls, _ = program_time(trace, program)
    counter = getattr(W.family(a.family), count, None)
    lo, hi = ctx["trace_span"]
    if not found or not found["total_s"] or not calls or counter is None \
            or lo is None or hi is None:
        return None
    seen = span_work(ctx["records"], lo, hi)
    batch = min(ctx["serve_batch"], seen["out_tokens"] / calls)
    flops, nbytes = counter(a, batch, seen["live_tokens_mean"],
                            window_counters(ctx))
    least = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / (found["total_s"] / calls)
