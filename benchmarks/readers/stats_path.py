"""Reads the gateway's own counters: ``/stats`` taken before the window,
at its close, and polled once a second in between. Host counters and
host wall clock — the metric names say so.

modes:
  dispatch_mean_ms   kinds=<regex>: over the ``engine.dispatch`` kinds
                     that match, steady (non-first-use) host wall ms per
                     dispatch, over the window (close minus before)
  dispatch_fill_pct  kinds=<regex>: tokens those dispatches landed over
                     (dispatches x slots): how full the batch ran
  peak_pct           num=<path> den=<path>: the largest num/den any poll
                     saw, in percent
"""

from __future__ import annotations

import re


def dig(doc: dict, path: str):
    for key in path.split("."):
        if not isinstance(doc, dict) or key not in doc:
            return None
        doc = doc[key]
    return doc


def _delta(ctx: dict, kinds: str, field: str) -> float:
    pat = re.compile(kinds)
    before = dig(ctx["stats_before"], "engine.dispatch") or {}
    after = dig(ctx["stats_at_close"], "engine.dispatch") or {}
    return sum(v.get(field, 0) - before.get(k, {}).get(field, 0)
               for k, v in after.items() if pat.search(k))


def read(ctx: dict, mode: str, kinds: str = "", num: str = "",
         den: str = ""):
    if ctx.get("stats_before") is None:
        return None
    if mode == "dispatch_mean_ms":
        n = _delta(ctx, kinds, "count") - _delta(ctx, kinds, "compiles")
        ms = _delta(ctx, kinds, "ms") - _delta(ctx, kinds, "compile_ms")
        return ms / n if n > 0 else None
    if mode == "dispatch_fill_pct":
        n = _delta(ctx, kinds, "count")
        return 100.0 * _delta(ctx, kinds, "tokens") / (
            n * ctx["serve_batch"]) if n > 0 else None
    if mode == "peak_pct":
        shares = [dig(s, num) / dig(s, den) for _, s in ctx["polls"]
                  if dig(s, num) is not None and dig(s, den)]
        return 100.0 * max(shares) if shares else None
    raise ValueError(f"stats_path: unknown mode {mode!r}")
