"""Reads the program's host phase ledger (``tony_tpu/obs/phases.py``):
``/stats`` ``engine.host``, the scheduler thread's wall clock by named
phase, taken before the window and at its close. Every number is the
window's delta (close minus before). ``edge.emit_lag`` (``/stats``
``edge``) and what no phase covers (``unnamed``) are read as two more
rows of the same table.

  phases=<regex>  the rows whose name matches (``re.search``)
  clock=ms        their wall milliseconds
  clock=offcpu    their wall minus CPU milliseconds: time the thread
                  spent off the CPU inside them (blocked, or waiting
                  for the GIL)
  per=decode      ... per decode dispatch (``engine.dispatch.decode``)
  per=count       ... per entry into those phases
  per=wall        ... as a percentage of the thread's wall clock
  per=busy        ... of the wall clock minus ``loop.idle_wait``
  per=ms          ... of the same rows' wall milliseconds

A program that has no ledger (``engine.host`` absent, as at the parent
of the PR that brought it) gives None, never 0.
"""

from __future__ import annotations

import re

from benchmarks.readers.stats_path import dig

IDLE = "loop.idle_wait"


def table(stats: dict) -> dict | None:
    """``{row: (count, ms, cpu_ms)}`` of one ``/stats`` document."""
    host = dig(stats, "engine.host")
    if not isinstance(host, dict) or "phases" not in host:
        return None
    rows = {name: (p.get("count", 0), p.get("ms", 0.0), p.get("cpu_ms", 0.0))
            for name, p in host["phases"].items()}
    # the thread is off the CPU for none of what it does unnamed, as far
    # as the ledger can say: count it as wall and as CPU alike
    rows["unnamed"] = (0, host.get("unnamed_ms", 0.0),
                       host.get("unnamed_ms", 0.0))
    lag = dig(stats, "edge.emit_lag")
    if isinstance(lag, dict):
        rows["edge.emit_lag"] = (lag.get("count", 0), lag.get("ms", 0.0),
                                 lag.get("ms", 0.0))
    return rows


def read(ctx: dict, phases: str, per: str, clock: str = "ms"):
    if ctx.get("stats_before") is None:
        return None
    before, after = table(ctx["stats_before"]), table(ctx["stats_at_close"])
    if before is None or after is None:
        return None
    pat = re.compile(phases)

    def delta(name: str, field: int) -> float:
        return after[name][field] - before.get(name, (0, 0.0, 0.0))[field]

    hit = [n for n in after if pat.search(n)]
    ms = sum(delta(n, 1) for n in hit)
    if clock == "ms":
        num = ms
    elif clock == "offcpu":
        num = ms - sum(delta(n, 2) for n in hit)
    else:
        raise ValueError(f"host_phase: unknown clock {clock!r}")
    wall = (dig(ctx["stats_at_close"], "engine.host.wall_ms")
            - dig(ctx["stats_before"], "engine.host.wall_ms"))
    if per == "decode":
        den = ((dig(ctx["stats_at_close"], "engine.dispatch.decode.count")
                or 0)
               - (dig(ctx["stats_before"], "engine.dispatch.decode.count")
                  or 0))
        return num / den if den > 0 else None
    if per == "count":
        den = sum(delta(n, 0) for n in hit)
        return num / den if den > 0 else None
    if per == "wall":
        den = wall
    elif per == "busy":
        den = wall - (delta(IDLE, 1) if IDLE in after else 0.0)
    elif per == "ms":
        den = ms
    else:
        raise ValueError(f"host_phase: unknown per {per!r}")
    return 100.0 * num / den if den > 0 else None
