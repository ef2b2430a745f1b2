"""Reads the program's start-up ledger (``tony_tpu/obs/startup.py``):
``/stats`` ``startup`` as taken before the window, when the program has
started and the warm-up traffic is over. Its stamps are seconds since
the OS started the serving process; its compile totals are cumulative
over the process, so before the window they are everything start-up
and warm-up compiled or loaded.

  add=[<path>, ...]  the sum of these paths under ``startup``
  less=<path>        minus this one

A program without the block (``startup`` absent, as at the parent of
the PR that brought it), a training cell (no ``/stats``) or a stamp
that is missing gives None, never 0.
"""

from __future__ import annotations

from benchmarks.readers.stats_path import dig


def read(ctx: dict, add: list, less: str = ""):
    block = dig(ctx.get("stats_before") or {}, "startup")
    if not isinstance(block, dict):
        return None
    values = [dig(block, path) for path in add + ([less] if less else [])]
    if any(not isinstance(v, (int, float)) for v in values):
        return None
    total = sum(values[:len(add)])
    return total - values[-1] if less else total
