"""The ratio of two of the gateway's counters over the window (``/stats``
at its close minus before it), times ``scale`` and, with ``times``, times
a third value read at the close (a size the program reports beside its
counters, not a count of events).

  num=<path> den=<path>  the two counters, ``/stats`` paths
  scale=<number>         100 for a share in percent
  times=<path>           e.g. the experts a chip holds, to turn
                         "fullest expert's load over all held pairs"
                         into "max over mean"

None — never 0 — where a counter is absent (a program without it) or the
denominator did not move.
"""

from __future__ import annotations

from benchmarks.readers.stats_path import dig


def read(ctx: dict, num: str, den: str, scale: float = 1.0, times: str = ""):
    before, after = ctx.get("stats_before"), ctx.get("stats_at_close")
    if before is None or after is None:
        return None
    top, bottom = (
        None if dig(after, path) is None
        else dig(after, path) - (dig(before, path) or 0)
        for path in (num, den))
    if top is None or not bottom:
        return None
    value = scale * top / bottom
    if times:
        factor = dig(after, times)
        if factor is None:
            return None
        value *= factor
    return value
