"""Device time of a compiled program, from the trace's ``XLA Modules``
line: milliseconds per call of the programs whose name matches."""

from __future__ import annotations

import re


def program_time(trace: dict | None, program: str):
    """(calls, seconds) of the programs matching ``program``."""
    if not trace or not trace.get("programs"):
        return 0.0, 0.0
    pat = re.compile(program)
    hit = [v for k, v in trace["programs"].items() if pat.search(k)]
    return sum(v["count"] for v in hit), sum(v["total_s"] for v in hit)


def read(ctx: dict, program: str):
    calls, secs = program_time(ctx.get("trace"), program)
    return 1e3 * secs / calls if calls else None
