"""The share of a program's device time that a set of its ops takes: the
metric's ``trace_query`` (a regex over the ``XLA Ops`` events' HLO text)
over the ``XLA Modules`` time of the programs matching ``program``."""

from __future__ import annotations

from benchmarks.readers.trace_program_ms import program_time


def read(ctx: dict, query: str, program: str):
    trace = ctx.get("trace")
    found = ((trace or {}).get("queries") or {}).get(query)
    _, secs = program_time(trace, program)
    if not found or not found["count"] or not secs:
        return None
    return 100.0 * found["total_s"] / secs
