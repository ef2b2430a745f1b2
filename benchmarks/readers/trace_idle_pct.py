"""The device's idle share of the traced span: 1 - (union of the ``XLA
Ops`` intervals) / span, averaged over the chips used."""

from __future__ import annotations


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s") or not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
