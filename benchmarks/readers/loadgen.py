"""Reads what the load generator and the requests' own answers say.

  field=<name>                 a number of ``loadgen.summarise`` (or of
                               the training window), e.g.
                               ``loadgen_late_p95_ms``
  request_metric=<name> q=<q>  the q-quantile, over the window's finished
                               requests, of a number the gateway reports
                               in each answer's ``metrics``
"""

from __future__ import annotations

from benchmarks.harness.loadgen import percentile


def read(ctx: dict, field: str = "", request_metric: str = "",
         q: float = 0.5):
    if field:
        return ctx["summary"].get(field)
    vals = [r.final["metrics"][request_metric] for r in ctx.get("records", [])
            if r.ok and request_metric in (r.final.get("metrics") or {})]
    return percentile(vals, q) if vals else None
