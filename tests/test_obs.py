"""Serving observability tests (ISSUE 6): traces, timeline, /metrics.

Four layers, pinned bottom-up:

- ``obs.trace`` units: span invariants (monotonic timestamps, strict
  nesting), the stale-span drop rule, Chrome trace-event export, the
  bounded trace ring;
- ``obs.prom`` units: exposition golden checks — ``# HELP``/``# TYPE``
  headers, label escaping, histogram bucket monotonicity and the
  ``+Inf`` tail;
- ``obs.timeline`` units: per-dispatch records, the compile/steady
  split, cross-replica merge — plus the ENGINE integration (a real
  ``serve.Server`` emits prefill/decode/verify records whose token
  counts reconcile with results);
- gateway integration: every completed request leaves a trace whose
  spans nest and whose export ``json.loads``; a forced mid-stream
  replica kill leaves ONE trace carrying BOTH attempts with distinct
  replica tags and the failover fence between them (the ISSUE-6
  acceptance pin); ``GET /metrics`` is format-valid and consistent
  with ``/stats``; ``/debug/trace/<id>`` and ``/debug/profile`` work
  over real HTTP; client-supplied request ids thread through every
  surface, absent ids come back as server UUIDs.
"""

import json
import re
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from tony_tpu.gateway import Gateway, GatewayHistory, GatewayHTTP, GenRequest
from tony_tpu.models import Transformer, TransformerConfig
from tony_tpu.obs import (DispatchRecord, DispatchTimeline, Histogram,
                          MetricFamily, RequestTrace, TraceBuffer,
                          check_invariants, escape_label_value,
                          prometheus_text, render)
from tony_tpu.serve import FaultPlan, Request, Server


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=32,
                            dtype=jnp.float32,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


# ------------------------------------------------------- trace units


def test_trace_spans_nest_and_export():
    tr = RequestTrace("r1", t0=100.0)
    tr.begin_attempt(replica=0, epoch=0, t0=100.5)
    tr.add("queue_wait", 100.5, 101.0, attempt=True)
    tr.add("prefill", 101.0, 101.5, attempt=True, bucket=16)
    tr.add("decode", 101.5, 102.0, attempt=True, tokens=4)
    tr.end_attempt(102.0, outcome="done")
    tr.finish(102.0, outcome="done")
    assert check_invariants(tr) == []
    assert tr.n_attempts == 1 and tr.done
    doc = tr.to_chrome()
    json.loads(json.dumps(doc))  # valid JSON end to end
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in events]
    assert names == ["request", "attempt-1", "queue_wait", "prefill",
                     "decode"]
    # complete events with microsecond ts/dur; spans inside the root
    # (5 us tolerance: ts is epoch microseconds ~1e15, where float64
    # granularity alone is ~0.25 us)
    root = events[0]
    for e in events[1:]:
        assert e["ts"] >= root["ts"] - 5
        assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 5
    # the attempt renders on its replica's pid, its own tid row
    att = events[1]
    assert att["pid"] == 0 and att["tid"] == 1
    assert doc["otherData"]["request_id"] == "r1"


def test_trace_stale_spans_dropped_after_steal_and_finish():
    """The failover fence, tracing flavor: spans from a stale owner
    (attempt already ended / trace already finished) are DROPPED, so a
    wedged replica returning late can never mutate an exported trace."""
    tr = RequestTrace("r2", t0=0.0)
    tr.begin_attempt(0, 0, t0=0.1)
    tr.end_attempt(0.5, outcome="failed")  # the supervisor's steal
    tr.add("decode", 0.4, 0.6, attempt=True)  # stale owner's late record
    assert tr.dropped == 1
    tr.begin_attempt(1, 0, t0=0.7)
    # the airtight fence: a stale owner that raced a steal AND the
    # survivor's re-placement must not land its span in the NEW
    # attempt — attempt_key is checked atomically under the trace lock
    tr.add("decode", 0.55, 0.65, attempt_key=(0, 0))  # old replica
    assert tr.dropped == 2
    tr.add("decode", 0.8, 0.9, attempt_key=(1, 0))  # current owner
    tr.finish(1.0)
    tr.add("decode", 1.0, 1.1)  # post-finish: dropped too
    assert tr.dropped == 3
    assert check_invariants(tr) == []
    assert tr.n_attempts == 2
    names = [c.name for a in tr.root.children for c in a.children]
    assert names == ["decode"]  # only the current owner's span landed


def test_trace_span_cap_bounds_memory():
    """A marathon generation (thousands of decode dispatches) must not
    grow its trace without bound: past max_spans further spans are
    counted as truncated, not stored, and the export stays valid."""
    tr = RequestTrace("big", t0=0.0, max_spans=4)
    tr.begin_attempt(0, 0, t0=0.1)
    for i in range(10):
        tr.add("decode", 0.2 + i * 0.1, 0.3 + i * 0.1, attempt=True)
    tr.finish(2.0)
    assert tr.truncated == 6
    assert check_invariants(tr) == []
    doc = tr.to_chrome()
    assert doc["otherData"]["truncated_spans"] == 6
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) == 6


def test_trace_open_spans_clamped_in_export():
    """An in-flight request inspected early must still export
    well-formed JSON: open spans clamp to the latest timestamp seen."""
    tr = RequestTrace("r3", t0=10.0)
    tr.begin_attempt(0, 0, t0=10.1)
    tr.add("decode", 10.2, 10.4, attempt=True)
    doc = tr.to_chrome()  # attempt + root still open
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            assert e["dur"] >= 0


def test_check_invariants_catches_violations():
    tr = RequestTrace("bad", t0=50.0)
    tr.add("inverted", 52.0, 51.0)  # t1 < t0
    tr.add("early", 49.0, 49.5)     # before the root AND before sibling
    tr.finish(53.0)
    problems = check_invariants(tr)
    assert any("t1" in p for p in problems)
    assert any("outside parent" in p or "before" in p for p in problems)


def test_trace_buffer_bounded_and_last_writer_wins():
    buf = TraceBuffer(capacity=2)
    for i in range(3):
        t = RequestTrace(f"t{i}", t0=float(i))
        t.finish(float(i) + 1)
        buf.put(t)
    assert len(buf) == 2
    assert buf.get("t0") is None  # evicted oldest-first
    assert buf.ids() == ["t1", "t2"]
    newer = RequestTrace("t1", t0=9.0)
    newer.finish(9.5)
    buf.put(newer)
    assert buf.get("t1") is newer  # re-used id: last writer wins


# ----------------------------------------------------- exposition units


def test_label_escaping():
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'


def test_metric_family_render_golden():
    fam = MetricFamily("tony_test_total", "counter", "A test counter")
    fam.add(3, {"replica": "0"})
    fam.add(4.5, {"replica": "1", "state": 'we"ird'})
    text = fam.render()
    lines = text.splitlines()
    assert lines[0] == "# HELP tony_test_total A test counter"
    assert lines[1] == "# TYPE tony_test_total counter"
    assert lines[2] == 'tony_test_total{replica="0"} 3'
    assert lines[3] == 'tony_test_total{replica="1",state="we\\"ird"} 4.5'


def test_histogram_buckets_cumulative_monotonic():
    h = Histogram(buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0, 0.05):
        h.observe(v)
    fam = h.family("tony_lat_seconds", "latency")
    text = fam.render()
    buckets = re.findall(r'le="([^"]+)"\} (\d+)', text)
    assert [b[0] for b in buckets] == ["0.01", "0.1", "1", "+Inf"]
    counts = [int(b[1]) for b in buckets]
    assert counts == sorted(counts)      # cumulative => monotonic
    assert counts == [1, 3, 4, 5]
    assert counts[-1] == h.count == 5    # +Inf == _count
    assert "tony_lat_seconds_count 5" in text
    assert h.snapshot()["count"] == 5
    # render() of the whole document ends with a newline (spec)
    assert render([fam]).endswith("\n")


# ------------------------------------------------------- timeline units


def test_timeline_summary_compile_split_and_merge():
    tl = DispatchTimeline(capacity=8)
    tl.record(DispatchRecord("decode", 0.0, 100.0, 2, 8, 16, True))
    tl.record(DispatchRecord("decode", 1.0, 2.0, 2, 8, 16, False))
    tl.record(DispatchRecord("decode", 2.0, 4.0, 2, 8, 16, False))
    tl.record(DispatchRecord("prefill", 3.0, 50.0, 1, 16, 1, True))
    s = tl.summary()
    d = s["decode"]
    assert d["count"] == 3 and d["compiles"] == 1
    assert d["compile_ms"] == 100.0
    # steady-state mean excludes the first-call spike
    assert d["steady_mean_ms"] == pytest.approx(3.0)
    assert d["tokens"] == 48 and d["tokens_per_dispatch"] == 16.0
    assert s["prefill"]["count"] == 1
    merged = DispatchTimeline.merge([s, s])
    assert merged["decode"]["count"] == 6
    assert merged["decode"]["steady_mean_ms"] == pytest.approx(3.0)
    assert merged["decode"]["max_ms"] == 100.0


def test_timeline_ring_and_cursor():
    tl = DispatchTimeline(capacity=4)
    for i in range(6):
        tl.record(DispatchRecord("decode", float(i), 1.0, 1, 1, 1, False))
    new, cursor = tl.take_new(0)
    assert cursor == 6
    assert [r.seq for r in new] == [3, 4, 5, 6]  # 2 evicted, gone
    assert tl.take_new(cursor) == ([], 6)
    assert len(tl.recent(2)) == 2
    # lifetime aggregates survive ring eviction
    assert tl.summary()["decode"]["count"] == 6


def test_engine_timeline_records_reconcile_with_results(tiny):
    """The engine integration: run real traffic, check record kinds,
    token accounting (landed tokens == emitted tokens, overshoot
    excluded), compile flags (first (kind, shape) call only), and the
    requests tag decode spans are attached by."""
    model, params = tiny
    server = Server(model, params, batch_size=2, min_bucket=8,
                    chunk_steps=2)
    results = list(server.run([
        Request([1, 2, 3], max_new_tokens=5, id="a"),
        Request([4, 5], max_new_tokens=3, id="b"),
        Request([6], max_new_tokens=4, id="c")]))
    recs = server.timeline.recent(100)
    kinds = {r.kind for r in recs}
    assert kinds == {"prefill", "decode"}
    prefills = [r for r in recs if r.kind == "prefill"]
    assert {r.request_id for r in prefills} == {"a", "b", "c"}
    assert all(r.tokens == 1 for r in prefills)  # first token rides admit
    decodes = [r for r in recs if r.kind == "decode"]
    # tokens landed across dispatches == tokens emitted minus the admit
    # ones; trimmed chunk overshoot is NOT counted as landed
    total_emitted = sum(len(r.tokens) for r in results)
    assert sum(r.tokens for r in decodes) == total_emitted - len(results)
    # compile flag: exactly one first-call per distinct program shape —
    # (kind, bucket) plus, on the paged engine, the bucketed view span
    # (tags.view_tokens), which is a second shape knob
    for kind in ("prefill", "decode"):
        by_bucket = {}
        for r in recs:
            if r.kind == kind:
                key = (r.bucket, r.tags.get("view_tokens", 0))
                by_bucket.setdefault(key, []).append(r.compile)
        for bucket, flags in by_bucket.items():
            assert flags[0] is True and not any(flags[1:]), (kind, bucket)
    # decode records carry the engine ids live at dispatch time
    assert all(set(r.tags["requests"]) <= {"a", "b", "c"}
               for r in decodes)
    assert all(r.occupancy >= 1 for r in decodes)
    summary = server.timeline.summary()
    assert summary["decode"]["count"] == len(decodes)


def test_engine_timeline_verify_records(tiny):
    """Speculation rounds record as kind=verify with drafted/accepted
    tags — the per-dispatch view of the spec counters."""
    model, params = tiny
    server = Server(model, params, batch_size=1, min_bucket=8,
                    chunk_steps=1, speculate_k=2)
    list(server.run([Request([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=8,
                             id="rep")]))
    recs = server.timeline.recent(100)
    verifies = [r for r in recs if r.kind == "verify"]
    assert verifies, [r.kind for r in recs]
    assert server.spec_rounds == len(verifies)
    assert sum(r.tags["drafted"] for r in verifies) == server.spec_drafted
    assert sum(r.tags["accepted"] for r in verifies) == server.spec_accepted
    assert all(r.bucket >= 2 for r in verifies)  # window = pow2 + 1


def test_engine_timeline_off_is_none(tiny):
    model, params = tiny
    server = Server(model, params, batch_size=1, min_bucket=8,
                    timeline=False)
    list(server.run([Request([1, 2], max_new_tokens=3, id="x")]))
    assert server.timeline is None  # and nothing crashed


# -------------------------------------------------- gateway integration


def _mk_gateway(tiny, n=1, history=None, stall_timeout_s=10.0,
                **server_kw):
    model, params = tiny
    servers = [Server(model, params, batch_size=2, min_bucket=8,
                      **server_kw) for _ in range(n)]
    return Gateway(servers, max_queue=32, history=history,
                   max_attempts=3, stall_timeout_s=stall_timeout_s,
                   breaker_base_s=0.05, breaker_max_s=0.2)


def test_gateway_trace_lifecycle_and_history(tiny, tmp_path):
    hist = GatewayHistory(str(tmp_path), n_replicas=1)
    gw = _mk_gateway(tiny, history=hist, chunk_steps=2).start()
    try:
        tickets = [gw.submit(GenRequest([1 + i, 2, 3], max_new_tokens=4,
                                        id=f"r{i}")) for i in range(3)]
        for t in tickets:
            t.result(timeout=120)
        for i in range(3):
            tr = gw.traces.get(f"r{i}")
            assert tr is not None and tr.done
            assert check_invariants(tr) == [], i
            doc = json.loads(tr.to_json())
            names = [e["name"] for e in doc["traceEvents"]
                     if e["ph"] == "X"]
            assert names[0] == "request"
            assert "attempt-1" in names and "queue_wait" in names
            assert "prefill" in names and "decode" in names
            # terminal tags carry the request metrics
            root = [e for e in doc["traceEvents"]
                    if e["name"] == "request"][0]
            assert root["args"]["outcome"] == "done"
            assert root["args"]["tokens_out"] == 4
    finally:
        assert gw.drain(timeout=60)
    import os

    rows = [json.loads(ln) for ln in
            open(os.path.join(hist.job_dir, "metrics", "traces.jsonl"))]
    assert {r["otherData"]["request_id"] for r in rows} == \
        {"r0", "r1", "r2"}
    assert all(r["traceEvents"] for r in rows)


def test_failover_produces_one_trace_with_both_attempts(tiny):
    """THE ISSUE-6 acceptance pin: a request that survives a mid-stream
    replica kill (TONY_SERVE_FAULTS-style injection) produces ONE trace
    containing both attempts — queue/admit/prefill/decode spans on the
    failed replica, then the failover fence and re-run spans on the
    survivor — exported as Chrome trace-event JSON that json.loads and
    the span-invariant checks accept."""
    model, params = tiny
    servers = [Server(model, params, batch_size=2, min_bucket=8,
                      chunk_steps=1,
                      fault_plan=(FaultPlan.fail_at(4) if i == 0
                                  else None))
               for i in range(2)]
    gw = Gateway(servers, max_queue=32, max_attempts=3,
                 stall_timeout_s=10.0, breaker_base_s=0.05,
                 breaker_max_s=0.2)
    prompts = [[1 + i, 2, 3] for i in range(4)]
    # pre-start submits: equal costs alternate 0,1,0,1 so replica 0
    # deterministically holds admitted tickets when dispatch 4 dies
    tickets = [gw.submit(GenRequest(p, max_new_tokens=8, id=f"c{i}"))
               for i, p in enumerate(prompts)]
    gw.start()
    try:
        for t in tickets:
            t.result(timeout=120)
        victims = [t for t in tickets if t.metrics["attempts"] >= 1]
        assert victims, "no ticket was failed over"
        for t in victims:
            tr = gw.traces.get(t.request.id)
            assert tr is not None and tr.n_attempts == 2
            assert check_invariants(tr) == []
            doc = json.loads(tr.to_json())
            events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
            atts = [e for e in events if e["name"].startswith("attempt-")]
            assert len(atts) == 2
            # distinct replica tags; the failed attempt says why
            assert atts[0]["args"]["replica"] == 0
            assert atts[1]["args"]["replica"] == 1
            assert atts[0]["args"]["outcome"] == "failed"
            assert atts[1]["args"]["outcome"] == "done"
            # epoch fence between them
            fo = [e for e in events if e["name"] == "failover"]
            assert len(fo) == 1
            assert fo[0]["args"]["from_replica"] == 0
            assert fo[0]["args"]["new_epoch"] == 1
            assert fo[0]["args"]["admitted"] is True
            # both attempts ran real engine work
            first = [e["name"] for e in events
                     if e.get("tid") == atts[0]["tid"]
                     and not e["name"].startswith("attempt-")]
            second = [e["name"] for e in events
                      if e.get("tid") == atts[1]["tid"]
                      and not e["name"].startswith("attempt-")]
            assert "prefill" in first
            assert "decode" in second
            # the attempts render on different pid (replica) rows
            assert atts[0]["pid"] != atts[1]["pid"]
    finally:
        assert gw.drain(timeout=60)


def test_shed_request_trace_is_exported(tiny):
    """A shed request's trace is exactly what an operator debugs — it
    lands in the buffer with outcome=shed and the status."""
    model, params = tiny
    gw = _mk_gateway(tiny).start()
    try:
        # ttl 100 ns: positive (a ttl <= 0 is refused AT SUBMIT, before
        # a trace exists), yet expired by the time the replica's pop
        # runs its deadline check — 0.0001 s flaked on fast boxes where
        # an idle replica's cv wakeup admitted inside the window
        t = gw.submit(GenRequest([1, 2], max_new_tokens=4, id="dead",
                                 ttl_s=1e-7))
        with pytest.raises(Exception):
            t.result(timeout=60)
        tr = gw.traces.get("dead")
        assert tr is not None and tr.done
        assert tr.root.tags["outcome"] == "shed"
        assert tr.root.tags["status"] == 504
        assert check_invariants(tr) == []
    finally:
        assert gw.drain(timeout=60)


def test_server_uuid_ids_and_stats_threading(tiny):
    """Absent ids come back as server-minted UUID strings, threaded
    into metrics rows and the trace buffer — the correlation satellite."""
    gw = _mk_gateway(tiny).start()
    try:
        t = gw.submit(GenRequest([1, 2, 3], max_new_tokens=3))
        rid = t.request.id
        assert isinstance(rid, str) and len(rid) == 32
        res = t.result(timeout=120)
        assert res.id == rid
        assert t.metrics["id"] == rid
        # the rolling /stats window rows carry the id (the handle the
        # history requests.jsonl rows and trace file share)
        assert rid in [r["id"] for r in gw.stats.window]
        assert gw.traces.get(rid) is not None
    finally:
        assert gw.drain(timeout=60)


def test_snapshot_dispatch_and_host_blocks(tiny):
    gw = _mk_gateway(tiny, n=2).start()
    try:
        for i in range(4):
            gw.submit(GenRequest([1 + i, 2], max_new_tokens=3,
                                 id=i)).result(timeout=120)
        snap = gw.snapshot()
        # per-replica host gauges: RSS is always there (this process)
        for row in snap["replicas"]:
            assert row["host"]["rss_bytes"] > 0
            assert "dispatch" in row
        # fleet dispatch block merges the replica summaries
        fleet = snap["engine"]["dispatch"]
        assert fleet["prefill"]["count"] == \
            sum(r["dispatch"].get("prefill", {}).get("count", 0)
                for r in snap["replicas"])
        assert fleet["prefill"]["count"] == snap["engine"]["prefills"]
        assert fleet["decode"]["tokens"] > 0
        assert fleet["decode"]["compiles"] >= 1
    finally:
        assert gw.drain(timeout=60)


def test_tracing_disabled_gateway_works(tiny):
    gw_off = Gateway([Server(*tiny, batch_size=2, min_bucket=8,
                             timeline=False)],
                     max_queue=8, tracing=False).start()
    try:
        res = gw_off.submit(GenRequest([1, 2, 3], max_new_tokens=3,
                                       id="q")).result(timeout=120)
        assert len(res.tokens) == 3
        assert gw_off.traces is None
        snap = gw_off.snapshot()
        assert snap["engine"]["dispatch"] == {}
    finally:
        assert gw_off.drain(timeout=60)


# -------------------------------------------------- /metrics exposition

# one line of the exposition: comment, blank, or sample with optional
# labels and a number (int/float/scientific/+Inf/NaN)
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\})?"
    r" (-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|-Inf|NaN)$")


def _validate_exposition(text: str) -> dict:
    """Format-validate a whole exposition document; returns
    {metric_name: type}. Asserts HELP/TYPE precede samples and
    histogram bucket series are cumulative-monotonic ending in +Inf."""
    types: dict = {}
    cur = None
    buckets: dict = {}
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            cur = line.split()[2]
            continue
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split(None, 3)
            assert name == cur, f"TYPE without preceding HELP: {line}"
            assert mtype in ("counter", "gauge", "histogram"), line
            types[name] = mtype
            continue
        assert not line.startswith("#"), line
        assert _SAMPLE_RE.match(line), f"malformed sample line: {line!r}"
        name = re.split(r"[{ ]", line, 1)[0]
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        owner = name if name in types else base
        assert owner in types, f"sample before TYPE: {line}"
        if types.get(base) == "histogram" and name.endswith("_bucket"):
            le = re.search(r'le="([^"]+)"', line).group(1)
            series = re.sub(r',?le="[^"]+"', "", line.split(" ")[0])
            val = float(line.rsplit(" ", 1)[1])
            buckets.setdefault(series, []).append((le, val))
    for series, pts in buckets.items():
        vals = [v for _, v in pts]
        assert vals == sorted(vals), f"non-monotonic buckets: {series}"
        assert pts[-1][0] == "+Inf", f"missing +Inf: {series}"
    return types


def test_metrics_exposition_format_and_stats_consistency(tiny):
    """The acceptance check at gateway level: /metrics renders
    format-valid text whose counters agree with /stats — TTFT/TPOT/
    queue-wait histograms, supervision, prefix, and spec counters."""
    gw = _mk_gateway(tiny, n=2, chunk_steps=2, prefix_cache_mb=1.0,
                     speculate_k=2).start()
    try:
        for i in range(6):
            gw.submit(GenRequest([1, 2, 3, 1, 2, 3, 1 + i],
                                 max_new_tokens=4,
                                 id=f"m{i}")).result(timeout=120)
        text = prometheus_text(gw)
        types = _validate_exposition(text)
        snap = gw.snapshot()
        # counters consistent with /stats
        assert f"tony_requests_completed_total {snap['completed']}" \
            in text
        assert f"tony_requests_accepted_total {snap['accepted']}" in text
        assert f"tony_tokens_out_total {snap['tokens_out']}" in text
        # histograms present, counts match completed requests
        for name in ("tony_request_ttft_seconds",
                     "tony_request_tpot_seconds",
                     "tony_request_queue_wait_seconds"):
            assert types[name] == "histogram"
            assert f"{name}_count {snap['completed']}" in text
        # supervision / prefix / spec families
        assert types["tony_replica_failures_total"] == "counter"
        assert types["tony_engine_prefix_hits_total"] == "counter"
        assert types["tony_engine_spec_accepted_total"] == "counter"
        assert types["tony_dispatch_seconds_total"] == "counter"
        assert types["tony_host_rss_bytes"] == "gauge"
        assert 'tony_replica_state{replica="0",state="healthy"} 1' in text
        # per-replica engine counters reconcile with the /stats rows
        for i, row in enumerate(snap["replicas"]):
            assert (f'tony_engine_prefills_total{{replica="{i}"}} '
                    f'{row["prefills"]}') in text
        # ISSUE-18: migration families render on every fleet (zero
        # here — nothing migrated) and agree with /stats on both the
        # per-replica rows and the carry-inclusive fleet rollup
        mig = snap["engine"]["migrations"]
        assert types["tony_migration_out_total"] == "counter"
        assert f'tony_migrations_total {snap["routing"]["migrations"]}' \
            in text
        for key, fam in (("out", "tony_migration_out_total"),
                         ("in", "tony_migration_in_total"),
                         ("local", "tony_migration_local_total"),
                         ("remote", "tony_migration_remote_total"),
                         ("pages_moved",
                          "tony_migration_pages_moved_total"),
                         ("bytes_avoided",
                          "tony_migration_bytes_avoided_total"),
                         # ISSUE-19: the wire-economy pair rides the
                         # same carry-inclusive rollup
                         ("bytes_wire",
                          "tony_migration_bytes_wire_total"),
                         ("delta_in",
                          "tony_migration_delta_in_total")):
            assert f"{fam} {mig[key]}" in text, fam
        for i, row in enumerate(snap["replicas"]):
            assert (f'tony_engine_migrations_out_total{{replica="{i}"}} '
                    f'{row["migrations_out"]}') in text
            assert (f'tony_engine_migrate_bytes_wire_total'
                    f'{{replica="{i}"}} '
                    f'{row["migrate_bytes_wire"]}') in text
        # ISSUE-19: rebalance families are absent until a Rebalancer
        # is attached, then agree with the /stats rebalance block
        assert "tony_rebalance_" not in text
        from tony_tpu.gateway import Rebalancer

        Rebalancer(gw, interval_s=999.0)  # registers, never started
        text2 = prometheus_text(gw)
        _validate_exposition(text2)
        rb = gw.snapshot()["rebalance"]
        assert rb["enabled"]
        for key, fam in (("moves", "tony_rebalance_moves_total"),
                         ("move_failures",
                          "tony_rebalance_move_failures_total"),
                         ("ticks", "tony_rebalance_ticks_total"),
                         ("streak", "tony_rebalance_streak")):
            assert f"{fam} {rb[key]}" in text2, fam
        # the paged-KV block: /metrics and /stats must agree on every
        # kv_pages figure (per-replica gauges sum to the engine rollup)
        kv = snap["engine"]["kv_pages"]
        assert kv["enabled"]
        assert "tony_kv_paged_enabled 1" in text
        for key, gauge in (("kv_pages_total", "tony_kv_pages_total_pages"),
                           ("kv_pages_used", "tony_kv_pages_used"),
                           ("kv_cow_shared", "tony_kv_cow_shared_pages"),
                           ("kv_bytes_resident", "tony_kv_bytes_resident"),
                           ("kv_tokens_resident",
                            "tony_kv_tokens_resident")):
            rollup_key = key.replace("kv_pages_", "").replace("kv_", "")
            total = 0
            for i, row in enumerate(snap["replicas"]):
                assert (f'{gauge}{{replica="{i}"}} '
                        f'{row[key]}') in text
                total += row[key]
            assert kv[rollup_key] == total, (key, kv)
        assert kv["used"] + kv["free"] == kv["total"]
        # the KV tree block rides the same per-replica counts: every
        # writer dispatch consumed the tree it was given
        tree = snap["engine"]["kv_tree"]
        assert tree["kept"] == 0 and tree["donated"] > 0
        assert tree["donated"] == sum(row["kv_tree_donated"]
                                      for row in snap["replicas"])
        # ISSUE-10: the goodput gauges carry the same ledger /stats
        # engine.goodput does. The ledger is TIME-dependent (idle
        # grows between two snapshots), so the exported values are
        # parsed back and compared with a drift tolerance; the
        # sums-to-<=1 invariant must hold exactly on the exported
        # document itself (both surfaces render ONE snapshot each).
        gp = snap["engine"]["goodput"]
        assert gp["buckets"] and sum(gp["buckets"].values()) <= 1 + 1e-6
        exported = {
            m.group(1): float(m.group(2)) for m in re.finditer(
                r'tony_goodput_fraction\{bucket="([^"]+)"\} (\S+)',
                text)}
        assert set(exported) == set(gp["buckets"])
        assert sum(exported.values()) <= 1.0 + 1e-6
        for bucket, v in gp["buckets"].items():
            assert exported[bucket] == pytest.approx(v, abs=0.05), bucket
        # per-replica dispatch cost estimates ride the dispatch family
        # (pure counters: exact across snapshots)
        from tony_tpu.obs.prom import _fmt

        for i, row in enumerate(snap["replicas"]):
            for kind, agg in row["dispatch"].items():
                assert (f'tony_dispatch_est_bytes_total{{replica="{i}"'
                        f',kind="{kind}"}} '
                        f'{_fmt(agg["est_bytes"])}') in text
        # build info + alert families (ISSUE-10 satellites)
        assert types["tony_build_info"] == "gauge"
        assert 'tony_build_info{version="' in text
        assert "tony_alerts_enabled 1" in text
        al = snap["alerts"]
        assert al["enabled"] and "kv_pages_pressure" in al["rules"]
        for rule in al["rules"]:
            assert (f'tony_alerts_fired_total{{alert="{rule}"}} '
                    f'{al["fired"].get(rule, 0)}') in text
    finally:
        assert gw.drain(timeout=60)


def test_metrics_exposition_consistency_with_remote_stub(tiny):
    """ISSUE-15: the exposition-consistency contract extended to a
    fleet with one REMOTE replica — dispatch families, goodput
    fractions, and the new clock-offset/obs-channel series must agree
    between /metrics and /stats. The obs-puller is frozen once the
    pulled timeline accounts for every landed token, so the two
    surfaces render the IDENTICAL pulled state and the comparison is
    exact, not tolerance-based."""
    import time as _time

    from tony_tpu.gateway.remote import RemoteServer
    from tony_tpu.serve.agent import AgentHTTP, ReplicaAgent

    model, params = tiny
    agent = AgentHTTP(ReplicaAgent(Server(
        model, params, batch_size=2, min_bucket=8))).start()
    stub = RemoteServer(agent.address, heartbeat_interval_s=0.1,
                        lease_misses=10, boot_timeout_s=20.0)
    gw = Gateway([stub], max_queue=32, max_attempts=3,
                 stall_timeout_s=10.0, breaker_base_s=0.05,
                 breaker_max_s=0.2).start()
    try:
        n, budget = 4, 4
        for i in range(n):
            gw.submit(GenRequest([1 + i, 2, 3], max_new_tokens=budget,
                                 id=f"rm{i}")).result(timeout=120)
        deadline = _time.monotonic() + 30
        while _time.monotonic() < deadline:
            summ = stub.timeline.summary()
            if summ and sum(a["tokens"] for a in summ.values()) \
                    >= n * budget:
                break
            _time.sleep(0.02)
        stub._obs_pull = False  # freeze: exact two-surface comparison
        text = prometheus_text(gw)
        _validate_exposition(text)
        snap = gw.snapshot()
        row = snap["replicas"][0]
        # dispatch families agree with the (pulled) /stats block
        for kind, agg in row["dispatch"].items():
            assert (f'tony_dispatch_count_total{{replica="0"'
                    f',kind="{kind}"}} {agg["count"]}') in text
            assert (f'tony_dispatch_tokens_total{{replica="0"'
                    f',kind="{kind}"}} {agg["tokens"]}') in text
        assert row["dispatch"]["prefill"]["count"] == n
        # goodput fractions: both surfaces render the same frozen
        # pulled ledger — exact equality per bucket
        gp = snap["engine"]["goodput"]
        assert gp["buckets"] and sum(gp["buckets"].values()) <= 1 + 1e-6
        exported = {
            m.group(1): float(m.group(2)) for m in re.finditer(
                r'tony_goodput_fraction\{bucket="([^"]+)"\} (\S+)',
                text)}
        assert exported == {k: pytest.approx(v)
                            for k, v in gp["buckets"].items()}
        # the clock-offset series agrees with the transport block
        tr = row["transport"]
        m = re.search(r'tony_transport_clock_offset_ms\{[^}]*\} (\S+)',
                      text)
        assert m is not None
        assert float(m.group(1)) == pytest.approx(
            tr["clock_offset_ms"], abs=1.0)
        assert "tony_transport_clock_offset_unc_ms{" in text
        # the obs channel's health series agree with the row's block
        obs = row["obs"]
        assert (f'tony_transport_obs_pulls_total{{replica="0",'
                f'host="{agent.address}"}} {obs["pulls"]}') in text
        assert (f'tony_transport_obs_pull_errors_total{{replica="0",'
                f'host="{agent.address}"}} 0') in text
    finally:
        gw.drain(timeout=60)
        agent.stop()


def test_metrics_exposition_edge_block(tiny):
    """ISSUE-16: the exposition-consistency contract extended to the
    connection plane — with an event edge attached, snapshot() grows
    an `edge` block and /metrics grows the tony_edge_* families, and
    the two surfaces agree on every figure."""
    from tony_tpu.gateway import GatewayEdge

    gw = _mk_gateway(tiny).start()
    edge = GatewayEdge(gw).start()
    try:
        url = f"http://{edge.host}:{edge.port}"
        body = json.dumps({"token_ids": [1, 2, 3], "max_new_tokens": 3,
                           "id": "e0"}).encode()
        req = urllib.request.Request(
            url + "/v1/generate", data=body,
            headers={"Content-Type": "application/json"})
        assert json.loads(urllib.request.urlopen(
            req, timeout=120).read())["id"] == "e0"
        text = prometheus_text(gw)
        types = _validate_exposition(text)
        snap = gw.snapshot()
        e = snap["edge"]
        assert e["kind"] == "event"
        assert types["tony_edge_threads"] == "gauge"
        assert types["tony_edge_accepts_total"] == "counter"
        assert types["tony_edge_requests_total"] == "counter"
        assert types["tony_edge_slow_client_aborts_total"] == "counter"
        assert types["tony_edge_conn_limit_sheds_total"] == "counter"
        assert f'tony_edge_threads {e["threads"]}' in text
        assert f'tony_edge_max_connections {e["max_connections"]}' \
            in text
        # counters only move via edge traffic, so they are exact
        # across the two snapshots here
        assert f'tony_edge_requests_total {e["requests"]}' in text
        assert f'tony_edge_accepts_total {e["accepts"]}' in text
        assert e["requests"] >= 1 and e["accepts"] >= 1
        # and /stats through the edge itself carries the same block
        stats = json.loads(urllib.request.urlopen(
            url + "/stats", timeout=60).read())
        assert stats["edge"]["kind"] == "event"
        assert stats["edge"]["requests"] >= e["requests"]
    finally:
        edge.stop()
        assert "edge" not in gw.snapshot()  # stop() detaches
        gw.drain(timeout=60)


# ------------------------------------------------------ HTTP endpoints


def _get(url, timeout=60):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.headers, r.read()


def test_http_metrics_and_trace_endpoints(tiny):
    """The network face: /metrics scrapes and /debug/trace/<id> serves
    a completed request's Chrome JSON."""
    gw = _mk_gateway(tiny).start()
    http = GatewayHTTP(gw, port=0).start()
    url = f"http://{http.host}:{http.port}"
    try:
        body = json.dumps({"token_ids": [1, 2, 3], "max_new_tokens": 3,
                           "request_id": "web-1"}).encode()
        req = urllib.request.Request(url + "/v1/generate", data=body)
        doc = json.loads(urllib.request.urlopen(req, timeout=120).read())
        assert doc["request_id"] == "web-1" and doc["id"] == "web-1"
        assert doc["metrics"]["id"] == "web-1"

        status, headers, data = _get(url + "/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        _validate_exposition(data.decode())
        assert b"tony_requests_completed_total 1" in data

        status, _, data = _get(url + "/debug/trace")
        assert status == 200
        assert "web-1" in json.loads(data)["request_ids"]
        status, _, data = _get(url + "/debug/trace/web-1")
        assert status == 200
        trace_doc = json.loads(data)
        assert trace_doc["otherData"]["request_id"] == "web-1"
        assert any(e["name"] == "prefill" for e in
                   trace_doc["traceEvents"])
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(url + "/debug/trace/nope")
        assert e.value.code == 404
    finally:
        http.stop()
        assert gw.drain(timeout=60)


@pytest.mark.slow  # the FIRST jax start_trace of a process blocks
# >10 s (plugin spin-up); the protocol itself is unit-tested fast in
# test_profiler, and serve-smoke drives this path on a live gateway
def test_http_profile_endpoint_real_capture(tiny, tmp_path):
    """POST /debug/profile arms a real jax.profiler capture that the
    fleet's next working iterations finish. Client logdir is a
    RELATIVE subdir of the server-configured profile dir; escapes 400."""
    model, params = tiny
    gw = Gateway([Server(model, params, batch_size=2, min_bucket=8)],
                 max_queue=32, max_attempts=3, stall_timeout_s=60.0,
                 breaker_base_s=0.05, breaker_max_s=0.2,
                 profile_dir=str(tmp_path)).start()
    http = GatewayHTTP(gw, port=0).start()
    url = f"http://{http.host}:{http.port}"
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(urllib.request.Request(
                url + "/debug/profile?steps=2&logdir=../escape",
                data=b"", method="POST"), timeout=60)
        assert e.value.code == 400  # no arbitrary-path write primitive
        logdir = str(tmp_path / "prof")
        req = urllib.request.Request(
            url + "/debug/profile?steps=2&logdir=prof", data=b"",
            method="POST")
        armed = json.loads(urllib.request.urlopen(req, timeout=60).read())
        # a fresh timestamped dir per capture under the validated sub:
        # re-using one name would double-count in the xplane parsers
        assert armed["armed"]
        assert armed["logdir"].startswith(logdir + "/profile-")
        logdir = armed["logdir"]
        # a second arm while pending is refused (409): jax has ONE
        # global profiler session
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(urllib.request.Request(
                url + "/debug/profile?steps=2", data=b"",
                method="POST"), timeout=60)
        assert e.value.code == 409
        body = json.dumps({"token_ids": [5, 6], "max_new_tokens": 6,
                           "request_id": "prof-drive"}).encode()
        urllib.request.urlopen(urllib.request.Request(
            url + "/v1/generate", data=body), timeout=120).read()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            status_doc = json.loads(_get(url + "/debug/profile")[2])
            if status_doc["captures"] >= 1:
                break
            # keep the fleet working so the armed steps burn down
            urllib.request.urlopen(urllib.request.Request(
                url + "/v1/generate", data=body), timeout=120).read()
        assert status_doc["captures"] == 1, status_doc
        assert status_doc["last_logdir"] == logdir
        assert not status_doc["active"]
        import glob
        assert glob.glob(logdir + "/**/*", recursive=True), \
            "capture wrote nothing"
    finally:
        http.stop()
        assert gw.drain(timeout=60)
