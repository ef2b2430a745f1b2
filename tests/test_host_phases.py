"""Host phase ledger tests (ISSUE 27): the scheduler thread's wall clock
by named phase, the ``tony.*`` spans it writes into a profiler capture,
and the join that names a device's idle gaps by them.

- ``obs.phases`` units: the partition invariant, leaves never nest,
  ``switch``/``rest`` bookkeeping, wall vs CPU time, the fleet merge;
- ENGINE integration: a real ``serve.Server`` run leaves a ledger whose
  phases and unnamed remainder sum to its wall clock and whose
  ``decode.*`` counts equal the timeline's decode dispatches; greedy
  tokens do not depend on who drives the loop;
- gateway integration: ``/stats`` ``engine.host`` is the sum of the
  replica rows, ``/metrics`` carries the phases as one labelled family,
  ``edge.emit_lag`` counts every token event of a streamed request;
- ``profiler.xplane``: a CPU ``jax.profiler`` capture of a tiny server
  holds ``tony.decode.wait`` spans in a host plane, and ``split_gaps``
  / ``clock_shift_ns`` do their arithmetic on hand-made planes.
"""

import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from tony_tpu.gateway import Gateway, GenRequest
from tony_tpu.gateway.edge import GatewayEdge
from tony_tpu.models import Transformer, TransformerConfig
from tony_tpu.obs import HostPhases, prometheus_text
from tony_tpu.profiler import xplane
from tony_tpu.serve import Request, Server


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


def _is_partition(snap, rel=1e-3):
    named = sum(p["ms"] for p in snap["phases"].values())
    return abs(named + snap["unnamed_ms"] - snap["wall_ms"]) \
        <= rel * snap["wall_ms"] + 0.01  # + the 3-decimal rounding


# ------------------------------------------------------------ the unit


def test_phases_partition_the_wall_clock():
    hp = HostPhases()
    with hp.rest("step.other"):
        with hp.phase("decode.prepare", seq=7):
            time.sleep(0.002)
            hp.switch("decode.wait")
            time.sleep(0.004)
            hp.switch("decode.emit")
        time.sleep(0.003)   # the enclosing span's own time
    time.sleep(0.002)       # nobody's
    snap = hp.snapshot()
    assert _is_partition(snap)
    ph = snap["phases"]
    assert [ph[n]["count"] for n in ("decode.prepare", "decode.wait",
                                     "decode.emit", "step.other")] \
        == [1, 1, 1, 1]
    assert ph["decode.wait"]["ms"] >= 4.0
    # the rest span books only what its leaves left: its own sleep, not
    # the 6 ms the leaves slept
    assert 3.0 <= ph["step.other"]["ms"] < 6.0
    assert snap["unnamed_ms"] >= 2.0


@pytest.mark.parametrize("opener", ["phase", "rest"])
def test_a_span_opened_inside_a_leaf_raises(opener):
    hp = HostPhases()
    with hp.phase("decode.wait"):
        with pytest.raises(AssertionError, match="decode.wait|inside"):
            with getattr(hp, opener)("loop.stream"):
                pass
    # the outer leaf closed normally and the ledger is still usable
    with hp.phase("loop.stream"):
        pass
    assert hp.snapshot()["phases"]["loop.stream"]["count"] == 1


def test_switch_needs_an_open_leaf_and_rest_does_not_nest():
    hp = HostPhases()
    with pytest.raises(AssertionError, match="no host phase open"):
        hp.switch("decode.wait")
    with hp.rest("step.other"):
        with pytest.raises(AssertionError, match="inside another"):
            with hp.rest("step.other"):
                pass


def test_a_leaf_closes_when_its_block_raises():
    hp = HostPhases()
    with pytest.raises(RuntimeError):
        with hp.rest("step.other"):
            with hp.phase("decode.prepare"):
                hp.switch("decode.enqueue")
                raise RuntimeError("an injected dispatch failure")
    # the failed-over replica steps again: nothing was left open
    with hp.rest("step.other"), hp.phase("decode.prepare"):
        pass
    ph = hp.snapshot()["phases"]
    assert ph["decode.prepare"]["count"] == 2
    assert ph["decode.enqueue"]["count"] == 1
    assert ph["step.other"]["count"] == 2


@pytest.mark.parametrize("work,cpu_share", [("spin", (0.5, 1.05)),
                                            ("sleep", (0.0, 0.2))])
def test_cpu_time_tells_work_from_waiting(work, cpu_share):
    hp = HostPhases()
    with hp.phase("p"):
        t_end = time.perf_counter() + 0.05
        if work == "sleep":
            time.sleep(0.05)
        else:
            while time.perf_counter() < t_end:
                pass
    row = hp.snapshot()["phases"]["p"]
    assert row["ms"] >= 49.0
    lo, hi = cpu_share
    assert lo * row["ms"] <= row["cpu_ms"] <= hi * row["ms"], row


def test_snapshot_books_an_open_leaf_to_its_phase():
    """A snapshot taken from another thread while the owner sits in a
    long leaf (an idle ``cv.wait``, a device wait) must not book that
    time to ``unnamed``: a window's delta would read negative once the
    leaf closes."""
    import threading

    hp = HostPhases()
    entered, leave = threading.Event(), threading.Event()

    def owner():
        with hp.phase("loop.idle_wait"):
            entered.set()
            leave.wait(timeout=10)

    t = threading.Thread(target=owner)
    t.start()
    try:
        assert entered.wait(timeout=10)
        time.sleep(0.03)
        mid = hp.snapshot()
    finally:
        leave.set()
        t.join(timeout=10)
    assert not t.is_alive()
    row = mid["phases"]["loop.idle_wait"]
    assert row["count"] == 0 and row["ms"] >= 30.0
    assert _is_partition(mid) and mid["unnamed_ms"] < 0.5 * row["ms"]
    end = hp.snapshot()
    assert end["phases"]["loop.idle_wait"]["count"] == 1
    assert end["phases"]["loop.idle_wait"]["ms"] >= row["ms"]
    # what the window between the two snapshots did not name is not
    # negative by the leaf that was open at its start
    assert end["unnamed_ms"] - mid["unnamed_ms"] >= -0.01


def test_merge_sums_replicas_and_stays_a_partition():
    a, b = HostPhases(), HostPhases()
    for hp, n in ((a, 2), (b, 3)):
        for _ in range(n):
            with hp.phase("decode.wait"):
                time.sleep(0.001)
    with b.phase("loop.beat"):
        pass
    sa, sb = a.snapshot(), b.snapshot()
    merged = HostPhases.merge([sa, sb])
    assert merged["phases"]["decode.wait"]["count"] == 5
    assert merged["phases"]["loop.beat"]["count"] == 1
    assert merged["wall_ms"] == pytest.approx(
        sa["wall_ms"] + sb["wall_ms"], abs=0.002)
    assert merged["phases"]["decode.wait"]["ms"] == pytest.approx(
        sa["phases"]["decode.wait"]["ms"]
        + sb["phases"]["decode.wait"]["ms"], abs=0.002)
    assert _is_partition(merged)
    assert HostPhases.merge([]) == {"wall_ms": 0.0, "phases": {},
                                    "unnamed_ms": 0.0}


# ---------------------------------------------------------- the engine


def _requests(n=3, new=8):
    return [Request(prompt=[1 + i, 2, 3, 4], max_new_tokens=new, id=i)
            for i in range(n)]


@pytest.mark.parametrize("server_kw", [
    {"chunk_steps": 2},
    {"chunk_steps": 2, "paged": False},
    {"chunk_steps": 4, "speculate_k": 2},
    {"chunk_steps": 2, "prefill_chunk_tokens": 8},
])
def test_engine_ledger_partitions_a_real_run(tiny, server_kw):
    model, params = tiny
    srv = Server(model, params, batch_size=2, min_bucket=8, **server_kw)
    reqs = _requests()
    if "prefill_chunk_tokens" in server_kw:   # a prompt of three chunks
        reqs.append(Request(prompt=list(range(1, 21)), max_new_tokens=4,
                            id=9))
    results = list(srv.run(reqs))
    assert len(results) == len(reqs)
    snap = srv.host_phases()
    assert _is_partition(snap)
    ph = snap["phases"]
    disp = srv.timeline.summary()
    # one of each decode leaf per decode dispatch, likewise verify
    for kind in ("decode", "verify"):
        n = disp.get(kind, {}).get("count", 0)
        for leaf in ("prepare", "enqueue", "wait", "emit", "record"):
            assert ph.get(f"{kind}.{leaf}", {"count": 0})["count"] == n, \
                (kind, leaf, n)
    assert disp["decode"]["count"] + disp.get("verify", {}).get(
        "count", 0) > 0
    # one admission each, and the wait is where the device was awaited
    admits = sum(disp.get(k, {}).get("count", 0)
                 for k in ("prefill", "hit_admit", "cow_admit"))
    assert ph["admit.wait"]["count"] == admits == len(reqs)
    assert ph["admit.emit"]["count"] == admits
    if "prefill_chunk_tokens" in server_kw:
        chunks = disp["prefill_chunk"]["count"]
        assert chunks == 2
        assert ph["prefill_chunk.wait"]["count"] == chunks
        assert ph["prefill_chunk.record"]["count"] == chunks
    assert ph["step.other"]["count"] >= disp["decode"]["count"]
    # a step blocks on the device only inside its wait leaves
    assert ph["decode.record"]["cpu_ms"] <= ph["decode.record"]["ms"] + 0.5


def test_tokens_do_not_depend_on_who_drives_the_loop(tiny):
    """Greedy tokens of fixed prompts: the same from ``Server.run`` as
    from a gateway replica's loop (which books its own phases into the
    same ledger), and the same again on a second engine. The named
    scopes ISSUE 27 asked to pin this way were left out (PERF.md)."""
    model, params = tiny
    direct = {r.id: r.tokens for r in Server(
        model, params, batch_size=2, min_bucket=8, chunk_steps=2).run(
            _requests())}
    gw = Gateway([Server(model, params, batch_size=2, min_bucket=8,
                         chunk_steps=2)], max_queue=8).start()
    try:
        tickets = [gw.submit(GenRequest([1 + i, 2, 3, 4], max_new_tokens=8,
                                        id=f"r{i}")) for i in range(3)]
        served = {i: t.result(timeout=120).tokens
                  for i, t in enumerate(tickets)}
    finally:
        assert gw.drain(timeout=60)
    assert served == direct


# --------------------------------------------------------- the gateway


def test_stats_engine_host_merges_two_replicas(tiny):
    model, params = tiny
    gw = Gateway([Server(model, params, batch_size=2, min_bucket=8)
                  for _ in range(2)], max_queue=32).start()
    try:
        tickets = [gw.submit(GenRequest([1 + i, 2], max_new_tokens=4,
                                        id=i)) for i in range(6)]
        for t in tickets:   # submitted together: both replicas work
            t.result(timeout=120)
        snap = gw.snapshot()
        text = prometheus_text(gw)
    finally:
        assert gw.drain(timeout=60)
    rows = [r["host_phases"] for r in snap["replicas"]]
    assert len(rows) == 2
    fleet = snap["engine"]["host"]
    assert _is_partition(fleet)
    for name in ("decode.wait", "loop.beat", "loop.deliver",
                 "loop.admit_queue", "loop.idle_wait"):
        assert fleet["phases"][name]["count"] == sum(
            r["phases"].get(name, {"count": 0})["count"] for r in rows)
        assert fleet["phases"][name]["ms"] == pytest.approx(sum(
            r["phases"].get(name, {"ms": 0})["ms"] for r in rows),
            abs=0.01)
    # the ledger explains the dispatch block beside it
    assert fleet["phases"]["decode.wait"]["count"] \
        == snap["engine"]["dispatch"]["decode"]["count"]
    # the process sample keeps its key in the replica row
    assert snap["replicas"][0]["host"]["rss_bytes"] > 0
    # /metrics: one family per quantity, a label per phase
    assert text.count("# TYPE tony_host_phase_seconds_total counter") == 1
    for rep in ("0", "1"):
        assert (f'tony_host_phase_count_total{{replica="{rep}",'
                f'phase="decode.wait"}}') in text
        assert (f'tony_host_phase_seconds_total{{replica="{rep}",'
                f'phase="unnamed"}}') in text


def test_edge_emit_lag_counts_every_token_event(tiny):
    model, params = tiny
    gw = Gateway([Server(model, params, batch_size=2, min_bucket=8,
                         chunk_steps=2)], max_queue=8).start()
    edge = GatewayEdge(gw, port=0).start()
    url = f"http://{edge.host}:{edge.port}"
    try:
        body = json.dumps({"token_ids": [3, 4, 5], "max_new_tokens": 9,
                           "stream": True}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                url + "/v1/generate", data=body), timeout=120) as resp:
            lines = [json.loads(ln) for ln in resp.read().splitlines()
                     if ln.strip()]
        deltas = [ln for ln in lines if "token_ids" in ln
                  and "finish_reason" not in ln]
        assert sum(len(d["token_ids"]) for d in deltas) == 9
        # a unary request's events are consumed, never counted
        urllib.request.urlopen(urllib.request.Request(
            url + "/v1/generate", data=json.dumps(
                {"token_ids": [3, 4], "max_new_tokens": 4}).encode()),
            timeout=120).read()
        lag = gw.snapshot()["edge"]["emit_lag"]
        text = prometheus_text(gw)
    finally:
        edge.stop()
        assert gw.drain(timeout=60)
    assert lag["count"] == len(deltas) >= 2
    assert 0.0 < lag["ms"] and lag["max_ms"] <= lag["ms"]
    assert f"tony_edge_emit_lag_events_total {len(deltas)}" in text


# ------------------------------------------------- the capture, the join


def test_cpu_capture_holds_the_engines_spans(tiny, tmp_path):
    model, params = tiny
    srv = Server(model, params, batch_size=2, min_bucket=8, chunk_steps=2)
    list(srv.run(_requests(2, 4)))      # compile outside the capture
    before = srv.timeline.seq
    jax.profiler.start_trace(str(tmp_path))
    try:
        list(srv.run(_requests(2, 6)))
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path = xplane.xplane_files(str(tmp_path))[-1]
    found: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tony."):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    n_decode = srv.timeline.summary()["decode"]["count"]
    assert len(found["tony.decode.wait"]) >= 2
    assert len(found["tony.decode.wait"]) <= n_decode
    # a decode span leads to its timeline record, an admission's to its
    # request
    seqs = {int(st["seq"]) for st in found["tony.decode.wait"]}
    recs = {r.seq: r for r in srv.timeline.since(before)}
    assert seqs and all(recs[s].kind == "decode" for s in seqs)
    # a round's enqueue half comes a round before its record's number
    # is known: both halves carry the round's ordinal, and so does the
    # record
    for st in found["tony.decode.wait"]:
        assert recs[int(st["seq"])].tags["round"] == int(st["round"])
    assert {int(st["round"]) for st in found["tony.decode.enqueue"]} \
        >= {int(st["round"]) for st in found["tony.decode.wait"]}
    assert {str(st["rid"]) for st in found["tony.admit.wait"]} \
        == {"0", "1"}
    assert "tony.step.other" in found
    # the command's reader finds the same spans (no device plane on
    # the CPU backend, so there is no gap to split)
    report = xplane.idle_gaps(str(tmp_path))
    assert report["path"] == path
    assert report["host_spans"] >= sum(len(v) for v in found.values())
    assert "tony.* host spans" in xplane.format_gaps(report)


MS = 1_000_000


def _planes(device_events, host_events, device2=None):
    lines = [("/device:TPU:0", "XLA Modules", device_events),
             ("/host:CPU", "gateway-replica-0", host_events)]
    if device2 is not None:
        lines.append(("/device:TPU:1", "XLA Modules", device2))
    return lines


def test_split_gaps_a_gap_wholly_under_one_span():
    # two programs 10 ms apart; one emit span covers the whole gap
    report = xplane.split_gaps(_planes(
        [("jit__decode_chunk(11)", 0, 20 * MS),
         ("jit__decode_chunk(11)", 30 * MS, 20 * MS)],
        [("tony.decode.emit", 19 * MS, 12 * MS)]), shift_ns=0)
    assert report["planes"] == 1
    assert report["idle_s"] == pytest.approx(0.010)
    assert report["busy_s"] == pytest.approx(0.040)
    assert report["window_s"] == pytest.approx(0.050)
    assert report["uncovered_s"] == 0
    assert report["phases_s"] == {"decode.emit": pytest.approx(0.010)}
    gap = report["gaps"]["jit__decode_chunk -> jit__decode_chunk"]
    assert gap["idle_s"] == pytest.approx(0.010)


def test_split_gaps_a_gap_over_two_spans_an_enclosing_one_and_nothing():
    # gap [20, 30): wait's copy back to 21, emit to 24, 2 ms that only
    # the enclosing step.other covers, enqueue from 26 to 29, then 1 ms
    # under no span at all
    report = xplane.split_gaps(_planes(
        [("jit__decode_chunk(11)", 0, 20 * MS),
         ("jit__decode_chunk(11)", 30 * MS, 20 * MS)],
        [("tony.step.other", 1 * MS, 28 * MS),
         ("tony.decode.wait", 2 * MS, 19 * MS),
         ("tony.decode.emit", 21 * MS, 3 * MS),
         ("tony.decode.enqueue", 26 * MS, 3 * MS),
         ("not.ours", 29 * MS, 1 * MS)]), shift_ns=0)
    assert report["phases_s"] == {
        "decode.emit": pytest.approx(0.003),
        "decode.enqueue": pytest.approx(0.003),
        "step.other": pytest.approx(0.002),
        "decode.wait": pytest.approx(0.001)}
    assert report["uncovered_s"] == pytest.approx(0.001)
    assert sum(report["phases_s"].values()) + report["uncovered_s"] \
        == pytest.approx(report["idle_s"])
    assert "(uncovered)" in xplane.format_gaps(report)


def test_split_gaps_two_device_planes_and_program_pairs():
    # plane 0 idles 10 ms between decode steps, plane 1 idles 4 ms
    # between a prefill and a decode step: seconds are per plane
    report = xplane.split_gaps(_planes(
        [("jit__decode_chunk(11)", 0, 20 * MS),
         ("jit__decode_chunk(11)", 30 * MS, 20 * MS)],
        [("tony.loop.stream", 18 * MS, 14 * MS)],
        device2=[("jit__paged_prefill_admit(5)", 0, 22 * MS),
                 ("jit__decode_chunk(11)", 26 * MS, 24 * MS)]),
        shift_ns=0)
    assert report["planes"] == 2
    assert report["idle_s"] == pytest.approx(0.007)
    assert list(report["gaps"]) == [
        "jit__decode_chunk -> jit__decode_chunk",
        "jit__paged_prefill_admit -> jit__decode_chunk"]
    assert report["gaps"]["jit__paged_prefill_admit -> jit__decode_chunk"][
        "phases_s"] == {"loop.stream": pytest.approx(0.002)}
    assert report["uncovered_s"] == 0
    # overlapping programs are one busy interval, not a negative gap
    report = xplane.split_gaps(_planes(
        [("a(1)", 0, 20 * MS), ("b(2)", 10 * MS, 5 * MS),
         ("a(1)", 25 * MS, 5 * MS)], []), shift_ns=0)
    assert report["busy_s"] == pytest.approx(0.025)
    assert report["gaps"] == {"a -> a": {
        "idle_s": pytest.approx(0.005), "uncovered_s": pytest.approx(0.005),
        "phases_s": {}}}


def test_clock_shift_puts_the_device_back_under_its_waits():
    # the device plane runs 1.5 ms early: each program "ends" 1.7 ms
    # before the wait that returned it (0.2 ms of copy back)
    device, host = [], []
    for i in range(40):
        t = i * 30 * MS
        true_start, true_end = t + 4 * MS, t + 22 * MS
        device.append(("jit__decode_chunk(11)", true_start - 1_500_000,
                       true_end - true_start))
        host += [("tony.decode.enqueue", t, 5 * MS),
                 ("tony.decode.wait", t + 5 * MS,
                  true_end - (t + 5 * MS) + 200_000 + (i % 4) * 50_000),
                 ("tony.decode.emit", true_end + 400_000, 2 * MS)]
    lines = _planes(device, host)
    shift, pairs = xplane.clock_shift_ns(lines)
    assert pairs == 40
    assert shift == pytest.approx(1_700_000, abs=60_000)
    raw = xplane.split_gaps(lines, shift_ns=0)
    fixed = xplane.split_gaps(lines)
    assert fixed["clock_shift_ms"] == pytest.approx(1.7, abs=0.06)
    # unshifted, 1.7 ms of every gap reads as waiting; shifted, the
    # wait keeps only its copy back's spread
    assert raw["phases_s"]["decode.wait"] > 39 * 0.0015
    assert fixed["phases_s"]["decode.wait"] < 39 * 0.0003
    assert xplane.clock_shift_ns(_planes(device, [])) == (0, 0)
