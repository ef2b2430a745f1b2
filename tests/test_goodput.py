"""Goodput ledger + alert bus tests (ISSUE 10).

Pinned bottom-up:

- ``obs.goodput`` units: the cost model's scaling behavior (bytes grow
  with depth/view, verify reads parameters once where a chunk reads
  them per micro-step), the roofline-reference detection, and the
  ledger's structural sums-to-<=1 invariant on synthetic summaries;
- THE acceptance pin: on a live engine run the ledger's bucket
  fractions sum to <= 1.0 AND reconcile exactly with the timeline
  (per-kind useful+padding+overshoot+rejected == steady ms) and the
  engine counters (``sum(fed - tokens)`` over decode+verify ==
  ``wasted_steps``; landed tokens == tokens the requests kept);
- ``obs.alerts`` units: fire-once dedup, resolve debounce, the rule
  predicates (queue aging, KV pressure, TTFT burn over histogram
  deltas, breaker flap windows, goodput collapse vs baseline), and a
  raising rule never taking the bus down;
- gateway integration: a deliberately tiny KV page pool under live
  load fires ``kv_pages_pressure`` into /stats alerts + history
  ``metrics/alerts.jsonl`` and RESOLVES when load stops;
  ``GET /debug/goodput`` names a largest waste bucket and
  ``GET /debug/traces`` lists terminal tags over real HTTP.
"""

import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from tony_tpu.gateway import Gateway, GatewayHistory, GatewayHTTP, GenRequest
from tony_tpu.models import Transformer, TransformerConfig
from tony_tpu.obs.alerts import (AlertBus, BreakerFlapRule,
                                 GoodputCollapseRule, KvPagesPressureRule,
                                 QueueAgingRule, Rule, TtftSloBurnRule)
from tony_tpu.obs.goodput import (WASTE_BUCKETS, CostModel,
                                  detect_hbm_gbps, ledger, merge_ledgers)
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


# ---------------------------------------------------- cost model units


def _cm(**kw):
    base = dict(param_bytes=10_000_000, param_count=5_000_000,
                kv_token_bytes=256.0, n_heads=8, head_dim=64,
                vocab_size=32_000)
    base.update(kw)
    return CostModel(**base)


def test_cost_model_scales_with_depth_and_view():
    cm = _cm()
    b1, f1 = cm.decode(1, 4, 128)
    b8, f8 = cm.decode(8, 4, 128)
    assert b8 == pytest.approx(8 * b1) and f8 == pytest.approx(8 * f1)
    bwide, _ = cm.decode(1, 4, 1024)
    assert bwide > b1  # a longer live view moves more cache bytes
    # a verify window reads the parameters ONCE; a chunk of the same
    # depth re-reads them per micro-step — the whole point of the
    # one-dispatch verify
    bv, _ = cm.verify(8, 4, 128)
    assert bv < b8
    # paged exact-hit admission moves ~a page; the unpaged hit copies
    # a whole row (the extras.paged 14.8x fewer-bytes claim, in model)
    bhit, _ = cm.hit_admit(row_bytes=1_000_000)
    bcow, _ = cm.cow_admit(fork_bytes=4_096)
    assert bcow < bhit


def test_cost_model_utilization_reference_gating():
    summary = {"decode": {"ms": 10.0, "est_bytes_steady": 5e9,
                          "est_flops_steady": 100e12 * 0.01}}
    none = ledger(summary, 20.0)["utilization"]["decode"]
    # no reference: null
    assert none["hbm_bw_pct"] is None and none["mfu_pct"] is None
    util = ledger(summary, 20.0, hbm_gbps=1000.0,
                  peak_flops=100e12)["utilization"]["decode"]
    # 5 GB in 10 ms against 1000 GB/s = 50%; 1e12 FLOPs in 10 ms
    # against 100 TFLOP/s = 100%
    assert util["hbm_bw_pct"] == pytest.approx(50.0, abs=0.1)
    assert util["mfu_pct"] == pytest.approx(100.0, abs=0.1)


def test_detect_hbm_gbps_env_override(monkeypatch):
    monkeypatch.setenv("TONY_HBM_GBPS", "123.5")
    assert detect_hbm_gbps() == 123.5
    monkeypatch.setenv("TONY_HBM_GBPS", "not-a-number")
    assert detect_hbm_gbps() >= 0.0  # falls through to the chip table


def test_ledger_structural_invariant_synthetic():
    summary = {
        "decode": {"ms": 80.0, "compile_ms": 20.0, "useful_ms": 40.0,
                   "padding_ms": 10.0, "overshoot_ms": 8.0,
                   "rejected_ms": 2.0, "est_bytes": 1e9,
                   "est_flops": 1e12, "est_bytes_steady": 8e8,
                   "est_flops_steady": 8e11},
        "prefill": {"ms": 20.0, "compile_ms": 5.0, "useful_ms": 12.0,
                    "padding_ms": 3.0, "overshoot_ms": 0.0,
                    "rejected_ms": 0.0, "est_bytes": 1e8,
                    "est_flops": 1e11, "est_bytes_steady": 9e7,
                    "est_flops_steady": 9e10},
    }
    led = ledger(summary, wall_ms=200.0, hbm_gbps=819.0)
    total = sum(led["buckets"].values())
    assert total <= 1.0 + 1e-9
    assert led["buckets"]["idle"] == pytest.approx(0.5)
    assert led["largest_waste"] == "idle"
    assert led["utilization"]["decode"]["hbm_bw_pct"] is not None
    assert led["utilization"]["decode"]["mfu_pct"] is None  # no peak
    # wall SHORTER than dispatch time (clock jitter): still <= 1
    led2 = ledger(summary, wall_ms=50.0)
    assert sum(led2["buckets"].values()) <= 1.0 + 1e-9
    assert led2["buckets"]["idle"] == 0.0
    # fleet merge re-weights by wall
    merged = merge_ledgers([led, led])
    assert sum(merged["buckets"].values()) <= 1.0 + 1e-9
    assert merged["wall_ms"] == pytest.approx(400.0)
    assert merged["largest_waste"] == "idle"
    assert merge_ledgers([]) == {} and merge_ledgers([None]) == {}


# ----------------------------------------- THE live reconciliation pin


@pytest.mark.parametrize("paged", [True, False])
def test_ledger_reconciles_with_timeline_and_counters(tiny, paged):
    """The acceptance invariant: bucket fractions sum to <= 1.0 and
    reconcile with timeline ms/compile_ms/tokens and the engine's
    wasted_steps/spec counters on a LIVE run (speculation + prefix on,
    mixed budgets so chunk overshoot, draft rejection, and padding all
    actually occur)."""
    model, params = tiny
    server = Server(model, params, batch_size=3, eos_id=-1,
                    chunk_steps=4, speculate_k=3, prefix_cache_mb=1.0,
                    paged=paged)

    def reqs(base):
        return [Request([1, 2, 3, 1, 2, 3, 1, 2], 9, id=base),
                Request([5, 4, 3, 2], 3, id=base + 1),
                Request([1, 2, 3, 1, 2, 3, 1, 2], 11, id=base + 2),
                Request([9, 8], 5, id=base + 3)]

    # two passes through the SAME engine: the first pays every
    # (kind, shape) first-call — all compile-bucket — the second runs
    # the same programs steady, so overshoot/padding carry real time
    results = list(server.run(reqs(0))) + list(server.run(reqs(10)))
    assert len(results) == 8

    summ = server.timeline.summary()
    # per-kind exact split: useful+padding+overshoot+rejected == steady
    for kind, a in summ.items():
        split = (a["useful_ms"] + a["padding_ms"] + a["overshoot_ms"]
                 + a["rejected_ms"])
        assert split == pytest.approx(a["ms"] - a["compile_ms"],
                                      abs=0.05), kind
    # position accounting reproduces the engine's waste counter
    wasted = sum(summ[k]["fed"] - summ[k]["tokens"]
                 for k in ("decode", "verify") if k in summ)
    assert wasted == server.wasted_steps
    # landed tokens reconcile with what the requests kept
    landed = sum(a["tokens"] for a in summ.values())
    assert landed == sum(len(r.tokens) for r in results)
    # every record was priced
    assert all(a["est_bytes"] > 0 for a in summ.values())

    led = server.goodput()
    assert sum(led["buckets"].values()) <= 1.0 + 1e-6
    assert led["largest_waste"] in WASTE_BUCKETS
    assert led["useful_fraction"] > 0
    # fresh engine: the first calls flagged compile carry real time
    assert led["ms"]["compile"] > 0
    # batch 3 with stragglers pads (empty slots in the static shape);
    # chunk overshoot has its own deterministic pin below
    assert led["ms"]["padding"] > 0
    # CPU box: no roofline reference -> utilization is null, bytes
    # real (speculation can make every decode round a verify, so pick
    # whichever step kind this run produced)
    step_kind = "verify" if "verify" in led["utilization"] else "decode"
    if detect_hbm_gbps() == 0.0:
        assert led["hbm_gbps"] is None
        assert led["utilization"][step_kind]["hbm_bw_pct"] is None
    assert led["utilization"][step_kind]["est_bytes"] > 0


def test_in_dispatch_eos_zeroes_the_overshoot_bucket(tiny):
    """A mixed-budget workload (a budget-3 slot beside a k=4 chunk
    round) lands ZERO overshoot — fed == landed on every decode
    dispatch, the trailing positions are frozen re-emits charged to
    padding, and wasted_steps stays exactly sum(fed - tokens) == 0."""
    model, params = tiny
    server = Server(model, params, batch_size=2, eos_id=-1,
                    chunk_steps=4)

    def run_pair(base):
        list(server.run([Request([1, 2, 3], 3, id=base),
                         Request([4, 5, 6], 9, id=base + 1)]))

    run_pair(0)   # first pass pays the compiles
    run_pair(10)  # steady: the budget-3 slot FREEZES inside the chunk
    assert server.wasted_steps == 0
    assert server.frozen_steps > 0
    assert server.freeze_faults == 0
    summ = server.timeline.summary()
    assert summ["decode"]["fed"] == summ["decode"]["tokens"]
    led = server.goodput()
    assert led["ms"]["overshoot"] == 0.0
    assert led["ms"]["padding"] > 0  # the frozen tail lands here


def test_explicit_hbm_reference_prices_utilization(tiny):
    model, params = tiny
    server = Server(model, params, batch_size=2, eos_id=-1,
                    hbm_gbps=800.0)
    list(server.run([Request([1, 2, 3], 4, id=0)]))
    list(server.run([Request([1, 2, 4], 4, id=1)]))  # steady pass
    led = server.goodput()
    assert led["hbm_gbps"] == 800.0
    util = led["utilization"]["decode"]
    # a tiny model's share of a host-timed dispatch may round to 0.00:
    # what must hold is that steady bytes were priced against the knob
    assert util["hbm_bw_pct"] is not None
    assert server.timeline.summary()["decode"]["est_bytes_steady"] > 0
    # the share is the ledger's, per kind; a record carries no tag
    recs = [r for r in server.timeline.recent() if r.kind == "decode"]
    assert recs and not any("hbm_bw_pct" in r.tags for r in recs)


def test_merged_local_plus_remote_ledger_pins(tiny):
    """ISSUE-15: the sums-<=1 and fed==landed reconciliation pins,
    extended to a MERGED local+remote fleet — a gateway over one
    in-process engine and one remote stub whose ledger/timeline
    arrive over the obs-pull channel. The merged engine.dispatch
    block must keep the position-accounting identities (in-dispatch
    EOS: fed == tokens on decode, fleet wasted_steps == 0) and the
    merged ledger must keep its structural invariant with the pulled
    remote ledger included."""
    import time as _time

    from tony_tpu.gateway.remote import RemoteServer
    from tony_tpu.serve.agent import AgentHTTP, ReplicaAgent

    model, params = tiny
    agent = AgentHTTP(ReplicaAgent(Server(
        model, params, batch_size=2, eos_id=-1))).start()
    stub = RemoteServer(agent.address, heartbeat_interval_s=0.1,
                        lease_misses=10, boot_timeout_s=20.0)
    local = Server(model, params, batch_size=2, eos_id=-1)
    gw = Gateway([local, stub], max_queue=32, max_attempts=3,
                 stall_timeout_s=10.0, breaker_base_s=0.05,
                 breaker_max_s=0.2).start()
    try:
        n, budget = 6, 8
        tickets = [gw.submit(GenRequest([1 + i, 2, 3],
                                        max_new_tokens=budget,
                                        id=i)) for i in range(n)]
        for t in tickets:
            t.result(timeout=120)
        # both replicas actually served (least-outstanding spread)
        hosts = {t.metrics["host"] for t in tickets}
        assert hosts == {"local", agent.address}, hosts
        remote_tokens = sum(budget for t in tickets
                            if t.metrics["host"] == agent.address)
        deadline = _time.monotonic() + 30
        while _time.monotonic() < deadline:
            summ = stub.timeline.summary()
            if summ and sum(a["tokens"] for a in summ.values()) \
                    >= remote_tokens:
                break
            _time.sleep(0.02)
        stub._obs_pull = False  # freeze the pulled state
        snap = gw.snapshot()
        disp = snap["engine"]["dispatch"]
        # landed tokens reconcile across BOTH replicas' timelines
        assert sum(a["tokens"] for a in disp.values()) == n * budget
        # in-dispatch EOS identity survives the merge: every decode
        # position fed landed a kept token, fleet-wide
        assert disp["decode"]["fed"] == disp["decode"]["tokens"]
        assert snap["engine"]["wasted_steps"] == 0
        # the merged ledger: local + pulled-remote, sums <= 1, and
        # both constituent ledgers were real
        rows = {r["replica"]: r for r in snap["replicas"]}
        assert rows[0]["goodput"] is not None  # local
        assert rows[1]["goodput"] is not None  # pulled remote
        for row in rows.values():
            assert sum(row["goodput"]["buckets"].values()) <= 1 + 1e-6
        fleet = snap["engine"]["goodput"]
        assert fleet and sum(fleet["buckets"].values()) <= 1 + 1e-6
        assert fleet["wall_ms"] > max(
            rows[0]["goodput"]["wall_ms"],
            rows[1]["goodput"]["wall_ms"])  # both walls summed
        assert fleet["useful_fraction"] > 0
        assert fleet["largest_waste"] in WASTE_BUCKETS
        # /debug/goodput's report shape holds over the mixed fleet
        report = gw.goodput_report()
        assert report["enabled"]
        assert {r["replica"] for r in report["replicas"]} == {0, 1}
    finally:
        gw.drain(timeout=60)
        agent.stop()


def test_goodput_none_with_timeline_off(tiny):
    model, params = tiny
    server = Server(model, params, batch_size=2, eos_id=-1,
                    timeline=False)
    list(server.run([Request([1, 2, 3], 3, id=0)]))
    assert server.goodput() is None


# ------------------------------------------------------ alert bus units


def test_alert_bus_fire_once_resolve_debounced():
    state = {"on": False}
    rule = Rule("toggling", check=lambda s: {"x": 1} if state["on"]
                else None, fire_after=1, resolve_after=2)
    bus = AlertBus([rule])
    assert bus.evaluate({}) == []
    state["on"] = True
    events = bus.evaluate({})
    assert [e.state for e in events] == ["firing"]
    # active: no re-fire while the condition holds
    assert bus.evaluate({}) == [] and len(bus.active()) == 1
    state["on"] = False
    assert bus.evaluate({}) == []  # first clear tick: debounced
    events = bus.evaluate({})      # second: resolves
    assert [e.state for e in events] == ["resolved"]
    assert bus.active() == []
    snap = bus.snapshot()
    assert snap["fired"]["toggling"] == 1
    assert snap["resolved"]["toggling"] == 1
    assert len(snap["recent"]) == 2
    # a blip shorter than fire_after never fires
    blip = Rule("blip", check=lambda s: s.get("d"), fire_after=2)
    bus2 = AlertBus([blip])
    bus2.evaluate({"d": {"x": 1}})
    assert bus2.evaluate({}) == [] and bus2.active() == []


def test_alert_bus_survives_raising_rule():
    def boom(signals):
        raise RuntimeError("broken rule")

    bus = AlertBus([Rule("boom", check=boom),
                    Rule("ok", check=lambda s: {"v": 1})])
    events = bus.evaluate({})
    assert [e.alert for e in events] == ["ok"]


def test_queue_and_kv_rules_predicates():
    q = QueueAgingRule(queue_wait_s=2.0)
    assert q.evaluate({"oldest_wait_s": 1.0}) is None
    assert q.evaluate({"oldest_wait_s": 3.0, "depth": 4})[
        "oldest_wait_s"] == 3.0
    kv = KvPagesPressureRule(kv_free_frac=0.15)
    assert kv.evaluate({"kv_pages_total": 0}) is None  # unpaged fleet
    busy = {"kv_pages_total": 10, "kv_pages_free": 10,
            "kv_pages_reserved": 10, "active_slots": 1, "depth": 0}
    assert kv.evaluate(busy)["free_after_reserve_frac"] == 0.0
    idle = dict(busy, active_slots=0)
    assert kv.evaluate(idle) is None  # residency without load != pressure
    roomy = dict(busy, kv_pages_reserved=2)
    assert kv.evaluate(roomy) is None


def test_ttft_burn_rule_histogram_delta():
    rule = TtftSloBurnRule(ttft_slo_s=0.25, burn_frac=0.10,
                           min_samples=5)

    def hist(count, over):
        return {"count": count,
                "buckets": {"0.25": count - over, "1": over,
                            "+Inf": 0}}

    assert rule.evaluate({"ttft_hist": hist(10, 0)}) is None  # baseline
    # 6 new completions, 0 over: no burn
    assert rule.evaluate({"ttft_hist": hist(16, 0)}) is None
    # 8 new, 4 over the SLO edge: 50% burn
    out = rule.evaluate({"ttft_hist": hist(24, 4)})
    assert out and out["burn_frac"] == pytest.approx(0.5)
    # tiny tick below min_samples never judges
    assert rule.evaluate({"ttft_hist": hist(26, 6)}) is None
    # slo 0 = rule off
    assert TtftSloBurnRule(ttft_slo_s=0.0).evaluate(
        {"ttft_hist": hist(100, 100)}) is None


def test_breaker_flap_and_goodput_collapse_rules():
    flap = BreakerFlapRule(flap_failures=2, flap_window_s=60.0)
    assert flap.evaluate({"now": 0.0, "replica_failures": 0,
                          "states": ["healthy"]}) is None
    assert flap.evaluate({"now": 1.0, "replica_failures": 1,
                          "states": ["healthy"]}) is None
    out = flap.evaluate({"now": 2.0, "replica_failures": 2,
                         "states": ["broken"]})
    assert out and out["failures_in_window"] == 2
    assert out["unhealthy_replicas"] == 1
    # breaker STATES alone never fire: a probing/broken replica is
    # also the routine autoscale probe-admission path — a critical
    # alert per healthy scale-up would bury the real signal
    assert BreakerFlapRule().evaluate(
        {"now": 0.0, "replica_failures": 0,
         "states": ["healthy", "broken", "probing"]}) is None

    from tony_tpu.obs.alerts import ShedStormRule

    storm = ShedStormRule(storm_count=10, storm_window_s=5.0)
    assert storm.evaluate({"now": 0.0,
                           "shed_capacity_total": 0}) is None
    # a slow trickle of sheds never accumulates past the window
    assert storm.evaluate({"now": 1.0,
                           "shed_capacity_total": 4}) is None
    out = storm.evaluate({"now": 2.0, "shed_capacity_total": 15})
    assert out and out["sheds_in_window"] == 15
    assert out["window_s"] == 5.0
    # the window prunes by TIME: the burst above ages out, so the
    # same cumulative level 10 s later is calm, not a storm
    assert storm.evaluate({"now": 12.0,
                           "shed_capacity_total": 16}) is None

    col = GoodputCollapseRule(collapse_frac=0.5, min_updates=3)
    state = {"toks": 0, "useful": 0.0, "disp": 0.0}

    def tick(rule, d_useful, d_disp, flowing=True):
        state["toks"] += 10 if flowing else 0
        state["useful"] += d_useful
        state["disp"] += d_disp
        return rule.evaluate({"goodput_useful_ms": state["useful"],
                              "goodput_dispatch_ms": state["disp"],
                              "tokens_out": state["toks"]})

    for _ in range(5):  # establish the baseline at ~0.8 per-tick
        assert tick(col, 80.0, 100.0) is None
    out = tick(col, 10.0, 100.0)  # this tick's useful collapsed
    assert out and out["baseline"] == pytest.approx(0.8, abs=0.01)
    assert out["useful_fraction"] == pytest.approx(0.1, abs=0.01)
    # idle lulls and trickle traffic must NOT fire: the denominator
    # is DISPATCH time, and tiny-dispatch ticks are not judged
    col2 = GoodputCollapseRule(collapse_frac=0.5, min_updates=3)
    state = {"toks": 0, "useful": 0.0, "disp": 0.0}
    for _ in range(5):
        tick(col2, 80.0, 100.0)
    # fully idle tick (no dispatch, no tokens): not judged
    assert tick(col2, 0.0, 0.0, flowing=False) is None
    # trickle tick: one short healthy request in a mostly-idle
    # second — per-dispatch fraction is still ~0.8, no false fire
    assert tick(col2, 24.0, 30.0) is None
    # sub-threshold dispatch activity: not judged at all
    assert tick(col2, 1.0, 10.0) is None


# ------------------------------------------------- gateway integration


def test_kv_pressure_alert_fires_and_resolves_live(tiny, tmp_path):
    """The serve-smoke acceptance, in-process: a tiny KV page pool
    under live load fires kv_pages_pressure into /stats alerts and
    history metrics/alerts.jsonl, then RESOLVES once load stops."""
    model, params = tiny
    # 6 pages x 4 tokens = 24-token pool; each request's worst case
    # (3 + 20 = 23 tokens -> 6 pages) reserves the WHOLE pool, so
    # pressure is sustained while anything runs and others queue
    hist = GatewayHistory(str(tmp_path))
    gw = Gateway([Server(model, params, batch_size=2, eos_id=-1,
                         kv_page_size=4, kv_pages=6)],
                 history=hist, alert_interval_s=0.02,
                 alert_thresholds={"kv_free_frac": 0.15}).start()
    try:
        tickets = [gw.submit(GenRequest([1 + i, 2, 3],
                                        max_new_tokens=20, id=i))
                   for i in range(6)]
        deadline = time.monotonic() + 60
        fired = False
        while time.monotonic() < deadline and not fired:
            snap = gw.alerts.snapshot()
            fired = any(a["alert"] == "kv_pages_pressure"
                        for a in snap["active"])
            time.sleep(0.005)
        assert fired, gw.alerts.snapshot()
        for t in tickets:
            t.result(timeout=120)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            snap = gw.alerts.snapshot()
            if not snap["active"] and \
                    snap["resolved"].get("kv_pages_pressure"):
                break
            time.sleep(0.02)
        snap = gw.snapshot()["alerts"]
        assert snap["enabled"] and not snap["active"], snap
        assert snap["fired"]["kv_pages_pressure"] >= 1
        assert snap["resolved"]["kv_pages_pressure"] >= 1
    finally:
        assert gw.drain(timeout=60)
    rows = [json.loads(ln) for ln in
            open(hist._alerts_path) if ln.strip()]
    states = {(r["alert"], r["state"]) for r in rows}
    assert ("kv_pages_pressure", "firing") in states, rows
    assert ("kv_pages_pressure", "resolved") in states, rows


def test_alerts_disabled_gateway(tiny):
    model, params = tiny
    gw = Gateway([Server(model, params, batch_size=2, eos_id=-1)],
                 alerts=False).start()
    try:
        gw.submit(GenRequest([1, 2, 3], max_new_tokens=3,
                             id="a")).result(timeout=60)
        assert gw.snapshot()["alerts"] == {"enabled": False}
    finally:
        assert gw.drain(timeout=60)


def _get_json(url):
    with urllib.request.urlopen(url, timeout=60) as r:
        return r.status, json.loads(r.read())


def test_http_debug_goodput_and_traces(tiny):
    """GET /debug/goodput names a largest waste bucket and
    /debug/traces lists buffered traces WITH terminal tags, over real
    HTTP."""
    model, params = tiny
    gw = Gateway([Server(model, params, batch_size=2, eos_id=-1)]).start()
    http = GatewayHTTP(gw, port=0).start()
    url = f"http://{http.host}:{http.port}"
    try:
        body = json.dumps({"token_ids": [1, 2, 3], "max_new_tokens": 4,
                           "request_id": "gp-1"}).encode()
        req = urllib.request.Request(url + "/v1/generate", data=body)
        urllib.request.urlopen(req, timeout=120).read()

        status, doc = _get_json(url + "/debug/goodput")
        assert status == 200 and doc["enabled"]
        assert doc["largest_waste"] in WASTE_BUCKETS
        assert sum(doc["fleet"]["buckets"].values()) <= 1.0 + 1e-6
        assert doc["replicas"][0]["replica"] == 0

        status, doc = _get_json(url + "/debug/traces")
        assert status == 200
        rows = {r["request_id"]: r for r in doc["traces"]}
        assert rows["gp-1"]["outcome"] == "done"
        assert rows["gp-1"]["tokens_out"] == 4
        assert rows["gp-1"]["placements"] == 1  # replica placements
        assert rows["gp-1"]["attempts"] == 0    # failed engine runs
    finally:
        http.stop()
        assert gw.drain(timeout=60)
