"""Crash-safe control plane (ISSUE-20): orphan parking, the adopt
protocol, failover park-adoption, client resume, and journal-driven
restart recovery.

The house rule holds through a gateway crash: every recovered stream
is pinned BYTE-IDENTICAL to a no-crash control — an adopted parked
session resumes mid-stream with zero re-prefill and no attempt
charged, a re-run is charged exactly one attempt and regenerates the
same bytes (deterministic decode), and a request that finished into
the void comes back as its buffered result. The protocol half pins the
agent-side machinery: gateway silence freezes in-flight slots into
parked snapshots, the park TTL reaps them, the epoch fence makes
double-adoption impossible (409, never a second copy), and
``GET /v1/stream/<id>?offset=`` serves the absolute token sequence on
both edges.

In-process agents speak REAL HTTP over localhost (same trick as
test_remote); ``Gateway.kill()`` dies the way SIGKILL would — no
drain, no journal compaction, no epoch bumps. The subprocess flavor
(actual ``kill -9`` on a CLI gateway) runs in ``make recovery-smoke``.
Engines are throttled with a wedge fault (30 ms per dispatch,
token-exact preserved) so mid-stream windows exist on a tiny model.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.gateway import journal as jr
from tony_tpu.gateway.core import Gateway, GenRequest
from tony_tpu.models import Transformer, TransformerConfig
from tony_tpu.serve import Request, Server
from tony_tpu.serve.faults import FaultPlan

pytestmark = pytest.mark.filterwarnings("ignore::ResourceWarning")


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _prompt(seed=5, n=11):
    return np.random.default_rng(seed).integers(1, 64, size=n).tolist()


def _slow():
    # 30 ms per dispatch: a 40-token stream stays in flight ~1.2 s,
    # wide enough to crash/park/adopt mid-stream deterministically
    return FaultPlan.wedge_at(1, 0.03, times=-1)


def _mk(tiny, **kw):
    model, params = tiny
    kw.setdefault("prefix_cache_mb", 0)
    kw.setdefault("batch_size", 2)
    kw.setdefault("min_bucket", 8)
    # one token per dispatch (the wedge meters REAL wall time per
    # token) and paged KV (wire snapshots gather page content)
    kw.setdefault("chunk_steps", 1)
    kw.setdefault("paged", True)
    kw.setdefault("kv_page_size", 8)
    return Server(model, params, eos_id=-1, **kw)


def _control(tiny, prompt, budget):
    srv = _mk(tiny)
    srv.submit(Request(list(prompt), budget, id="c"))
    return list(list(srv.run())[0].tokens)


def _wait(cond, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def _start_agent(tiny, **agent_kw):
    from tony_tpu.serve.agent import AgentHTTP, ReplicaAgent

    server_kw = agent_kw.pop("server_kw", {})
    server_kw.setdefault("fault_plan", _slow())
    return AgentHTTP(ReplicaAgent(_mk(tiny, **server_kw), **agent_kw),
                     port=0).start()


def _stub(address, **kw):
    from tony_tpu.gateway.remote import RemoteServer

    kw.setdefault("heartbeat_interval_s", 0.1)
    kw.setdefault("lease_misses", 10)
    kw.setdefault("read_timeout_s", 2.0)
    kw.setdefault("boot_timeout_s", 20.0)
    return RemoteServer(address, **kw)


# ---------------------------------------------- park/adopt protocol


class TestParkAdopt:
    def test_gateway_silence_parks_then_adopt_token_exact(self, tiny):
        """The watchdog story end to end: gateway contact goes silent
        past the grace -> the live slot freezes into a parked wire
        snapshot with real progress; a fresh engine adopting that
        snapshot finishes the stream byte-identical to an uninterrupted
        control. The epoch fence then guarantees single ownership: a
        second adopter on a stale epoch gets 409, and the handed-out
        session is gone even for the current epoch."""
        from tony_tpu.serve.agent import ReplicaAgent, _StaleEpoch

        prompt, budget = _prompt(), 40
        expect = _control(tiny, prompt, budget)
        agent = ReplicaAgent(_mk(tiny, fault_plan=_slow()),
                             gateway_grace_s=0.3, park_ttl_s=60).start()
        try:
            agent.submit({"prompt": prompt, "max_new_tokens": budget,
                          "id": "p1", "rid": "rid-1", "epoch": 0})
            _wait(lambda: any(not r["finished"] and r["rid"] == "rid-1"
                              for r in agent.parked()["parked"]),
                  msg="watchdog parking the orphaned slot")
            row = [r for r in agent.parked()["parked"]
                   if r["rid"] == "rid-1"][0]
            assert row["offset"] > 0  # froze MID-stream, not at admit
            resp = agent.adopt({"id": "rid-1", "epoch": agent.epoch + 1})
            assert resp["found"] and not resp.get("finished")
            snap = resp["snapshot"]
            assert resp["offset"] == len(snap["generated"]) > 0
            # stale second adopter: fenced, never a second copy
            with pytest.raises(_StaleEpoch):
                agent.adopt({"id": "rid-1", "epoch": agent.epoch - 1})
            # current epoch, but the session was already handed out
            assert not agent.adopt({"id": "rid-1",
                                    "epoch": agent.epoch})["found"]
            adopter = _mk(tiny)
            adopter.submit(Request(list(prompt), budget, id="p1",
                                   migrate=snap))
            res = list(adopter.run())[0]
            assert list(res.tokens) == expect
        finally:
            agent.stop()

    def test_adopt_freezes_still_live_slot_on_the_spot(self, tiny):
        """A recovering gateway must not wait out the watchdog grace:
        /v1/adopt on a rid still in a live decode slot freezes it
        right there and hands back the snapshot."""
        from tony_tpu.serve.agent import ReplicaAgent

        prompt, budget = _prompt(seed=7), 40
        expect = _control(tiny, prompt, budget)
        agent = ReplicaAgent(_mk(tiny, fault_plan=_slow())).start()
        try:
            agent.submit({"prompt": prompt, "max_new_tokens": budget,
                          "id": "p2", "rid": "rid-2", "epoch": 0})
            _wait(lambda: agent.server.n_active > 0, msg="slot active")
            assert agent.healthz()["n_parked"] == 0  # no watchdog ran
            resp = agent.adopt({"id": "rid-2", "epoch": 1})
            assert resp["found"] and resp.get("snapshot") is not None
            adopter = _mk(tiny)
            adopter.submit(Request(list(prompt), budget, id="p2",
                                   migrate=resp["snapshot"]))
            assert list(list(adopter.run())[0].tokens) == expect
        finally:
            agent.stop()

    def test_finished_undelivered_result_adoptable_once(self, tiny):
        """A request that finishes with nobody listening parks as its
        result; adoption returns the full buffered stream exactly
        once."""
        from tony_tpu.serve.agent import ReplicaAgent

        prompt, budget = _prompt(seed=9), 8
        expect = _control(tiny, prompt, budget)
        agent = ReplicaAgent(_mk(tiny)).start()
        try:
            agent.submit({"prompt": prompt, "max_new_tokens": budget,
                          "id": "p3", "rid": "rid-3", "epoch": 0})
            _wait(lambda: any(r["finished"] and r["rid"] == "rid-3"
                              for r in agent.parked()["parked"]),
                  msg="finished result parked")
            resp = agent.adopt({"id": "rid-3", "epoch": 1})
            assert resp["found"] and resp["finished"]
            assert list(resp["result"]["tokens"]) == expect
            assert not agent.adopt({"id": "rid-3",
                                    "epoch": agent.epoch})["found"]
        finally:
            agent.stop()

    def test_stale_incarnation_id_collision_readmits(self, tiny):
        """A restarted gateway's engine-id counter starts over, so its
        id 1 can collide with the DEAD incarnation's finished ticket
        (retained for the reconnect grace). The submit idempotence
        guard is epoch-scoped: the colliding newer-epoch submit must
        evict the stale record and run the new request — not echo
        `duplicate` and stream the old gateway's result."""
        from tony_tpu.serve.agent import ReplicaAgent

        prompt, budget = _prompt(seed=3), 40
        expect = _control(tiny, prompt, budget)
        agent = ReplicaAgent(_mk(tiny)).start()
        try:
            # incarnation 1 (epoch 0): id 1 runs to completion and its
            # finished ticket lingers within park_ttl_s
            agent.submit({"prompt": [7, 7], "max_new_tokens": 2,
                          "id": 1, "rid": "old-warm", "epoch": 0})
            _wait(lambda: agent._tickets[1].result is not None,
                  msg="incarnation-1 result buffered")
            # a same-epoch retry IS a duplicate (stub retry semantics)
            assert agent.submit({"prompt": [7, 7], "max_new_tokens": 2,
                                 "id": 1, "rid": "old-warm",
                                 "epoch": 0})["duplicate"]
            # incarnation 2 (epoch 1): same id, different request
            resp = agent.submit({"prompt": prompt,
                                 "max_new_tokens": budget,
                                 "id": 1, "rid": "new-r1", "epoch": 1})
            assert "duplicate" not in resp
            _wait(lambda: agent._tickets[1].result is not None,
                  msg="incarnation-2 result")
            got = agent._tickets[1]
            assert got.rid == "new-r1"
            assert list(got.result["tokens"]) == expect
        finally:
            agent.stop()

    def test_channel_never_serves_stale_epoch_ticket(self, tiny):
        """A reconnecting channel's resume map names engine ids the
        NEW gateway incarnation assigned, but the agent may still hold
        a DEAD incarnation's finished ticket under a colliding id
        until the in-flight submit evicts it. The channel must skip
        the stale record while it waits — streaming its tokens or
        done-result would land ANOTHER request's output on the fresh
        stream (the recovery-smoke truncation bug: a resumed stream
        went terminal with the dead gateway's warmup metrics)."""
        from tony_tpu.serve.agent import ReplicaAgent

        prompt, budget = _prompt(seed=9), 24
        expect = _control(tiny, prompt, budget)
        agent = ReplicaAgent(_mk(tiny), keepalive_s=0.05).start()
        try:
            # incarnation 1 (epoch 0): id 1 finished, undelivered
            agent.submit({"prompt": [7, 7], "max_new_tokens": 2,
                          "id": 1, "rid": "old-warm", "epoch": 0})
            _wait(lambda: agent._tickets[1].result is not None,
                  msg="stale finished ticket")
            # the restarted gateway fences to epoch 1, and its channel
            # reconnect names id 1 BEFORE the evicting submit lands
            agent.check_epoch(1)
            gen = agent.channel_events({1: 0}, epoch=1)
            assert next(gen)["channel"]
            early = [next(gen) for _ in range(3)]
            assert all(f.get("keepalive") for f in early), early
            # the evicting submit lands: the SAME channel now streams
            # the fresh request from offset 0 — never the warm result
            agent.submit({"prompt": prompt, "max_new_tokens": budget,
                          "id": 1, "rid": "new-r1", "epoch": 1})
            toks, done = [], None
            deadline = time.monotonic() + 30
            while done is None and time.monotonic() < deadline:
                f = next(gen)
                if f.get("keepalive"):
                    continue
                if f.get("done"):
                    done = f
                    break
                assert f.get("rid") == 1 and "token_ids" in f, f
                assert f["off"] == len(toks)
                toks.extend(f["token_ids"])
            assert done is not None and done["rid"] == 1
            assert toks == expect
            assert list(done["result"]["tokens"]) == expect
        finally:
            agent.stop()

    def test_park_ttl_reaps(self, tiny):
        """Nobody came back: a parked snapshot past the TTL is reaped
        (the pages were gathered to host memory at freeze time, so the
        reap is a dict delete) and a late adopter gets found=false —
        the 404 that tells a recovering gateway to re-run from the
        prompt."""
        from tony_tpu.serve.agent import ReplicaAgent

        agent = ReplicaAgent(_mk(tiny, fault_plan=_slow()),
                             gateway_grace_s=0.2,
                             park_ttl_s=0.5).start()
        try:
            agent.submit({"prompt": _prompt(), "max_new_tokens": 40,
                          "id": "p4", "rid": "rid-4", "epoch": 0})
            # NB: poll parked(), not healthz() — healthz IS gateway
            # contact and would keep resetting the silence clock
            _wait(lambda: len(agent._parked) >= 1, msg="parking")
            _wait(lambda: len(agent._parked) == 0, msg="TTL reap")
            assert not agent.adopt({"id": "rid-4",
                                    "epoch": 1})["found"]
        finally:
            agent.stop()


# ------------------------------------- failover park-adoption (R4)


def test_failover_adopts_parked_session_token_exact(tiny):
    """The ROADMAP-4 residue: a lease that expires because the
    GATEWAY-SIDE heartbeat flapped (not because the agent died) leaves
    the agent holding a perfectly good live session. The failover must
    check the park lease FIRST and adopt it — pins: ONE attempt
    charged, the stream byte-identical to a no-failure control, zero
    5xx, and the adoption visible in routing stats (the zero-re-prefill
    witness: the session crossed as a snapshot, not a prompt)."""
    prompt, budget = _prompt(seed=11), 40
    expect = _control(tiny, prompt, budget)
    agents = [_start_agent(tiny), _start_agent(tiny)]
    stubs = [_stub(a.address) for a in agents]
    gw = Gateway(stubs, stall_timeout_s=10.0, breaker_base_s=0.05,
                 breaker_max_s=0.25).start()
    try:
        ticket = gw.submit(GenRequest(list(prompt),
                                      max_new_tokens=budget, id="fo"))
        _wait(lambda: ticket._n_emitted >= 3, msg="mid-stream")
        src = ticket.replica
        assert src is not None
        # sever ONLY the lease ping: heartbeats still reach the agent
        # (its watchdog never fires) but the monitor starves and
        # declares the replica dead — the transport-flap shape
        stubs[src]._monitor.register = lambda *a, **kw: None
        res = ticket.result(timeout=120)
        assert list(res.tokens) == expect
        assert ticket.metrics["attempts"] == 1  # exactly one charged
        snap = gw.snapshot()
        assert snap["shed"] == {}  # zero 5xx
        assert snap["routing"]["park_adoptions"] >= 1
        assert snap["routing"]["migrations"] >= 1
    finally:
        gw.drain(timeout=60)
        for a in agents:
            a.stop()


# -------------------------------------------- client resume (edges)


@pytest.fixture(params=["event", "threaded"])
def resume_edge(tiny, request):
    from tony_tpu.gateway import GatewayEdge, GatewayHTTP

    gw = Gateway([_mk(tiny, fault_plan=_slow())], max_queue=8).start()
    edge = (GatewayEdge(gw) if request.param == "event"
            else GatewayHTTP(gw)).start()
    yield gw, f"http://{edge.host}:{edge.port}"
    gw.drain(timeout=60)
    edge.stop()


def _resume_lines(url, rid, offset=0, timeout=120):
    resp = urllib.request.urlopen(
        f"{url}/v1/stream/{rid}?offset={offset}", timeout=timeout)
    assert resp.status == 200
    assert resp.headers.get("Content-Type") == "application/x-ndjson"
    return [json.loads(ln) for ln in resp.read().decode().splitlines()
            if not json.loads(ln).get("keepalive")]


def test_resume_stream_absolute_offsets_both_edges(tiny, resume_edge):
    """GET /v1/stream/<id>?offset=N on both edges: a watcher joining
    mid-flight gets the absolute suffix from ITS OWN cursor plus the
    terminal line; N watchers of one request see the same bytes; the
    original consumer's event queue is never consumed. Unknown rids
    404, junk offsets 400."""
    gw, url = resume_edge
    prompt, budget = _prompt(seed=13), 24
    expect = _control(tiny, prompt, budget)
    ticket = gw.submit(GenRequest(list(prompt), max_new_tokens=budget,
                                  id="rs"))
    _wait(lambda: ticket._n_emitted >= 3, msg="mid-stream")
    got = {}

    def watch(offset):
        got[offset] = _resume_lines(url, "rs", offset)

    threads = [threading.Thread(target=watch, args=(off,))
               for off in (0, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for off in (0, 2):
        lines = got[off]
        assert lines[-1]["done"] and "metrics" in lines[-1]
        toks = [t for ln in lines[:-1] for t in ln["token_ids"]]
        assert toks == expect[off:]
        assert lines[0]["offset"] == off
    # the original consumer still gets its full stream: resume taps
    # the buffer, never the single-consumer queue
    assert list(ticket.result(timeout=120).tokens) == expect
    # a client who comes back AFTER the finish gets suffix + terminal
    late = _resume_lines(url, "rs", 5)
    assert [t for ln in late[:-1] for t in ln["token_ids"]] == expect[5:]
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(url + "/v1/stream/nope", timeout=30)
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(url + "/v1/stream/rs?offset=junk",
                               timeout=30)
    assert e.value.code == 400


# ------------------------------------------- journal-driven restart


def test_crash_recover_rerun_local_token_exact(tiny, tmp_path):
    """Local replicas died with the process — --recover re-runs every
    live journaled request from its prompt under the ORIGINAL id,
    charged exactly one attempt, byte-identical to a no-crash
    control."""
    prompts = [_prompt(seed=s) for s in (21, 22)]
    budget = 40
    expect = [_control(tiny, p, budget) for p in prompts]
    j1 = jr.TicketJournal(str(tmp_path / "j1.ndjson"))
    gw1 = Gateway([_mk(tiny, fault_plan=_slow())], journal=j1).start()
    tickets = [gw1.submit(GenRequest(list(p), max_new_tokens=budget,
                                     id=f"rr{i}"))
               for i, p in enumerate(prompts)]
    _wait(lambda: all(t._n_emitted >= 3 for t in tickets),
          msg="both mid-stream")
    gw1.kill()  # SIGKILL-shaped: no drain, no compaction
    entries = jr.replay(j1.path)
    assert sorted(rid for rid, e in entries.items() if e.live) \
        == ["rr0", "rr1"]
    j2 = jr.TicketJournal(str(tmp_path / "j2.ndjson"))
    gw2 = Gateway([_mk(tiny, fault_plan=_slow())], journal=j2).start()
    try:
        report = gw2.recover_from_journal(entries)
        assert report["rerun"] == 2 and report["adopted"] == 0
        assert report["shed"] == 0
        for i, exp in enumerate(expect):
            t = gw2.resume_ticket(f"rr{i}")
            assert t is not None
            res = t.result(timeout=120)
            assert list(res.tokens) == exp
            assert t.metrics["attempts"] == 1
        snap = gw2.snapshot()
        assert snap["shed"] == {}
        assert snap["recovery"]["recoveries"] == 1
        assert snap["recovery"]["sessions_rerun"] == 2
    finally:
        gw2.drain(timeout=60)
    # clean drain compacted THIS boot's journal down to nothing
    assert jr.replay(j2.path) == {}


def test_crash_recover_adopts_parked_and_finished(tiny, tmp_path):
    """THE in-process recovery anchor: gateway crashes mid-stream over
    two live agents; one request finishes into the void (parks as its
    result), one gets frozen by the agent watchdog (parks as a
    snapshot). The restarted gateway replays the WAL and adopts BOTH —
    the in-flight session resumes token-exact with zero re-prefill and
    no attempt charged, the finished one materializes terminal with
    its exact bytes, and a resuming client pulls byte-identical
    streams through the registry. Zero 5xx anywhere."""
    short_p, long_p = _prompt(seed=31), _prompt(seed=32)
    expect_short = _control(tiny, short_p, 8)
    expect_long = _control(tiny, long_p, 40)
    # grace wide enough for the short request's tail (~0.15s of wedged
    # decode) to FINISH into the void, narrow enough that the long one
    # (~1.1s left) parks as a snapshot — deterministic either side
    agents = [_start_agent(tiny, gateway_grace_s=0.5, park_ttl_s=60)
              for _ in range(2)]
    j1 = jr.TicketJournal(str(tmp_path / "j1.ndjson"))
    gw1 = Gateway([_stub(a.address) for a in agents],
                  journal=j1, park_ttl_s=60).start()
    ts = gw1.submit(GenRequest(list(short_p), max_new_tokens=8,
                               id="fin"))
    tl = gw1.submit(GenRequest(list(long_p), max_new_tokens=40,
                               id="mid"))
    _wait(lambda: ts._n_emitted >= 3 and tl._n_emitted >= 3,
          msg="both mid-stream")
    gw1.kill()
    entries = jr.replay(j1.path)
    assert entries["fin"].live and entries["mid"].live
    assert entries["mid"].offset >= 3  # emit rows made it to the WAL

    def rows():
        return [r for a in agents for r in a.agent.parked()["parked"]]

    # the short one FINISHES into the void; the long one is frozen by
    # the agent watchdog once the gateway goes silent past the grace
    _wait(lambda: any(r["finished"] and r["rid"] == "fin"
                      for r in rows())
          and any(not r["finished"] and r["rid"] == "mid"
                  for r in rows()),
          msg="agents parking the orphans")
    j2 = jr.TicketJournal(str(tmp_path / "j2.ndjson"))
    gw2 = Gateway([_stub(a.address) for a in agents],
                  journal=j2, park_ttl_s=60).start()
    try:
        report = gw2.recover_from_journal(entries)
        assert report["adopted"] == 1, report
        assert report["finished"] == 1, report
        assert report["rerun"] == 0 and report["shed"] == 0
        # the finished request: immediately terminal, exact bytes,
        # metrics flagged recovered with no attempt charged
        tf = gw2.resume_ticket("fin")
        assert list(tf.result(timeout=30).tokens) == expect_short
        assert tf.metrics["recovered"] and tf.metrics["attempts"] == 0
        # the adopted session: resumes mid-stream token-exact — and a
        # client resuming at its own (journal-lagged) offset gets the
        # exact suffix through resume_events
        tm = gw2.resume_ticket("mid")
        assert list(tm.result(timeout=120).tokens) == expect_long
        assert tm.attempts == 0  # adopted, never re-run
        toks = []
        for doc in gw2.resume_events("mid", offset=2):
            if doc.get("done"):
                break
            toks.extend(doc.get("token_ids", []))
        assert toks == expect_long[2:]
        snap = gw2.snapshot()
        assert snap["shed"] == {}  # zero 5xx
        assert snap["recovery"]["recoveries"] == 1
        assert snap["recovery"]["sessions_adopted"] == 1
        assert snap["recovery"]["recovered_finished"] == 1
        # zero re-prefill: the adopting ENGINE admitted the session as
        # a migrate-in (page install + sampler restore), not a prompt
        assert sum(a.agent.server.migrations_in for a in agents) >= 1
        # the recovery alert fired and carries the signal
        sig = gw2.alert_signals()
        assert sig["recovered_ago_s"] is not None
    finally:
        gw2.drain(timeout=60)
        for a in agents:
            a.stop()


def test_recover_unknown_host_reruns_and_shed_is_terminal(tiny,
                                                          tmp_path):
    """A journal whose host is gone (agent reaped the park, or never
    came back) re-runs from the prompt — the adopt 404 funnels into
    the rerun path, never an error. And a journaled terminal shed
    stays dead: replay must not resurrect it."""
    prompt, budget = _prompt(seed=41), 24
    expect = _control(tiny, prompt, budget)
    j1 = jr.TicketJournal(str(tmp_path / "j1.ndjson"))
    j1.admit("ghost", {"prompt": prompt, "max_new_tokens": budget,
                       "temperature": 0.0, "top_k": 0, "seed": 0},
             time.time())
    j1.route("ghost", 0, "127.0.0.1:1")  # a host nobody answers at
    j1.admit("dead", {"prompt": prompt, "max_new_tokens": 4},
             time.time())
    j1.shed("dead", 503)
    j1.close()
    entries = jr.replay(j1.path)
    gw2 = Gateway([_mk(tiny)]).start()
    try:
        report = gw2.recover_from_journal(entries)
        assert report["live"] == 1  # the shed entry never replays
        assert report["rerun"] == 1
        t = gw2.resume_ticket("ghost")
        assert list(t.result(timeout=120).tokens) == expect
        assert gw2.resume_ticket("dead") is None
    finally:
        gw2.drain(timeout=60)
