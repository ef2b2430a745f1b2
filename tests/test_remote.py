"""Remote replicas: agent protocol, the RemoteServer stub, transport
fault injection, and the remote chaos anchor.

The acceptance pins for ISSUE 11: 2 localhost agents under concurrent
load with one killed (network-SIGKILL) mid-stream and the other's
transport disconnected mid-stream -> zero 5xx, every client stream
byte-identical to a fault-free control, the survivor keeps serving, a
restarted agent rejoins through the probe path, and stale-epoch
responses from a revived/superseded host are discarded. Plus: a full
black-hole partition funnels through lease expiry into token-exact
failover, and a dead remote replica's slice is deprovisioned with
nothing leaked.

Agents here are in-process ``AgentHTTP`` servers speaking REAL HTTP
over localhost — ``kill()`` drops them off the network exactly like a
SIGKILLed process (open streams die mid-line, new connections are
refused) while the test stays fast. The subprocess flavor of the same
story runs in ``tools/serve_smoke.sh`` (``make remote-smoke``).
"""

import time

import pytest

from tony_tpu.serve.engine import Request, Server
from tony_tpu.serve.faults import Fault, FaultPlan

pytestmark = pytest.mark.filterwarnings("ignore::ResourceWarning")


@pytest.fixture(scope="module")
def demo():
    from tony_tpu.cli.gateway import demo_model

    model, params = demo_model()
    return model, params


def make_server(demo, **kw):
    model, params = demo
    kw.setdefault("batch_size", 2)
    kw.setdefault("eos_id", -1)
    return Server(model, params, **kw)


def start_agent(demo, port=0, **server_kw):
    from tony_tpu.serve.agent import AgentHTTP, ReplicaAgent

    return AgentHTTP(ReplicaAgent(make_server(demo, **server_kw)),
                     port=port).start()


def make_stub(address, **kw):
    from tony_tpu.gateway.remote import RemoteServer

    # a 1 s lease (10 misses of 0.1 s; tests of the expiry set their
    # own). A heartbeat here is /healthz then /v1/obs, each answered
    # in two writes under Nagle's algorithm: 40 ms a call, 85-120 ms a
    # beat under a streaming engine, beats 176 ms apart at the median
    # (PERF.md section 7 row 13). A 0.3 s lease was one late beat from
    # expiring, and under six workers the engine's two rounds in
    # flight supplied it (a tiny model's scheduler thread no longer
    # blocks in its sync): a healthy agent expired in every whole run
    # of PR 34's first draft. Curing the 40 ms in the agent uncovers a
    # race in the mux channel (same row), so the lease has the room
    kw.setdefault("heartbeat_interval_s", 0.1)
    kw.setdefault("lease_misses", 10)
    kw.setdefault("read_timeout_s", 2.0)
    kw.setdefault("boot_timeout_s", 20.0)
    return RemoteServer(address, **kw)


def make_gateway(stubs, **kw):
    from tony_tpu.gateway.core import Gateway

    kw.setdefault("stall_timeout_s", 10.0)
    kw.setdefault("breaker_base_s", 0.05)
    kw.setdefault("breaker_max_s", 0.25)
    kw.setdefault("quarantine_after", 100)
    return Gateway(stubs, **kw).start()


def control_outputs(demo, requests):
    """The fault-free control: the same requests on a fresh local
    engine (deterministic decode -> the remote fleet must match it
    token for token, faults or not)."""
    server = make_server(demo)
    for r in requests:
        server.submit(Request(list(r.prompt), r.max_new_tokens,
                              temperature=r.temperature, top_k=r.top_k,
                              seed=r.seed, id=r.id))
    return {res.id: list(res.tokens) for res in server.run()}


def wait_for(cond, timeout=20.0, interval=0.01, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


# --------------------------------------------------------------------
# transport fault plan units (no jax, no sockets)
# --------------------------------------------------------------------

class TestTransportFaults:
    def test_engine_op_rejects_call_trigger(self):
        with pytest.raises(ValueError, match="'call' trigger"):
            Fault("fail", call=1)

    def test_transport_op_rejects_dispatch_trigger(self):
        with pytest.raises(ValueError, match="'dispatch' trigger"):
            Fault("refuse", dispatch=1)

    def test_delay_needs_seconds(self):
        with pytest.raises(ValueError, match="seconds > 0"):
            Fault("delay", call=1)

    def test_refuse_fires_on_call_count_and_spends(self):
        plan = FaultPlan([Fault("refuse", call=2)])
        plan.on_call("a")  # call 1: below trigger
        with pytest.raises(ConnectionRefusedError):
            plan.on_call("b")
        plan.on_call("c")  # spent (times=1)
        assert plan.fired == 1

    def test_blackhole_times_forever(self):
        plan = FaultPlan([Fault("blackhole", call=1, times=-1)])
        for _ in range(3):
            with pytest.raises(TimeoutError):
                plan.on_call("x")
        assert plan.fired == 3

    def test_disconnect_fires_on_stream_not_call(self):
        plan = FaultPlan([Fault("disconnect", call=1, times=-1)])
        plan.on_call("connect")  # call ops don't include disconnect
        with pytest.raises(ConnectionResetError):
            plan.on_stream("read")

    def test_half_open_fires_on_stream(self):
        plan = FaultPlan([Fault("half_open", call=1)])
        plan.on_call("connect")
        with pytest.raises(TimeoutError):
            plan.on_stream("read")

    def test_request_triggered_transport_fault(self):
        plan = FaultPlan([Fault("refuse", request=7)])
        plan.on_call("a", request=3)
        with pytest.raises(ConnectionRefusedError):
            plan.on_call("b", request=7)

    def test_delay_proceeds(self):
        plan = FaultPlan([Fault("delay", call=1, seconds=0.01)])
        t0 = time.monotonic()
        plan.on_call("a")  # no raise
        assert time.monotonic() - t0 >= 0.01

    def test_env_partition_engine_vs_transport(self):
        env = {"TONY_SERVE_FAULTS":
               '[{"op": "fail", "dispatch": 3, "replica": 0},'
               ' {"op": "blackhole", "call": 1, "replica": 1,'
               '  "times": -1}]'}
        eng0 = FaultPlan.from_env(replica=0, env=env)
        assert [f.op for f in eng0.faults] == ["fail"]
        assert FaultPlan.from_env(replica=1, env=env) is None
        tr1 = FaultPlan.transport_from_env(replica=1, env=env)
        assert [f.op for f in tr1.faults] == ["blackhole"]
        assert FaultPlan.transport_from_env(replica=0, env=env) is None

    def test_env_invalid_transport_spec_raises(self):
        env = {"TONY_SERVE_FAULTS": '[{"op": "refuse", "dispatch": 1}]'}
        with pytest.raises(ValueError):
            FaultPlan.transport_from_env(replica=0, env=env)


# --------------------------------------------------------------------
# agent protocol (direct HTTP, no gateway)
# --------------------------------------------------------------------

class TestAgentProtocol:
    @pytest.fixture()
    def agent(self, demo):
        http = start_agent(demo)
        yield http
        http.stop()

    def transport(self, agent, **kw):
        from tony_tpu.gateway.remote import AgentTransport

        kw.setdefault("read_timeout_s", 5.0)
        return AgentTransport(agent.address, **kw)

    def test_healthz_shape(self, agent):
        t = self.transport(agent)
        doc = t.call("GET", "/healthz")
        assert doc["ok"] is True
        assert doc["epoch"] == 0
        assert doc["batch_size"] == 2
        assert doc["max_seq_len"] == 64
        assert "decode_steps" in doc["counters"]
        assert doc["stepper_age_s"] < 5.0

    def test_submit_stream_roundtrip_token_exact(self, agent, demo):
        t = self.transport(agent)
        resp = t.call("POST", "/v1/submit", {
            "id": 0, "prompt": [1, 2, 3], "max_new_tokens": 12,
            "epoch": 0})
        assert resp["ok"] and resp["id"] == 0
        tokens, result = [], None
        for doc in t.stream_lines("/v1/stream/0?offset=0&epoch=0"):
            if doc.get("keepalive"):
                continue
            if "token_ids" in doc:
                assert doc["offset"] == len(tokens)  # absolute offsets
                tokens.extend(doc["token_ids"])
            if doc.get("done"):
                result = doc["result"]
                break
        assert result is not None
        assert tokens == result["tokens"]
        ctrl = control_outputs(
            demo, [Request([1, 2, 3], 12, id=0)])
        assert tokens == ctrl[0]

    def test_stream_resume_by_offset(self, agent, demo):
        t = self.transport(agent)
        t.call("POST", "/v1/submit", {"id": 5, "prompt": [4, 5],
                                      "max_new_tokens": 16, "epoch": 0})
        # read a couple of windows, then "drop the connection"
        got = []
        stream = t.stream_lines("/v1/stream/5?offset=0&epoch=0")
        for doc in stream:
            if "token_ids" in doc:
                got.extend(doc["token_ids"])
                if len(got) >= 2:
                    break
        stream.close()
        # reconnect AT THE OFFSET HELD: the tail picks up exactly there
        for doc in t.stream_lines(
                f"/v1/stream/5?offset={len(got)}&epoch=0"):
            if "token_ids" in doc:
                assert doc["offset"] == len(got)
                got.extend(doc["token_ids"])
            if doc.get("done"):
                assert got == doc["result"]["tokens"]  # gap/dup-free
                break
        ctrl = control_outputs(demo, [Request([4, 5], 16, id=5)])
        assert got == ctrl[5]

    def test_long_chunked_sampled_stream_token_exact(self, demo):
        """Regression pin: the stepper must APPEND live_progress tails
        (they are deltas past the held count) — the old replace-if-
        longer merge delivered wrong tokens at wrong offsets for any
        generation spanning >2 chunks, masked by the constant-token
        greedy demo output. Sampled + chunk_steps=4 + 40 tokens makes
        the corruption visible, and the mid-stream lines (not just the
        terminal doc) must match the control."""
        agent = start_agent(demo, chunk_steps=4)
        try:
            from tony_tpu.gateway.remote import AgentTransport

            t = AgentTransport(agent.address)
            t.call("POST", "/v1/submit", {
                "id": 11, "prompt": [3, 1, 4], "max_new_tokens": 40,
                "temperature": 1.0, "top_k": 8, "seed": 123,
                "epoch": 0})
            streamed, result = [], None
            lines_before_done = 0
            for doc in t.stream_lines("/v1/stream/11?offset=0&epoch=0"):
                if "token_ids" in doc:
                    assert doc["offset"] == len(streamed)
                    streamed.extend(doc["token_ids"])
                    if result is None:
                        lines_before_done += 1
                if doc.get("done"):
                    result = doc["result"]
                    break
            ctrl = control_outputs(demo, [Request(
                [3, 1, 4], 40, temperature=1.0, top_k=8, seed=123,
                id=11)])
            assert streamed == result["tokens"] == ctrl[11]
            assert lines_before_done >= 2  # it actually STREAMED
        finally:
            agent.stop()

    def test_submit_idempotent_on_request_id(self, agent):
        # the stub's connect-retry may re-send a submit the agent
        # already processed: the second must be a no-op ack, not a
        # duplicate engine request burning a second slot
        t = self.transport(agent)
        doc = {"id": 8, "prompt": [2, 2], "max_new_tokens": 30,
               "epoch": 0}
        t.call("POST", "/v1/submit", doc)
        resp = t.call("POST", "/v1/submit", doc)
        assert resp["ok"] and resp.get("duplicate") is True
        srv = agent.agent.server
        assert srv.n_pending + srv.n_active <= 1
        assert len(agent.agent._tickets) == 1

    def test_finished_result_still_fetchable(self, agent):
        t = self.transport(agent)
        t.call("POST", "/v1/submit", {"id": 9, "prompt": [7],
                                      "max_new_tokens": 4, "epoch": 0})
        wait_for(lambda: agent.agent._tickets[9].result is not None,
                 msg="result")
        # a client reconnecting AFTER the finish still gets the
        # terminal line (the reconnect-grace window)
        docs = list(t.stream_lines("/v1/stream/9?offset=0&epoch=0"))
        assert any(d.get("done") for d in docs)

    def test_stale_epoch_refused_and_adopted(self, agent):
        from tony_tpu.gateway.remote import AgentHTTPError

        t = self.transport(agent)
        t.call("POST", "/v1/reset", {"epoch": 3})
        assert t.call("GET", "/healthz")["epoch"] == 3
        # older epoch -> 409, body names the agent's epoch
        with pytest.raises(AgentHTTPError) as ei:
            t.call("POST", "/v1/submit", {"id": 1, "prompt": [1],
                                          "max_new_tokens": 2,
                                          "epoch": 2})
        assert ei.value.status == 409
        assert ei.value.doc["epoch"] == 3
        # stream with an older epoch: 409 too
        with pytest.raises(AgentHTTPError) as ei:
            list(t.stream_lines("/v1/stream/1?offset=0&epoch=1"))
        assert ei.value.status == 409

    def test_reset_drops_tickets_and_engine_state(self, agent):
        t = self.transport(agent)
        t.call("POST", "/v1/submit", {"id": 2, "prompt": [1, 1],
                                      "max_new_tokens": 30, "epoch": 0})
        t.call("POST", "/v1/reset", {"epoch": 1})
        wait_for(lambda: agent.agent.server.done, msg="engine reset")
        assert agent.agent._tickets == {}
        from tony_tpu.gateway.remote import AgentHTTPError

        with pytest.raises(AgentHTTPError) as ei:
            list(t.stream_lines("/v1/stream/2?offset=0&epoch=1"))
        assert ei.value.status == 404  # ticket gone

    def test_submit_validation_maps_to_400(self, agent):
        from tony_tpu.gateway.remote import AgentHTTPError

        t = self.transport(agent)
        with pytest.raises(AgentHTTPError) as ei:
            t.call("POST", "/v1/submit", {"id": 3, "prompt": [],
                                          "max_new_tokens": 2,
                                          "epoch": 0})
        assert ei.value.status == 400
        assert ei.value.doc["kind"] == "ValueError"

    def test_obs_channel_cursor_semantics(self, agent, demo):
        """GET /v1/obs (ISSUE-15): records are cursor-incremental,
        the summary is lifetime, the goodput ledger rides along, and
        timestamps are the AGENT's monotonic clock (t_mono brackets
        them)."""
        t = self.transport(agent)
        t.call("POST", "/v1/submit", {"id": 20, "prompt": [1, 2],
                                      "max_new_tokens": 6, "epoch": 0})
        wait_for(lambda: agent.agent._tickets[20].result is not None,
                 msg="result")
        doc = t.call("GET", "/v1/obs?cursor=0")
        assert doc["cursor"] > 0
        kinds = {r["kind"] for r in doc["records"]}
        assert "prefill" in kinds and "decode" in kinds
        prefills = [r for r in doc["records"] if r["kind"] == "prefill"]
        assert prefills[0]["request_id"] == 20
        decodes = [r for r in doc["records"] if r["kind"] == "decode"]
        assert all(20 in r["tags"]["requests"] for r in decodes)
        # timestamps live in the agent's monotonic clock
        assert all(0 < r["t0"] <= doc["t_mono"] for r in doc["records"])
        assert doc["summary"]["prefill"]["count"] >= 1
        assert doc["goodput"] is not None
        assert sum(doc["goodput"]["buckets"].values()) <= 1.0 + 1e-6
        # incremental: re-reading at the cursor returns nothing new,
        # but the lifetime summary stays
        doc2 = t.call("GET", f"/v1/obs?cursor={doc['cursor']}")
        assert doc2["records"] == []
        assert doc2["cursor"] == doc["cursor"]
        assert doc2["summary"]["prefill"]["count"] \
            == doc["summary"]["prefill"]["count"]

    def test_drain_finishes_then_refuses(self, agent):
        from tony_tpu.gateway.remote import AgentHTTPError

        t = self.transport(agent)
        t.call("POST", "/v1/submit", {"id": 4, "prompt": [2],
                                      "max_new_tokens": 6, "epoch": 0})
        doc = t.call("POST", "/v1/drain", {"timeout_s": 60},
                     timeout=90.0)
        assert doc["drained"] is True
        with pytest.raises(AgentHTTPError) as ei:
            t.call("POST", "/v1/submit", {"id": 6, "prompt": [2],
                                          "max_new_tokens": 2,
                                          "epoch": 0})
        assert ei.value.status == 503
        assert agent.agent.drained.is_set()  # the CLI exit signal


# --------------------------------------------------------------------
# transport backoff + fault hooks at the stub
# --------------------------------------------------------------------

class TestAgentTransport:
    def test_backoff_capped_and_jittered(self):
        from tony_tpu.gateway.remote import AgentTransport

        t = AgentTransport("127.0.0.1:1", backoff_base_s=0.1,
                           backoff_max_s=0.4)
        for attempt, cap in ((0, 0.1), (1, 0.2), (2, 0.4), (9, 0.4)):
            vals = {t._backoff(attempt) for _ in range(16)}
            assert all(cap * 0.5 <= v <= cap for v in vals)
        # jitter actually varies
        assert len({t._backoff(3) for _ in range(16)}) > 1

    def test_connect_retries_heal_transient_refusal(self, demo):
        # a times=2 refusal is a transient blip: the in-lease retry
        # path absorbs it and the call still succeeds — and the retry
        # count surfaces for the transport stats block
        from tony_tpu.gateway.remote import AgentTransport

        agent = start_agent(demo)
        try:
            plan = FaultPlan([Fault("refuse", call=1, times=2)])
            t = AgentTransport(agent.address, fault_plan=plan,
                               backoff_base_s=0.01, backoff_max_s=0.02)
            doc = t.call("GET", "/healthz")
            assert doc["ok"] is True
            assert t.retries == 2
            assert t.connect_errors == 2
        finally:
            agent.stop()

    def test_refusal_beyond_budget_raises(self, demo):
        from tony_tpu.gateway.remote import AgentTransport

        agent = start_agent(demo)
        try:
            plan = FaultPlan([Fault("refuse", call=1, times=-1)])
            t = AgentTransport(agent.address, fault_plan=plan,
                               connect_retries=2, backoff_base_s=0.01,
                               backoff_max_s=0.02)
            with pytest.raises(ConnectionRefusedError):
                t.call("GET", "/healthz")
            assert t.retries == 2
        finally:
            agent.stop()

    def test_blackhole_not_retried(self, demo):
        from tony_tpu.gateway.remote import AgentTransport

        agent = start_agent(demo)
        try:
            plan = FaultPlan([Fault("blackhole", call=1)])
            t = AgentTransport(agent.address, fault_plan=plan,
                               backoff_base_s=0.01)
            with pytest.raises(TimeoutError):
                t.call("GET", "/healthz")
            assert t.retries == 0  # the caller already paid the wait
        finally:
            agent.stop()


# --------------------------------------------------------------------
# the multiplexed agent channel (ISSUE-16)
# --------------------------------------------------------------------

class TestMuxChannel:
    """ONE long-lived /v1/channel connection carries every ticket
    stream as tagged frames. make_stub defaults to mux, so the whole
    remote suite (epoch fence, chaos anchor, disconnect-resume) runs
    over the channel; this class pins the channel-specific claims."""

    def _drain(self, stub, n, timeout=120.0):
        got = {}
        deadline = time.monotonic() + timeout
        while len(got) < n and time.monotonic() < deadline:
            for res in stub.step():
                got[res.id] = list(res.tokens)
        return got

    def test_64_streams_one_connection_token_exact(self, demo,
                                                   monkeypatch):
        from tony_tpu.serve.agent import AgentHandler

        calls = {"stream": 0, "channel": 0}
        orig_get = AgentHandler.do_GET
        orig_post = AgentHandler.do_POST

        def counting_get(self):
            if self.path.startswith("/v1/stream/"):
                calls["stream"] += 1
            return orig_get(self)

        def counting_post(self):
            if self.path.partition("?")[0] == "/v1/channel":
                calls["channel"] += 1
            return orig_post(self)

        monkeypatch.setattr(AgentHandler, "do_GET", counting_get)
        monkeypatch.setattr(AgentHandler, "do_POST", counting_post)
        agent = start_agent(demo, batch_size=8)
        stub = make_stub(agent.address)
        try:
            reqs = [Request([1 + (i % 5), 2, 3], 4, id=f"m{i}")
                    for i in range(64)]
            ctrl = control_outputs(demo, reqs)
            for r in reqs:
                stub.submit(r)
            got = self._drain(stub, len(reqs))
            assert sorted(got) == sorted(ctrl)
            for rid, toks in got.items():
                assert toks == ctrl[rid], rid
            # the whole fan-in rode ONE channel connection: no
            # per-ticket stream was ever opened
            assert calls["channel"] == 1, calls
            assert calls["stream"] == 0, calls
            assert stub.transport_stats()["channel"] == "mux"
            assert stub.reconnects == 0
        finally:
            stub.close()
            agent.stop()

    def test_warm_engine_fast_finish_race(self, demo):
        """Regression pin: a warm engine can finish a request and the
        channel deliver EVERY frame before the submit POST returns.
        The stub pre-registers tickets (and ignores the racing `gone`)
        so nothing is dropped — this exact shape deadlocked before."""
        agent = start_agent(demo, batch_size=8)
        stub = make_stub(agent.address)
        try:
            stub.submit(Request([9, 2, 3], 4, id="warm"))
            assert "warm" in self._drain(stub, 1)
            # now every submit races a hot engine
            reqs = [Request([1 + i, 2, 3], 4, id=f"r{i}")
                    for i in range(8)]
            ctrl = control_outputs(demo, reqs)
            for r in reqs:
                stub.submit(r)
            got = self._drain(stub, len(reqs), timeout=60.0)
            assert sorted(got) == sorted(ctrl), got
            for rid, toks in got.items():
                assert toks == ctrl[rid], rid
        finally:
            stub.close()
            agent.stop()

    def test_garbled_frame_degrades_not_dies(self, demo, monkeypatch):
        """WIRE-LEVEL pin for the ISSUE-16 bugfix: one corrupted
        channel frame must be counted + resynced (reconnect at held
        offsets), never kill the demux loop — streams stay
        token-exact."""
        from tony_tpu.serve.agent import AgentHandler

        orig_chunk = AgentHandler._chunk
        hits = {"n": 0}

        def corrupting(self, doc):
            if "token_ids" in doc and "rid" in doc:
                hits["n"] += 1
                if hits["n"] == 2:  # swallow a REAL token frame and
                    # emit garbage instead: both the parse failure and
                    # the hidden window must heal via resync
                    data = b'{"rid": ### not json\n'
                    self.wfile.write(f"{len(data):X}\r\n".encode()
                                     + data + b"\r\n")
                    self.wfile.flush()
                    return
            return orig_chunk(self, doc)

        monkeypatch.setattr(AgentHandler, "_chunk", corrupting)
        agent = start_agent(demo, batch_size=4)
        stub = make_stub(agent.address)
        try:
            reqs = [Request([2 + i, 3, 4], 8, id=f"g{i}")
                    for i in range(4)]
            ctrl = control_outputs(demo, reqs)
            for r in reqs:
                stub.submit(r)
            got = self._drain(stub, len(reqs))
            for rid, toks in got.items():
                assert toks == ctrl[rid], rid
            assert len(got) == len(reqs)
            assert stub.garbled_frames >= 1
            assert stub.transport_stats()["garbled_frames"] >= 1
        finally:
            stub.close()
            agent.stop()

    def test_mux_disconnect_resume_by_offset(self, demo):
        """The PR-11 resume contract over the channel: injected
        disconnects mid-channel -> reconnect re-establishes every
        in-flight stream at its absolute offset, token-exact."""
        agent = start_agent(demo, batch_size=4)
        stub = make_stub(agent.address)
        try:
            # warm first so faults land mid-decode, not mid-compile
            stub.submit(Request([8, 8], 2, id="w"))
            self._drain(stub, 1)
            stub.transport.fault_plan = FaultPlan(
                [Fault("disconnect", call=1, times=3)])
            reqs = [Request([1 + i, 2, 3], 24, id=f"d{i}")
                    for i in range(4)]
            ctrl = control_outputs(demo, reqs)
            for r in reqs:
                stub.submit(r)
            got = self._drain(stub, len(reqs), timeout=120.0)
            for rid, toks in got.items():
                assert toks == ctrl[rid], rid
            assert len(got) == len(reqs)
            assert stub.reconnects >= 1
        finally:
            stub.close()
            agent.stop()

    def test_per_ticket_ab_control(self, demo):
        """--agent-channel per-ticket: the pre-mux path stays as the
        A/B control and produces identical outputs."""
        agent = start_agent(demo, batch_size=4)
        stub = make_stub(agent.address, agent_channel="per-ticket")
        try:
            reqs = [Request([1 + i, 2, 3], 6, id=f"p{i}")
                    for i in range(4)]
            ctrl = control_outputs(demo, reqs)
            for r in reqs:
                stub.submit(r)
            got = self._drain(stub, len(reqs))
            for rid, toks in got.items():
                assert toks == ctrl[rid], rid
            assert len(got) == len(reqs)
            assert stub.transport_stats()["channel"] == "per-ticket"
        finally:
            stub.close()
            agent.stop()


# --------------------------------------------------------------------
# the stub + gateway over remote replicas
# --------------------------------------------------------------------

class TestRemoteGateway:
    def test_parity_and_host_attribution(self, demo):
        from tony_tpu.gateway.core import GenRequest

        agents = [start_agent(demo) for _ in range(2)]
        stubs = [make_stub(a.address) for a in agents]
        gw = make_gateway(stubs)
        try:
            reqs = [Request([1 + i, 2, 3], 10, id=i) for i in range(4)]
            reqs.append(Request([9, 9], 8, temperature=1.0, top_k=4,
                                seed=7, id="sampled"))
            ctrl = control_outputs(demo, reqs)
            tickets = [gw.submit(GenRequest(
                list(r.prompt), max_new_tokens=r.max_new_tokens,
                temperature=r.temperature, top_k=r.top_k, seed=r.seed,
                id=r.id)) for r in reqs]
            addrs = {a.address for a in agents}
            for r, t in zip(reqs, tickets):
                res = t.result(timeout=120)
                assert list(res.tokens) == ctrl[r.id]
                # host attribution (ISSUE-11 satellite): the record
                # names the machine that served the request
                assert t.metrics["host"] in addrs
            snap = gw.snapshot()
            assert snap["shed"] == {}
            for row in snap["replicas"]:
                tr = row["transport"]
                assert tr["address"] in addrs
                assert tr["rtt_ms"] >= 0.0
                assert tr["lease_expiries"] == 0
            # both replicas actually served (least-outstanding spread)
            assert all(row["completed"] > 0
                       for row in snap["replicas"])
        finally:
            gw.drain(timeout=60)
            for a in agents:
                a.stop()

    def test_local_replica_host_is_local(self, demo):
        from tony_tpu.gateway.core import GenRequest

        gw = make_gateway([make_server(demo)])
        try:
            t = gw.submit(GenRequest([1, 2], max_new_tokens=4))
            t.result(timeout=60)
            assert t.metrics["host"] == "local"
            assert "transport" not in gw.snapshot()["replicas"][0]
        finally:
            gw.drain(timeout=60)

    def test_stub_submit_typed_refusals(self, demo):
        agent = start_agent(demo)
        stub = make_stub(agent.address)
        try:
            with pytest.raises(ValueError):
                stub.submit(Request([], 4, id="bad"))
            from tony_tpu.serve.engine import QueueFull  # noqa: F401
        finally:
            stub.close()
            agent.stop()

    def test_transport_metrics_in_exposition(self, demo):
        from tony_tpu.gateway.core import GenRequest
        from tony_tpu.obs import prometheus_text

        agent = start_agent(demo)
        gw = make_gateway([make_stub(agent.address)])
        try:
            gw.submit(GenRequest([3, 1], max_new_tokens=4)) \
                .result(timeout=60)
            text = prometheus_text(gw)
            assert "tony_transport_rtt_seconds{" in text
            assert "tony_transport_reconnects_total{" in text
            assert f'host="{agent.address}"' in text
        finally:
            gw.drain(timeout=60)
            agent.stop()


# --------------------------------------------------------------------
# the fleet observability plane (ISSUE-15)
# --------------------------------------------------------------------


def wait_obs_settled(stub, expect_tokens, timeout=30.0):
    """Wait until the stub's pulled timeline accounts for
    ``expect_tokens`` landed tokens, then FREEZE the puller so the
    caller can compare surfaces exactly (no pull can land between two
    snapshots)."""
    def settled():
        summ = stub.timeline.summary()
        return summ and sum(a["tokens"] for a in summ.values()) \
            >= expect_tokens
    wait_for(settled, timeout=timeout, msg="obs pull settled")
    stub._obs_pull = False


class TestRemoteObservability:
    def test_dispatch_goodput_and_trace_spans_merged(self, demo):
        """The tentpole pin: a remote replica's dispatch timeline,
        goodput ledger, and per-request dispatch spans land in the
        gateway's surfaces exactly like a local engine's — merged
        engine.dispatch, a non-null per-replica goodput block, an
        explicit obs health block, and trace spans grafted into the
        attempt tree carrying host + clock-offset tags."""
        from tony_tpu.gateway.core import GenRequest
        from tony_tpu.obs.trace import check_invariants

        agent = start_agent(demo)
        stub = make_stub(agent.address)
        gw = make_gateway([stub])
        try:
            n, budget = 3, 10
            tickets = [gw.submit(GenRequest([1 + i, 2, 3],
                                            max_new_tokens=budget,
                                            id=f"ob{i}"))
                       for i in range(n)]
            for t in tickets:
                t.result(timeout=120)
            wait_obs_settled(stub, n * budget)
            snap = gw.snapshot()
            row = snap["replicas"][0]
            # the pulled timeline IS the replica's dispatch block, and
            # it agrees with the agent's own engine exactly
            agent_summ = agent.agent.server.timeline.summary()
            assert row["dispatch"] == agent_summ
            assert row["dispatch"]["prefill"]["count"] == n
            # ...and the fleet merge carries it
            eng = snap["engine"]["dispatch"]
            assert eng["prefill"]["count"] == n
            assert eng["decode"]["tokens"] > 0
            # the pulled ledger is a real merged-able goodput block
            assert row["goodput"] is not None
            assert sum(row["goodput"]["buckets"].values()) <= 1 + 1e-6
            fleet = snap["engine"]["goodput"]
            assert fleet and sum(fleet["buckets"].values()) <= 1 + 1e-6
            # the obs health block: pulls counted, lag fresh, errors 0
            obs = row["obs"]
            assert obs["enabled"] and obs["pulls"] >= 1
            assert obs["pull_errors"] == 0
            assert obs["cursor"] > 0 and obs["lag_s"] is not None
            # remote dispatch spans grafted into the attempt tree,
            # offset-corrected and tagged with the host + the offset
            # and its uncertainty
            tr = gw.traces.get("ob0")
            assert tr is not None and check_invariants(tr) == []
            attempts = [c for c in tr.root.children
                        if c.name.startswith("attempt-")]
            assert attempts[0].tags["host"] == agent.address
            remote_spans = [c for c in attempts[0].children
                            if c.tags.get("host") == agent.address]
            assert remote_spans, [c.name for c in attempts[0].children]
            assert any(s.name in ("prefill", "decode")
                       for s in remote_spans)
            for s in remote_spans:
                assert "clock_offset_ms" in s.tags
                assert "clock_offset_unc_ms" in s.tags
            # the Chrome export names the process after the host, and
            # /debug/traces summaries carry the host column
            doc = tr.to_chrome()
            procs = [e for e in doc["traceEvents"]
                     if e.get("name") == "process_name"]
            assert any(agent.address in e["args"]["name"]
                       for e in procs)
            rows = {r["request_id"]: r
                    for r in gw.traces.summaries()}
            assert rows["ob0"]["host"] == agent.address
        finally:
            gw.drain(timeout=60)
            agent.stop()

    def test_local_replica_traces_name_host_local(self, demo):
        from tony_tpu.gateway.core import GenRequest

        gw = make_gateway([make_server(demo)])
        try:
            gw.submit(GenRequest([4, 2], max_new_tokens=3,
                                 id="loc")).result(timeout=60)
            rows = {r["request_id"]: r for r in gw.traces.summaries()}
            assert rows["loc"]["host"] == "local"
        finally:
            gw.drain(timeout=60)

    def test_obs_pull_failure_degrades_to_staleness(self, demo):
        """The acceptance pin's graceful-degrade half: obs pulls that
        fail (here: an agent without the channel — 404s) count
        pull_errors and leave lag_s stale, but the replica stays
        HEALTHY, keeps serving with zero 5xx, and its /stats row says
        explicitly that it is unobserved (goodput null) rather than
        silently omitting the keys. Per-ticket mode: a pre-ISSUE-15
        agent predates the mux channel too (under mux the channel
        itself delivers obs, so the pull path never runs dry)."""
        from tony_tpu.gateway.core import GenRequest

        agent = start_agent(demo)
        stub = make_stub(agent.address, agent_channel="per-ticket")
        stub._OBS_PATH = "/v1/obs-not-there"  # a pre-ISSUE-15 agent
        gw = make_gateway([stub])
        try:
            t = gw.submit(GenRequest([5, 1], max_new_tokens=6,
                                     id="deg"))
            res = t.result(timeout=120)
            assert len(res.tokens) == 6
            wait_for(lambda: stub.obs_stats()["pull_errors"] >= 2,
                     msg="pull errors counted")
            snap = gw.snapshot()
            assert snap["shed"] == {}          # never a 5xx
            row = snap["replicas"][0]
            assert row["state"] == "healthy"   # never a failure
            obs = row["obs"]
            assert obs["pulls"] == 0 and obs["pull_errors"] >= 2
            assert obs["lag_s"] is None        # never pulled: stale
            # explicit "unobserved", not a silently missing key
            assert "goodput" in row and row["goodput"] is None
            assert row["dispatch"] == {}
        finally:
            gw.drain(timeout=60)
            agent.stop()

    def test_profile_fanout_arms_agents(self, demo):
        """POST /debug/profile's remote half: the gateway fans the
        capture request to each agent's /v1/profile and reports
        per-host armed/error — a busy agent's 409 never blocks the
        rest. (The real jax capture path is exercised by the smoke's
        remote round; here the agent profilers are recorders, so the
        fast tier never pays start_trace's >10 s first-call.)"""
        class FakeProfiler:
            def __init__(self, busy=False):
                self.busy_ = busy
                self.requests = []

            def request(self, steps, logdir=None):
                if self.busy_:
                    raise RuntimeError("a profile capture is already "
                                       "pending or active")
                self.requests.append(steps)
                return "/on/agent/profiles/profile-1"

            def status(self):
                return {"active": bool(self.requests),
                        "captures": 0}

            def close(self):
                pass

        agents = [start_agent(demo) for _ in range(2)]
        agents[0].agent.profiler = FakeProfiler()
        agents[1].agent.profiler = FakeProfiler(busy=True)
        stubs = [make_stub(a.address) for a in agents]
        gw = make_gateway(stubs)
        try:
            out = gw.arm_remote_profiles(3)
            assert out[agents[0].address]["armed"] is True
            assert out[agents[0].address]["logdir"] \
                == "/on/agent/profiles/profile-1"
            assert agents[0].agent.profiler.requests == [3]
            assert out[agents[1].address]["armed"] is False
            assert out[agents[1].address]["status"] == 409
            status = gw.remote_profile_status()
            assert status[agents[0].address]["active"] is True
        finally:
            gw.drain(timeout=60)
            for a in agents:
                a.stop()

    def test_autotune_never_samples_remote_stubs(self, demo):
        """Regression pin: the shape controller's 'remote stubs are
        never actuated' gate used to key on ``timeline is None`` —
        ISSUE-15 gave stubs a real (pulled) timeline, but their shape
        knobs still live on the AGENT's engine, so the gate must key
        on the transport instead."""
        from tony_tpu.serve.autotune import AutotuneController

        agent = start_agent(demo)
        stub = make_stub(agent.address)
        try:
            assert stub.timeline is not None  # the ISSUE-15 change
            assert AutotuneController()._sample(stub) is None
        finally:
            stub.close()
            agent.stop()

    def test_local_arm_does_not_block_remote_fanout(self, demo):
        """Mixed local+remote fleet: jax's one-global-session rule is
        PER PROCESS, so a pending gateway-local capture (armed, idle
        fleet — never burns down) must not 409 the agent fan-out. The
        POST reports the local refusal in ``local_error`` and still
        arms the agents; a LOCAL-only fleet keeps the 409 contract
        (pinned by test_http_profile_endpoint_real_capture)."""
        import json as _json
        import urllib.request

        from tony_tpu.gateway import GatewayHTTP

        class FakeProfiler:
            def request(self, steps, logdir=None):
                return "/on/agent/profiles/profile-x"

            def status(self):
                return {"active": True, "captures": 0}

            def close(self):
                pass

        agent = start_agent(demo)
        agent.agent.profiler = FakeProfiler()
        gw = make_gateway([make_server(demo),
                           make_stub(agent.address)])
        http = GatewayHTTP(gw, port=0).start()
        url = f"http://{http.host}:{http.port}"
        try:
            gw.profiler.request(5)  # pending local capture, idle fleet
            req = urllib.request.Request(url + "/debug/profile?steps=2",
                                         data=b"", method="POST")
            doc = _json.loads(
                urllib.request.urlopen(req, timeout=60).read())
            assert doc["remote"][agent.address]["armed"] is True
            assert doc["logdir"] is None
            assert "already" in doc["local_error"]
            assert doc["armed"] is True  # the fleet IS capturing
        finally:
            http.stop()
            gw.drain(timeout=60)
            agent.stop()


# --------------------------------------------------------------------
# epoch fence pins
# --------------------------------------------------------------------

class TestEpochFence:
    def test_reset_discards_superseded_stream(self, demo):
        # the revived-host shape: a stream opened under epoch 0 keeps
        # flowing while the stub moves to epoch 1 (reset) — the
        # agent's superseded stream ends, and whatever it still says
        # is dropped by the echo check, counted in stale_epoch_drops
        agent = start_agent(demo)
        stub = make_stub(agent.address)
        try:
            stub.submit(Request([1, 2], 40, id="long"))
            wait_for(lambda: stub.live_progress().get("long"),
                     msg="first tokens")
            stub.reset()  # epoch 0 -> 1; agent adopts 1
            wait_for(lambda: stub.stale_epoch_drops > 0,
                     msg="stale drop counted")
            assert stub.epoch == 1
            assert stub._tickets == {}  # nothing stale survives
            # and the agent refuses the OLD epoch outright now
            from tony_tpu.gateway.remote import AgentHTTPError

            with pytest.raises(AgentHTTPError) as ei:
                stub.transport.call("POST", "/v1/submit", {
                    "id": "z", "prompt": [1], "max_new_tokens": 2,
                    "epoch": 0})
            assert ei.value.status == 409
        finally:
            stub.close()
            agent.stop()

    def test_submit_after_agent_restart_adopts_epoch(self, demo):
        agent = start_agent(demo)
        stub = make_stub(agent.address)
        try:
            stub.reset()
            stub.reset()  # stub at epoch 2
            host, port = agent.address.split(":")
            agent.stop()
            agent = start_agent(demo, port=int(port))  # fresh epoch 0
            stub.submit(Request([5], 4, id="post"))
            assert agent.agent.epoch == 2  # adopted, not rewound
            results = []
            wait_for(lambda: results.extend(stub.step()) or results,
                     msg="finish")  # step() collects the result
            assert results[0].id == "post"
        finally:
            stub.close()
            agent.stop()


# --------------------------------------------------------------------
# chaos: the remote anchors
# --------------------------------------------------------------------

class TestRemoteChaos:
    def test_remote_chaos_anchor(self, demo):
        """THE ISSUE-11 anchor: 2 agents under concurrent load; agent
        0 dies a network-SIGKILL mid-stream (failover path), agent 1's
        streams are disconnected mid-read by injected transport faults
        (resume path) -> zero 5xx, byte-identical outputs, survivor
        keeps serving WITHOUT ever being failed, and a restarted agent
        0 rejoins through the probe path.

        ISSUE-15 extension: after the kill + failover, a victim's
        SINGLE trace carries attempt spans from BOTH hosts — the dead
        host's attempt holding offset-corrected remote dispatch spans
        pulled before it died — and the fleet goodput merge still
        sums <= 1 with the survivor's remote ledger included."""
        from tony_tpu.gateway.core import GenRequest
        from tony_tpu.obs.trace import check_invariants

        agents = [start_agent(demo) for _ in range(2)]
        stubs = [make_stub(a.address) for a in agents]
        gw = make_gateway(stubs)
        try:
            reqs = [Request([1 + i, 2, 3], 48, id=i) for i in range(6)]
            ctrl = control_outputs(demo, reqs)
            # warm the remote path so the kill lands mid-decode, not
            # mid-compile
            gw.submit(GenRequest([7, 7], max_new_tokens=2,
                                 id="warm")).result(timeout=120)

            # throttle the DOOMED engine (every dispatch sleeps a
            # beat, well under the stall horizon) so the kill lands
            # mid-decode even on a warm process — the mux channel
            # otherwise delivers all six streams before the grafted
            # span below is ever observed
            agents[0].agent.server.fault_plan = FaultPlan(
                [Fault("wedge", dispatch=1, seconds=0.25, times=-1)])

            # arm disconnect-mid-stream on the SURVIVOR's transport:
            # times=3 transient — resume-by-offset must absorb it
            stubs[1].transport.fault_plan = FaultPlan(
                [Fault("disconnect", call=1, times=3)])

            tickets = [gw.submit(GenRequest(
                list(r.prompt), max_new_tokens=r.max_new_tokens,
                id=r.id)) for r in reqs]
            wait_for(lambda: stubs[0].n_active > 0, msg="r0 active")

            # the kill must land AFTER at least one of the doomed
            # host's dispatch spans was pulled and grafted — that is
            # exactly the record the flight-recorder story needs to
            # survive the host's death
            a0 = agents[0].address

            def r0_span_attached():
                for t in tickets:
                    tr = t.trace
                    if tr is None:
                        continue
                    for att in tr.root.children:
                        if att.name.startswith("attempt-") \
                                and att.tags.get("host") == a0 \
                                and any(c.tags.get("host") == a0
                                        for c in att.children):
                            return True
                return False

            wait_for(r0_span_attached, msg="r0 dispatch span grafted")
            agents[0].kill()  # SIGKILL, as the network sees it

            for r, t in zip(reqs, tickets):
                res = t.result(timeout=180)
                assert list(res.tokens) == ctrl[r.id], \
                    f"request {r.id} diverged after chaos"
            # the lease is the death authority; the re-runs can finish
            # FASTER than the lease horizon on a warm engine, so wait
            # for the expiry rather than racing it
            wait_for(lambda: stubs[0].lease_expiries >= 1,
                     timeout=30, msg="lease expiry")
            snap = gw.snapshot()
            assert snap["shed"] == {}  # zero 5xx
            assert snap["supervision"]["replica_failures"] >= 1
            assert snap["supervision"]["failovers"] >= 1
            rows = {row["replica"]: row for row in snap["replicas"]}
            # the survivor resumed, never failed
            assert rows[1]["failures"] == 0
            assert rows[1]["transport"]["reconnects"] >= 1
            assert rows[1]["completed"] >= 1
            assert rows[0]["transport"]["lease_expiries"] >= 1

            # ISSUE-15: ONE trace spans both hosts of the failover
            victims = [t for t in tickets
                       if t.metrics and t.metrics["attempts"] >= 1]
            assert victims, "no ticket was failed over"
            both_hosts_seen = False
            for t in victims:
                tr = gw.traces.get(t.request.id)
                assert tr is not None and tr.n_attempts >= 2
                assert check_invariants(tr) == []
                hosts = [a.tags.get("host") for a in tr.root.children
                         if a.name.startswith("attempt-")]
                if {agents[0].address, agents[1].address} \
                        <= set(hosts):
                    both_hosts_seen = True
                # the dead host's attempt kept its pulled dispatch
                # spans, offset-corrected (the fence dropped only
                # what arrived AFTER the steal)
                for att in tr.root.children:
                    if not att.name.startswith("attempt-") \
                            or att.tags.get("host") != a0:
                        continue
                    spans = [c for c in att.children
                             if c.tags.get("host") == a0]
                    if spans:
                        assert all("clock_offset_ms" in c.tags
                                   for c in spans)
            assert both_hosts_seen
            # ...and the merged fleet ledger still holds its invariant
            # with the survivor's remote ledger included
            assert rows[1]["goodput"] is not None
            fleet = snap["engine"]["goodput"]
            assert fleet and sum(fleet["buckets"].values()) <= 1 + 1e-6

            # restart agent 0 on the SAME port: the breaker's probe
            # path must rejoin it without operator action
            host, port = agents[0].address.split(":")
            agents[0] = start_agent(demo, port=int(port))
            wait_for(lambda: gw.replicas[0].state == "healthy",
                     timeout=60, msg="rejoin via probe")
            assert gw.snapshot()["supervision"]["rejoins"] >= 1
            # post-chaos, the rejoined host's obs channel works: a new
            # request's trace grafts dispatch spans from the restarted
            # agent (same address, fresh agent-side timeline)
            t = gw.submit(GenRequest([3, 3, 3], max_new_tokens=6,
                                     id="post-rejoin",
                                     session="pin0"))
            assert len(t.result(timeout=120).tokens) == 6
        finally:
            gw.drain(timeout=60)
            for a in agents:
                a.stop()

    def test_blackhole_partition_fails_over_token_exact(self, demo):
        """A full network partition (every call to agent 0 times out,
        injected) is indistinguishable from a dead host: the lease
        expires, everything fails over token-exactly, zero 5xx."""
        from tony_tpu.gateway.core import GenRequest

        agents = [start_agent(demo) for _ in range(2)]
        stubs = [make_stub(a.address) for a in agents]
        gw = make_gateway(stubs)
        try:
            reqs = [Request([2 + i, 4], 32, id=i) for i in range(4)]
            ctrl = control_outputs(demo, reqs)
            gw.submit(GenRequest([7, 7], max_new_tokens=2,
                                 id="warm")).result(timeout=120)
            # drop the partition: EVERYTHING to/from agent 0
            # black-holes from here on — the submit the router sends
            # it next must fail over, and the heartbeat blackout must
            # expire the lease (permanent, so no timing race)
            stubs[0].transport.fault_plan = FaultPlan(
                [Fault("blackhole", call=1, times=-1)])
            tickets = [gw.submit(GenRequest(
                list(r.prompt), max_new_tokens=r.max_new_tokens,
                id=r.id)) for r in reqs]
            for r, t in zip(reqs, tickets):
                res = t.result(timeout=180)
                assert list(res.tokens) == ctrl[r.id]
            # the lease is the death authority: the heartbeat blackout
            # must expire it even though the failover already happened
            # via the admission route
            wait_for(lambda: stubs[0].lease_expiries >= 1,
                     timeout=30, msg="lease expiry")
            snap = gw.snapshot()
            assert snap["shed"] == {}  # zero 5xx
            assert snap["supervision"]["replica_failures"] >= 1
            rows = {row["replica"]: row for row in snap["replicas"]}
            tr0 = rows[0]["transport"]
            assert tr0["heartbeat_failures"] >= 1
            assert rows[0]["state"] in ("broken", "probing")
            assert rows[1]["completed"] >= len(reqs)
        finally:
            gw.drain(timeout=60)
            for a in agents:
                a.stop()

    def test_wedged_remote_engine_fails_over(self, demo):
        """A dispatch that WEDGES on the agent (engine wedge fault)
        stops the agent's stepper beat; the stub's heartbeat sees a
        busy agent whose stepper age exceeds the stall horizon and
        withholds the lease ping — same funnel, token-exact."""
        from tony_tpu.gateway.core import GenRequest
        from tony_tpu.serve.faults import FaultPlan as FP

        agents = [start_agent(demo) for _ in range(2)]
        # wedge replica 0's engine on a mid-generation dispatch, long
        # enough to blow the stub's (tight) stall horizon
        agents[0].agent.server.fault_plan = FP.wedge_at(
            dispatch=4, seconds=4.0)
        stubs = [make_stub(agents[0].address, stall_timeout_s=0.5),
                 make_stub(agents[1].address)]
        gw = make_gateway(stubs)
        try:
            req = Request([6, 1], 24, id="w")
            ctrl = control_outputs(demo, [req])
            # route to replica 0 via session affinity being moot on an
            # idle fleet: least-outstanding picks 0 first
            ticket = gw.submit(GenRequest([6, 1], max_new_tokens=24,
                                          id="w"))
            res = ticket.result(timeout=180)
            assert list(res.tokens) == ctrl["w"]
            assert gw.snapshot()["shed"] == {}
        finally:
            gw.drain(timeout=60)
            for a in agents:
                a.stop()


@pytest.mark.slow
def test_subprocess_agent_sigkill_e2e(tmp_path, demo):
    """The subprocess flavor of the anchor: two REAL ``python -m
    tony_tpu.cli.replica`` processes, one killed with an actual
    SIGKILL mid-stream -> zero 5xx, token-exact outputs, clean drain
    of the survivor. (The in-process anchor above runs in tier-1; this
    is the no-simulation version, also exercised by
    ``make remote-smoke``.)"""
    import os
    import signal as sig

    from tony_tpu.cli.gateway import build_gateway, build_parser
    from tony_tpu.gateway.core import GenRequest

    procs, addrs = [], []
    try:
        for i in range(2):
            proc, addr = launch_agent_subprocess(tmp_path, i)
            procs.append(proc)
            addrs.append(addr)
        # quarantine the corpse FAST: endless probe laps against a
        # dead port would starve the survivor's decode on a 1-CPU box
        args = build_parser().parse_args([
            "--agents", ",".join(addrs), "--serve-batch", "2",
            "--agent-heartbeat", "0.1", "--agent-lease-misses", "3",
            "--breaker-base", "0.05", "--breaker-max", "0.25",
            "--quarantine-after", "3", "--compile-cache", ""])
        gw = build_gateway(args, None, None, []).start()
        try:
            reqs = [Request([1 + i, 2, 3], 48, id=i) for i in range(6)]
            ctrl = control_outputs(demo, reqs)
            gw.submit(GenRequest([7, 7], max_new_tokens=2,
                                 id="warm")).result(timeout=180)
            tickets = [gw.submit(GenRequest(
                list(r.prompt), max_new_tokens=r.max_new_tokens,
                id=r.id)) for r in reqs]
            stub0 = gw.replicas[0].server
            wait_for(lambda: stub0.n_active > 0, timeout=60,
                     msg="r0 active")
            os.kill(procs[0].pid, sig.SIGKILL)  # the real thing
            for r, t in zip(reqs, tickets):
                assert list(t.result(timeout=180).tokens) == ctrl[r.id]
            snap = gw.snapshot()
            assert snap["shed"] == {}
            assert snap["supervision"]["replica_failures"] >= 1
        finally:
            gw.drain(timeout=60)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=20)
            except Exception:
                p.kill()


@pytest.mark.slow
def test_remote_drain_then_sigterm_exits_zero(tmp_path):
    """Regression pin: the scale-down sequence (gateway POSTs
    /v1/drain, then close() sends ONE polite SIGTERM) must exit 0 —
    the signal handler counts SIGNALS for its force path, it must not
    read an HTTP-initiated drain as 'second signal'."""
    import signal as sig

    from tony_tpu.gateway.remote import AgentTransport

    proc, addr = launch_agent_subprocess(tmp_path, 0)
    try:
        t = AgentTransport(addr)
        assert t.call("POST", "/v1/drain",
                      {"timeout_s": 60}, timeout=90.0)["drained"]
        proc.send_signal(sig.SIGTERM)
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()


def test_agent_argv_passes_host_share():
    # launched localhost agents must size auto KV pools for the fleet
    # CEILING sharing the host (the PR-8 oversubscription rule)
    from tony_tpu.cli.gateway import agent_argv, build_parser

    args = build_parser().parse_args(
        ["--demo-model", "--remote-replica", "--replicas", "2",
         "--autoscale-max", "3"])
    argv = agent_argv(args, 1)
    i = argv.index("--host-share")
    assert argv[i + 1] == "3"


def launch_agent_subprocess(tmp_path, index):
    from tony_tpu.gateway.remote import launch_local_agent

    return launch_local_agent(
        ["--demo-model", "--serve-batch", "2", "--port", "0",
         "--replica-index", str(index), "--compile-cache", ""],
        port_file=str(tmp_path / f"agent-{index}.port"),
        boot_timeout_s=180.0)


# --------------------------------------------------------------------
# provisioner integration: no leaked capacity
# --------------------------------------------------------------------

class _FakeProvisioner:
    def __init__(self):
        self.provisioned = False
        self.deprovisioned = False

    def provision(self):
        self.provisioned = True
        return ["127.0.0.1"]

    def deprovision(self):
        self.deprovisioned = True


class TestProvisionerRemote:
    def test_dead_remote_slice_deprovisioned_no_leak(self, demo):
        """The acceptance pin: a scaled-up REMOTE replica whose host
        dies is quarantine-first victim at the next scale-down tick —
        remove_replica drains the corpse, the stub closes, and the
        slice is deprovisioned. Nothing leaks."""
        from tony_tpu.gateway.autoscale import (AutoScaler,
                                                ProvisionerBackend)

        agents = []

        def server_factory(hosts):
            assert hosts == ["127.0.0.1"]
            agent = start_agent(demo)
            agents.append(agent)
            return make_stub(agent.address)

        prov = _FakeProvisioner()
        gw = make_gateway([make_server(demo)], quarantine_after=1)
        backend = ProvisionerBackend(lambda slot: prov, server_factory)
        scaler = AutoScaler(gw, backend, min_replicas=1, max_replicas=2,
                            interval_s=3600, down_stable=1,
                            cooldown_up_s=0.0, cooldown_down_s=0.0)
        try:
            server = backend.create()
            assert prov.provisioned
            idx = gw.add_replica(server, probe=True)
            scaler._servers[idx] = server
            wait_for(lambda: gw.replicas[idx].state == "healthy",
                     timeout=60, msg="probe admission")
            agents[0].kill()  # the host dies
            wait_for(lambda: gw.replicas[idx].state == "quarantined",
                     timeout=60, msg="quarantine")
            # drive the control loop by hand: idle fleet + a dead
            # replica -> scale-down picks the corpse first
            wait_for(lambda: scaler.tick() == "down", timeout=30,
                     interval=0.05, msg="scale-down of the corpse")
            assert gw.replicas[idx].retired
            assert gw.replicas[idx].server is None
            assert prov.deprovisioned  # the slice went back
            assert backend._slices == {}  # nothing leaked
        finally:
            scaler.stop(timeout=5)
            gw.drain(timeout=60)
            for a in agents:
                try:
                    a.stop()
                except Exception:
                    pass
