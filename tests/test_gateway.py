"""Gateway invariants (tony_tpu.gateway) on the CPU tiny model.

The four ISSUE-2 acceptance properties:
- greedy outputs through the gateway are token-identical to a direct
  ``Server.run()`` (the front door adds routing, never math);
- a deadline-expired request is shed with 504 BEFORE it ever occupies
  a slot (prefill count is the witness);
- graceful drain under load loses zero accepted requests;
- two replicas both stay busy under skewed request lengths
  (least-outstanding-tokens routing).

Plus the serve-engine backpressure/drain hooks the gateway depends on
(``QueueFull``, ``Server.drain()``) and the HTTP face (unary +
streaming + health/stats) in-process. CPU-only, tiny model — the slow
marker end-to-end subprocess test lives at the bottom.
"""

import json
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.gateway import (BadRequest, DeadlineExceeded, Gateway,
                              GatewayClosed, GatewayEdge, GatewayHTTP,
                              GatewayQueueFull, GenRequest)
from tony_tpu.models import Transformer, TransformerConfig, generate
from tony_tpu.serve import QueueFull, Request, Server


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


def _servers(tiny, n, **kw):
    model, params = tiny
    kw.setdefault("batch_size", 2)
    kw.setdefault("min_bucket", 8)
    return [Server(model, params, **kw) for _ in range(n)]


def _solo(tiny, prompt, n):
    model, params = tiny
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n)
    return np.asarray(out)[0].tolist()


# ----------------------------------------------------- engine hooks


def test_server_submit_queue_full_typed(tiny):
    model, params = tiny
    server = Server(model, params, batch_size=1, min_bucket=8,
                    max_pending=2)
    server.submit(Request([1, 2], max_new_tokens=2))
    server.submit(Request([3, 4], max_new_tokens=2))
    with pytest.raises(QueueFull, match="max_pending=2"):
        server.submit(Request([5, 6], max_new_tokens=2))
    # QueueFull is a typed signal, not a ValueError (callers branch)
    assert not isinstance(QueueFull("x"), ValueError)
    assert sum(1 for _ in server.run()) == 2


def test_server_drain_finishes_in_flight_only(tiny):
    """drain() completes the slots without admitting pending — the
    graceful-shutdown primitive the gateway builds on."""
    model, params = tiny
    server = Server(model, params, batch_size=2, min_bucket=8)
    for i in range(4):
        server.submit(Request([1 + i, 2, 3], max_new_tokens=4, id=i))
    first = server.step()  # admits 2, decodes a chunk
    drained = server.drain()
    done_ids = {r.id for r in first} | {r.id for r in drained}
    assert done_ids == {0, 1}  # the two that held slots
    assert server.slots.n_active == 0
    assert server.n_pending == 2  # pending untouched, caller's call
    # results are exact, not truncated, for what drained
    by_id = {r.id: r for r in drained}
    for rid, res in by_id.items():
        assert res.tokens == _solo(tiny, res.prompt, 4)


def test_server_live_progress_tracks_generation(tiny):
    model, params = tiny
    server = Server(model, params, batch_size=1, min_bucket=8,
                    chunk_steps=1)
    server.submit(Request([1, 2, 3], max_new_tokens=4, id="x"))
    server.step()
    p1 = server.live_progress()
    assert list(p1) == ["x"] and len(p1["x"]) >= 1
    server.step()
    p2 = server.live_progress()
    assert len(p2["x"]) > len(p1["x"])
    assert p2["x"][:len(p1["x"])] == p1["x"]  # append-only


def test_server_reset_clears_live_and_pending(tiny):
    """reset() after a failed step must leave no engine ghosts: no
    pending, no _live entries decoding phantom results, all slots free
    — and the engine serves fresh requests exactly afterwards."""
    model, params = tiny
    server = Server(model, params, batch_size=2, min_bucket=8)
    for i in range(3):
        server.submit(Request([1 + i, 2, 3], max_new_tokens=6, id=i))
    server.step()  # two slots live, one pending
    server.reset()
    assert server.done and server.n_pending == 0
    assert server.live_progress() == {}
    assert server.slots.free_slots() == [0, 1]
    server.submit(Request([7, 2], max_new_tokens=4, id="fresh"))
    res = {r.id: r for r in server.run()}
    assert list(res) == ["fresh"]
    assert res["fresh"].tokens == _solo(tiny, [7, 2], 4)


# ------------------------------------------------------- gateway core


def test_gateway_vs_direct_greedy_parity(tiny):
    """The acceptance anchor: same tokens through the front door as
    through the engine directly, 1 and 2 replicas."""
    model, params = tiny
    prompts = [[1, 2, 3], [5, 9], [17, 46, 10, 20, 62, 26], [7, 2, 5, 11]]
    direct = {r.id: r.tokens for r in
              Server(model, params, batch_size=2, min_bucket=8).run(
                  Request(p, max_new_tokens=6, id=j)
                  for j, p in enumerate(prompts))}
    for n_replicas in (1, 2):
        gw = Gateway(_servers(tiny, n_replicas), max_queue=16).start()
        tickets = [gw.submit(GenRequest(p, max_new_tokens=6, id=i))
                   for i, p in enumerate(prompts)]
        for i, t in enumerate(tickets):
            assert t.result(timeout=120).tokens == direct[i], \
                (n_replicas, prompts[i])
        assert gw.drain(timeout=60)


def test_deadline_expired_requests_never_take_a_slot(tiny):
    """A request whose deadline passed while queued is shed with 504
    having cost ZERO device work: no prefill, no slot. Deterministic:
    tickets queue up before the replica thread starts."""
    servers = _servers(tiny, 1, batch_size=1)
    gw = Gateway(servers, max_queue=16)
    t_live = gw.submit(GenRequest([1, 2, 3], max_new_tokens=6, id="live"))
    t_dead = gw.submit(GenRequest([5, 9], max_new_tokens=6, id="dead",
                                  ttl_s=1e-6))  # expires instantly
    t_after = gw.submit(GenRequest([7, 2], max_new_tokens=4, id="after"))
    gw.start()
    with pytest.raises(DeadlineExceeded, match="deadline exceeded"):
        t_dead.result(timeout=120)
    assert t_live.result(timeout=120).tokens == _solo(tiny, [1, 2, 3], 6)
    assert t_after.result(timeout=120).tokens == _solo(tiny, [7, 2], 4)
    # an already-dead ttl is refused synchronously at submit
    with pytest.raises(DeadlineExceeded):
        gw.submit(GenRequest([1], max_new_tokens=1, ttl_s=0.0))
    assert gw.drain(timeout=60)
    # the witness: exactly the two admitted requests prefilled
    assert servers[0].prefills == 2
    snap = gw.snapshot()
    assert snap["shed"] == {504: 2}
    assert snap["completed"] == 2


def test_drain_under_load_loses_zero_accepted_requests(tiny):
    """SIGTERM semantics: everything accepted before the drain signal
    completes with a real result; nothing hangs, nothing is dropped."""
    gw = Gateway(_servers(tiny, 2), max_queue=64).start()
    prompts = [[1 + (i % 5), 2, 3] for i in range(12)]
    tickets = [gw.submit(GenRequest(p, max_new_tokens=3 + (i % 4), id=i))
               for i, p in enumerate(prompts)]
    assert gw.drain(timeout=180)  # most tickets still queued right now
    for i, t in enumerate(tickets):
        res = t.result(timeout=1)  # already terminal: must not block
        assert res.tokens == _solo(tiny, prompts[i],
                                   3 + (i % 4)), i
    snap = gw.snapshot()
    assert snap["completed"] == len(tickets)
    assert snap["queued"] == 0 and not snap["ready"]
    with pytest.raises(GatewayClosed):
        gw.submit(GenRequest([1, 2], max_new_tokens=2))


def test_two_replica_routing_spreads_skewed_load(tiny):
    """Least-outstanding-tokens routing: one 25-token request must not
    serialize the small requests behind it — both replicas do real
    work."""
    servers = _servers(tiny, 2, batch_size=2)
    gw = Gateway(servers, max_queue=64).start()
    tickets = [gw.submit(GenRequest([17, 46, 10], max_new_tokens=25,
                                    id="huge"))]
    tickets += [gw.submit(GenRequest([1 + i, 2], max_new_tokens=4,
                                     id=f"s{i}")) for i in range(8)]
    for t in tickets:
        t.result(timeout=120)
    assert gw.drain(timeout=60)
    stats = [r.stats() for r in gw.replicas]
    assert all(s["completed"] >= 1 for s in stats), stats
    assert all(s["prefills"] >= 1 and s["decode_steps"] > 0
               for s in stats), stats
    assert sum(s["completed"] for s in stats) == len(tickets)


def test_session_affinity_pins_replica(tiny):
    gw = Gateway(_servers(tiny, 2), max_queue=64).start()
    tickets = [gw.submit(GenRequest([1 + i, 2], max_new_tokens=2,
                                    session="conversation-42"))
               for i in range(4)]
    others = [gw.submit(GenRequest([9, 9 - i], max_new_tokens=2,
                                   session=f"other-{i}"))
              for i in range(4)]
    for t in tickets + others:
        t.result(timeout=120)
    assert len({t.replica for t in tickets}) == 1  # pinned
    assert len({t.replica for t in tickets + others}) == 2  # but not all
    assert gw.drain(timeout=60)


def test_admission_queue_bound_and_validation(tiny):
    """429 past max_queue; 400-class validation synchronously."""
    gw = Gateway(_servers(tiny, 1), max_queue=2)  # NOT started: queue
    gw.submit(GenRequest([1, 2], max_new_tokens=2))  # depth is exact
    gw.submit(GenRequest([3, 4], max_new_tokens=2))
    with pytest.raises(GatewayQueueFull, match="max_queue=2"):
        gw.submit(GenRequest([5, 6], max_new_tokens=2))
    with pytest.raises(BadRequest, match="empty"):
        gw.submit(GenRequest([], max_new_tokens=2))
    with pytest.raises(BadRequest, match="no room"):
        gw.submit(GenRequest(list(range(32)), max_new_tokens=2))
    with pytest.raises(BadRequest, match="max_new_tokens"):
        gw.submit(GenRequest([1], max_new_tokens=0))
    # every refusal is counted, by status — /stats must not undercount
    assert gw.snapshot()["shed"] == {429: 1, 400: 3}


def test_gateway_streaming_deltas_reassemble_exactly(tiny):
    """Concatenated token events == the final result tokens (chunk 1:
    per-token streaming)."""
    gw = Gateway(_servers(tiny, 1, chunk_steps=1), max_queue=8).start()
    got: list[int] = []
    done = threading.Event()

    def on_event(ticket, event):
        if event[0] == "tokens":
            got.extend(event[1])
        elif event[0] in ("done", "shed"):
            done.set()

    t = gw.submit(GenRequest([1, 2, 3], max_new_tokens=6), on_event)
    res = t.result(timeout=120)
    assert done.wait(timeout=10)
    assert got == res.tokens == _solo(tiny, [1, 2, 3], 6)
    assert gw.drain(timeout=60)


def test_per_request_metrics_recorded(tiny):
    from tony_tpu.metrics import MetricsStore

    store = MetricsStore()
    gw = Gateway(_servers(tiny, 1), max_queue=8,
                 metrics_store=store).start()
    t = gw.submit(GenRequest([1, 2, 3], max_new_tokens=5))
    t.result(timeout=120)
    assert gw.drain(timeout=60)
    snap = gw.snapshot()
    assert snap["tokens_in"] == 3 and snap["tokens_out"] == 5
    for key in ("queue_wait_ms", "ttft_ms", "tpot_ms"):
        assert snap[key]["p50"] >= 0.0
    rep = store.get_metrics("gateway:replica-0")
    assert rep["completed"] == 1 and rep["prefills"] == 1


def test_gateway_history_feeds_portal(tiny, tmp_path):
    """--history: the gateway shows up as a history job whose metrics
    page lists the per-request rows — zero portal changes."""
    from tony_tpu.events import history
    from tony_tpu.gateway import GatewayHistory

    hist = GatewayHistory(str(tmp_path), n_replicas=1)
    gw = Gateway(_servers(tiny, 1), max_queue=8, history=hist).start()
    gw.submit(GenRequest([1, 2, 3], max_new_tokens=4,
                         id="req-a")).result(timeout=120)
    assert gw.drain(timeout=60)
    jobs = history.list_jobs(str(tmp_path))
    assert [j["app_id"] for j in jobs] == [hist.app_id]
    assert jobs[0]["status"] == "SUCCEEDED"
    rows = [json.loads(ln) for ln in open(
        tmp_path / "intermediate" / hist.app_id / "metrics" /
        "requests.jsonl")]
    assert [r["id"] for r in rows] == ["req-a"]
    assert rows[0]["tokens_out"] == 4 and rows[0]["replica"] == 0


# -------------------------------------------------------------- http


@pytest.fixture(params=["event", "threaded"])
def http_gateway(tiny, request):
    # every front-door contract runs against BOTH edges: the event
    # loop (default) and the thread-per-connection A/B control
    gw = Gateway(_servers(tiny, 1, chunk_steps=1), max_queue=8).start()
    if request.param == "event":
        http = GatewayEdge(gw).start()
    else:
        http = GatewayHTTP(gw).start()
    yield gw, f"http://{http.host}:{http.port}"
    gw.drain(timeout=60)
    http.stop()


def _post(url, doc, timeout=120):
    req = urllib.request.Request(
        url + "/v1/generate", data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def test_http_unary_and_health(tiny, http_gateway):
    gw, url = http_gateway
    health = json.loads(urllib.request.urlopen(
        url + "/healthz", timeout=30).read())
    assert health["status"] == "ok" and health["healthy"] == 1
    assert health["replicas"][0]["state"] == "healthy"
    assert health["replicas"][0]["heartbeat_age_s"] < 30
    assert urllib.request.urlopen(url + "/readyz", timeout=30).status == 200
    doc = json.loads(_post(url, {"token_ids": [1, 2, 3],
                                 "max_new_tokens": 5, "id": "u"}).read())
    assert doc["id"] == "u"
    assert doc["token_ids"] == [1, 2, 3] + _solo(tiny, [1, 2, 3], 5)
    assert doc["finish_reason"] == "length"
    assert doc["metrics"]["tokens_out"] == 5
    stats = json.loads(urllib.request.urlopen(
        url + "/stats", timeout=30).read())
    assert stats["completed"] >= 1 and len(stats["replicas"]) == 1


def test_http_streaming_ndjson(tiny, http_gateway):
    gw, url = http_gateway
    resp = _post(url, {"token_ids": [1, 2, 3], "max_new_tokens": 5,
                       "stream": True, "id": "s"})
    assert resp.headers.get("Content-Type") == "application/x-ndjson"
    lines = [json.loads(ln) for ln in resp.read().decode().splitlines()]
    assert len(lines) >= 2  # at least one delta + the final doc
    toks = [t for ln in lines[:-1] for t in ln["token_ids"]]
    final = lines[-1]
    assert final["finish_reason"] == "length"
    assert final["token_ids"] == [1, 2, 3] + toks
    assert toks == _solo(tiny, [1, 2, 3], 5)


def test_http_error_mapping(tiny, http_gateway):
    gw, url = http_gateway
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(url, {"max_new_tokens": 5})
    assert e.value.code == 400  # no prompt/token_ids
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(url, {"token_ids": [1], "ttl_s": 0})
    assert e.value.code == 504  # dead on arrival
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(url + "/nope", timeout=30)
    assert e.value.code == 404
    gw.drain(timeout=60)  # front door closes
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(url, {"token_ids": [1, 2]})
    assert e.value.code == 503
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(url + "/readyz", timeout=30)
    assert e.value.code == 503


# --------------------------------------------------------------- e2e


@pytest.mark.slow  # heavyweight; tier-1 runs -m 'not slow'
def test_gateway_cli_e2e_concurrent_and_sigterm(tmp_path):
    """The CLI front door end-to-end: boot --demo-model, fire
    concurrent clients (streaming + unary), then SIGTERM and assert a
    clean zero-loss drain (exit 0)."""
    import os
    import signal
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.dirname(os.path.dirname(
               os.path.abspath(__file__)))}
    proc = subprocess.Popen(
        [sys.executable, "-m", "tony_tpu.cli.gateway", "--demo-model",
         "--replicas", "2", "--port", "0", "--compile-cache", ""],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env)
    try:
        boot = proc.stdout.readline().strip()
        url = boot.split()[3]
        results: dict[int, dict] = {}
        errors: list = []

        def client(i):
            try:
                stream = i % 2 == 0
                doc = {"token_ids": [1 + i, 2, 3],
                       "max_new_tokens": 4 + i % 3, "stream": stream,
                       "id": i}
                body = _post(url, doc, timeout=240).read().decode()
                results[i] = json.loads(body.splitlines()[-1])
            except Exception as e:  # noqa: BLE001 — collected, asserted
                errors.append((i, e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not errors, errors
        assert set(results) == set(range(8))
        for i, doc in results.items():
            assert doc["finish_reason"] in ("eos", "length"), doc
            assert doc["token_ids"][:3] == [1 + i, 2, 3]
        stats = json.loads(urllib.request.urlopen(
            url + "/stats", timeout=30).read())
        assert stats["completed"] == 8
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0
    finally:
        if proc.poll() is None:
            proc.kill()


@pytest.mark.parametrize("cli", ["gateway", "replica"])
def test_retired_flags_are_refused(cli, capsys):
    """``--no-in-dispatch-eos`` went with the arm it chose: a launcher
    that still passes it is told so (exit 2), not obeyed in silence by
    an engine that has no such arm."""
    import importlib

    parser = importlib.import_module(f"tony_tpu.cli.{cli}").build_parser()
    parser.parse_args(["--demo-model"])
    with pytest.raises(SystemExit) as exc:
        parser.parse_args(["--demo-model", "--no-in-dispatch-eos"])
    assert exc.value.code == 2
    assert "--no-in-dispatch-eos" in capsys.readouterr().err
