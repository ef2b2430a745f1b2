"""Live session migration (ISSUE-18): the zero-copy KV fabric that
moves in-flight streams between replicas token-exact.

The exactness discipline is the house rule: every migrated stream is
pinned BYTE-IDENTICAL to a no-migration control on a fresh engine —
greedy and seeded sampling, speculation live — because a freeze
captures the rng chain mid-flight and the adopting engine resumes it
at the exact position. The structural claims ride deterministic
counters: a shared-pool owner swap moves ZERO pages (bytes_avoided
grows instead), a cross-host migration ships real pages over the
wire (pages_moved grows), a retiring replica's out-side ledger
survives its own departure via the gateway carry, and the page pool
conserves refcounts (n_used == 0 after drain, always).

The failure half of the contract: a migrated payload is ONE-SHOT —
consumed at admit — so a SIGKILL on the adopting host afterwards
degrades to the ordinary crash path (re-run from the prompt), which
determinism makes token-exact too. Zero 5xx throughout.

Tiny reference-attention model, CPU-only; engines are throttled with
a wedge fault (30 ms per dispatch, token-exact preserved) so the
mid-stream windows the tests need actually exist on a model this
small.
"""

import itertools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.gateway.core import Gateway, GenRequest
from tony_tpu.models import Transformer, TransformerConfig
from tony_tpu.serve import Request, Server
from tony_tpu.serve.faults import FaultPlan
from tony_tpu.serve.slots import PagePool

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


def _prompt(seed=3, n=13):
    return np.random.default_rng(seed).integers(1, 64, size=n).tolist()


def _slow():
    # 30 ms per dispatch: a 40-token stream stays in flight ~1.2 s,
    # wide enough to freeze mid-stream deterministically
    return FaultPlan.wedge_at(1, 0.03, times=-1)


def _mk(tiny, **kw):
    model, params = tiny
    kw.setdefault("prefix_cache_mb", 0)
    kw.setdefault("batch_size", 2)
    return Server(model, params, eos_id=-1, paged=True,
                  kv_page_size=8, **kw)


def _control(tiny, prompt, budget, *, temperature=0.0, top_k=0,
             seed=0, **server_kw):
    """No-migration control on a fresh single engine."""
    srv = _mk(tiny, **server_kw)
    srv.submit(Request(list(prompt), budget, id="c",
                       temperature=temperature, top_k=top_k, seed=seed))
    return list(srv.run())[0].tokens


def _hold_after(srv, n_steps, gate):
    """From its step ``n_steps + 1`` on, and for as long as it has a
    live slot and ``gate`` is closed, ``srv.step()`` does nothing: the
    loop that drives it keeps turning (and sees a retire or a freeze),
    the stream stands. It then cannot run to its end on the wall clock
    while the test's own thread is kept off the CPU: the mid-stream
    window stays open until the test has used it, however the host
    stalls."""
    real, count = srv.step, itertools.count()

    def step():
        if next(count) >= n_steps and srv.n_active and not gate.is_set():
            time.sleep(0.01)
            return []
        return real()

    srv.step = step
    return srv


def _wait(cond, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def _wait_emitted(t, n, timeout=30.0):
    _wait(lambda: t._n_emitted >= n, timeout,
          f"{n} tokens emitted (got {t._n_emitted})")


# ------------------------------------------------- local owner swap


@pytest.mark.parametrize("temperature,top_k,seed",
                         [(0.0, 0, 0), (0.8, 8, 7)])
def test_remove_replica_migrates_mid_stream_token_exact(
        tiny, temperature, top_k, seed):
    """THE local anchor: two replicas lease one shared PagePool;
    remove_replica mid-stream freezes the live session and the
    survivor adopts it by OWNER SWAP — zero pages copied, tokens
    byte-identical to the no-migration control, both greedy and
    seeded (the rng chain migrates at its exact position). The trace
    carries the migrate fence between the two attempt spans, and the
    pool refcounts conserve to zero after drain."""
    model, params = tiny
    prompt, budget = _prompt(), 40
    expect = _control(tiny, prompt, budget, temperature=temperature,
                      top_k=top_k, seed=seed)
    pool = PagePool(model, params, 128, 8, shared=True)
    gw = Gateway([_mk(tiny, page_pool=pool, fault_plan=_slow()),
                  _mk(tiny, page_pool=pool, fault_plan=_slow())]).start()
    try:
        t = gw.submit(GenRequest(list(prompt), max_new_tokens=budget,
                                 temperature=temperature, top_k=top_k,
                                 seed=seed, id="mig"))
        _wait_emitted(t, 3)
        src = t.replica
        assert src is not None
        assert gw.remove_replica(src, timeout=60)
        res = t.result(timeout=120)
        assert list(res.tokens) == list(expect)
        snap = gw.snapshot()
        assert snap["shed"] == {}  # zero 5xx
        assert snap["routing"]["migrations"] >= 1
        mig = snap["engine"]["migrations"]
        # out-side counters survived the source's retirement (carry)
        assert mig["out"] >= 1 and mig["in"] >= 1
        # owner swap: both sides count local, nothing crosses a wire
        assert mig["local"] >= 2 and mig["remote"] == 0
        assert mig["pages_moved"] == 0
        assert mig["bytes_avoided"] > 0
        assert mig["freeze_resume_ms"] >= 0
        # ONE trace spans the handover: attempt on the source ends
        # with the migrate fence, attempt on the survivor follows
        tr = gw.traces.get("mig")
        assert tr is not None and tr.n_attempts >= 2
        names = {e.get("name")
                 for e in tr.to_chrome().get("traceEvents", [])}
        assert "migrate" in names, names
    finally:
        assert gw.drain(timeout=60)
    assert pool.n_used == 0
    assert (np.asarray(pool.refcount) >= 0).all()


def test_migration_with_speculation_live_token_exact(tiny):
    """Speculation survives the freeze: the snapshot carries the
    draft-acceptance EMA and the adopting engine keeps speculating —
    output still byte-identical to a speculating control. Greedy with
    a repetitive prompt: prompt-lookup drafting only arms on greedy
    requests, and the repeated n-gram guarantees proposals fire."""
    prompt, budget = [1, 2, 3] * 4 + [1, 2], 40
    model, params = tiny
    expect = _control(tiny, prompt, budget, speculate_k=2)
    pool = PagePool(model, params, 128, 8, shared=True)
    gw = Gateway([_mk(tiny, page_pool=pool, fault_plan=_slow(),
                      speculate_k=2),
                  _mk(tiny, page_pool=pool, fault_plan=_slow(),
                      speculate_k=2)]).start()
    try:
        t = gw.submit(GenRequest(list(prompt), max_new_tokens=budget,
                                 id="spec"))
        _wait_emitted(t, 3)
        assert gw.remove_replica(t.replica, timeout=60)
        res = t.result(timeout=120)
        assert list(res.tokens) == list(expect)
        snap = gw.snapshot()
        assert snap["shed"] == {}
        assert snap["engine"]["migrations"]["out"] >= 1
        # the adopter actually speculated after the handover
        assert snap["engine"]["spec"]["rounds"] >= 1
    finally:
        assert gw.drain(timeout=60)
    assert pool.n_used == 0


# ------------------------------------------------- cross-host wire


def _start_agent(tiny, **server_kw):
    from tony_tpu.serve.agent import AgentHTTP, ReplicaAgent

    return AgentHTTP(ReplicaAgent(_mk(tiny, **server_kw))).start()


def _stub(address, **kw):
    from tony_tpu.gateway.remote import RemoteServer

    kw.setdefault("heartbeat_interval_s", 0.1)
    kw.setdefault("lease_misses", 10)
    kw.setdefault("boot_timeout_s", 20.0)
    return RemoteServer(address, **kw)


def test_cross_host_migration_token_exact(tiny):
    """The wire anchor: one local replica, one remote agent. Removing
    whichever replica holds the stream ships the session to the other
    side of the wire — gathered pages travel as the codec's bitwise
    wire form (pages_moved > 0; this direction has no shared pool to
    swap within) and the stream stays byte-identical to the
    control."""
    prompt, budget = _prompt(), 40
    expect = _control(tiny, prompt, budget)
    # whichever side holds the stream stands after six steps until the
    # freeze has come: a test thread kept off the CPU for the second
    # the stream lasts on the clock would otherwise find the session
    # finished where it was, and the counters below never settle
    moved = threading.Event()
    http = _start_agent(tiny, fault_plan=_slow(), prefix_cache_mb=4)
    _hold_after(http.agent.server, 6, moved)
    gw = Gateway([_hold_after(_mk(tiny, fault_plan=_slow()), 6, moved),
                  _stub(http.address)]).start()
    try:
        t = gw.submit(GenRequest(list(prompt), max_new_tokens=budget,
                                 id="wire"))
        _wait_emitted(t, 3)
        assert gw.remove_replica(t.replica, timeout=60)
        moved.set()
        res = t.result(timeout=120)
        assert list(res.tokens) == list(expect)
        assert gw.snapshot()["shed"] == {}

        def _settled():
            m = gw.snapshot()["engine"]["migrations"]
            return m["out"] >= 1 and m["in"] >= 1 \
                and m["pages_moved"] >= 1
        # remote counters ride the next heartbeat; don't race it
        _wait(_settled, msg="migration counters settled")
        mig = gw.snapshot()["engine"]["migrations"]
        assert mig["remote"] >= 1
    finally:
        moved.set()
        assert gw.drain(timeout=60)
        http.stop()


def test_sigkill_after_migration_falls_back_to_rerun(tiny):
    """The failure half of the one-shot payload contract: migrate a
    stream between two REMOTE replicas, then SIGKILL the adopter (as
    the network sees it). The payload was consumed at admit, so
    failover re-runs the request from its prompt on the survivor —
    greedy determinism makes even the re-run token-exact, and no
    client ever sees a 5xx."""
    prompt, budget = _prompt(9), 40
    expect = _control(tiny, prompt, budget)
    # 100 ms wedge (vs _slow's 30): the remote-to-remote migration
    # dance (probe, extract through a first-time XLA gather compile,
    # ship, adopt) costs 1-2 s on a starved 1-core host, and the
    # stream must still have tokens LEFT afterwards for the kill to
    # land on a live migrated session.
    agents = [_start_agent(tiny,
                           fault_plan=FaultPlan.wedge_at(1, 0.1,
                                                         times=-1))
              for _ in range(2)]
    # lease_misses=30 (3 s lease): the wire extract holds the agent's
    # dispatch lock through that same compile stall, which can outlive
    # the default 0.3 s lease — expiring the SOURCE mid-migration and
    # turning the test into a different (crash-path) scenario than the
    # one under test. The kill half only needs expiry to happen at
    # all, not fast.
    gw = Gateway([_stub(a.address, lease_misses=30) for a in agents],
                 stall_timeout_s=10.0, breaker_base_s=0.05,
                 breaker_max_s=0.25).start()
    try:
        t = gw.submit(GenRequest(list(prompt), max_new_tokens=budget,
                                 id="chaos"))
        _wait_emitted(t, 3)
        src = t.replica
        assert gw.migrate_session("chaos") is True
        _wait(lambda: t.replica is not None and t.replica != src,
              msg="stream adopted by the other replica")
        target = t.replica
        # let the adopter stream a few tokens so the kill lands on a
        # LIVE migrated session, then drop it off the network
        n_now = t._n_emitted
        _wait_emitted(t, n_now + 2)
        agents[target].kill()
        res = t.result(timeout=180)
        assert list(res.tokens) == list(expect)
        snap = gw.snapshot()
        assert snap["shed"] == {}  # zero 5xx
        assert snap["supervision"]["failovers"] >= 1
    finally:
        gw.drain(timeout=60)
        for a in agents:
            a.stop()


# ---------------------------------------------- rebalance + affinity


def test_migrate_session_rebalances_token_exact(tiny):
    """The operator-driven flavor: migrate_session moves a live
    stream with NO retirement — the source keeps serving — and an
    unknown request id reports False instead of raising."""
    model, params = tiny
    prompt, budget = _prompt(), 40
    expect = _control(tiny, prompt, budget, temperature=0.6, top_k=4,
                      seed=5)
    pool = PagePool(model, params, 128, 8, shared=True)
    gw = Gateway([_mk(tiny, page_pool=pool, fault_plan=_slow()),
                  _mk(tiny, page_pool=pool, fault_plan=_slow())]).start()
    try:
        t = gw.submit(GenRequest(list(prompt), max_new_tokens=budget,
                                 temperature=0.6, top_k=4, seed=5,
                                 id="reb"))
        _wait_emitted(t, 3)
        src = t.replica
        assert gw.migrate_session("reb") is True
        res = t.result(timeout=120)
        assert list(res.tokens) == list(expect)
        assert t.replica != src
        assert gw.migrate_session("nope") is False
        assert gw.snapshot()["shed"] == {}
    finally:
        assert gw.drain(timeout=60)
    assert pool.n_used == 0


def test_kill_between_freeze_and_ship_adopts_leased_snapshot(tiny):
    """The extract-vs-steal lease (this PR): the source replica dies
    WHILE the migrate extract is in flight — the old behavior
    abandoned the frozen snapshot and re-ran the victim from its
    prompt even when the freeze completed a moment later. With the
    lease, failover waits for the in-flight extract and ADOPTS the
    completed snapshot: the stream resumes token-exact with no
    recompute, and migrate_lease_adoptions proves the path taken."""
    import threading

    prompt, budget = _prompt(11), 40
    expect = _control(tiny, prompt, budget)
    srv0 = _mk(tiny, fault_plan=_slow())
    gw = Gateway([srv0, _mk(tiny, fault_plan=_slow())]).start()
    froze = threading.Event()   # the real extract finished
    release = threading.Event()  # let the wrapper return the snap
    real_extract = srv0.extract_session

    def held_extract(engine_id, wire=True):
        snap = real_extract(engine_id, wire=wire)
        froze.set()
        # the kill window: the snapshot exists but has not shipped —
        # the test fails the source here, then lets us return
        assert release.wait(20.0), "test release never arrived"
        return snap

    srv0.extract_session = held_extract
    try:
        t = gw.submit(GenRequest(list(prompt), max_new_tokens=budget,
                                 id="lease"))
        _wait_emitted(t, 3)
        r0 = gw.replicas[t.replica]
        epoch = r0.epoch
        mover = threading.Thread(
            target=lambda: gw.migrate_session("lease"), daemon=True)
        mover.start()
        assert froze.wait(30.0), "extract never froze the session"
        # SIGKILL-as-the-gateway-sees-it, mid-extract: the steal runs
        # on its own thread (like the watchdog) and its _failover
        # blocks inside the lease claim until the extract completes
        killer = threading.Thread(
            target=lambda: gw._fail_replica(
                r0, epoch, "test: source died mid-extract"),
            daemon=True)
        killer.start()
        _wait(lambda: not gw._snap_leases, msg="failover claimed the "
                                               "in-flight lease")
        release.set()
        mover.join(30.0)
        killer.join(30.0)
        res = t.result(timeout=120)
        assert list(res.tokens) == list(expect)
        snap = gw.snapshot()
        assert snap["shed"] == {}  # zero 5xx
        assert snap["routing"]["migrate_lease_adoptions"] == 1
        # adopted, not recomputed: the survivor resumed mid-stream
        # (its engine counted a migrate-in), and the whole fleet never
        # re-prefilled the prompt a second time
        assert snap["engine"]["migrations"]["in"] >= 1
    finally:
        srv0.extract_session = real_extract
        gw.drain(timeout=60)


def test_lease_expiry_falls_back_to_rerun(tiny):
    """The lease's other half: an extract that NEVER completes (agent
    truly dead) must not wedge failover — the claim times out after
    migrate_lease_s, the ticket re-runs from its prompt (token-exact
    by determinism), and the late snapshot is released by the
    abandoned flag, not leaked."""
    import threading

    prompt, budget = _prompt(12), 40
    expect = _control(tiny, prompt, budget)
    srv0 = _mk(tiny, fault_plan=_slow())
    gw = Gateway([srv0, _mk(tiny, fault_plan=_slow())]).start()
    gw.migrate_lease_s = 0.2  # keep the test fast
    froze = threading.Event()
    release = threading.Event()
    real_extract = srv0.extract_session

    def wedged_extract(engine_id, wire=True):
        snap = real_extract(engine_id, wire=wire)
        froze.set()
        release.wait(20.0)  # holds well past the 0.2 s lease
        return snap

    srv0.extract_session = wedged_extract
    try:
        t = gw.submit(GenRequest(list(prompt), max_new_tokens=budget,
                                 id="wedge"))
        _wait_emitted(t, 3)
        r0 = gw.replicas[t.replica]
        epoch = r0.epoch
        mover = threading.Thread(
            target=lambda: gw.migrate_session("wedge"), daemon=True)
        mover.start()
        assert froze.wait(30.0), "extract never froze the session"
        gw._fail_replica(r0, epoch, "test: extract wedged")  # blocks
        # ~migrate_lease_s, then gives up and requeues crash-path
        release.set()  # the late snapshot arrives AFTER abandonment
        mover.join(30.0)
        res = t.result(timeout=120)
        assert list(res.tokens) == list(expect)
        snap = gw.snapshot()
        assert snap["shed"] == {}
        assert snap["routing"]["migrate_lease_adoptions"] == 0
        assert snap["supervision"]["failovers"] >= 1
        assert not gw._snap_leases  # nothing leaked on either path
    finally:
        srv0.extract_session = real_extract
        gw.drain(timeout=60)


# ------------------------------------------- prefix-delta migration


@pytest.fixture(scope="module", params=[False, True],
                ids=["f32kv", "int8kv"])
def kvmodel(request):
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32,
                            attention_backend="reference",
                            kv_cache_quant=request.param)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _freeze_wire(srv, prompt, budget, rid="src", min_gen=4):
    """Run ``prompt`` on ``srv`` until at least ``min_gen`` tokens are
    live, then freeze + evict it as a WIRE snapshot (page content, not
    ids) — the in-process stand-in for a source replica mid-stream."""
    srv.submit(Request(list(prompt), budget, id=rid))
    for _ in range(600):
        srv.step()
        lv = next((l for l in srv._live
                   if l is not None and l.request.id == rid), None)
        if lv is not None and len(lv.generated) >= min_gen:
            break
    else:
        raise AssertionError("source stream never reached min_gen")
    snap = srv.extract_session(rid, wire=True)
    assert snap is not None
    return snap


def _warm(srv, tokens):
    """Put ``tokens``' KV into ``srv``'s prefix store (run + donate)."""
    srv.submit(Request(list(tokens), 1, id=f"warm{len(tokens)}"))
    list(srv.run())


def _wire_pages(payload):
    for d in payload["leaves"]:
        ax = d.get("page_axis")
        if ax is not None:
            return int(d["shape"][int(ax)])
    return 0


@pytest.mark.parametrize("scenario",
                         ["exact", "partial", "nomatch", "stale"])
def test_delta_migration_matrix(kvmodel, scenario):
    """The delta-trim contract cell by cell, f32 and int8-KV pages:

    - exact:   target store covers the whole context -> only the
               final (always-shipped) page crosses; the adopter
               refcount-shares its own store pages for the prefix.
    - partial: target covers a shorter prefix -> exactly the
               uncovered suffix ships.
    - nomatch: cold target summary -> the trim declines (None) and
               the full payload ships, delta counters untouched.
    - stale:   the summary CLAIMS coverage the target no longer has
               -> submit refuses with StaleDelta (no pin leaked) and
               the full-payload re-ship lands token-exact.

    Every cell's resumed stream is byte-identical to the no-migration
    control, and ``migrate_bytes_wire`` counts exactly the shipped
    pages."""
    from tony_tpu.serve.migrate import StaleDelta, delta_trim_doc, \
        snapshot_to_doc
    from tony_tpu.serve.prefix import summary_match_len
    from tony_tpu.serve.tier import payload_nbytes

    prompt, budget = _prompt(11, 21), 12
    expect = _control(kvmodel, prompt, budget)
    src = _mk(kvmodel)
    snap = _freeze_wire(src, prompt, budget)
    doc = snapshot_to_doc(snap)
    ctx = [int(t) for t in snap.prompt] \
        + [int(t) for t in snap.generated][:-1]
    ps = src.slots.pool.page_size
    n = -(-int(doc["n_tokens"]) // ps)
    assert n >= 3  # the matrix needs room between exact and partial

    tgt = _mk(kvmodel, prefix_cache_mb=2.0)
    if scenario == "exact":
        _warm(tgt, ctx)
    elif scenario == "partial":
        _warm(tgt, ctx[:2 * ps])
    summary = tgt.prefix_summary()
    if scenario == "stale":
        # an honest summary from a DIFFERENT warm engine: it claims
        # coverage the actual target does not hold
        helper = _mk(kvmodel, prefix_cache_mb=2.0)
        _warm(helper, ctx)
        summary = helper.prefix_summary()
    trimmed = delta_trim_doc(doc, summary)

    if scenario == "nomatch":
        assert trimmed is None
        send = doc
    else:
        assert trimmed is not None
        covered = summary_match_len(summary, ctx)
        k = min(covered // ps, n - 1)
        assert trimmed["delta"]["prefix_tokens"] == k * ps
        assert _wire_pages(trimmed["pages"]) == n - k
        if scenario == "exact":
            assert k == n - 1          # only the tail page ships
        elif scenario == "partial":
            assert k == 2 and k < n - 1
        assert payload_nbytes(trimmed["pages"]) \
            < payload_nbytes(doc["pages"])
        send = trimmed

    if scenario == "stale":
        with pytest.raises(StaleDelta):
            tgt.submit(Request(list(prompt), budget, id="adopt",
                               migrate=send))
        assert not tgt._migrate_pins  # the refusal released its pin
        send = doc                    # the sender's contracted retry

    tgt.submit(Request(list(prompt), budget, id="adopt", migrate=send))
    res = {r.id: r for r in tgt.run()}["adopt"]
    assert list(res.tokens) == list(expect)
    nb = tgt.slots.pool.page_nbytes
    if scenario in ("exact", "partial"):
        assert tgt.migrate_delta_in == 1
        assert tgt.migrate_bytes_wire == (n - k) * nb
        assert tgt.migrate_bytes_avoided >= k * nb
    else:
        assert tgt.migrate_delta_in == 0
        assert tgt.migrate_bytes_wire == n * nb
    assert tgt.migrations_in == 1 and tgt.migrations_remote == 1
    assert not tgt._migrate_pins


def test_remote_delta_migration_ships_suffix_only(tiny):
    """The wire half of the tentpole: the gateway's RemoteServer stub
    trims the migrate doc against the target agent's heartbeat radix
    summary, so a migration into a warm remote ships only the
    uncovered suffix pages — token-exact, with the trim visible in the
    stub's ``migrate_delta_trims`` and the agent engine's
    ``delta_in``/``bytes_avoided`` counters riding the next
    heartbeat."""
    prompt, budget = _prompt(), 24
    expect = _control(tiny, prompt, budget)
    http = _start_agent(tiny, prefix_cache_mb=2.0, fault_plan=_slow())
    # heartbeats stay at 0.1 s (they ship the radix summary) but the
    # lease is 5 s, not the helper's 0.3 s: nothing here tests lease
    # expiry, and under a loaded multi-worker run a 0.3 s scheduling
    # stall expired it, broke the remote and left the migration no taker
    stub = _stub(http.address, lease_misses=50)
    # affinity off: it would route the live stream straight onto the
    # warm remote, and the point is to MIGRATE into it over the wire
    gw = Gateway([_mk(tiny, fault_plan=_slow()), stub],
                 prefix_affinity=False).start()
    try:
        # warm the REMOTE with the stream's eventual full context
        # (greedy determinism makes it knowable in advance), then let
        # a heartbeat ship the summary that proves it
        gw.replicas[0].outstanding = 500
        gw.submit(GenRequest(list(prompt) + list(expect), 1,
                             id="warm")).result(timeout=300)
        gw.replicas[0].outstanding = 0
        _wait(lambda: stub.prefix_match_len(list(prompt)) >= 8,
              msg="heartbeat shipped the radix summary")
        # pin the live stream on the LOCAL replica
        gw.replicas[1].outstanding = 500
        t = gw.submit(GenRequest(list(prompt), max_new_tokens=budget,
                                 id="d"))
        _wait_emitted(t, 3)
        gw.replicas[1].outstanding = 0
        assert gw.migrate_session("d") is True
        res = t.result(timeout=120)
        assert list(res.tokens) == list(expect)
        assert gw.snapshot()["shed"] == {}
        assert stub.migrate_delta_trims >= 1
        assert stub.migrate_delta_fallbacks == 0

        def _settled():
            m = gw.snapshot()["engine"]["migrations"]
            return m["delta_in"] >= 1 and m["bytes_wire"] > 0
        _wait(_settled, msg="delta counters settled")
        m = gw.snapshot()["engine"]["migrations"]
        assert m["bytes_avoided"] > 0  # the prefix never crossed
    finally:
        gw.drain(timeout=60)
        http.stop()


def test_remote_delta_stale_summary_falls_back_full(tiny):
    """The fallback half: a stale summary makes the adopter refuse
    with kind=StaleDelta and the stub re-ships the FULL payload
    exactly once — the stream stays token-exact, and the episode is
    visible as one trim + one fallback."""
    from tony_tpu.gateway.remote import RemoteServer

    class _ForcedSummary(RemoteServer):
        # heartbeats cannot clear the forced summary: the staleness
        # window stays open for as long as the test needs it
        @property
        def _prefix_summary(self):
            return getattr(self, "_forced", [])

        @_prefix_summary.setter
        def _prefix_summary(self, value):
            pass

    prompt, budget = _prompt(9), 24
    expect = _control(tiny, prompt, budget)
    # the agent's store is ENABLED but cold; the forced summary is an
    # honest one from a different warm engine
    helper = _mk(tiny, prefix_cache_mb=2.0)
    _warm(helper, list(prompt) + list(expect))
    http = _start_agent(tiny, prefix_cache_mb=2.0, fault_plan=_slow())
    stub = _ForcedSummary(http.address, heartbeat_interval_s=0.1,
                          lease_misses=10, boot_timeout_s=20.0)
    gw = Gateway([_mk(tiny, fault_plan=_slow()), stub],
                 prefix_affinity=False).start()
    try:
        stub._forced = helper.prefix_summary()
        gw.replicas[1].outstanding = 500
        t = gw.submit(GenRequest(list(prompt), max_new_tokens=budget,
                                 id="d"))
        _wait_emitted(t, 3)
        gw.replicas[1].outstanding = 0
        assert gw.migrate_session("d") is True
        res = t.result(timeout=120)
        assert list(res.tokens) == list(expect)
        assert gw.snapshot()["shed"] == {}  # the fallback is silent
        assert stub.migrate_delta_trims == 1
        assert stub.migrate_delta_fallbacks == 1
    finally:
        gw.drain(timeout=60)
        http.stop()


def test_remote_prefix_affinity_via_heartbeat_summary(tiny):
    """Satellite: a REMOTE replica's warmth is visible to the
    prefix-affinity router through the bounded radix summary its
    agent ships on every heartbeat — the warm remote wins the probe
    over a cold local even when least-outstanding points the other
    way."""
    base = list(range(1, 21))
    http = _start_agent(tiny, prefix_cache_mb=2.0)
    stub = _stub(http.address)
    gw = Gateway([stub, _mk(tiny, prefix_cache_mb=2.0)],
                 prefix_affinity=True).start()
    try:
        # pin the warm-up on the remote, then let a heartbeat ship
        # the summary that proves it holds the prefix
        gw.replicas[1].outstanding = 500
        gw.submit(GenRequest(list(base), 4,
                             id="warm")).result(timeout=300)
        gw.replicas[1].outstanding = 0
        _wait(lambda: stub.prefix_match_len(base) >= len(base),
              msg="heartbeat shipped the radix summary")
        # skew load so least-outstanding prefers the cold local
        gw.replicas[0].outstanding = 500
        t = gw.submit(GenRequest(list(base) + [7, 8], 4, id="probe"))
        t.result(timeout=300)
        assert t.metrics["replica"] == 0
        assert gw.snapshot()["routing"]["prefix_routed"] >= 1
    finally:
        gw.replicas[0].outstanding = 0
        gw.drain(timeout=60)
        http.stop()
