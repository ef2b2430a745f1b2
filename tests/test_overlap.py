"""Two chunk rounds in the device's queue (``serve/engine.Server._decode_round``).

The engine enqueues round n+1 before it has read round n's tokens, so
its host mirrors lag the device by the rounds in flight. Held here:
(a) streams under that order — requests admitted and finishing while
rounds are in flight, greedy and sampled — are the unbatched ones;
(b) a round is walked over its RIDERS, so a slot re-admitted while a
round is in flight gets nothing from it; (c) every caller that reads
the mirrors as the truth first settles them (``extract_session`` and
the adoption, ``drain()``, ``reset()``, a failed dispatch, a verify
round), at depth 1 with one round in flight and at depths 4 and 8
with two; (d) a finish by length costs no round more than the serial
order took; and the records: a round left without riders is dropped
and counted, and no record of the engine overlaps another although a
prefill queues behind a round. The device's half of the contract (a
row that finished in the round before starts the next one frozen) is
in ``test_serve.py``. CPU-only, tiny model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import Transformer, TransformerConfig, generate
from tony_tpu.serve import Request, Server
from tony_tpu.serve.faults import FaultPlan, InjectedFault


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


def _solo(model, params, prompt, n, eos=-1):
    """The unbatched greedy stream, cut at the stop token inclusive."""
    toks = np.asarray(generate(
        model, params, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=n, eos_id=eos))[0].tolist()
    return toks[:toks.index(eos) + 1] if eos in toks else toks


def _serial(model, params, req, **kw):
    """``req`` alone in the SERIAL order, the chunk round's halves
    driven by hand: each round's tokens are read before the next round
    is enqueued. The reference for a sampled stream (a request's draws
    are its own, whoever it is batched with), and for the dispatches a
    lone request takes."""
    srv = Server(model, params, batch_size=1, **kw)
    done: list = []
    with srv.phases.phase("admit.host"):
        assert srv._admit_one(req, done)
    while not done:
        with srv.phases.phase("decode.prepare"):
            srv._enqueue_round(*srv._plan_round())
        srv._arrive(done)
    assert srv.rounds_overlapped == 0 and not srv._inflight
    return srv, done[0]


def _prompt(i, n=5):
    return [(7 * i + 3 * j) % 63 + 1 for j in range(n)]


@pytest.fixture(scope="module")
def stop_probe(tiny):
    """(prompt, greedy tokens, stop id, its index): a prompt whose
    greedy stream first emits some token mid-sequence, so that as a
    stop token it strikes after real decoding (the tiny model mostly
    repeats itself: search seeded prompts)."""
    model, params = tiny
    rng = np.random.default_rng(0)
    for _ in range(64):
        prompt = rng.integers(1, 64, size=6).tolist()
        solo = _solo(model, params, prompt, 8)
        hit = next(((t, i) for i, t in enumerate(solo)
                    if i > 1 and t not in solo[:i]), None)
        if hit is not None:
            return prompt, solo, hit[0], hit[1]
    pytest.fail("no seeded prompt emits a new token mid-sequence")


# ------------------------------------------ (a) streams under the overlap


@pytest.mark.parametrize("chunk_steps", [1, 4])
@pytest.mark.parametrize("paged", [True, False])
def test_streams_under_the_overlap_are_the_unbatched_ones(
        tiny, stop_probe, paged, chunk_steps):
    """Seven requests through two slots, so that every admission but
    the first two and every finish happens with a round in flight:
    greedy ones (one stopping on a stop token mid-stream) equal
    ``generate()``'s unbatched output, sampled ones equal themselves
    served alone in the serial order."""
    model, params = tiny
    stopper, _, eos, _ = stop_probe
    kw = dict(eos_id=eos, min_bucket=8, chunk_steps=chunk_steps,
              paged=paged)
    reqs = [Request(_prompt(0), 9, id="g0"),
            Request(_prompt(1, 3), 6, id="s1", temperature=0.9, top_k=8,
                    seed=11),
            Request(stopper, 8, id="stop"),
            Request(_prompt(3, 7), 2, id="g3"),
            Request(_prompt(4), 12, id="s4", temperature=0.7, seed=5),
            Request(_prompt(5, 4), 5, id="g5"),
            Request(_prompt(6), 7, id="s6", temperature=1.1, top_k=4,
                    seed=2)]
    srv = Server(model, params, batch_size=2, **kw)
    got = {r.id: r for r in srv.run(reqs)}
    assert len(got) == len(reqs)
    for req in reqs:
        if req.temperature == 0.0:
            want = _solo(model, params, list(req.prompt),
                         req.max_new_tokens, eos)
        else:
            want = _serial(model, params, req, **kw)[1].tokens
        assert got[req.id].tokens == want, req.id
    assert got["stop"].finish_reason == "eos"
    c = srv.counters()
    assert 0 < c["decode_rounds_overlapped"] < c["decode_rounds"]
    assert c["decode_settles"] == 0
    assert c["freeze_faults"] == 0 and c["kv_tree_kept"] == 0
    assert not srv._inflight and srv.done
    if paged:
        assert srv.slots.pool.n_used == 0  # every page came back


def test_overlap_counters_reach_stats_engine(tiny):
    from tony_tpu.gateway import Gateway, GenRequest

    model, params = tiny
    gw = Gateway([Server(model, params, batch_size=2, min_bucket=8,
                         chunk_steps=1)]).start()
    try:
        tok = gw.submit(GenRequest(_prompt(0), max_new_tokens=8)).result(
            timeout=120).tokens
        eng = gw.snapshot()["engine"]
    finally:
        assert gw.drain(timeout=60)
    assert tok == _solo(model, params, _prompt(0), 8)
    # 7 rounds of one step: all but the first were enqueued behind one
    assert eng["decode_rounds"] == 7
    assert eng["decode_rounds_overlapped"] == 6
    assert eng["decode_settles"] == 0


# ------------------------------------- (b) the riders, not _live, are walked


def test_readmitted_slot_gets_nothing_from_the_round_in_flight(
        tiny, stop_probe):
    """A finishes on a stop token the host could not see coming, so
    the round already queued carries its row (frozen by the device).
    B takes the slot while that round is in flight: its arrival walks
    the riders it was enqueued with, and B — whose row the device held
    frozen under A's last token — receives nothing from it."""
    model, params = tiny
    stopper, solo, eos, idx = stop_probe
    srv = Server(model, params, batch_size=2, eos_id=eos, min_bucket=8,
                 chunk_steps=1)
    co, b = _prompt(8), _prompt(9)
    assert eos not in _solo(model, params, co, 20)
    srv.submit(Request(stopper, 8, id="A"))
    srv.submit(Request(co, 20, id="co"))
    done = []
    for _ in range(idx):    # one step a token after admission's
        done += srv.step()
    assert [(r.id, r.tokens, r.finish_reason) for r in done] \
        == [("A", solo[:idx + 1], "eos")]
    # the round queued behind the finish: A rode it, frozen, and rides
    # it no longer; the co-tenant keeps it alive
    (queued,) = srv._inflight
    assert list(queued.riders) == [1] and srv.frozen_steps == 1
    srv.submit(Request(b, 6, id="B"))
    assert srv.step() == []
    live_b = srv._live[0]
    assert live_b.request.id == "B" and len(live_b.generated) == 1
    assert len(srv._live[1].generated) == idx + 2   # the round did land
    got = {r.id: r.tokens for r in srv.run()}
    assert got == {"B": _solo(model, params, b, 6),
                   "co": _solo(model, params, co, 20)}
    assert srv.freeze_faults == 0 and srv.frozen_steps == 1


def test_a_round_left_without_riders_is_dropped_and_counted(
        tiny, stop_probe):
    """A lone stream ends on a stop token with the next round already
    queued: nobody rides it any more, so it is dropped unread. The
    device runs it frozen (one step of waste, counted) and it leaves
    no timeline record, only ``decode_rounds_dropped``."""
    model, params = tiny
    stopper, solo, eos, idx = stop_probe
    srv = Server(model, params, batch_size=1, eos_id=eos, min_bucket=8,
                 chunk_steps=1)
    (got,) = srv.run([Request(stopper, 8, id="A")])
    assert (got.tokens, got.finish_reason) == (solo[:idx + 1], "eos")
    c = srv.counters()
    assert c["decode_rounds_dropped"] == 1 == c["frozen_steps"]
    assert c["decode_rounds"] == idx + 1
    assert srv.timeline.summary()["decode"]["count"] == idx
    assert not srv._inflight and srv.done


# --------------------------------------------------- (c) the settle points


def _two_live(tiny, **kw):
    """An engine two steps into two long greedy streams: one round
    read, one in flight."""
    model, params = tiny
    kw.setdefault("chunk_steps", 1)
    srv = Server(model, params, batch_size=2, min_bucket=8, **kw)
    srv.submit(Request(_prompt(0), 12, id="a"))
    srv.submit(Request(_prompt(1), 12, id="b"))
    srv.step()
    srv.step()
    assert len(srv._inflight) == 1 and srv.rounds_overlapped == 2
    return srv


def test_extraction_and_adoption_settle(tiny):
    """``extract_session`` cuts the snapshot from mirrors it has first
    caught up with the device, and the engine that adopts the session
    settles its own round in flight; the moved stream is the unbatched
    one. A co-tenant the settle finishes is handed out by the next
    ``step()``, and the engine counts as busy until then."""
    model, params = tiny
    src = _two_live(tiny)
    seen = len(src._live[0].generated)
    snap = src.extract_session("a", wire=True)
    assert not src._inflight and src.settles == 1
    assert len(snap.generated) == seen + 1     # the round in flight
    assert snap.n_tokens == len(snap.prompt) + len(snap.generated) - 1
    tgt = Server(model, params, batch_size=2, min_bucket=8, chunk_steps=1)
    tgt.submit(Request(_prompt(1), 12, id="own"))
    tgt.step()
    assert len(tgt._inflight) == 1
    tgt.submit(Request(_prompt(0), 12, id="moved", migrate=snap))
    tgt.step()
    assert tgt.settles == 1 and tgt.migrations_in == 1
    got = {r.id: r.tokens for r in tgt.run()}
    assert got["moved"] == _solo(model, params, _prompt(0), 12)
    # a session that finishes under the settle is not extracted, and
    # its result waits for the next step
    short = Server(model, params, batch_size=2, min_bucket=8,
                   chunk_steps=1)
    short.submit(Request(_prompt(2), 3, id="short"))
    short.submit(Request(_prompt(3), 12, id="long"))
    short.step()
    assert short._inflight and len(short._live[0].generated) == 2
    assert short.extract_session("short", wire=True) is None
    assert short._live[0] is None and short.n_active == 2
    assert [(r.id, r.tokens) for r in short.step()] \
        == [("short", _solo(model, params, _prompt(2), 3))]


def _enqueue_another(srv) -> None:
    """A second round into the device's queue, as ``_decode_round``
    enqueues it: the engine as a caller on another thread meets it
    mid-step, two rounds in flight and none of them read."""
    with srv.phases.phase("decode.prepare"):
        srv._enqueue_round(*srv._plan_round())
    assert len(srv._inflight) == 2


@pytest.mark.parametrize("depth", [4, 8])
def test_extraction_and_adoption_under_two_deep_rounds(tiny, depth):
    """The same at the depths a deployment chunks to, with TWO rounds
    in flight on either side: the snapshot holds both rounds' tokens,
    the adopting engine settles its own two before it takes the
    session in, and the moved stream is the unbatched one. A session
    whose whole remaining budget is in those rounds finishes under the
    settle: it is not moved, and its result is held for the next
    ``step()``."""
    model, params = tiny
    kw = dict(batch_size=2, min_bucket=8, chunk_steps=depth)
    n = 27      # 5-token prompts in a 32-token model
    src = Server(model, params, **kw)
    src.submit(Request(_prompt(0), n, id="a"))
    src.submit(Request(_prompt(1), 1 + 2 * depth, id="short"))
    src.step()      # first tokens, two rounds enqueued, one read
    _enqueue_another(src)
    assert [len(live.generated) for live in src._live] == [1 + depth] * 2
    # "short" rides only the older of the two: the younger would find
    # its budget spent
    assert [list(r.riders) for r in src._inflight] == [[0, 1], [0]]
    assert src.extract_session("short", wire=True) is None
    assert src.settles == 1 and not src._inflight
    snap = src.extract_session("a", wire=True)
    assert len(snap.generated) == 1 + 3 * depth and src.settles == 1
    assert [(r.id, r.tokens) for r in src.step()] == [
        ("short", _solo(model, params, _prompt(1), 1 + 2 * depth))]
    tgt = Server(model, params, **kw)
    tgt.submit(Request(_prompt(2), n, id="own"))
    tgt.step()
    _enqueue_another(tgt)
    tgt.submit(Request(_prompt(0), n, id="moved", migrate=snap))
    done = tgt.step()   # (at depth 8 both streams end in this step)
    assert tgt.settles == 1 and tgt.migrations_in == 1
    got = {r.id: r.tokens for r in done + list(tgt.run())}
    assert got == {"moved": _solo(model, params, _prompt(0), n),
                   "own": _solo(model, params, _prompt(2), n)}
    for srv in (src, tgt):
        assert srv.freeze_faults == 0 and srv.rounds_dropped == 0
        assert srv.slots.pool.n_used == 0


def test_drain_and_reset_leave_nothing_in_flight(tiny):
    model, params = tiny
    srv = _two_live(tiny)
    got = {r.id: r.tokens for r in srv.drain()}
    assert got == {"a": _solo(model, params, _prompt(0), 12),
                   "b": _solo(model, params, _prompt(1), 12)}
    assert not srv._inflight and srv.done
    srv = _two_live(tiny)
    srv.reset()     # drops the handle without reading it
    assert not srv._inflight and srv.done and srv.settles == 0
    assert srv.counters()["decode_rounds_dropped"] == 1
    # the device still holds the dropped streams' rows, live: the next
    # rounds' patches empty or re-arm them
    got = {r.id: r.tokens for r in srv.run(
        [Request(_prompt(4), 9, id="c"), Request(_prompt(5), 4, id="d")])}
    assert got == {"c": _solo(model, params, _prompt(4), 9),
                   "d": _solo(model, params, _prompt(5), 4)}


@pytest.mark.parametrize("recover", ["reset", "go_on"])
def test_failed_dispatch_with_a_round_in_flight(tiny, recover):
    """A fault plan fails the third ``step()`` while a round is in
    flight. A caller that resets (the gateway's recovery) drops it
    unread and serves on; one that steps on loses nothing: the handle
    is still the oldest round and its tokens are read next."""
    model, params = tiny
    srv = Server(model, params, batch_size=2, min_bucket=8,
                 chunk_steps=1, fault_plan=FaultPlan.fail_at(3))
    srv.submit(Request(_prompt(0), 12, id="a"))
    srv.step()
    srv.step()
    with pytest.raises(InjectedFault):
        srv.step()
    assert len(srv._inflight) == 1
    if recover == "reset":
        srv.reset()
        assert not srv._inflight
        srv.submit(Request(_prompt(0), 12, id="a"))
    got = {r.id: r.tokens for r in srv.run()}
    assert got == {"a": _solo(model, params, _prompt(0), 12)}
    assert srv.settles == 0 and srv.freeze_faults == 0


def test_a_speculating_engine_runs_serial(tiny):
    """A verify round is fed from the host's mirrors, so an engine
    that speculates keeps one round in flight at most — and one on
    which speculation is switched on mid-stream settles first."""
    model, params = tiny
    reqs = [Request(_prompt(i), 6 + i, id=i) for i in range(4)]
    srv = Server(model, params, batch_size=2, min_bucket=8,
                 chunk_steps=2, speculate_k=2)
    got = {r.id: r.tokens for r in srv.run(reqs)}
    assert got == {i: _solo(model, params, _prompt(i), 6 + i)
                   for i in range(4)}
    c = srv.counters()
    assert c["decode_rounds_overlapped"] == 0 == c["decode_settles"]
    srv = _two_live(tiny)
    srv.speculate_k = 2     # what serve/autotune.py may do
    got = {r.id: r.tokens for r in srv.run()}
    assert got == {"a": _solo(model, params, _prompt(0), 12),
                   "b": _solo(model, params, _prompt(1), 12)}
    assert srv.settles == 1 and srv.rounds_overlapped == 2


# ------------------------------- (d) a finish by length costs no round more


@pytest.mark.parametrize("chunk_steps", [1, 4])
@pytest.mark.parametrize("n_tokens", [2, 5, 10])
def test_a_lone_request_takes_the_serial_orders_dispatches(
        tiny, n_tokens, chunk_steps):
    """The host sees a finish by length a round ahead and enqueues no
    round in which nothing could move: depth by depth the rounds are
    the serial order's."""
    model, params = tiny
    kw = dict(min_bucket=8, chunk_steps=chunk_steps)
    ref, res = _serial(model, params,
                       Request(_prompt(0), n_tokens, id=0), **kw)
    srv = Server(model, params, batch_size=1, **kw)
    (got,) = srv.run([Request(_prompt(0), n_tokens, id=0)])
    assert got.tokens == res.tokens == _solo(model, params, _prompt(0),
                                             n_tokens)
    assert (srv.dispatches, srv.steps, srv.frozen_steps) \
        == (ref.dispatches, ref.steps, ref.frozen_steps)
    assert srv.rounds_overlapped == max(0, srv.dispatches - 1)
    assert not srv._inflight


# ------------------------------------------ the records under the overlap


def test_records_bill_each_wall_once_and_lead_to_their_rounds(tiny):
    """A prefill admitted behind a round in flight waits that round
    out: the round's record closes where the prefill's wait saw it
    done and the prefill's record starts there, so no record of the
    engine overlaps another and the round keeps its own wall. Every
    decode record carries its round's ordinal (the ``round=`` of both
    halves' spans)."""
    model, params = tiny
    srv = Server(model, params, batch_size=2, min_bucket=8,
                 chunk_steps=1)
    srv.submit(Request(_prompt(0), 12, id="a"))
    srv.step()
    srv.step()
    (queued,) = srv._inflight
    srv.submit(Request(_prompt(1), 6, id="b"))
    srv.step()      # b's prefill runs behind ``queued``
    assert queued.done_at > 0.0
    list(srv.run())
    recs = sorted(srv.timeline.since(0), key=lambda r: r.seq)
    decode = [r for r in recs if r.kind == "decode"]
    assert [r.tags["round"] for r in decode] \
        == list(range(1, srv.dispatches + 1))
    (behind,) = [r for r in decode if r.tags["round"] == queued.rid]
    (admit_b,) = [r for r in recs if r.request_id == "b"]
    assert behind.seq == admit_b.seq + 1   # recorded at its arrival
    assert abs(behind.t0 + behind.dur_ms / 1e3 - queued.done_at) < 1e-6
    assert admit_b.t0 >= queued.done_at
    spans = sorted((r.t0, r.t0 + r.dur_ms / 1e3) for r in recs)
    for (_, end), (start, _) in zip(spans, spans[1:]):
        assert start >= end - 1e-6
