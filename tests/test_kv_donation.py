"""The KV tree is donated through the engine's read-dispatch-reassign
chain (serve/slots.SlotCache.cache): every program that takes the tree
and returns its successor consumes it, so a write lands in place and
not in a second copy of the pool.

What this file pins, on the CPU (where jax honours donation too, so a
stale reference raises "Array has been deleted" instead of reading old
values): each writer consumes the tree it was given and ``kv_tree.kept``
stays 0; every cache leaf of the lowered programs aliases its output,
with no "donated buffers were not usable" warning; readers on a shared
pool (extract, spill, handoff gather) survive co-located engines'
donating dispatches; ``reset()`` replaces a tree that a failed dispatch
took, and leaves alone one that a host-side fault left alive. Token
parity with the undonated engine is the existing parity matrix's job
(tests/test_paged.py, test_serve.py, test_tier.py): it passes unchanged.
"""

import sys
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import Transformer, TransformerConfig, generate
from tony_tpu.serve import PagePool, Request, Server
from tony_tpu.serve import engine as E
from tony_tpu.serve.faults import FaultPlan, InjectedFault
from tony_tpu.serve.migrate import gather_local
from tony_tpu.serve.slots import STATE_COLS, paged_cache, tree_consumed


def _model(scan_layers=False, kv_int8=False):
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32, scan_layers=scan_layers,
                            kv_cache_quant=kv_int8,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def tiny():
    return _model()


def _solo(model, params, prompt, n):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n)
    return np.asarray(out)[0].tolist()


def _prompts(n, length=24, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 64, size=length).tolist() for _ in range(n)]


def _step_watching_tree(srv) -> list:
    """``srv.step()``, asserting that whenever a writer ran the tree
    held before the step is gone — every leaf of it."""
    held = jax.tree_util.tree_leaves(srv.slots.cache)
    wrote = srv.slots.tree_donated + srv.slots.tree_kept
    out = srv.step()
    if srv.slots.tree_donated + srv.slots.tree_kept > wrote:
        assert all(leaf.is_deleted() for leaf in held)
    return out


# repetitive prompts: the n-gram drafter proposes, so verify rounds run
_REPEAT = [[5, 6, 7, 8] * 5, [9, 10, 11] * 6]


@pytest.mark.parametrize("kwargs,prompts,kinds", [
    ({"paged": True}, _prompts(3), {"prefill", "decode"}),
    ({"paged": True, "prefill_chunk_tokens": 8, "min_bucket": 8},
     _prompts(2), {"prefill_chunk", "prefill", "decode"}),
    ({"paged": True, "speculate_k": 3}, _REPEAT, {"verify"}),
    ({"paged": True, "speculate_k": 3, "chunk_steps": 1},
     _REPEAT, {"verify"}),
    # a store squeezed to ~2 entries spills to the host tier; the
    # repeats page back in through _scatter_pages
    ({"paged": True, "kv_page_size": 8, "prefix_cache_mb": 0.025,
      "kv_host_mb": 8.0, "prefix_donate": False},
     (lambda d: d + d[:2])(_prompts(3, seed=2)),
     {"host_spill", "host_page_in", "cow_admit"}),
    ({"paged": False}, _prompts(3), {"prefill", "decode"}),
    ({"paged": False, "prefill_chunk_tokens": 8, "min_bucket": 8},
     _prompts(2), {"prefill_chunk", "prefill", "decode"}),
    # exact repeats: _hit_admit; finished slots: _read_slot donation
    ({"paged": False, "prefix_cache_mb": 1.0},
     (lambda d: d + d)(_prompts(2, seed=3)), {"hit_admit", "decode"}),
    ({"paged": False, "speculate_k": 3}, _REPEAT, {"verify"}),
], ids=["paged", "paged-chunked", "paged-verify", "paged-verify-depth1",
        "paged-page-in", "rows", "rows-chunked", "rows-prefix-hit",
        "rows-verify"])
def test_every_writer_consumes_the_tree(tiny, kwargs, prompts, kinds):
    model, params = tiny
    srv = Server(model, params, batch_size=2, **kwargs)
    got = {}
    for i, p in enumerate(prompts):  # serial: repeats find their entry
        srv.submit(Request(list(p), 6, id=i))
        while not srv.done:
            for res in _step_watching_tree(srv):
                got[res.id] = res.tokens
    for i, p in enumerate(prompts):
        assert got[i] == _solo(model, params, p, 6), i
    seen = set(srv.timeline.summary())
    assert kinds <= seen, (kinds, seen)
    c = srv.counters()
    assert c["kv_tree_kept"] == 0 and c["kv_tree_donated"] > 0, c
    assert not tree_consumed(srv.slots.cache)  # the live version lives


@pytest.mark.parametrize("scan_layers,kv_int8", [
    (False, False), (True, False), (False, True), (True, True)])
@pytest.mark.parametrize("program", ["decode", "prefill", "verify"])
def test_every_cache_leaf_aliases(program, scan_layers, kv_int8):
    """Lowered for the tiny model, each engine program marks EVERY
    cache leaf as aliasing an output, and compiling it raises no
    "Some donated buffers were not usable" warning."""
    model, params = _model(scan_layers, kv_int8)
    b, cols = 2, 2
    cache = paged_cache(model, params, 8, 16)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    table = i32(b, cols)
    if program == "decode":
        lowered = E._decode_chunk.lower(
            model, params, cache, i32(b, STATE_COLS),
            i32(b, 1 + STATE_COLS), table, n_steps=4, eos_ids=(2,))
    elif program == "verify":
        lowered = E._verify_chunk.lower(
            model, params, cache, i32(b, 3), i32(b, 3), i32(b),
            jnp.zeros(b), i32(b), jnp.zeros((b, 2), jnp.uint32), i32(b),
            table, window=3, n_steps=2, eos_ids=(2,))
    else:
        lowered = E._paged_prefill_admit.lower(
            model, params, cache, i32(1, 16), i32(1, 16), jnp.int32(5),
            table[:1], jnp.float32(0), jnp.int32(0),
            jnp.zeros(2, jnp.uint32))
    n_leaves = len(jax.tree_util.tree_leaves(cache))
    assert lowered.as_text().count("tf.aliasing_output") == n_leaves
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        lowered.compile()


def test_readers_on_a_shared_pool_survive_donating_neighbours(tiny):
    """Two engines step concurrently on one shared pool. Between its
    steps the first freezes sessions out (``extract_session``'s
    gather; an owner swap made portable by ``gather_local``) and its
    squeezed prefix store spills to the host tier, while the second
    keeps writing: every reader takes its reference and enqueues
    inside one tree-lock window, so none ever names a tree that the
    neighbour's dispatch consumed. The frozen sessions resume on the
    second engine, token-exact."""
    model, params = tiny
    pool = PagePool(model, params, n_pages=96, page_size=8, shared=True)
    a = Server(model, params, batch_size=2, page_pool=pool,
               chunk_steps=2, prefix_cache_mb=0.025, kv_host_mb=8.0)
    b = Server(model, params, batch_size=2, page_pool=pool,
               chunk_steps=2)
    prompts = _prompts(10, length=20, seed=7)
    n_new = 24
    want = {i: _solo(model, params, p, n_new)
            for i, p in enumerate(prompts)}
    for i, p in enumerate(prompts):
        (a if i % 2 == 0 else b).submit(Request(list(p), n_new, id=i))
    got: dict = {}
    errors: list = []
    moved: list = []
    stop = threading.Event()

    def freeze_one():
        """As a replica's driver does between dispatches: move the
        first live session that has not moved yet from A to B."""
        for live in a._live:
            if live is None or live.request.id in moved:
                continue
            rid = live.request.id
            snap = a.extract_session(rid, wire=len(moved) % 2 == 0)
            if snap.local:
                snap.pages = gather_local(pool, snap.pages)
                snap.local, snap.pool = False, None
            moved.append(rid)
            b.submit(Request(list(prompts[rid]), n_new, id=rid,
                             migrate=snap))
            return

    def drive(srv):
        try:
            while not stop.is_set():
                for res in srv.step():
                    got[res.id] = res.tokens
                if srv is a and srv.steps % 3 == 0:
                    freeze_one()
                if srv.done:
                    time.sleep(0.001)
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)
            stop.set()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=drive, args=(s,), daemon=True)
               for s in (a, b)]
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 120
        while len(got) < len(prompts) and not stop.is_set() \
                and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        stop.set()
        for t in threads:
            t.join(30)
        sys.setswitchinterval(old)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    assert moved, "no session was frozen mid-stream"
    assert got == want
    assert a.host_tier.flush(30)
    assert a.host_tier.stats()["spills"] >= 1
    for srv in (a, b):
        c = srv.counters()
        assert c["kv_tree_kept"] == 0 and c["kv_tree_donated"] > 0, c


def _consume(tree) -> None:
    """What a donating program that failed at run time leaves behind."""
    for leaf in jax.tree_util.tree_leaves(tree):
        leaf.delete()


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "rows"])
def test_reset_after_a_consumed_tree_serves_the_next_request(tiny, paged):
    model, params = tiny
    srv = Server(model, params, batch_size=2, paged=paged,
                 prefix_cache_mb=1.0)
    p0, p1 = _prompts(2, seed=11)
    assert [r.tokens for r in srv.run([Request(p0, 6)])] \
        == [_solo(model, params, p0, 6)]
    # a stream long enough to outlast the two rounds its first step
    # leaves in the device's queue: the next step() has a round to
    # enqueue, and meets the tree that is gone
    srv.submit(Request(p1, 24))
    srv.step()
    assert len(srv._inflight) == 1 and srv._plan_round() is not None
    stored = len(srv.prefix)
    assert stored > 0
    _consume(srv.slots.cache)
    with pytest.raises(RuntimeError, match="deleted"):
        srv.step()
    srv.reset()
    assert not tree_consumed(srv.slots.cache)
    # by-reference page entries pointed into the tree that is gone;
    # an unpaged entry is a row of its own and still holds its K/V
    assert len(srv.prefix) == (0 if paged else stored)
    if paged:
        pool = srv.slots.pool
        assert pool.n_used == 0 and pool.reserved == 0
    for p in (p1, p0):  # p0: a re-prefill (paged) or a prefix hit
        assert [r.tokens for r in srv.run([Request(p, 6)])] \
            == [_solo(model, params, p, 6)]
    assert srv.counters()["kv_tree_kept"] == 0


def test_a_host_side_fault_leaves_the_tree_alive(tiny):
    """``serve/faults.py`` raises on the HOST side of a dispatch (at the
    top of ``step()``, or before an admission's prefill): no donating
    program was enqueued, so ``reset()`` is pure host work — the same
    tree, and the prefix store's page entries with it."""
    model, params = tiny
    srv = Server(model, params, batch_size=2, prefix_cache_mb=1.0,
                 fault_plan=FaultPlan.fail_at(4))
    p0, p1 = _prompts(2, seed=12)
    srv.submit(Request(p0, 6))
    for _ in range(3):
        srv.step()
    srv.submit(Request(p1, 6))
    before = jax.tree_util.tree_leaves(srv.slots.cache)
    stored = len(srv.prefix)
    assert stored > 0
    with pytest.raises(InjectedFault):
        srv.step()
    srv.reset()
    after = jax.tree_util.tree_leaves(srv.slots.cache)
    assert all(x is y for x, y in zip(before, after))
    assert len(srv.prefix) == stored  # their pages are still real
    assert srv.slots.pool.tree_epoch == 0
    assert [r.tokens for r in srv.run([Request(p1, 6)])] \
        == [_solo(model, params, p1, 6)]


def test_a_lost_shared_tree_stops_the_neighbour_until_its_reset(tiny):
    """On a shared pool, the engine whose dispatch lost the tree
    allocates the next one; its neighbour's pages hold nothing now, so
    the neighbour refuses to step (its caller's recovery sheds the
    sessions) until its own ``reset()`` has caught up."""
    model, params = tiny
    pool = PagePool(model, params, n_pages=32, page_size=8, shared=True)
    a = Server(model, params, batch_size=2, page_pool=pool)
    b = Server(model, params, batch_size=2, page_pool=pool,
               prefix_cache_mb=1.0)
    pa, pb = _prompts(2, seed=13)
    a.submit(Request(pa, 24))   # outlasts the rounds in flight
    b.submit(Request(pb, 6))
    a.step()
    b.step()
    assert len(b.prefix) == 1
    _consume(pool.cache)
    with pytest.raises(RuntimeError, match="deleted"):
        a.step()
    a.reset()
    assert pool.tree_epoch == 1 and not tree_consumed(pool.cache)
    with pytest.raises(RuntimeError, match="tree was lost"):
        b.step()
    b.reset()
    assert len(b.prefix) == 0 and pool.n_used == 0
    for srv, p in ((a, pa), (b, pb)):
        assert [r.tokens for r in srv.run([Request(p, 6)])] \
            == [_solo(model, params, p, 6)]


def test_the_fork_program_is_compiled_before_the_first_request(tiny):
    """A paged engine with a prefix store compiles ``_copy_page`` at
    construction: the first admission that matches a stored prefix
    mid-page (one chance first-token match is enough) forks a page
    without compiling under live streams. A pool geometry no other
    test uses, so that the jit's cache cannot already hold it."""
    from tony_tpu.serve.slots import _copy_page

    model, params = tiny
    before = _copy_page._cache_size()
    srv = Server(model, params, batch_size=2, paged=True, kv_pages=23,
                 kv_page_size=4, prefix_cache_mb=1.0)
    warmed = _copy_page._cache_size()
    assert warmed == before + 1
    base = _prompts(1, length=10, seed=21)[0]
    for i, tail in enumerate(([7, 8, 9], [11, 12, 13])):
        assert [r.tokens for r in srv.run([Request(base + tail, 4, id=i)])] \
            == [_solo(model, params, base + tail, 4)]
    assert srv.slots.pool.forks >= 1      # the shared 10 tokens end mid-page
    assert _copy_page._cache_size() == warmed
    assert srv.counters()["kv_tree_kept"] == 0
