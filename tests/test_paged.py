"""Paged KV cache (serve/slots.PagePool + the paged engine paths).

Two layers of pinning: PagePool/SlotCache property tests (alloc/free
round-trips never leak, refcounts pin shared pages, reservations keep
the no-preemption invariant, a copy-on-write fork preserves the
parent page bit-for-bit) and the serving exactness anchor — paged
greedy outputs byte-identical to the unpaged fixed-shape path and to
solo ``generate()`` across the rope/learned x scan_layers x int8-KV
matrix, under prefix sharing, speculation, pool pressure, and
eviction. CPU-only; the paged attention gathers the same values to
the same logical positions as the unpaged buffer, so parity is exact,
not approximate.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import Transformer, TransformerConfig, generate
from tony_tpu.serve import PagePool, PoolExhausted, Request, Server


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


def _solo(model, params, prompt, n):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n)
    return np.asarray(out)[0].tolist()


# ------------------------------------------------------------ PagePool


def test_pool_alloc_free_roundtrip_never_leaks(tiny):
    """Randomized alloc/share/unref churn holds the conservation
    invariant (free + used == total, refcounts never negative) and
    returns every page once the last holder lets go."""
    model, params = tiny
    pool = PagePool(model, params, n_pages=7, page_size=8)
    rng = np.random.default_rng(0)
    held: list[int] = []
    for _ in range(200):
        op = rng.integers(0, 3)
        if op == 0 and pool.available() > 0:
            held.extend(pool.alloc(1))
        elif op == 1 and held:
            page = held[rng.integers(len(held))]
            pool.share([page])
            held.append(page)
        elif held:
            page = held.pop(rng.integers(len(held)))
            pool.unref([page])
        assert pool.n_free + pool.n_used == pool.n_pages
        assert (pool.refcount >= 0).all()
        # every held reference is to a live page
        for page in held:
            assert pool.refcount[page] > 0
    for page in held:
        pool.unref([page])
    assert pool.n_used == 0 and pool.n_free == pool.n_pages
    assert (pool.refcount == 0).all()
    assert pool.allocs == pool.frees


def test_pool_refcount_pins_shared_pages(tiny):
    model, params = tiny
    pool = PagePool(model, params, n_pages=4, page_size=8)
    (page,) = pool.alloc(1)
    pool.share([page])           # second holder
    assert pool.cow_shared() == 1
    pool.unref([page])           # first holder gone
    assert pool.n_used == 1      # still pinned
    assert pool.cow_shared() == 0
    pool.unref([page])
    assert pool.n_used == 0
    with pytest.raises(ValueError, match="free page"):
        pool.unref([page])
    with pytest.raises(ValueError, match="free page"):
        pool.share([page])


def test_pool_reservation_invariant(tiny):
    """free >= reserved always: a granted reservation can always be
    allocated (the no-preemption guarantee), over-asks are refused,
    and alloc past the reservation is an engine bug that raises."""
    model, params = tiny
    pool = PagePool(model, params, n_pages=4, page_size=8)
    assert pool.reserve(3)
    assert not pool.reserve(2)          # only 1 unreserved left
    assert pool.available() == 1
    got = pool.alloc(2, from_reservation=True)
    assert pool.reserved == 1 and len(got) == 2
    with pytest.raises(RuntimeError, match="reservation"):
        pool.alloc(2, from_reservation=True)
    with pytest.raises(RuntimeError, match="available"):
        pool.alloc(2)                   # 2 free, 1 reserved -> 1 available
    pool.cancel(1)
    assert pool.reserved == 0
    with pytest.raises(ValueError, match="cancel"):
        pool.cancel(1)
    pool.unref(got)
    assert pool.available() == 4


def test_pool_concurrent_churn_reconciles(tiny):
    """The two-lock allocator under real thread contention: several
    threads churn reserve/alloc/share/unref/cancel against ONE shared
    pool, and the ledger reconciles exactly — no page is ever issued
    to two owners (the final free list holds each page id exactly
    once), ``free >= reserved`` holds at every sampled instant, and
    once every thread drops its references the pool is empty with
    ``allocs == frees``."""
    model, params = tiny
    pool = PagePool(model, params, n_pages=32, page_size=8, shared=True)
    n_threads, iters = 6, 250
    barrier = threading.Barrier(n_threads)
    errors: list[BaseException] = []

    def churn(seed):
        rng = np.random.default_rng(seed)
        held: list[int] = []   # pages this thread holds one ref to
        barrier.wait()
        try:
            for _ in range(iters):
                op = int(rng.integers(0, 5))
                if op == 0:                      # reserve -> alloc
                    n = int(rng.integers(1, 3))
                    if pool.reserve(n):
                        got = pool.alloc(n, from_reservation=True)
                        # freshly allocated pages belong to this
                        # thread alone: refcount is exactly 1
                        assert all(pool.refcount[p] == 1 for p in got)
                        held.extend(got)
                elif op == 1:                    # reserve -> cancel
                    n = int(rng.integers(1, 3))
                    if pool.reserve(n):
                        pool.cancel(n)
                elif op == 2 and held:           # cow fork: extra ref
                    page = held[int(rng.integers(len(held)))]
                    pool.share([page])
                    held.append(page)
                elif held:                       # drop one ref
                    page = held.pop(int(rng.integers(len(held))))
                    pool.unref([page])
                st = pool.stats()                # one _mu snapshot
                assert st["free"] + st["used"] == st["total"]
                assert st["free"] >= st["reserved"] >= 0
        except BaseException as e:               # pragma: no cover
            errors.append(e)
        finally:
            for page in held:
                pool.unref([page])

    threads = [threading.Thread(target=churn, args=(i,), daemon=True)
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors
    assert pool.n_used == 0 and pool.reserved == 0
    assert (pool.refcount == 0).all()
    assert pool.allocs == pool.frees
    # a double-issued page would appear twice here (or be missing)
    assert sorted(pool._free) == list(range(pool.n_pages))


def test_cow_fork_preserves_parent(tiny):
    """seed_pages forking a mid-page boundary copies the page: the
    fresh page starts bit-identical, and writes to it never touch the
    shared parent (the copy-on-write contract prefix consumers rely
    on)."""
    from tony_tpu.serve import SlotCache, cache_batch_axis

    model, params = tiny
    pool = PagePool(model, params, n_pages=6, page_size=8)
    slots = SlotCache(model, params, 2, pool=pool)
    (parent,) = pool.alloc(1)

    def paged_leaves(cache):
        return [leaf for path, leaf
                in jax.tree_util.tree_flatten_with_path(cache)[0]
                if cache_batch_axis(path, leaf) is not None]

    # stamp recognizable content into the parent page (every pool leaf)
    slots.cache = jax.tree_util.tree_map_with_path(
        lambda p, l: l.at[parent].set(7.0)
        if cache_batch_axis(p, l) is not None else l, slots.cache)
    before = [np.asarray(leaf[parent]) for leaf in paged_leaves(slots.cache)]
    assert pool.reserve(3)
    forked = slots.seed_pages(0, [parent], seed_len=5, reserve=3)
    assert forked and pool.forks == 1
    fresh = int(slots.page_table[0, 0])
    assert fresh != parent
    for leaf, want in zip(paged_leaves(slots.cache), before):
        assert np.array_equal(np.asarray(leaf[fresh]), want)  # exact copy
    # mutate the fork; the parent must not move
    slots.cache = jax.tree_util.tree_map_with_path(
        lambda p, l: l.at[fresh].set(-1.0)
        if cache_batch_axis(p, l) is not None else l, slots.cache)
    for leaf, want in zip(paged_leaves(slots.cache), before):
        assert np.array_equal(np.asarray(leaf[parent]), want)
    # parent still pinned by its original holder only
    assert pool.refcount[parent] == 1


# ------------------------------------------------ serving exactness


@pytest.mark.parametrize("positional,scan_layers,kv_int8", [
    ("rope", False, False),
    ("rope", False, True),
    ("rope", True, False),
    ("rope", True, True),
    ("learned", False, False),
    ("learned", True, True),
])
def test_paged_unpaged_greedy_parity_matrix(positional, scan_layers,
                                            kv_int8):
    """The tentpole anchor, mirroring test_serve's slot-row matrix:
    paged and unpaged servers produce byte-identical outputs (greedy
    AND seeded sampling) across positional encoding x scan_layers x
    int8-KV — the paged gather feeds the same values at the same
    logical positions into the same reduction."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=32,
                            dtype=jnp.float32,
                            attention_backend="reference",
                            positional=positional,
                            norm="layer" if positional == "learned"
                            else "rms",
                            scan_layers=scan_layers,
                            kv_cache_quant=kv_int8)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    reqs = [Request([1, 2, 3], 6, id="a"),
            Request([17, 46, 10, 20, 62], 5, id="b"),
            Request([5, 9], 6, temperature=0.9, top_k=8, seed=3, id="c"),
            Request([7, 7, 2, 1], 4, id="d")]
    import copy

    out = {}
    for paged in (False, True):
        srv = Server(model, params, batch_size=2, eos_id=-1, min_bucket=8,
                     paged=paged, kv_page_size=8)
        out[paged] = {r.id: (r.tokens, r.finish_reason)
                      for r in srv.run(copy.deepcopy(reqs))}
    assert out[True] == out[False]


def test_paged_matches_solo_and_page_boundaries(tiny):
    """Sequences long enough to cross several page boundaries match
    solo generate() token for token (page extension mid-decode is
    invisible), and the pool drains back to empty."""
    model, params = tiny
    srv = Server(model, params, batch_size=2, eos_id=-1, min_bucket=8,
                 paged=True, kv_page_size=4)  # tiny pages: many crossings
    prompts = [[1, 2, 3], [17, 46, 10, 20, 62, 26, 3]]
    res = {r.id: r for r in srv.run(
        Request(p, max_new_tokens=12) for p in prompts)}
    for i, p in enumerate(prompts):
        assert res[i].tokens == _solo(model, params, p, 12), p
    assert srv.slots.pool.n_used == 0
    assert srv.slots.pool.reserved == 0


def test_exact_hit_is_cow_admit_not_prefill(tiny):
    """Satellite: a paged exact-prefix hit is its own dispatch kind.
    The second identical prompt must cost zero prefill dispatches and
    land as one ``cow_admit`` timeline record (bucket 0), so
    tokens_per_dispatch for prefill is not diluted by aliasing
    admits."""
    model, params = tiny
    srv = Server(model, params, batch_size=1, eos_id=-1, min_bucket=8,
                 paged=True, kv_page_size=8, prefix_cache_mb=4.0)
    p = [17, 46, 10, 20, 62, 26]
    first = {r.id: r for r in srv.run([Request(p, 4, id="one")])}
    prefills_after_first = srv.prefills
    second = {r.id: r for r in srv.run([Request(p, 4, id="two")])}
    assert second["two"].tokens == first["one"].tokens
    assert srv.prefills == prefills_after_first  # no new prefill
    kinds = srv.timeline.summary()
    assert kinds["cow_admit"]["count"] == 1
    assert kinds["prefill"]["count"] == prefills_after_first
    assert second["two"].prefix_hit_tokens == len(p)
    rec = [r for r in srv.timeline.recent() if r.kind == "cow_admit"][0]
    assert rec.bucket == 0 and rec.request_id == "two"


def test_partial_hit_unaligned_forks_and_matches(tiny):
    """A prompt extending a stored prefix whose boundary falls mid-page
    forks exactly one page (parent preserved for the store) and stays
    token-exact vs solo."""
    model, params = tiny
    srv = Server(model, params, batch_size=1, eos_id=-1, min_bucket=8,
                 paged=True, kv_page_size=8, prefix_cache_mb=4.0)
    base = [17, 46, 10, 20, 62]          # 5 tokens: mid-page boundary
    ext = base + [26, 3, 9]
    list(srv.run([Request(base, 4, id="seed")]))
    forks_before = srv.slots.pool.forks
    res = {r.id: r for r in srv.run([Request(ext, 5, id="ext")])}
    assert res["ext"].tokens == _solo(model, params, ext, 5)
    assert res["ext"].prefix_hit_tokens > 0
    assert srv.slots.pool.forks > forks_before
    assert srv.counters()["kv_cow_forks"] == srv.slots.pool.forks


def test_tight_pool_backpressure_serializes_without_loss(tiny):
    """A pool holding ~one request's worst case at a time: admissions
    queue behind the reservation gate (no preemption, no crash, no
    drop) and every output stays token-exact."""
    model, params = tiny
    srv = Server(model, params, batch_size=4, eos_id=-1, min_bucket=8,
                 paged=True, kv_page_size=8, kv_pages=4)
    prompts = [[1, 2, 3], [5, 9], [17, 46, 10, 20, 62, 26], [7, 7, 7, 2]]
    res = {r.id: r for r in srv.run(
        Request(p, max_new_tokens=6) for p in prompts)}
    assert len(res) == len(prompts)
    for i, p in enumerate(prompts):
        assert res[i].tokens == _solo(model, params, p, 6), p
    assert srv.slots.pool.n_used == 0


def test_pool_exhaustion_sheds_typed_not_crash(tiny):
    """A request bigger than the whole pool sheds with the typed
    PoolExhausted (-> 503 at the gateway), and the engine keeps
    serving admissible requests afterwards."""
    model, params = tiny
    srv = Server(model, params, batch_size=2, eos_id=-1, min_bucket=8,
                 paged=True, kv_page_size=8, kv_pages=2)
    with pytest.raises(PoolExhausted, match="KV pages"):
        srv.submit(Request([1] * 20, max_new_tokens=10))
    res = {r.id: r for r in srv.run([Request([1, 2, 3], 4, id="ok")])}
    assert res["ok"].tokens == _solo(model, params, [1, 2, 3], 4)


def test_pool_exhaustion_gateway_sheds_503(tiny):
    """The gateway maps PoolExhausted to a 503 shed — capacity, not a
    400 malformation — and counts it in /stats."""
    from tony_tpu.gateway import Gateway, GenRequest, Shed

    model, params = tiny
    srv = Server(model, params, batch_size=2, eos_id=-1, min_bucket=8,
                 paged=True, kv_page_size=8, kv_pages=2)
    gw = Gateway([srv]).start()
    try:
        with pytest.raises(Shed, match="KV pages") as exc:
            gw.submit(GenRequest([1] * 20, max_new_tokens=10,
                                 id="big")).result(timeout=60)
        assert exc.value.http_status == 503
        res = gw.submit(GenRequest([1, 2, 3], max_new_tokens=4,
                                   id="ok")).result(timeout=120)
        assert res.tokens == _solo(model, params, [1, 2, 3], 4)
        assert gw.snapshot()["shed"].get(503, 0) >= 1
    finally:
        assert gw.drain(timeout=60)


def test_store_squeeze_under_pool_pressure(tiny):
    """Prefix-store pages yield to admissions: with the pool sized so
    retained store entries would block the next request, admission
    evicts LRU store entries (freeing their pages) instead of
    stalling; outputs stay exact and the engine reports evictions."""
    model, params = tiny
    srv = Server(model, params, batch_size=1, eos_id=-1, min_bucket=8,
                 paged=True, kv_page_size=8, kv_pages=4,
                 prefix_cache_mb=4.0)
    prompts = [[i + 1, i + 2, i + 3, i + 4, i + 5] for i in range(5)]
    res = {r.id: r for r in srv.run(
        Request(p, max_new_tokens=6) for p in prompts)}
    for i, p in enumerate(prompts):
        assert res[i].tokens == _solo(model, params, p, 6), p
    assert srv.prefix.stats()["evictions"] > 0
    # the store keeps whatever still fits; pool accounting stays sane
    pool = srv.slots.pool
    assert pool.n_used + pool.n_free == pool.n_pages
    assert pool.reserved == 0


def test_donation_is_refcount_bump_pages_survive_evict(tiny):
    """EOS donation pins the slot's own pages into the store — after
    the slot is evicted the pages stay resident under the store's
    refcount (no read_slot_row dispatch, no copy), and the next turn
    seeds from them token-exactly."""
    model, params = tiny
    srv = Server(model, params, batch_size=1, eos_id=-1, min_bucket=8,
                 paged=True, kv_page_size=8, prefix_cache_mb=4.0)
    t1 = [11, 12, 13]
    r1 = {r.id: r for r in srv.run([Request(t1, 4, id="t1")])}
    pool = srv.slots.pool
    assert pool.n_used > 0          # store-held pages outlive the slot
    turn2 = t1 + r1["t1"].tokens[:-1] + [14]
    r2 = {r.id: r for r in srv.run([Request(turn2, 4, id="t2")])}
    assert r2["t2"].tokens == _solo(model, params, turn2, 4)
    assert r2["t2"].prefix_hit_tokens > 0


def test_paged_speculation_parity(tiny):
    """Speculative decoding over the paged cache: greedy outputs
    unchanged, drafts accepted, verify windows write through page
    tables."""
    model, params = tiny
    rep = [3, 4, 3, 4, 3, 4]
    import copy

    reqs = [Request(rep, 8, id="r"), Request([1, 2], 8, id="s")]
    out = {}
    for paged in (False, True):
        srv = Server(model, params, batch_size=2, eos_id=-1, min_bucket=8,
                     paged=paged, kv_page_size=8, speculate_k=4,
                     chunk_steps=1)
        out[paged] = ({r.id: r.tokens for r in srv.run(
            copy.deepcopy(reqs))}, srv.spec_accepted)
    assert out[True][0] == out[False][0]
    assert out[True][0]["r"] == _solo(model, params, rep, 8)
    assert out[True][1] > 0  # drafts actually flowed through verify


def test_paged_flash_decode_backend():
    """The pallas flash-decode kernel consumes the gathered paged
    buffers unchanged (contiguous [b, span] views) — parity vs the
    einsum path's solo generate."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=32,
                            dtype=jnp.float32,
                            attention_backend="reference",
                            decode_attention="flash")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    srv = Server(model, params, batch_size=2, eos_id=-1, min_bucket=8,
                 paged=True, kv_page_size=8)
    prompts = [[1, 2, 3], [17, 46, 10, 20, 62]]
    res = {r.id: r for r in srv.run(
        Request(p, max_new_tokens=6) for p in prompts)}
    for i, p in enumerate(prompts):
        assert res[i].tokens == _solo(model, params, p, 6), p


def test_paged_refuses_sliding_window_explicitly(tiny):
    """Same precedent as the prefix store: parity over sliding-window
    models is unpinned — explicit paged=True fails loud, the default
    downgrades to unpaged."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=32,
                            dtype=jnp.float32, sliding_window=8,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(NotImplementedError, match="sliding-window"):
        Server(model, params, batch_size=1, paged=True)
    srv = Server(model, params, batch_size=1)  # default: auto-downgrade
    assert not srv.paged


def test_kv_counters_block(tiny):
    """counters() carries the kv_pages observability block with sane
    arithmetic mid-flight and after drain."""
    model, params = tiny
    srv = Server(model, params, batch_size=2, eos_id=-1, min_bucket=8,
                 paged=True, kv_page_size=8, prefix_cache_mb=4.0)
    srv.submit(Request([1, 2, 3, 4, 5], 6, id="x"))
    srv.step()
    c = srv.counters()
    assert c["kv_pages_total"] == srv.slots.pool.n_pages
    assert c["kv_pages_used"] + c["kv_pages_free"] == c["kv_pages_total"]
    assert c["kv_bytes_resident"] == \
        c["kv_pages_used"] * srv.slots.pool.page_nbytes
    assert c["kv_tokens_resident"] > 0
    assert c["kv_page_size"] == 8
    list(srv.run(()))  # drain
    c = srv.counters()
    # store retains the donated pages; live-slot tokens are gone
    assert c["kv_pages_reserved"] == 0


@pytest.mark.parametrize("stats, expect_pages, source", [
    # the CPU reports no memory stats: the capacity-parity floor
    (None, 8, "capacity-parity floor"),
    # a device with room: half of what is free, capped at 4x the floor
    ({"bytes_limit": 1 << 30, "bytes_in_use": 0}, 32, "memory_stats"),
    # a nearly full device: never below the floor
    ({"bytes_limit": 1 << 20, "bytes_in_use": (1 << 20) - 8}, 8,
     "memory_stats"),
    # half of the free bytes, shared by the replicas that will coexist
    ({"bytes_limit": 40 * 8192, "bytes_in_use": 0}, 10, "memory_stats"),
])
def test_auto_pool_is_sized_from_the_device_it_already_holds(
        tiny, monkeypatch, capsys, stats, expect_pages, source):
    """``--kv-pages 0`` asks the device this process already holds
    (``memory_stats()``), not a second program, and says on stderr which
    source sized the pool. tiny: max_seq_len 32 -> pages of 16 tokens,
    8192 bytes each; batch 4 -> a floor of 8 pages."""
    import argparse

    from tony_tpu.cli.generate import resolve_paged_kv

    class _Device:
        def memory_stats(self):
            return stats

    model, _ = tiny
    monkeypatch.setattr(jax, "local_devices", lambda: [_Device()])
    args = argparse.Namespace(kv_pages=0, kv_page_size=0, no_paged_kv=False)
    got = resolve_paged_kv(args, model, 4, n_replicas=2)
    assert got == {"paged": True, "kv_page_size": 16,
                   "kv_pages": expect_pages}
    note = capsys.readouterr().err
    assert f"kv pool: {expect_pages} pages x 16 tokens" in note
    assert source in note
